"""Subprocess worker for tests/test_multichip_scale.py — runs one scale phase on
a 16- or 32-virtual-device CPU mesh (the parent sets
``XLA_FLAGS=--xla_force_host_platform_device_count=N``) and writes a JSON
verdict.

Phases (VERDICT r4 item 4 — shard-map bugs that only appear past 2-way axes):

- ``compose4`` — ONE 4-axis ``(data, seq, stage, model)`` mesh: dp batch
  sharding, exact ring-attention sequence parallelism over ``seq``,
  a ppermute pipeline over ``stage`` (depth 4 at n=32) whose stages are
  Megatron-style tensor-parallel MLPs (hidden dim sharded over ``model``,
  psum restores the output). Asserts value AND grad parity against the dense
  sequential network, then trains 4 adam steps and asserts the loss decreases.
- ``compose4_expert`` — the 'model-or-expert' variant: ``(data, seq, stage,
  expert)`` mesh where each pipeline stage is an EXPERT-PARALLEL MoE FFN
  (all-to-all over ``expert`` via ``ops.sharded_moe.sharded_moe_ffn``); the
  dense oracle routes per (microbatch, data-shard, seq-shard) token block with
  the shard-local capacity; same parity + loss-decrease assertions.
- ``wide3`` — ``(data=2, seq=4, model=4)`` mesh: a 4-hop ring (multi-step
  ppermute ordering) composed with 4-way tensor parallelism in one shard_map;
  same parity + loss-decrease assertions.
- ``dryrun`` — the driver-contract ``__graft_entry__.dryrun_multichip(n)``
  at n past the default 8 (exercises the generalized ``_mesh_axis_sizes``).

Runs standalone: ``python tests/_multichip_scale_worker.py <phase> <n> <out.json>``.
"""

import json
import sys

import numpy as np

H, D = 2, 4
E = H * D
F = 32          # MLP hidden; divisible by every 'model' axis used (2 and 4)
V = 32
B, T, M = 4, 16, 2


def _nll(logits, labels):
    import jax
    import jax.numpy as jnp
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return jnp.mean(-jnp.take_along_axis(logp, labels[..., None], axis=-1))


def _dense_causal_attn(q, k, v):
    """Dense reference for ring_attention(causal=True) — the project's ONE
    numerical definition (ops.ring_attention.dense_attention), not a copy."""
    from petastorm_tpu.ops.ring_attention import dense_attention
    return dense_attention(q, k, v, causal=True)


def _tree_max_delta(a, b):
    import jax
    deltas = jax.tree.map(
        lambda x, y: float(np.max(np.abs(np.asarray(x) - np.asarray(y)))), a, b)
    return max(jax.tree.leaves(deltas))


def _adam_descends(loss_fn, params, args, steps=4):
    import jax
    import optax
    opt = optax.adam(1e-2)
    opt_state = opt.init(params)

    @jax.jit
    def step(params, opt_state):
        loss, grads = jax.value_and_grad(loss_fn)(params, *args)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    losses = []
    for _ in range(steps):
        params, opt_state, loss = step(params, opt_state)
        losses.append(float(loss))
    return losses


def _mat(rng, *shape, scale=0.1):
    import jax.numpy as jnp
    return jnp.asarray(rng.randn(*shape) * scale, jnp.float32)


def _attended(params, tokens, attn_fn):
    """Shared attention front end: embed -> (H, D) heads -> attn_fn -> residual
    projection. The sharded phases pass the shard_map ring wrapper, the dense
    oracles pass the shared dense reference."""
    x = params['embed'][tokens]
    b, t = tokens.shape
    q = (x @ params['wq']).reshape(b, t, H, D)
    k = (x @ params['wk']).reshape(b, t, H, D)
    v = (x @ params['wv']).reshape(b, t, H, D)
    return x + attn_fn(q, k, v).reshape(b, t, E) @ params['wo']


def _finish_phase(mesh, mesh_dims, rng, loss_sharded, loss_dense,
                  sharded_params, params):
    """Shared phase tail: (data, seq)-sharded tokens, value+grad on both
    paths, 4 adam steps on the sharded one, and the result dict the parent
    test asserts on."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    tokens = rng.randint(0, V, (B, T)).astype(np.int32)
    labels = rng.randint(0, V, (B, T)).astype(np.int32)
    tok_sharding = NamedSharding(mesh, P('data', 'seq'))
    tokens_s = jax.device_put(jnp.asarray(tokens), tok_sharding)
    labels_s = jax.device_put(jnp.asarray(labels), tok_sharding)
    loss_s, grads_s = jax.jit(jax.value_and_grad(loss_sharded))(
        sharded_params, tokens_s, labels_s)
    loss_d, grads_d = jax.jit(jax.value_and_grad(loss_dense))(
        params, jnp.asarray(tokens), jnp.asarray(labels))
    losses = _adam_descends(loss_sharded, sharded_params, (tokens_s, labels_s))
    return {
        'mesh': mesh_dims,
        'loss_sharded': float(loss_s), 'loss_dense': float(loss_d),
        'loss_delta': abs(float(loss_s) - float(loss_d)),
        'grad_max_delta': _tree_max_delta(grads_s, grads_d),
        'adam_losses': losses,
    }


def run_compose4(n):
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from petastorm_tpu.ops.ring_attention import ring_attention
    from petastorm_tpu.parallel import (make_pipeline, microbatch,
                                        stack_stage_params, unstack_stage_params)

    data, seq, stage, model = {16: (2, 2, 2, 2), 32: (2, 2, 4, 2)}[n]
    mesh = Mesh(np.asarray(jax.devices()[:n]).reshape(data, seq, stage, model),
                ('data', 'seq', 'stage', 'model'))
    rng = np.random.RandomState(0)

    stages = [{'w1': _mat(rng, E, F), 'w2': _mat(rng, F, E)}
              for _ in range(stage)]
    params = {'embed': _mat(rng, V, E, scale=0.3),
              'wq': _mat(rng, E, E), 'wk': _mat(rng, E, E),
              'wv': _mat(rng, E, E), 'wo': _mat(rng, E, E),
              'stages': stack_stage_params(stages),
              'w_out': _mat(rng, E, V, scale=0.3)}
    stage_specs = {'w1': P('stage', None, 'model'), 'w2': P('stage', 'model', None)}
    param_specs = dict({k: P(None, None) for k in
                        ('embed', 'wq', 'wk', 'wv', 'wo', 'w_out')},
                       stages=stage_specs)
    sharded_params = jax.tree.map(
        lambda v, s: jax.device_put(v, NamedSharding(mesh, s)), params, param_specs,
        is_leaf=lambda x: isinstance(x, P))

    qkv_spec = P('data', 'seq', None, None)
    sp_attn = jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v, axis_name='seq', causal=True),
        mesh=mesh, in_specs=(qkv_spec, qkv_spec, qkv_spec), out_specs=qkv_spec, check_vma=False)

    def tp_stage_fn(p, mb):
        h = jax.nn.gelu(mb @ p['w1'])
        return mb + jax.lax.psum(h @ p['w2'], 'model')

    def dense_stage_fn(p, mb):
        return mb + jax.nn.gelu(mb @ p['w1']) @ p['w2']

    pipe = make_pipeline(tp_stage_fn, mesh,
                         xs_spec=P(None, 'data', 'seq', None),
                         out_spec=P(None, 'data', 'seq', None),
                         params_spec=stage_specs)

    def loss_sharded(params, tokens, labels):
        x = _attended(params, tokens, sp_attn)
        y = pipe(params['stages'], microbatch(x, M)).reshape(x.shape)
        return _nll(y @ params['w_out'], labels)

    def loss_dense(params, tokens, labels):
        y = _attended(params, tokens, _dense_causal_attn)
        for i in range(stage):
            y = dense_stage_fn(unstack_stage_params(params['stages'], i), y)
        return _nll(y @ params['w_out'], labels)

    return _finish_phase(
        mesh, {'data': data, 'seq': seq, 'stage': stage, 'model': model},
        rng, loss_sharded, loss_dense, sharded_params, params)


def run_wide3(n):
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from petastorm_tpu.ops.ring_attention import ring_attention

    data, seq, model = {32: (2, 4, 4)}[n]
    mesh = Mesh(np.asarray(jax.devices()[:n]).reshape(data, seq, model),
                ('data', 'seq', 'model'))
    rng = np.random.RandomState(1)

    params = {'embed': _mat(rng, V, E, scale=0.3), 'w1': _mat(rng, E, F),
              'w2': _mat(rng, F, E), 'w_out': _mat(rng, E, V, scale=0.3)}
    param_specs = {'embed': P(None, None), 'w1': P(None, 'model'),
                   'w2': P('model', None), 'w_out': P(None, None)}
    sharded_params = {k: jax.device_put(v, NamedSharding(mesh, param_specs[k]))
                      for k, v in params.items()}

    def block_local(x, w1, w2):
        # ring attention over a 4-hop 'seq' ring, then a Megatron MLP whose
        # hidden slice lives on this device; psum over 'model' restores it
        attn = ring_attention(x, x, x, axis_name='seq', causal=True)
        e = attn.reshape(attn.shape[0], attn.shape[1], E)
        h = jax.nn.gelu(e @ w1)
        return e + jax.lax.psum(h @ w2, 'model')

    x_spec = P('data', 'seq', None, None)
    block = jax.shard_map(
        block_local, mesh=mesh,
        in_specs=(x_spec, P(None, 'model'), P('model', None)),
        out_specs=P('data', 'seq', None), check_vma=False)

    def loss_sharded(params, tokens, labels):
        x = params['embed'][tokens].reshape(tokens.shape[0], tokens.shape[1], H, D)
        y = block(x, params['w1'], params['w2'])
        return _nll(y @ params['w_out'], labels)

    def loss_dense(params, tokens, labels):
        x = params['embed'][tokens].reshape(tokens.shape[0], tokens.shape[1], H, D)
        attn = _dense_causal_attn(x, x, x)
        e = attn.reshape(tokens.shape[0], tokens.shape[1], E)
        y = e + jax.nn.gelu(e @ params['w1']) @ params['w2']
        return _nll(y @ params['w_out'], labels)

    return _finish_phase(mesh, {'data': data, 'seq': seq, 'model': model},
                         rng, loss_sharded, loss_dense, sharded_params, params)


def run_compose4_expert(n):
    """The 'model-or-expert' 4-axis variant: ONE (data, seq, stage, expert)
    mesh — ring attention over ``seq`` feeding a ppermute pipeline over
    ``stage`` whose stages are EXPERT-PARALLEL MoE FFNs (all-to-all over
    ``expert`` via ops.sharded_moe.sharded_moe_ffn). The dense oracle routes
    per (microbatch, data-shard, seq-shard) token block with the same capacity
    the shard-local instances compute, so values AND grads must match."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from petastorm_tpu.models.moe import _capacity, switch_routing
    from petastorm_tpu.ops.ring_attention import ring_attention
    from petastorm_tpu.ops.sharded_moe import sharded_moe_ffn
    from petastorm_tpu.parallel import (make_pipeline, microbatch,
                                        stack_stage_params, unstack_stage_params)

    data, seq, stage, expert = {16: (2, 2, 2, 2), 32: (2, 2, 4, 2)}[n]
    mesh = Mesh(np.asarray(jax.devices()[:n]).reshape(data, seq, stage, expert),
                ('data', 'seq', 'stage', 'expert'))
    X, FE, CAP = 4, 16, 8.0  # experts, expert hidden, no-drop capacity factor
    rng = np.random.RandomState(2)

    stages = [{'router': _mat(rng, E, X, scale=0.5),
               'w1': _mat(rng, X, E, FE, scale=0.3),
               'w2': _mat(rng, X, FE, E, scale=0.3)} for _ in range(stage)]
    params = {'embed': _mat(rng, V, E, scale=0.3),
              'wq': _mat(rng, E, E), 'wk': _mat(rng, E, E),
              'wv': _mat(rng, E, E), 'wo': _mat(rng, E, E),
              'stages': stack_stage_params(stages),
              'w_out': _mat(rng, E, V, scale=0.3)}
    stage_specs = {'router': P('stage', None, None),
                   'w1': P('stage', 'expert', None, None),
                   'w2': P('stage', 'expert', None, None)}
    param_specs = dict({k: P(None, None) for k in
                        ('embed', 'wq', 'wk', 'wv', 'wo', 'w_out')},
                       stages=stage_specs)
    sharded_params = jax.tree.map(
        lambda v, s: jax.device_put(v, NamedSharding(mesh, s)), params, param_specs,
        is_leaf=lambda x: isinstance(x, P))

    qkv_spec = P('data', 'seq', None, None)
    sp_attn = jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v, axis_name='seq', causal=True),
        mesh=mesh, in_specs=(qkv_spec, qkv_spec, qkv_spec), out_specs=qkv_spec, check_vma=False)

    def moe_stage_fn(p, mb):
        flat = mb.reshape(-1, E)
        out, _, _ = sharded_moe_ffn(flat, p['router'], p['w1'], p['w2'],
                                    'expert', capacity_factor=CAP)
        return mb + out.reshape(mb.shape)

    def dense_moe_block(p, block):
        """shard_reference-style MoE on ONE local token block (same routing +
        capacity math sharded_moe_ffn computes from its local pool)."""
        flat = block.reshape(-1, E)
        probs = jax.nn.softmax(flat @ p['router'], axis=-1)
        cap = _capacity(flat.shape[0], X, 1, CAP)
        dispatch, combine, _, _ = switch_routing(probs, cap, 1)
        expert_in = jnp.einsum('sxc,sd->xcd', dispatch, flat)
        h = jax.nn.gelu(jnp.einsum('xcd,xdf->xcf', expert_in, p['w1']))
        out = jnp.einsum('xcf,xfd->xcd', h, p['w2'])
        return block + jnp.einsum('xcd,sxc->sd', out, combine).reshape(block.shape)

    pipe = make_pipeline(moe_stage_fn, mesh,
                         xs_spec=P(None, 'data', 'seq', None),
                         out_spec=P(None, 'data', 'seq', None),
                         params_spec=stage_specs)

    def loss_sharded(params, tokens, labels):
        x = _attended(params, tokens, sp_attn)
        y = pipe(params['stages'], microbatch(x, M)).reshape(x.shape)
        return _nll(y @ params['w_out'], labels)

    def loss_dense(params, tokens, labels):
        x = _attended(params, tokens, _dense_causal_attn)
        xs = x.reshape(M, B // M, T, E)
        b_blk, t_blk = (B // M) // data, T // seq
        y = jnp.zeros_like(xs)
        for m in range(M):
            for d in range(data):
                for s in range(seq):
                    rows = slice(d * b_blk, (d + 1) * b_blk)
                    cols = slice(s * t_blk, (s + 1) * t_blk)
                    block = xs[m, rows, cols]
                    for i in range(stage):
                        block = dense_moe_block(
                            unstack_stage_params(params['stages'], i), block)
                    y = y.at[m, rows, cols].set(block)
        y = y.reshape(B, T, E)
        return _nll(y @ params['w_out'], labels)

    return _finish_phase(
        mesh, {'data': data, 'seq': seq, 'stage': stage, 'expert': expert},
        rng, loss_sharded, loss_dense, sharded_params, params)


def main():
    phase, n, out_path = sys.argv[1], int(sys.argv[2]), sys.argv[3]
    import jax
    try:
        jax.config.update('jax_platforms', 'cpu')
    except RuntimeError:
        pass
    available = len(jax.devices())
    if available < n:
        raise SystemExit('need {} devices, have {}'.format(n, available))
    result = {'phase': phase, 'n_devices': n}
    if phase == 'compose4':
        result.update(run_compose4(n))
    elif phase == 'compose4_expert':
        result.update(run_compose4_expert(n))
    elif phase == 'wide3':
        result.update(run_wide3(n))
    elif phase == 'dryrun':
        import __graft_entry__
        __graft_entry__.dryrun_multichip(n)
        result['dryrun_ok'] = True
    else:
        raise SystemExit('unknown phase {!r}'.format(phase))
    with open(out_path, 'w') as f:
        json.dump(result, f)


if __name__ == '__main__':
    main()
