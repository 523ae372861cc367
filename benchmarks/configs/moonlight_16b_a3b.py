"""Moonlight-16B-A3B's layers trained by Adam, on one chip's share of an
expert-parallel deployment: the program's step, and its plain reference in
``jax.numpy`` at float32 ``highest``.

The program is ``petastorm_tpu.models.DeepSeekV3LM`` at this model's widths, in
bfloat16 with a float32 router and logits, its attention on the Pallas flash
kernels (q/k heads 192, value heads 128) and its routed experts on the dropless
grouped-matmul dispatch. The reference computes the same equations plainly:
multi-head latent attention in blocks of query rows (each rematerialised, the
scores dense over every key, masked), and each held expert as its dense
SwiGLU over every token times its gate, which is zero where the token did not
choose it; its training steps take the gradients a layer at a time. It
imports nothing of the program.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax

from benchmarks import common

WORK_UNIT = 'tokens'
HIGHEST = jax.lax.Precision.HIGHEST
#: query rows of one block of the reference's attention
REFERENCE_QUERY_BLOCK = 1024


def weight_rule(name, shape):
    leaf = name.rsplit('/', 1)[-1]
    if leaf in ('kernel', 'embedding', 'w_in', 'w_out'):
        return ('normal', 0.02)
    if leaf == 'scale':
        return ('const', 1.0)
    raise ValueError('no weight rule for ' + name)


def _adam(cfg):
    opt = cfg['optimizer']
    return optax.adam(opt['learning_rate'], b1=opt['b1'], b2=opt['b2'], eps=opt['eps'])


def _routed_rows_per_token(cfg):
    """Expert rows this chip computes per token, on average: k x held / routed."""
    return cfg['num_experts_per_tok'] * cfg['n_routed_experts'] / cfg['router_experts']


class Program(object):
    """The system under test: DeepSeekV3LM's train step on the loader's batches,
    the selection biases updated in the step."""

    def __init__(self, cfg, mesh=None):
        from petastorm_tpu.models import (DeepSeekConfig, DeepSeekV3LM, moe_aux_total,
                                          next_token_loss)
        from petastorm_tpu.ops.flash_attention import flash_attention
        self.cfg = cfg
        self.model = DeepSeekV3LM(
            DeepSeekConfig.from_hf(cfg, first_expert=cfg['first_expert'],
                                   kv_norm_eps=cfg['kv_norm_eps'],
                                   bias_step=cfg['bias_update_speed']),
            dtype=jnp.dtype(cfg['compute_dtype']), remat=True,
            attention_fn=lambda q, k, v: flash_attention(q, k, v, causal=True))
        self.tx = _adam(cfg)
        self.shapes = jax.eval_shape(self.model.init, jax.random.PRNGKey(0),
                                     jnp.zeros((1, cfg['seq_len']), jnp.int32))
        model, tx, alpha = self.model, self.tx, cfg['seq_aux_alpha']

        def step(state, batch):
            (params, router), opt_state = state
            tokens = batch['tokens']

            def loss_fn(p):
                logits, mutated = model.apply({'params': p, 'router_state': router}, tokens,
                                              mutable=['losses', 'router_state'])
                loss = next_token_loss(logits, tokens) + moe_aux_total(mutated, alpha)
                return loss, mutated['router_state']

            (loss, router), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
            updates, opt_state = tx.update(grads, opt_state, params)
            return ((optax.apply_updates(params, updates), router), opt_state), loss

        self.step = step

    def init(self, key):
        params = common.draw_weights(key, self.shapes['params'], weight_rule)
        router = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                              self.shapes['router_state'])
        return (params, router), self.tx.init(params)

    @staticmethod
    def params(state):
        return state[0][0]

    def first_grads(self, state):
        """The gradient of the first step, from Adam's first moment then:
        ``mu = (1 - b1) g``."""
        b1 = self.cfg['optimizer']['b1']
        return jax.tree.map(lambda m: m / (1.0 - b1), state[1][0].mu)

    def init_params(self, key):
        return common.draw_weights(key, self.shapes['params'], weight_rule)

    def flops_per_chip_step(self, compiled, chips):
        return train_flops(self.cfg, self.cfg['batch_per_chip'])

    def work(self, batch_rows):
        return batch_rows * self.cfg['seq_len']

    def attention(self):
        """What the kernels' rooflines need to know beyond the trace: the causal
        mask and the attention heads' widths."""
        cfg = self.cfg
        return {'causal': True, 'q_head': cfg['qk_nope_head_dim'] + cfg['qk_rope_head_dim'],
                'v_head': cfg['v_head_dim']}


def train_flops(cfg, batch):
    """Model operations of one train step on one chip (forward and twice it for
    the backward), by the analytic count: 2 per multiply-add. Attention scores
    are credited their causal half (q.k at the q/k head, p.v at the value head);
    routed experts the rows expected here, ``tokens x k x held / routed``; the
    router's matmul counts, lookups, norms and the recomputed forward do not."""
    d, h, t = cfg['hidden_size'], cfg['num_attention_heads'], cfg['seq_len']
    nope, rot, dv = cfg['qk_nope_head_dim'], cfg['qk_rope_head_dim'], cfg['v_head_dim']
    r, f = cfg['kv_lora_rank'], cfg['moe_intermediate_size']
    layers, dense = cfg['num_hidden_layers'], cfg['first_k_dense_replace']
    attention = 2 * d * (h * (nope + rot) + r + rot) + 2 * r * h * (nope + dv) \
        + 2 * h * dv * d + t * h * (nope + rot + dv)
    swiglu = lambda width: 6 * d * width  # noqa: E731
    routed = (2 * d * cfg['router_experts'] + swiglu(f * cfg['n_shared_experts'])
              + _routed_rows_per_token(cfg) * swiglu(f))
    per_token = (layers * attention + dense * swiglu(cfg['intermediate_size'])
                 + (layers - dense) * routed + 2 * d * cfg['vocab_size'])
    return 3 * batch * t * per_token


# ------------------------------------------------------------------ reference

def _dot(low):
    return functools.partial(common.contract,
                             functools.partial(jnp.matmul, precision=HIGHEST), low=low)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def _rotate(x, positions, theta):
    """RoPE on ``x [B, H, T, R]``: pairs (2i, 2i + 1) turn by position /
    theta**(2i / R); out come the pairs' first members, then their second."""
    n = x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, n, 2, dtype=np.float64) / n)
    angle = jnp.asarray(np.outer(positions, inv), jnp.float32)          # [T, R/2]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention_rows(low, k, v, q, first):
    """Causal softmax attention of the query rows ``first..`` of ``q [B, H, Tq,
    Dq]`` over the keys ``k [B, H, T, Dq]`` and values that precede them."""
    dot = _dot(low)
    s = dot(q, k.transpose(0, 1, 3, 2)) * q.shape[-1] ** -0.5
    rows = first + jnp.arange(q.shape[2])[:, None]
    s = jnp.where(rows >= jnp.arange(k.shape[2])[None], s, -jnp.inf)
    return dot(jax.nn.softmax(s, axis=-1), v)


def _mla(cfg, low, x, p):
    dot = _dot(low)
    b, t, _ = x.shape
    h, nope, rot, dv = (cfg['num_attention_heads'], cfg['qk_nope_head_dim'],
                        cfg['qk_rope_head_dim'], cfg['v_head_dim'])
    r, theta = cfg['kv_lora_rank'], cfg['rope_theta']
    positions = np.arange(t)
    q = dot(x, p['q_proj']['kernel']).reshape(b, t, h, nope + rot).transpose(0, 2, 1, 3)
    q = jnp.concatenate([q[..., :nope], _rotate(q[..., nope:], positions, theta)], -1)
    kv_a = dot(x, p['kv_a_proj']['kernel'])
    latent = _rms_norm(kv_a[..., :r], p['kv_a_norm']['scale'], cfg['kv_norm_eps'])
    k_pe = _rotate(kv_a[:, None, :, r:], positions, theta)                # [B, 1, T, R]
    kv = dot(latent, p['kv_b_proj']['kernel']).reshape(b, t, h, nope + dv).transpose(
        0, 2, 1, 3)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_pe, (b, h, t, rot))], -1)
    v = kv[..., nope:]
    block = min(REFERENCE_QUERY_BLOCK, t)
    rows = jax.checkpoint(functools.partial(_attention_rows, low, k, v))
    blocks = q.reshape(b, h, t // block, block, nope + rot).transpose(2, 0, 1, 3, 4)
    _, out = jax.lax.scan(lambda _, qf: (None, rows(*qf)), None,
                          (blocks, jnp.arange(0, t, block)))            # [N, B, H, Tq, Dv]
    out = out.transpose(1, 0, 3, 2, 4).reshape(b, t, h * dv)
    return dot(out, p['o_proj']['kernel'])


def _swiglu(dot, x, gate, up, down):
    return dot(jax.nn.silu(dot(x, gate)) * dot(x, up), down)


def _routed(cfg, low, x, p, bias):
    """The layer's experts on ``x [S, D]``: the shared SwiGLU plus each held
    expert's dense SwiGLU times its gate. Returns the output, the sequence-wise
    balance loss and the load of every expert."""
    dot = _dot(low)
    n_exp, k = cfg['router_experts'], cfg['num_experts_per_tok']
    first, held, f = cfg['first_expert'], cfg['n_routed_experts'], cfg['moe_intermediate_size']
    logits = jnp.matmul(x, p['router']['kernel'], precision=HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, chosen = jax.lax.top_k(scores + bias, k)                         # [S, k]
    picked = jax.nn.one_hot(chosen, n_exp, dtype=jnp.float32)           # [S, k, X]
    weight = jnp.take_along_axis(scores, chosen, axis=-1)
    weight = weight / (jnp.sum(weight, -1, keepdims=True) + 1e-20)
    weight = weight * cfg['routed_scaling_factor']
    gates = jnp.einsum('skx,sk->sx', picked, weight, precision=HIGHEST)  # [S, X]
    shared = p['shared']
    out = _swiglu(dot, x, shared['gate_proj']['kernel'], shared['up_proj']['kernel'],
                  shared['down_proj']['kernel'])
    @jax.checkpoint
    def expert(out, weights):
        w_in, w_out, gate = weights
        return out + gate[:, None] * _swiglu(dot, x, w_in[:, :f], w_in[:, f:], w_out), None

    out, _ = jax.lax.scan(expert, out, (p['w_in'], p['w_out'],
                                        gates[:, first:first + held].T))
    load = jnp.sum(picked, axis=(0, 1))                                 # [X]
    t = cfg['seq_len']
    batch = x.shape[0] // t
    share = (scores / jnp.sum(scores, -1, keepdims=True)).reshape(batch, t, n_exp)
    routed_to = jnp.sum(picked, axis=1).reshape(batch, t, n_exp)
    balance = jnp.mean(jnp.sum(jnp.sum(routed_to, 1) * n_exp / (k * t)
                               * jnp.mean(share, 1), axis=-1))
    return out, balance, load


def _block(cfg, low, dense, x, p, bias):
    eps = cfg['rms_norm_eps']
    x = x + _mla(cfg, low, _rms_norm(x, p['input_norm']['scale'], eps), p['attention'])
    h = _rms_norm(x, p['post_attention_norm']['scale'], eps)
    if dense:
        m = p['mlp']
        return x + _swiglu(_dot(low), h, m['gate_proj']['kernel'], m['up_proj']['kernel'],
                           m['down_proj']['kernel']), 0.0, None
    b, t, d = h.shape
    out, balance, load = _routed(cfg, low, h.reshape(b * t, d), p['moe'], bias)
    return x + out.reshape(b, t, d), balance, load


def reference_loss(cfg, params, batch, low=False, rows=None, biases=None):
    """The loss: next-token cross entropy plus alpha times the routed layers'
    balance losses, their selection biases ``biases`` (``{layer name: [X]}``,
    zero where None)."""
    return _forward(cfg, params, batch, low, rows, biases or {})[0]


def _forward(cfg, params, batch, low, rows, biases):
    """The loss and each routed layer's expert load."""
    tokens = batch['tokens'] if rows is None else batch['tokens'][:rows]
    lookup = common.fp8_round if low else (lambda table: table)
    x = lookup(params['embed']['embedding'])[tokens]
    balance, loads = 0.0, {}
    for i in range(cfg['num_hidden_layers']):
        name = 'layer_{}'.format(i)
        dense = i < cfg['first_k_dense_replace']
        block = jax.checkpoint(functools.partial(_block, cfg, low, dense))
        bias = biases.get(name, jnp.zeros((cfg['router_experts'],), jnp.float32))
        x, layer_balance, load = block(x, params[name], None if dense else bias)
        balance = balance + layer_balance
        if load is not None:
            loads[name] = load
    head = {k: params[k] for k in ('final_norm', 'lm_head')}
    return _head_loss(cfg, low, head, x, tokens) + cfg['seq_aux_alpha'] * balance, loads


def _head_loss(cfg, low, head, x, tokens):
    """Next-token cross entropy of the final norm and the head on ``x``."""
    x = _rms_norm(x, head['final_norm']['scale'], cfg['rms_norm_eps'])
    logits = common.contract(functools.partial(jnp.matmul, precision=HIGHEST), x,
                             head['lm_head']['kernel'], low)
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1))


def _layerwise(cfg, low, rows):
    """``(params, biases, batch) -> (loss, grads, loads)``, the same numbers as
    differentiating :func:`_forward`, computed a layer at a time: each layer's
    forward, and then in reverse each layer's backward (which recomputes its
    forward), is one small program run once per layer, so that the routed
    layers share one compiled forward and one compiled backward."""
    alpha = cfg['seq_aux_alpha']
    lookup = common.fp8_round if low else (lambda table: table)

    def layer(p, x, bias, dense):
        y, balance, load = _block(cfg, low, dense, x, p, bias)
        return y, jnp.asarray(balance, jnp.float32), load

    forward = jax.jit(layer, static_argnums=3)

    @functools.partial(jax.jit, static_argnums=4)
    def backward(p, x, bias, g, dense):
        _, pull = jax.vjp(lambda p_, x_: layer(p_, x_, bias, dense)[:2], p, x)
        return pull((g, jnp.float32(alpha)))

    embed = jax.jit(lambda table, tokens: lookup(table)[tokens])
    embed_grad = jax.jit(lambda table, tokens, g: jax.vjp(
        lambda t: lookup(t)[tokens], table)[1](g)[0])
    head = jax.jit(jax.value_and_grad(functools.partial(_head_loss, cfg, low),
                                      argnums=(0, 1)))

    def run(params, biases, batch):
        tokens = batch['tokens'] if rows is None else batch['tokens'][:rows]
        names = ['layer_{}'.format(i) for i in range(cfg['num_hidden_layers'])]
        dense = [i < cfg['first_k_dense_replace'] for i in range(len(names))]
        xs, loss, loads = [embed(params['embed']['embedding'], tokens)], 0.0, {}
        for name, is_dense in zip(names, dense):
            x, balance, load = forward(params[name], xs[-1], biases.get(name), is_dense)
            xs.append(x)
            loss = loss + alpha * balance
            if load is not None:
                loads[name] = load
        head_params = {k: params[k] for k in ('final_norm', 'lm_head')}
        nll, (grads, g) = head(head_params, xs.pop(), tokens)
        for name, is_dense in reversed(list(zip(names, dense))):
            grads[name], g = backward(params[name], xs.pop(), biases.get(name), g, is_dense)
        grads['embed'] = {'embedding': embed_grad(params['embed']['embedding'], tokens, g)}
        return loss + nll, grads, loads

    return run


def _param_shapes(cfg):
    d, v = cfg['hidden_size'], cfg['vocab_size']
    h, nope, rot, dv = (cfg['num_attention_heads'], cfg['qk_nope_head_dim'],
                        cfg['qk_rope_head_dim'], cfg['v_head_dim'])
    r, f, held = cfg['kv_lora_rank'], cfg['moe_intermediate_size'], cfg['n_routed_experts']
    s = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)  # noqa: E731
    kernel = lambda a, b: {'kernel': s(a, b)}  # noqa: E731
    swiglu = lambda n: {'gate_proj': kernel(d, n), 'up_proj': kernel(d, n),  # noqa: E731
                        'down_proj': kernel(n, d)}
    tree = {'embed': {'embedding': s(v, d)}, 'final_norm': {'scale': s(d)},
            'lm_head': kernel(d, v)}
    for i in range(cfg['num_hidden_layers']):
        layer = {'input_norm': {'scale': s(d)}, 'post_attention_norm': {'scale': s(d)},
                 'attention': {'q_proj': kernel(d, h * (nope + rot)),
                               'kv_a_proj': kernel(d, r + rot),
                               'kv_a_norm': {'scale': s(r)},
                               'kv_b_proj': kernel(r, h * (nope + dv)),
                               'o_proj': kernel(h * dv, d)}}
        if i < cfg['first_k_dense_replace']:
            layer['mlp'] = swiglu(cfg['intermediate_size'])
        else:
            layer['moe'] = {'router': kernel(d, cfg['router_experts']),
                            'shared': swiglu(cfg['n_shared_experts'] * f),
                            'w_in': s(held, d, 2 * f), 'w_out': s(held, f, d)}
        tree['layer_{}'.format(i)] = layer
    return tree


def reference_run(cfg, weight_seed, batches, low=False, rows=None, shardings=None):
    """Three Adam steps of the reference from the seed's weights over ``batches``
    (host dicts of ``tokens``), each routed layer's selection bias updated after
    each step: the losses, the first gradient's norms and the parameters' change
    norms after the last step, per leaf."""
    shapes = _param_shapes(cfg)
    draw = jax.jit(lambda k: common.draw_weights(k, shapes, weight_rule))
    params, batches = common.place(draw(common.weight_key(weight_seed)), batches, shardings)
    biases = {'layer_{}'.format(i): jnp.zeros((cfg['router_experts'],), jnp.float32)
              for i in range(cfg['first_k_dense_replace'], cfg['num_hidden_layers'])}
    opt = cfg['optimizer']
    lr, b1, b2, eps = opt['learning_rate'], opt['b1'], opt['b2'], opt['eps']
    gamma = cfg['bias_update_speed']

    loss_and_grads = _layerwise(cfg, low, rows)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
    def update(params, mu, nu, grads, biases, loads, count):
        mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
        nu = jax.tree.map(lambda n, g: b2 * n + (1 - b2) * g * g, nu, grads)
        c1, c2 = 1 - b1 ** count, 1 - b2 ** count
        params = jax.tree.map(lambda p, m, n: p - lr * (m / c1) / (jnp.sqrt(n / c2) + eps),
                              params, mu, nu)
        biases = {name: b + gamma * jnp.sign(jnp.mean(loads[name]) - loads[name])
                  for name, b in biases.items()}
        return params, mu, nu, biases

    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    losses, grads = [], None
    for count, batch in enumerate(batches, 1):
        loss, step_grads, loads = loss_and_grads(params, biases, batch)
        losses.append(float(loss))
        if grads is None:
            grads = common.host_norms(common.leaf_norms(step_grads))
        params, mu, nu, biases = update(params, mu, nu, step_grads, biases, loads,
                                        np.float32(count))
    del mu, nu
    start, _ = common.place(draw(common.weight_key(weight_seed)), [], shardings)
    return {'loss': losses, 'grad': grads,
            'change': common.host_norms(common.change_norms(params, start))}
