"""Mixture-of-Experts layers — expert parallelism (ep) for the device mesh.

The reference has no MoE; this completes the parallelism families the TPU framework
serves (dp/sp/tp in ``__graft_entry__``/examples, pp in ``parallel/pipeline.py``, ep
here). Design is TPU-first, not a torch translation:

- **Static capacity dispatch.** Top-k routing with a fixed per-expert capacity
  ``C = ceil(capacity_factor * k * tokens / num_experts)`` so every shape is known at
  trace time — no ragged gathers, no data-dependent shapes that would break XLA tiling.
  Dispatch and combine are one-hot einsum masks, which land on the MXU.
- **Sharding by annotation.** Expert weights carry a leading experts axis; shard them
  ``PartitionSpec('expert', ...)`` (see :func:`expert_partition_specs`) and jit under a
  mesh with an ``'expert'`` axis — XLA places the all-to-all that moves token slots to
  their expert's device on ICI (the scaling-book recipe: annotate, let the compiler
  insert collectives). The module itself stays mesh-free; an optional
  ``expert_axis`` adds a ``with_sharding_constraint`` hint on the dispatched blocks.
- **Residual overflow.** Tokens past capacity contribute zero from the MoE branch and
  ride the block's residual connection (Switch Transformer semantics).

The router runs in float32 (softmax stability); expert FFNs run in ``dtype``
(bfloat16 by default, MXU-native). The load-balance auxiliary loss is sown into the
``'losses'`` collection — collect with :func:`moe_aux_total`.
"""

import math
from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax


def _capacity(num_tokens, num_experts, num_selected, capacity_factor):
    cap = int(math.ceil(capacity_factor * num_selected * num_tokens / num_experts))
    return max(1, cap)


def _ambient_mesh_axes():
    """Axis names of the mesh context the caller is tracing under, or None when no
    mesh context is active (plain single-chip execution)."""
    from jax.sharding import get_abstract_mesh
    mesh = get_abstract_mesh()
    if mesh.axis_names:
        return set(mesh.axis_names)
    return None


def _sharding_hint(x, spec_axes):
    """with_sharding_constraint when a mesh context is active. A mesh that exists but
    lacks the named axis raises — silently skipping the constraint would disable
    expert parallelism with no signal. With no ambient mesh at all (single-chip runs,
    or jit driven purely by in_shardings without a mesh context) the hint cannot be
    applied as a bare PartitionSpec; that case warns instead of raising so a model
    configured with ``expert_axis`` still runs unsharded (the default warnings filter
    dedups repeats per call site — no hand-rolled once flag, which would also
    suppress the signal for later, genuinely misconfigured models)."""
    import warnings

    from jax.sharding import PartitionSpec
    axes = _ambient_mesh_axes()
    if axes is None:
        warnings.warn(
            'MoE expert_axis={!r} set but no mesh context is active; the expert '
            'sharding hint was skipped. Trace under `with jax.set_mesh(mesh):` for '
            'expert parallelism.'.format(spec_axes[0]), stacklevel=2)
        return x
    wanted = {a for a in spec_axes if a is not None}
    if not wanted <= axes:
        raise ValueError('expert_axis {} not in ambient mesh axes {}; fix the mesh '
                         'or the MoE expert_axis argument'
                         .format(sorted(wanted - axes), sorted(axes)))
    return lax.with_sharding_constraint(x, PartitionSpec(*spec_axes))


def switch_routing(probs, capacity, num_selected):
    """Top-k routing with static capacity: ``probs [S, X]`` (row-softmax) ->
    ``(dispatch [S, X, C], combine [S, X, C], aux, drop_fraction)``.

    Pure function shared by :class:`MoEMlp` (annotation-based expert parallelism)
    and ``ops.sharded_moe`` (explicit all-to-all under shard_map) so the two
    execution paths can never route differently. Slot-major priority: all
    first-choice assignments win capacity before any second choice (Switch/GShard);
    positions use an int32 cumsum (exact past 2^24 token-slots)."""
    n_tokens, n_exp = probs.shape
    k = num_selected
    gate, expert_idx = lax.top_k(probs, k)                              # [S, k]
    if k > 1:
        gate = gate / jnp.sum(gate, axis=-1, keepdims=True)

    onehot_i = jax.nn.one_hot(expert_idx, n_exp, dtype=jnp.int32)       # [S, k, X]
    flat_i = onehot_i.transpose(1, 0, 2).reshape(k * n_tokens, n_exp)   # slot-major
    flat = flat_i.astype(jnp.float32)
    pos_in_expert = jnp.cumsum(flat_i, axis=0) - flat_i                 # [kS, X]
    position = jnp.sum(pos_in_expert * flat_i, axis=-1)                 # [kS] int32
    assigned = jnp.sum(flat, axis=-1)
    keep = assigned * (position < capacity).astype(jnp.float32)         # [kS]

    pos_onehot = jax.nn.one_hot(position, capacity, dtype=jnp.float32)  # [kS, C]
    dispatch_flat = (flat[:, :, None] * pos_onehot[:, None, :]
                     * keep[:, None, None])                             # [kS, X, C]
    gate_flat = gate.transpose(1, 0).reshape(k * n_tokens)
    combine_flat = dispatch_flat * gate_flat[:, None, None]
    dispatch = dispatch_flat.reshape(k, n_tokens, n_exp, capacity).sum(0)
    combine = combine_flat.reshape(k, n_tokens, n_exp, capacity).sum(0)

    # Switch load-balance loss: X * sum_x f_x * P_x, minimized (=1) when uniform.
    frac_tokens = jnp.mean(onehot_i[:, 0, :].astype(jnp.float32), axis=0)
    mean_probs = jnp.mean(probs, axis=0)
    aux = n_exp * jnp.sum(frac_tokens * mean_probs)
    drop_fraction = 1.0 - jnp.sum(keep) / float(k * n_tokens)
    return dispatch, combine, aux, drop_fraction


class MoEMlp(nn.Module):
    """Top-k routed expert MLP: ``[B, T, D] -> [B, T, D]``.

    Shard ``w1``/``w2`` over their leading experts axis (``expert_partition_specs``)
    for expert parallelism. ``expert_axis`` (optional) names the mesh axis for
    sharding hints on the dispatched activations; leave ``None`` when running
    unsharded (single chip or replicated).
    """

    num_experts: int
    capacity_factor: float = 1.25
    num_selected: int = 1
    hidden_mult: int = 4
    dtype: Any = jnp.bfloat16
    expert_axis: Optional[str] = None

    @nn.compact
    def __call__(self, x):
        batch, seqlen, d = x.shape
        n_tokens = batch * seqlen
        n_exp = self.num_experts
        k = self.num_selected
        if k > n_exp:
            raise ValueError('num_selected={} exceeds num_experts={}'.format(k, n_exp))
        cap = _capacity(n_tokens, n_exp, k, self.capacity_factor)
        hidden = self.hidden_mult * d

        tokens = x.reshape(n_tokens, d)
        # Router in float32: softmax over experts must not run in bf16.
        logits = nn.Dense(n_exp, use_bias=False, dtype=jnp.float32,
                          param_dtype=jnp.float32, name='router')(
                              tokens.astype(jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)                      # [S, X]
        dispatch, combine, aux, drop_fraction = switch_routing(probs, cap, k)

        w1 = self.param('w1', nn.initializers.lecun_normal(batch_axis=(0,)),
                        (n_exp, d, hidden), jnp.float32)
        w2 = self.param('w2', nn.initializers.lecun_normal(batch_axis=(0,)),
                        (n_exp, hidden, d), jnp.float32)

        compute_dtype = self.dtype
        # init() traces outside any mesh; the hint (and its no-mesh warning) only
        # matters on real forward/backward traces.
        want_hint = self.expert_axis is not None and not self.is_initializing()
        expert_in = jnp.einsum('sd,sxc->xcd', tokens.astype(compute_dtype),
                               dispatch.astype(compute_dtype))          # [X, C, D]
        if want_hint:
            expert_in = _sharding_hint(expert_in, (self.expert_axis, None, None))
        h = jnp.einsum('xcd,xdf->xcf', expert_in, w1.astype(compute_dtype))
        h = nn.gelu(h)
        expert_out = jnp.einsum('xcf,xfd->xcd', h, w2.astype(compute_dtype))
        if want_hint:
            expert_out = _sharding_hint(expert_out, (self.expert_axis, None, None))
        y = jnp.einsum('xcd,sxc->sd', expert_out.astype(jnp.float32),
                       combine.astype(jnp.float32))

        self.sow('losses', 'moe_aux', aux)
        # Diagnostics: fraction of (token, slot) assignments dropped by capacity.
        self.sow('losses', 'moe_drop_fraction', drop_fraction)

        return y.reshape(batch, seqlen, d).astype(x.dtype)


def expert_partition_specs(params, expert_axis='expert'):
    """PartitionSpecs for a pytree of params: MoE expert weights (leading experts
    axis, i.e. param names ``w1``/``w2`` under an ``MoEMlp``) sharded over
    ``expert_axis``, everything else replicated. Feed to ``NamedSharding``/jit."""
    from jax.sharding import PartitionSpec as P

    # Scopes holding a 'router' child: MoEMlp always carries its router Dense beside
    # w1/w2, so a router sibling — not path depth — is the signal that a top-level
    # w1/w2 belongs to a root-module MoEMlp. A non-MoE root module with 3-D params
    # that happen to be named w1/w2 has no router and stays replicated (ADVICE r3).
    router_scopes = set()
    for path, _ in jax.tree_util.tree_flatten_with_path(params)[0]:
        names = tuple(str(getattr(p, 'key', getattr(p, 'name', ''))) for p in path)
        if 'router' in names:
            router_scopes.add(names[:names.index('router')])

    def spec(path, leaf):
        names = [str(getattr(p, 'key', getattr(p, 'name', ''))) for p in path]
        # Expert weights are the 3-D [experts, in, out] leaves named w1/w2 — under a
        # nested MoEMlp_* scope, or beside a router Dense when MoEMlp is the root
        # module. Both the scope and ndim conditions are required: a bare top-level
        # w1/w2 (e.g. stack_stage_params output) must not be captured, and an MoE
        # leaf with extra leading axes (nn.scan / stacked pipeline stages) must fail
        # loudly, not shard the wrong axis.
        in_moe_scope = (any('MoEMlp' in n for n in names)
                        or tuple(names[:-1]) in router_scopes)
        if names and names[-1] in ('w1', 'w2') and in_moe_scope:
            if leaf.ndim == 3:
                return P(expert_axis, *([None] * (leaf.ndim - 1)))
            if any('MoEMlp' in n for n in names):
                raise ValueError(
                    'MoE expert weight {} has ndim {} (expected 3): scanned/stacked '
                    'MoE params need hand-written specs'.format(
                        '/'.join(names), leaf.ndim))
        return P(*([None] * leaf.ndim))

    return jax.tree_util.tree_map_with_path(spec, params)


def collect_sown(mutables, sown_key):
    """Latest sown value of ``sown_key`` from every MoE layer in a ``'losses'``
    collection (as returned by ``model.apply(..., mutable='losses')``) — one entry
    per layer, traced-safe. ``sow`` appends one value per apply, so only each
    tuple's LAST entry belongs to the current step; taking the whole tuple would
    double-count when the collection was threaded through from a previous apply
    (e.g. from ``init``)."""
    losses = mutables.get('losses', mutables)
    leaves = []

    def visit(tree, under_key=False):
        if isinstance(tree, dict):
            for key, sub in tree.items():
                visit(sub, under_key or key == sown_key)
        elif isinstance(tree, (tuple, list)):
            if under_key and tree:
                visit(tree[-1], under_key)
            elif not under_key:
                for sub in tree:
                    visit(sub, under_key)
        elif under_key:
            leaves.append(tree)

    visit(losses)
    return leaves


def moe_aux_total(mutables, weight=1.0):
    """Sum of every MoE layer's latest Switch load-balance loss, scaled by
    ``weight``. Train on ``variables['params']`` only; never feed the init-time
    ``'losses'`` collection to the optimizer."""
    leaves = collect_sown(mutables, 'moe_aux')
    if not leaves:
        return jnp.float32(0)
    return weight * sum(leaves)


def moe_drop_fractions(mutables):
    """Every MoE layer's latest capacity drop fraction (list of scalars; empty when
    the model has no MoE layers)."""
    return collect_sown(mutables, 'moe_drop_fraction')


class MoEBlock(nn.Module):
    """Pre-norm transformer block whose MLP is a routed expert MLP."""

    heads: int
    num_experts: int
    attention_fn: Callable
    capacity_factor: float = 1.25
    num_selected: int = 1
    dtype: Any = jnp.bfloat16
    expert_axis: Optional[str] = None

    @nn.compact
    def __call__(self, x):
        from petastorm_tpu.models.transformer import attention_sublayer
        x = attention_sublayer(x, self.heads, self.attention_fn, self.dtype)
        h = nn.LayerNorm(dtype=jnp.float32)(x).astype(self.dtype)
        return x + MoEMlp(num_experts=self.num_experts,
                          capacity_factor=self.capacity_factor,
                          num_selected=self.num_selected,
                          dtype=self.dtype,
                          expert_axis=self.expert_axis)(h)


class MoETransformerLM(nn.Module):
    """Decoder-only LM with routed-expert MLP blocks: tokens ``[B, T]`` -> logits
    ``[B, T, vocab]`` float32. Every ``moe_every``-th block is MoE (1 = all)."""

    vocab: int = 256
    embed: int = 64
    heads: int = 4
    layers: int = 2
    num_experts: int = 4
    capacity_factor: float = 1.25
    num_selected: int = 1
    moe_every: int = 1
    max_len: int = 8192
    dtype: Any = jnp.bfloat16
    attention_fn: Optional[Callable] = None
    expert_axis: Optional[str] = None
    remat: bool = False

    @nn.compact
    def __call__(self, tokens, positions=None):
        """``positions`` mirrors TransformerLM: optional [B, T] per-token position
        ids so packed batches restart each document at position 0."""
        from petastorm_tpu.models.transformer import Block, dense_causal_attention
        if self.embed % self.heads != 0:
            raise ValueError('embed={} must be divisible by heads={}'
                             .format(self.embed, self.heads))
        if tokens.shape[1] > self.max_len:
            raise ValueError('sequence length {} exceeds max_len={}'
                             .format(tokens.shape[1], self.max_len))
        attention_fn = self.attention_fn or dense_causal_attention
        # Same remat/naming treatment as TransformerLM: recompute block activations
        # in the backward, with explicit per-class names reproducing the auto scheme
        # so the param tree is identical with and without remat (the sown 'losses'
        # collection passes through nn.remat unchanged).
        dense_cls = nn.remat(Block) if self.remat else Block
        moe_cls = nn.remat(MoEBlock) if self.remat else MoEBlock
        x = nn.Embed(self.vocab, self.embed, dtype=self.dtype)(tokens)
        pos_table = nn.Embed(self.max_len, self.embed, dtype=self.dtype)
        if positions is None:
            x = x + pos_table(jnp.arange(tokens.shape[1]))[None]
        else:
            x = x + pos_table(positions)
        n_moe = n_dense = 0
        for i in range(self.layers):
            if (i + 1) % self.moe_every == 0:
                x = moe_cls(heads=self.heads, num_experts=self.num_experts,
                            capacity_factor=self.capacity_factor,
                            num_selected=self.num_selected,
                            attention_fn=attention_fn, dtype=self.dtype,
                            expert_axis=self.expert_axis,
                            name='MoEBlock_{}'.format(n_moe))(x)
                n_moe += 1
            else:
                x = dense_cls(heads=self.heads, attention_fn=attention_fn,
                              dtype=self.dtype,
                              name='Block_{}'.format(n_dense))(x)
                n_dense += 1
        x = nn.LayerNorm(dtype=jnp.float32)(x)
        return nn.Dense(self.vocab, dtype=jnp.float32)(x)
