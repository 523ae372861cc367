"""Ring attention: exact attention over a sequence sharded across a mesh axis.

The reference has no model-side sequence parallelism (SURVEY.md §5.7: NGram is pure data
windowing); long-context consumers of this framework need the compute side too. This is
blockwise/flash-style streaming attention where each device holds one sequence shard of
K/V and the shards rotate around the ring via ``lax.ppermute`` (ICI neighbor exchange),
with an online log-sum-exp softmax so the result is exact — the standard ring-attention
construction (Liu et al., 2023), written for XLA: static shapes, ``lax.fori_loop``, no
host control flow.

Use inside ``shard_map`` over a mesh axis carrying the sequence dimension; or call
:func:`ring_attention_sharded` to get the shard_map wrapper built for you.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax

_NEG_INF = -1e30


def _block_attn(q, k, v, bias):
    """One blockwise attention contribution: returns (scores_max, exp-weights sum,
    weighted values) for the online-softmax accumulator. Shapes: q [B,Tq,H,D],
    k/v [B,Tb,H,D], bias broadcastable to [B,H,Tq,Tb]."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum('bqhd,bkhd->bhqk', q, k) * scale
    if bias is not None:
        s = s + bias
    m = jnp.max(s, axis=-1)                                    # [B,H,Tq]
    p = jnp.exp(s - m[..., None])                              # [B,H,Tq,Tb]
    # A fully-masked row has every s at _NEG_INF, making exp(s - m) == 1 — zero
    # those entries so a masked-out block contributes nothing to the accumulator
    # (segment masking can fully mask a block; plain causal never does).
    p = p * (s > _NEG_INF / 2)
    l = jnp.sum(p, axis=-1)                                    # [B,H,Tq]
    o = jnp.einsum('bhqk,bkhd->bqhd', p, v)                    # [B,Tq,H,D]
    return m, l, o


def ring_attention(q, k, v, axis_name, causal=False, segments=None):
    """Exact attention with K/V ring-rotated over ``axis_name``. Must run inside
    ``shard_map``; every array is the per-device shard ``[B, T_local, H, D]``. The global
    sequence is the concatenation of shards in ring order.

    :param causal: apply a causal mask over GLOBAL positions (shard offsets accounted
        for), so the result equals dense causal attention on the gathered sequence.
    :param segments: optional ``[B, T_local]`` int32 shard of packed-sequence segment
        ids (``ops.packing`` convention: 0 = padding, documents numbered from 1).
        Attention is confined to same-segment pairs; padding positions attend to
        nothing and return zeros. Segment ids rotate around the ring with their K/V
        blocks, so packing composes with sequence parallelism.
    """
    axis_size = lax.psum(1, axis_name)
    my_index = lax.axis_index(axis_name)
    t_local = q.shape[1]
    q_positions = my_index * t_local + jnp.arange(t_local)      # global positions
    has_segments = segments is not None

    def make_bias(source_index, k_seg_blk):
        if not (causal or has_segments):
            return None
        allow = jnp.ones((1, 1, t_local, t_local), dtype=bool)  # [B?, 1, Tq, Tb]
        if causal:
            k_positions = source_index * t_local + jnp.arange(t_local)
            allow = allow & (q_positions[:, None]
                             >= k_positions[None, :])[None, None]
        if has_segments:
            # ONE definition of the segment/padding mask (ops.packing convention).
            from petastorm_tpu.ops.packing import segment_mask
            allow = allow & segment_mask(segments, k_seg_blk, causal=False)
        return jnp.where(allow, 0.0, _NEG_INF)

    def body(step, carry):
        if has_segments:
            o_acc, l_acc, m_acc, k_blk, v_blk, k_seg_blk = carry
        else:
            o_acc, l_acc, m_acc, k_blk, v_blk = carry
            k_seg_blk = None
        # K/V block currently held arrived from (my_index - step) around the ring.
        source_index = (my_index - step) % axis_size
        m_blk, l_blk, o_blk = _block_attn(q, k_blk, v_blk,
                                          make_bias(source_index, k_seg_blk))
        # Online softmax merge (flash-attention accumulator).
        m_new = jnp.maximum(m_acc, m_blk)
        corr_acc = jnp.exp(m_acc - m_new)
        corr_blk = jnp.exp(m_blk - m_new)
        l_new = l_acc * corr_acc + l_blk * corr_blk
        o_new = (o_acc * jnp.swapaxes(corr_acc, 1, 2)[..., None]
                 + o_blk * jnp.swapaxes(corr_blk, 1, 2)[..., None])
        # Rotate K/V to the next device; overlaps with the next block's compute on TPU.
        perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]
        k_next = lax.ppermute(k_blk, axis_name, perm)
        v_next = lax.ppermute(v_blk, axis_name, perm)
        if has_segments:
            # Segment ids travel WITH their K/V block; unsegmented calls skip this
            # collective entirely.
            seg_next = lax.ppermute(k_seg_blk, axis_name, perm)
            return o_new, l_new, m_new, k_next, v_next, seg_next
        return o_new, l_new, m_new, k_next, v_next

    b, t, h, d = q.shape
    o0 = jnp.zeros((b, t, h, d), dtype=jnp.float32)
    l0 = jnp.zeros((b, h, t), dtype=jnp.float32)
    m0 = jnp.full((b, h, t), _NEG_INF, dtype=jnp.float32)
    carry = (o0, l0, m0, k.astype(jnp.float32), v.astype(jnp.float32))
    if has_segments:
        carry = carry + (segments,)
    out = lax.fori_loop(0, axis_size, body, carry)
    o, l = out[0], out[1]
    # Padding rows attend to nothing (l == 0): emit zeros, not NaN.
    l = jnp.swapaxes(l, 1, 2)[..., None]
    o = jnp.where(l > 0, o / jnp.where(l > 0, l, 1.0), 0.0)
    return o.astype(q.dtype)


def ring_attention_sharded(mesh, seq_axis, causal=False, with_segments=False,
                           batch_axis=None):
    """Build a jittable ``fn(q, k, v)`` — or ``fn(q, k, v, segments)`` when
    ``with_segments`` — running ring attention with the sequence dimension sharded
    over ``mesh[seq_axis]``. ``batch_axis`` optionally shards the batch dimension
    (dp+sp); default replicates it. Inputs/outputs are GLOBAL arrays of shape
    [B, T, H, D] (segments [B, T] int32, ``ops.packing`` convention)."""
    from jax.sharding import PartitionSpec as P

    spec = P(batch_axis, seq_axis, None, None)
    inner = functools.partial(ring_attention, axis_name=seq_axis, causal=causal)
    if with_segments:
        def with_seg(q, k, v, segments):
            return inner(q, k, v, segments=segments)

        return jax.jit(jax.shard_map(
            with_seg, mesh=mesh,
            in_specs=(spec, spec, spec, P(batch_axis, seq_axis)), out_specs=spec,
            check_vma=False))
    return jax.jit(jax.shard_map(inner, mesh=mesh, in_specs=(spec, spec, spec),
                                 out_specs=spec, check_vma=False))


def dense_attention(q, k, v, causal=False):
    """Reference single-device attention (for testing ring_attention exactness)."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum('bqhd,bkhd->bhqk', q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        t_q, t_k = s.shape[-2], s.shape[-1]
        mask = jnp.arange(t_q)[:, None] >= jnp.arange(t_k)[None, :]
        s = jnp.where(mask[None, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum('bhqk,bkhd->bqhd', p, v.astype(jnp.float32)).astype(q.dtype)
