"""Pallas TPU flash attention: O(T)-memory blockwise attention on the MXU.

Forward pass is a Pallas kernel (grid over [batch*heads, q-blocks, kv-blocks], online
log-sum-exp softmax accumulated in VMEM scratch) that also emits the per-row
log-sum-exp. The MXU takes the blocks in the inputs' own dtype with float32
accumulation: bf16 blocks contract as bf16 in one pass, the softmax probabilities
and dS are rounded to bf16 right before their products, and float32 inputs contract
at fp32 (``HIGHEST``). The softmax statistics, lse, delta and the accumulators are
float32 throughout. Backward is the flash backward: two Pallas kernels (dQ,
and dK/dV) that REMATERIALIZE the score blocks from Q/K and the saved LSE — the
[T, T] attention matrix never exists in any pass, so training memory is O(T * block),
sub-quadratic in sequence length.

The value head may differ from the query/key head (multi-head latent attention:
q/k 192, v 128); a head that is not lane-aligned but wider than one lane tile is
taken whole by every block. Falls back to the XLA path
(:func:`petastorm_tpu.ops.ring_attention.dense_attention`) when shapes don't tile
(T % block != 0, a head narrower than 128 lanes and not aligned). The kernels compile
for the TPU and run in Pallas interpret mode on the CPU backend only
(:func:`pallas_interpret`), so CPU tests exercise the same kernel logic.

Under a causal mask only the blocks that straddle the diagonal build the mask; those
wholly below it skip it, and those wholly above it do no work and fetch nothing: their
index maps name the block the step before already holds.

Per-row operands keep the TPU block rule (last two block dims divisible by (8, 128)
or equal to the array's): the log-sum-exp, the backward's ``delta`` and the query
segment ids ride as ``[*, T, 1]`` columns (block ``(1, block_q, 1)``), the key
segment ids as ``[B, 1, T]`` rows (block ``(1, 1, block_k)``), so every in-kernel
broadcast is against a ``[Bq, 1]`` column or a ``[1, Bk]`` row with no transpose.

No reference analog (petastorm is data-layer only; SURVEY.md §5.7) — this is the compute
side of the long-context story next to :mod:`petastorm_tpu.ops.ring_attention`.
"""

import functools

import jax
import jax.numpy as jnp

_NEG_INF = -1e30
_LANE = 128


def pallas_interpret():
    """Whether this process's Pallas kernels run in interpret mode: on the CPU
    backend only. A TPU compiles them; any other backend is an error, never a
    silent interpreter run."""
    backend = jax.default_backend()
    if backend == 'tpu':
        return False
    if backend == 'cpu':
        return True
    raise RuntimeError('Pallas TPU kernels need a tpu (compiled) or cpu '
                       '(interpret) backend, not {!r}'.format(backend))


def _compiler_params(*dimension_semantics):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(dimension_semantics=dimension_semantics)


def _dot_precision(dtype):
    """Contraction precision of the kernels' matmuls, whose operands are in the
    inputs' dtype: bf16 blocks reach the MXU as bf16 with float32 accumulation at
    the default precision; float32 inputs ask Mosaic for fp32 contraction
    (``HIGHEST``), since its default takes f32 operands in one bf16 pass."""
    if jnp.dtype(dtype) == jnp.float32:
        return jax.lax.Precision.HIGHEST
    return jax.lax.Precision.DEFAULT


def _dot(a, b, contract, precision):
    """``dot_general`` contracting dims ``contract`` with f32 accumulation."""
    return jax.lax.dot_general(a, b, (contract, ((), ())), precision=precision,
                               preferred_element_type=jnp.float32)


def _block_segment_mask(qseg, kseg):
    """[Bq, 1] column, [1, Bk] row of int32 ids -> [Bq, Bk] bool: same packed
    segment, both non-padding (``ops.packing`` convention: 0 = padding)."""
    return (qseg == kseg) & (qseg > 0) & (kseg > 0)


def _causal_mask(x, q_first, k_first, fill):
    """``x`` [Bq, Bk] where query ``q_first + r`` may see key ``k_first + c``,
    else ``fill``."""
    shape = x.shape
    diag = (jax.lax.broadcasted_iota(jnp.int32, shape, 0)
            - jax.lax.broadcasted_iota(jnp.int32, shape, 1))
    return jnp.where(diag >= k_first - q_first, x, fill)


def _fold_live_blocks(fold, causal, q_first, k_first, block_q, block_k):
    """Run one grid step's ``fold(masked)``. Without ``causal`` every block folds
    unmasked. With it, a block whose keys all precede its queries folds without
    the causal mask, one that straddles the diagonal with it, and one above it
    not at all (the index maps, :func:`_causal_kv_block` and
    :func:`_causal_q_block`, keep its operands from being fetched)."""
    from jax.experimental import pallas as pl
    if not causal:
        fold(False)
        return
    live = k_first <= q_first + (block_q - 1)
    below = k_first + (block_k - 1) <= q_first

    @pl.when(below)
    def _():
        fold(False)

    @pl.when(jnp.logical_and(live, jnp.logical_not(below)))
    def _():
        fold(True)


def _causal_kv_block(i, j, block_q, block_k):
    """K-side block index of the forward's and dq's grid step (q-block ``i``,
    k-block ``j``) under a causal mask: past q-block ``i``'s last live k-block the
    step names that block again, already resident, so nothing is fetched."""
    return jnp.minimum(j, jax.lax.div(i * block_q + (block_q - 1), block_k))


def _causal_q_block(i, j, block_q, block_k):
    """Q-side block index of the dk/dv grid step (k-block ``i``, q-block ``j``)
    under a causal mask: before k-block ``i``'s first live q-block the step names
    that block, which the first live step then finds resident."""
    return jnp.maximum(j, jax.lax.div(i * block_k, block_q))


def _flash_kernel(q_ref, k_ref, v_ref, *rest, causal, segmented, block_q, block_k,
                  scale, precision):
    """One (bh, qi, ki) grid step: fold K/V block ``ki`` into the online softmax
    accumulator for Q block ``qi``. With ``segmented``, two extra int32 refs carry
    the packed-segment ids and attention is confined within segments."""
    from jax.experimental import pallas as pl

    if segmented:
        qseg_ref, kseg_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, lse_ref, m_scr, l_scr, acc_scr = rest

    # program_id must be read at kernel top level: inside a pl.when closure it does not
    # substitute under the CPU interpreter.
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _fold(masked):
        v = v_ref[0]                                           # [Bk, D]
        s = _dot(q_ref[0], k_ref[0], ((1,), (1,)), precision) * scale  # [Bq, Bk]
        if masked:
            s = _causal_mask(s, qi * block_q, ki * block_k, _NEG_INF)
        if segmented:
            s = jnp.where(_block_segment_mask(qseg_ref[0], kseg_ref[0]), s,
                          _NEG_INF)
        m_prev = m_scr[:, :1]                                  # [Bq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)                                 # [Bq, Bk]
        if segmented:
            # A fully-masked row has every s at _NEG_INF and would get p == 1
            # everywhere (exp(0)); zero those so empty rows accumulate nothing.
            p = jnp.where(s > _NEG_INF / 2, p, 0.0)
        corr = jnp.exp(m_prev - m_new)                         # [Bq, 1]
        l_new = l_scr[:, :1] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * corr + _dot(p.astype(v.dtype), v, ((1,), (0,)),
                                              precision)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    _fold_live_blocks(_fold, causal, qi * block_q, ki * block_k, block_q, block_k)

    @pl.when(ki == nk - 1)
    def _finalize():
        if segmented:
            l = l_scr[:, :1]
            nonempty = l > 0
            # Padding rows attend to nothing: emit zeros, and an lse of 0 so the
            # backward's replay exp(s - lse) underflows to 0 instead of NaN.
            o_ref[0] = jnp.where(
                nonempty, acc_scr[:] / jnp.where(nonempty, l, 1.0), 0.0
            ).astype(o_ref.dtype)
            lse_ref[0] = jnp.where(nonempty, m_scr[:, :1] + jnp.log(
                jnp.where(nonempty, l, 1.0)), 0.0)
        else:
            o_ref[0] = (acc_scr[:] / l_scr[:, :1]).astype(o_ref.dtype)
            # log-sum-exp per query row: the backward's softmax replay key
            lse_ref[0] = m_scr[:, :1] + jnp.log(l_scr[:, :1])


def _inner_block(clamp, causal, block_q, block_k):
    """``(i, j) -> block`` on a grid's inner axis: ``clamp``'s under a causal mask,
    else ``j``."""
    if causal:
        return lambda i, j: clamp(i, j, block_q, block_k)
    return lambda i, j: j


def _segment_operands(segments, block_q, block_k, heads, kv_outer, inner):
    """Block specs and operands for the [B, T] packed-segment ids (shared across
    the ``heads`` interleaved into the BH dim): a [B, T, 1] column for the query
    block and a [B, 1, T] row for the key block. The grid is (bh, q-block,
    k-block), or (bh, k-block, q-block) with ``kv_outer``; ``inner`` maps
    ``(i, j)`` to the inner axis' block, as the kernel's other operands on it do."""
    from jax.experimental import pallas as pl
    h = heads
    if kv_outer:
        qmap = lambda b, i, j: (b // h, inner(i, j), 0)  # noqa: E731
        kmap = lambda b, i, j: (b // h, 0, i)  # noqa: E731
    else:
        qmap = lambda b, i, j: (b // h, i, 0)  # noqa: E731
        kmap = lambda b, i, j: (b // h, 0, inner(i, j))  # noqa: E731
    specs = [pl.BlockSpec((1, block_q, 1), qmap),
             pl.BlockSpec((1, 1, block_k), kmap)]
    return specs, [segments[:, :, None], segments[:, None, :]]


def _flash_forward(q, k, v, causal, block_q, block_k, interpret, segments=None,
                   heads=None):
    """q/k: [BH, T, D], v: [BH, T, Dv] -> (o: [BH, T, Dv], lse: [BH, T, 1]
    float32). ``segments`` is the [B, T] int32 packed-segment array."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, t, d = q.shape
    dv = v.shape[-1]
    tk = k.shape[1]
    nq, nk = t // block_q, tk // block_k
    scale = d ** -0.5
    segmented = segments is not None
    kernel = functools.partial(_flash_kernel, causal=causal, segmented=segmented,
                               block_q=block_q, block_k=block_k, scale=scale,
                               precision=_dot_precision(q.dtype))
    grid = (bh, nq, nk)
    kv = _inner_block(_causal_kv_block, causal, block_q, block_k)
    kspec = pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, kv(i, j), 0))
    vspec = pl.BlockSpec((1, block_k, dv), lambda b, i, j: (b, kv(i, j), 0))
    in_specs = [pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)), kspec, vspec]
    operands = [q, k, v]
    if segmented:
        seg_specs, seg_operands = _segment_operands(segments, block_q, block_k,
                                                    heads, False, kv)
        in_specs += seg_specs
        operands += seg_operands
    return pl.pallas_call(
        kernel,
        out_shape=[jax.ShapeDtypeStruct((bh, t, dv), q.dtype),
                   jax.ShapeDtypeStruct((bh, t, 1), jnp.float32)],
        grid=grid,
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((1, block_q, dv), lambda b, i, j: (b, i, 0)),
                   pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0))],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANE), jnp.float32),   # running max (lane-replicated)
            pltpu.VMEM((block_q, _LANE), jnp.float32),   # running denominator
            pltpu.VMEM((block_q, dv), jnp.float32),      # output accumulator
        ],
        compiler_params=_compiler_params('parallel', 'parallel', 'arbitrary'),
        interpret=interpret,
    )(*operands)


def _rematerialized_p_ds(q, k, v, do, lse, delta, q_first, k_first, masked, scale,
                         precision, seg_mask=None):
    """Shared backward-block math: replay P from (Q, K, LSE), form dS.

    Returns (p, ds), both [Bq, Bk] fp32. ``delta = rowsum(dO * O)`` is the softmax
    jacobian's diagonal correction (flash-attention backward identity); ``lse``
    and ``delta`` are [Bq, 1] columns. ``masked`` applies the causal mask of the
    block whose first query and key are ``q_first`` and ``k_first``.
    ``seg_mask`` re-applies the forward's segment confinement (the replayed
    exp(s - lse) is only meaningful where the forward attended)."""
    s = _dot(q, k, ((1,), (1,)), precision) * scale
    p = jnp.exp(s - lse)                                        # [Bq, Bk]
    if masked:
        p = _causal_mask(p, q_first, k_first, 0.0)
    if seg_mask is not None:
        p = jnp.where(seg_mask, p, 0.0)
    dp = _dot(do, v, ((1,), (1,)), precision)                  # [Bq, Bk]
    ds = p * (dp - delta)
    return p, ds


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                         causal, segmented, block_q, block_k, scale, precision):
    """Grid (bh, qi, ki): accumulate dQ for q-block qi over all k-blocks."""
    from jax.experimental import pallas as pl

    if segmented:
        qseg_ref, kseg_ref, dq_ref, dq_scr = rest
    else:
        dq_ref, dq_scr = rest

    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def _fold(masked):
        k = k_ref[0]
        seg_mask = (_block_segment_mask(qseg_ref[0], kseg_ref[0])
                    if segmented else None)
        _, ds = _rematerialized_p_ds(q_ref[0], k, v_ref[0], do_ref[0], lse_ref[0],
                                     delta_ref[0], qi * block_q, ki * block_k, masked,
                                     scale, precision, seg_mask)
        dq_scr[:] = dq_scr[:] + _dot(ds.astype(k.dtype), k, ((1,), (0,)), precision)

    _fold_live_blocks(_fold, causal, qi * block_q, ki * block_k, block_q, block_k)

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0] = (dq_scr[:] * scale).astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                          causal, segmented, block_q, block_k, scale, precision):
    """Grid (bh, ki, qi): accumulate dK/dV for k-block ki over all q-blocks."""
    from jax.experimental import pallas as pl

    if segmented:
        qseg_ref, kseg_ref, dk_ref, dv_ref, dk_scr, dv_scr = rest
    else:
        dk_ref, dv_ref, dk_scr, dv_scr = rest

    ki = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _fold(masked):
        q, do = q_ref[0], do_ref[0]
        seg_mask = (_block_segment_mask(qseg_ref[0], kseg_ref[0])
                    if segmented else None)
        p, ds = _rematerialized_p_ds(q, k_ref[0], v_ref[0], do, lse_ref[0],
                                     delta_ref[0], qi * block_q, ki * block_k, masked,
                                     scale, precision, seg_mask)
        dv_scr[:] = dv_scr[:] + _dot(p.astype(do.dtype), do, ((0,), (0,)), precision)
        dk_scr[:] = dk_scr[:] + _dot(ds.astype(q.dtype), q, ((0,), (0,)), precision)

    _fold_live_blocks(_fold, causal, qi * block_q, ki * block_k, block_q, block_k)

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = (dk_scr[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _flash_backward(q, k, v, o, lse, do, causal, block_q, block_k, interpret,
                    segments=None, heads=None):
    """q/k: [BH, T, D], v/o/do: [BH, T, Dv], lse: [BH, T, 1] -> (dq, dk, dv),
    blockwise (no [T, T])."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, t, d = q.shape
    dv = v.shape[-1]
    nq, nk = t // block_q, t // block_k
    scale = d ** -0.5
    segmented = segments is not None
    precision = _dot_precision(q.dtype)
    # Softmax jacobian diagonal: delta_i = sum_d dO_id * O_id (O(T*D), no score matrix).
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1,
                    keepdims=True)                                # [BH, T, 1]

    kv = _inner_block(_causal_kv_block, causal, block_q, block_k)
    qspec = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0))
    kspec = pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, kv(i, j), 0))
    vspec = pl.BlockSpec((1, block_k, dv), lambda b, i, j: (b, kv(i, j), 0))
    dospec = pl.BlockSpec((1, block_q, dv), lambda b, i, j: (b, i, 0))
    qcol = pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0))

    dq_in_specs = [qspec, kspec, vspec, dospec, qcol, qcol]
    dq_operands = [q, k, v, do, lse, delta]
    if segmented:
        seg_specs, seg_operands = _segment_operands(segments, block_q, block_k,
                                                    heads, False, kv)
        dq_in_specs += seg_specs
        dq_operands += seg_operands
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, causal=causal, segmented=segmented,
                          block_q=block_q, block_k=block_k, scale=scale,
                          precision=precision),
        out_shape=jax.ShapeDtypeStruct((bh, t, d), q.dtype),
        grid=(bh, nq, nk),
        in_specs=dq_in_specs,
        out_specs=qspec,
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=_compiler_params('parallel', 'parallel', 'arbitrary'),
        interpret=interpret,
    )(*dq_operands)

    # dK/dV iterate the OTHER way: outer over k-blocks, inner over q-blocks.
    qb = _inner_block(_causal_q_block, causal, block_q, block_k)
    kspec_o = pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, i, 0))
    vspec_o = pl.BlockSpec((1, block_k, dv), lambda b, i, j: (b, i, 0))
    qspec_i = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, qb(i, j), 0))
    dospec_i = pl.BlockSpec((1, block_q, dv), lambda b, i, j: (b, qb(i, j), 0))
    qcol_i = pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, qb(i, j), 0))
    dkv_in_specs = [qspec_i, kspec_o, vspec_o, dospec_i, qcol_i, qcol_i]
    dkv_operands = [q, k, v, do, lse, delta]
    if segmented:
        seg_specs, seg_operands = _segment_operands(segments, block_q, block_k,
                                                    heads, True, qb)
        dkv_in_specs += seg_specs
        dkv_operands += seg_operands
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, causal=causal, segmented=segmented,
                          block_q=block_q, block_k=block_k, scale=scale,
                          precision=precision),
        out_shape=[jax.ShapeDtypeStruct((bh, t, d), k.dtype),
                   jax.ShapeDtypeStruct((bh, t, dv), v.dtype)],
        grid=(bh, nk, nq),
        in_specs=dkv_in_specs,
        out_specs=[kspec_o, vspec_o],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, dv), jnp.float32)],
        compiler_params=_compiler_params('parallel', 'parallel', 'arbitrary'),
        interpret=interpret,
    )(*dkv_operands)
    return dq, dk, dv


def _head_fits(d):
    """Whether the kernels take a head of ``d`` lanes: lane-aligned, or wider than
    one lane tile, its blocks then spanning the array's whole last dimension (which
    the TPU block rule allows; MLA's 192-wide q/k head is one). A narrower
    unaligned head keeps the dense path."""
    return d % _LANE == 0 or d > _LANE


def _tiles(t, d, block_q, block_k):
    return t % block_q == 0 and t % block_k == 0 and _head_fits(d)


# 'auto' preference order: the largest that divides T. On one TPU v5e (bf16,
# BH 32, T 2,048, D 128, causal) the forward, dq and dk/dv kernels took 1.88 ms
# together at 512 x 512 tiles against 3.53 ms at 256 x 256; 128 widens Pallas
# coverage to shapes such as T = 384.
_BLOCK_CANDIDATES = (512, 256, 128)


def _resolve_blocks(t, block_q, block_k):
    """Turn ``'auto'`` block sizes into concrete tile sizes for sequence
    length ``t``. Deterministic in (t, request), so the custom-vjp forward and
    backward always resolve identically. When nothing divides ``t`` the first
    candidate stays as a placeholder that fails ``_tiles``, and the dense path
    runs, exactly like an explicit non-dividing request."""
    def one(req):
        if req == 'auto':
            return next((c for c in _BLOCK_CANDIDATES if t % c == 0),
                        _BLOCK_CANDIDATES[0])
        return req
    return one(block_q), one(block_k)


def _dispatch(q, k, block_q, block_k, v=None):
    """Single resolve-then-decide point shared by every fwd/bwd path:
    ``(use_pallas, resolved_block_q, resolved_block_k)``. ``v``, where given,
    may have a value head other than q's and k's."""
    b, t, h, d = q.shape
    block_q, block_k = _resolve_blocks(t, block_q, block_k)
    value_fits = v is None or _head_fits(v.shape[-1])
    return (_tiles(t, d, block_q, block_k) and value_fits and t == k.shape[1],
            block_q, block_k)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(q, k, v, causal=False, block_q='auto', block_k='auto'):
    """Flash attention over ``[B, T, H, D]`` inputs (same layout as
    :func:`~petastorm_tpu.ops.ring_attention.dense_attention`); ``v`` may have a
    head ``Dv`` of its own, and the result is then ``[B, T, H, Dv]``. Exact; both passes run
    as Pallas TPU kernels when shapes tile (XLA dense fallback otherwise), with
    O(T * block) memory in forward AND backward. Block sizes default to
    ``'auto'``: the largest of 512, 256 and 128 that divides T — pass ints to
    pin them (e.g. from a tile-size sweep)."""
    return _attention_impl(q, k, v, causal, block_q, block_k)


def _use_pallas(q, k, block_q, block_k):
    """Dispatch predicate only (bench.py asserts flash_no_fallback with it);
    kernel paths use _dispatch to also get the resolved block sizes."""
    return _dispatch(q, k, block_q, block_k)[0]


def _to_bh(x):
    b, t, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)


def _from_bh(x, b, h):
    bh, t, d = x.shape
    return x.reshape(b, h, t, d).transpose(0, 2, 1, 3)


def _attention_impl(q, k, v, causal, block_q, block_k):
    return _fwd(q, k, v, causal, block_q, block_k)[0]


def _fwd(q, k, v, causal, block_q, block_k):
    from petastorm_tpu.ops.ring_attention import dense_attention
    use, block_q, block_k = _dispatch(q, k, block_q, block_k, v)
    if not use:
        return dense_attention(q, k, v, causal=causal), (q, k, v, None, None, None)
    b, t, h, d = q.shape
    interpret = pallas_interpret()
    # Residuals stay in the kernels' [BH, T, D] layout so the backward re-uses the
    # forward's transposes instead of redoing them.
    q_bh, k_bh, v_bh = _to_bh(q), _to_bh(k), _to_bh(v)
    o_bh, lse = _flash_forward(q_bh, k_bh, v_bh, causal, block_q, block_k, interpret)
    return _from_bh(o_bh, b, h), (q_bh, k_bh, v_bh, o_bh, lse, (b, h))


def _bwd(causal, block_q, block_k, residuals, g):
    q_bh, k_bh, v_bh, o_bh, lse, bh_dims = residuals
    if o_bh is None:
        # Fallback shapes: recompute through the dense path (O(T^2) memory there too).
        from petastorm_tpu.ops.ring_attention import dense_attention
        _, vjp = jax.vjp(lambda a, b_, c: dense_attention(a, b_, c, causal=causal),
                         q_bh, k_bh, v_bh)
        return vjp(g)
    b, h = bh_dims
    interpret = pallas_interpret()
    block_q, block_k = _resolve_blocks(q_bh.shape[1], block_q, block_k)
    dq, dk, dv = _flash_backward(q_bh, k_bh, v_bh, o_bh, lse, _to_bh(g), causal,
                                 block_q, block_k, interpret)
    return _from_bh(dq, b, h), _from_bh(dk, b, h), _from_bh(dv, b, h)


flash_attention.defvjp(_fwd, _bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def flash_attention_segmented(q, k, v, segments, causal=False, block_q='auto',
                              block_k='auto'):
    """Flash attention confined to packed-sequence segments: ``[B, T, H, D]``
    inputs plus ``segments [B, T]`` int32 (``ops.packing`` convention — 0 is
    padding, documents numbered from 1; padding rows emit zeros). Same Pallas
    kernels as :func:`flash_attention` with the segment mask fused into every
    block, so packed single-chip training keeps the O(T * block) memory bound;
    falls back to the masked XLA dense path when shapes don't tile. Block
    sizes default to ``'auto'`` (see :func:`flash_attention`)."""
    return _seg_fwd(q, k, v, segments, causal, block_q, block_k)[0]


def _seg_fwd(q, k, v, segments, causal, block_q, block_k):
    use, block_q, block_k = _dispatch(q, k, block_q, block_k, v)
    if not use:
        from petastorm_tpu.ops.packing import masked_dense_attention, segment_mask
        mask = segment_mask(segments, segments, causal=causal)
        return (masked_dense_attention(q, k, v, mask),
                (q, k, v, segments, None, None, None))
    b, t, h, d = q.shape
    interpret = pallas_interpret()
    q_bh, k_bh, v_bh = _to_bh(q), _to_bh(k), _to_bh(v)
    o_bh, lse = _flash_forward(q_bh, k_bh, v_bh, causal, block_q, block_k,
                               interpret, segments=segments, heads=h)
    return _from_bh(o_bh, b, h), (q_bh, k_bh, v_bh, segments, o_bh, lse, (b, h))


def _seg_zero_cotangent(segments):
    import numpy as np
    return np.zeros(segments.shape, dtype=jax.dtypes.float0)


def _seg_bwd(causal, block_q, block_k, residuals, g):
    q_bh, k_bh, v_bh, segments, o_bh, lse, bh_dims = residuals
    if o_bh is None:
        from petastorm_tpu.ops.packing import masked_dense_attention, segment_mask
        mask = segment_mask(segments, segments, causal=causal)
        _, vjp = jax.vjp(lambda a, b_, c: masked_dense_attention(a, b_, c, mask),
                         q_bh, k_bh, v_bh)
        return vjp(g) + (_seg_zero_cotangent(segments),)
    b, h = bh_dims
    interpret = pallas_interpret()
    block_q, block_k = _resolve_blocks(q_bh.shape[1], block_q, block_k)
    dq, dk, dv = _flash_backward(q_bh, k_bh, v_bh, o_bh, lse, _to_bh(g), causal,
                                 block_q, block_k, interpret, segments=segments,
                                 heads=h)
    return (_from_bh(dq, b, h), _from_bh(dk, b, h), _from_bh(dv, b, h),
            _seg_zero_cotangent(segments))


flash_attention_segmented.defvjp(_seg_fwd, _seg_bwd)
