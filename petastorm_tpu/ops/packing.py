"""Sequence packing: variable-length token rows -> fixed-shape bins with segments.

XLA compiles static shapes, so variable-length LM corpora either pad every row to the
max (wasting ``1 - mean/max`` of the FLOPs) or PACK — several documents per
fixed-length bin, with segment ids keeping attention and the LM loss from crossing
document boundaries. The reference has no analog (its NGram builds windows from
fixed-length rows); this is the TPU-first treatment of ragged text:

- **host side**: :func:`pack_sequences` (greedy first-fit, deterministic) runs inside
  the reader worker via :func:`make_packing_transform` — a ``TransformSpec`` for
  ``make_batch_reader``, so packing parallelizes across rowgroup workers and the
  loader ships only dense ``[n_bins, seq_len]`` columns;
- **device side**: :func:`segment_causal_attention` (inject as ``TransformerLM``'s
  ``attention_fn``) masks attention to (same segment AND causal AND not padding), and
  :func:`packed_next_token_loss` masks targets that would cross a boundary.

Note on positions: pass the packed ``<field>_positions`` column as the models'
``positions`` argument (``TransformerLM``/``MoETransformerLM`` accept explicit
per-token position ids) so every packed document's position embedding restarts at
0; without it the bin-global arange leaks positions across document boundaries.
"""

import jax
import jax.numpy as jnp
import numpy as np

_NEG_INF = -1e30


def pack_sequences(sequences, seq_len, dtype=np.int32):
    """Greedy first-fit packing of 1-D arrays into fixed-length bins.

    :param sequences: iterable of 1-D integer arrays, each with
        ``0 < len <= seq_len`` (longer sequences raise — split upstream).
    :param seq_len: bin length.
    :returns: dict with ``tokens [n_bins, seq_len]``, ``segments`` (1-based per-bin
        segment ids, 0 = padding), ``positions`` (offset within the segment) — all
        ``dtype``/int32 numpy arrays. Deterministic: first-fit in arrival order.
    """
    bins = []          # per bin: list of sequences
    space = []         # per bin: remaining capacity
    for i, seq in enumerate(sequences):
        seq = np.asarray(seq)
        if seq.ndim != 1:
            raise ValueError('sequence {} has ndim {} (expected 1)'.format(i, seq.ndim))
        if len(seq) == 0:
            continue
        if len(seq) > seq_len:
            raise ValueError('sequence {} has length {} > seq_len {}; split it '
                             'upstream'.format(i, len(seq), seq_len))
        for b, free in enumerate(space):
            if free >= len(seq):
                bins[b].append(seq)
                space[b] -= len(seq)
                break
        else:
            bins.append([seq])
            space.append(seq_len - len(seq))

    n_bins = max(1, len(bins))
    tokens = np.zeros((n_bins, seq_len), dtype=dtype)
    segments = np.zeros((n_bins, seq_len), dtype=np.int32)
    positions = np.zeros((n_bins, seq_len), dtype=np.int32)
    for b, seqs in enumerate(bins):
        offset = 0
        for seg_id, seq in enumerate(seqs, start=1):
            end = offset + len(seq)
            tokens[b, offset:end] = seq
            segments[b, offset:end] = seg_id
            positions[b, offset:end] = np.arange(len(seq))
            offset = end
    return {'tokens': tokens, 'segments': segments, 'positions': positions}


def make_packing_transform(field, seq_len, dtype=np.int32):
    """``TransformSpec`` packing a ragged ``field`` inside ``make_batch_reader``
    workers: each rowgroup batch of variable-length rows becomes ``[n_bins,
    seq_len]`` columns ``field``, ``<field>_segments``, ``<field>_positions``.
    Feed the reader to ``JaxDataLoader`` as usual — every shape downstream is
    static. (Packing is per rowgroup batch: bins never mix rowgroups, mirroring the
    NGram window locality rule.)"""
    import pandas as pd

    from petastorm_tpu.transform import TransformSpec

    seg_field = field + '_segments'
    pos_field = field + '_positions'

    def func(frame):
        values = list(frame[field])
        if values and isinstance(values[0], bytes):
            raise ValueError(
                'field {!r} arrived as raw bytes: make_batch_reader on a Unischema '
                'store emits codec-encoded values. Pack from a NATIVE Parquet '
                'list column (the make_batch_reader contract), or decode with '
                'make_reader upstream.'.format(field))
        packed = pack_sequences(values, seq_len, dtype=dtype)
        return pd.DataFrame({field: list(packed['tokens']),
                             seg_field: list(packed['segments']),
                             pos_field: list(packed['positions'])})

    return TransformSpec(
        func,
        edit_fields=[(field, dtype, (seq_len,), False),
                     (seg_field, np.int32, (seq_len,), False),
                     (pos_field, np.int32, (seq_len,), False)],
        selected_fields=[field, seg_field, pos_field])


def segment_mask(q_segments, k_segments, causal=True):
    """Attention mask ``[B, 1, Tq, Tk]`` (broadcasts over heads): same segment AND
    both positions non-padding AND (optionally) causal."""
    same = q_segments[:, None, :, None] == k_segments[:, None, None, :]
    valid = ((q_segments > 0)[:, None, :, None]
             & (k_segments > 0)[:, None, None, :])
    mask = jnp.logical_and(same, valid)
    if causal:
        t_q, t_k = q_segments.shape[1], k_segments.shape[1]
        tri = jnp.arange(t_q)[:, None] >= jnp.arange(t_k)[None, :]
        mask = jnp.logical_and(mask, tri[None, None])
    return mask


def masked_dense_attention(q, k, v, mask):
    """``[B, T, H, D]`` attention with an explicit ``[B, 1, Tq, Tk]`` mask (fp32
    scores, like ``ops.ring_attention.dense_attention``). Query positions with no
    valid key (padding) return zeros instead of NaN."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum('bqhd,bkhd->bhqk', q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    s = jnp.where(mask, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    any_valid = jnp.any(mask, axis=-1, keepdims=True)          # [B, 1, Tq, 1]
    p = jnp.where(any_valid, p, 0.0)
    return jnp.einsum('bhqk,bkhd->bqhd', p, v.astype(jnp.float32)).astype(q.dtype)


def segment_causal_attention(segments, use_flash=False, block_q='auto',
                             block_k='auto'):
    """Attention backend for packed batches — inject into ``TransformerLM``:

        model = TransformerLM(attention_fn=segment_causal_attention(batch['tokens_segments']))

    Tokens attend causally WITHIN their segment only; padding attends nowhere.
    ``use_flash`` routes through the Pallas segmented flash kernels
    (:func:`petastorm_tpu.ops.flash_attention.flash_attention_segmented`,
    O(T * block) memory; falls back to this dense path when shapes don't tile)."""
    if use_flash:
        from petastorm_tpu.ops.flash_attention import (_use_pallas,
                                                       flash_attention_segmented)

        def attention_fn(q, k, v):
            if not _use_pallas(q, k, block_q, block_k):
                # The flag promises the O(T*block) flash memory bound; a silent
                # dense fallback here would materialize [B, H, T, T] with no signal.
                import warnings
                warnings.warn(
                    'segment_causal_attention(use_flash=True): shapes {}x{} head_dim'
                    ' {} do not tile (need T % block == 0 and a head_dim that is a '
                    'multiple of 128 or wider than 128); '
                    'running the O(T^2) masked dense path instead.'.format(
                        q.shape[1], k.shape[1], q.shape[-1]), stacklevel=2)
            return flash_attention_segmented(q, k, v, segments, True,
                                             block_q, block_k)
        return attention_fn

    def attention_fn(q, k, v):
        return masked_dense_attention(q, k, v, segment_mask(segments, segments))
    return attention_fn


def packed_next_token_loss(logits, tokens, segments):
    """Causal LM loss over a packed batch: position ``t`` predicts ``t+1`` only when
    both lie in the SAME non-padding segment; the mean runs over valid predictions
    only."""
    if tokens.shape[1] < 2:
        raise ValueError('packed_next_token_loss needs seq_len >= 2 (got {})'
                         .format(tokens.shape[1]))
    valid = jnp.logical_and(segments[:, 1:] == segments[:, :-1],
                            segments[:, :-1] > 0).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, tokens[:, 1:, None].astype(jnp.int32),
                               axis=-1)[..., 0]
    return jnp.sum(nll * valid) / jnp.maximum(jnp.sum(valid), 1.0)
