"""Raw-payload decode kernels for the device-resident decode tail.

When a reader ships codec payloads raw (``make_reader(device_decode_fields=...)``,
docs/performance.md "Device-resident decode tail"), the loader uploads compressed
or packed bytes and the decode math runs on the accelerator. Two kernel families
live here:

- **npy-unpack** (:func:`bitcast_rows`, :func:`unpack_npy_rows`): a packed
  ``(n, stride)`` uint8 byte matrix of equal-layout ``.npy`` payloads becomes a
  typed ``(n,) + shape`` array through static slices + ``bitcast_convert_type``
  — pure view-level work XLA fuses into the consuming program, matching
  ``jax.device_put``'s dtype canonicalization exactly (under x32, int64/uint64
  land as the little-endian low word).
- **deflate-lite** (:func:`parse_stored_deflate_layout`, :func:`plan_stored_batch`,
  :func:`stored_inflate`): raw-deflate streams whose every block is *stored*
  (BTYPE=00 — what ``CompressedNdarrayCodec(compresslevel=0)`` writes; at
  zlib's default level a member always opens with a Huffman block, because
  the ``.npy`` header compresses) are just framed memcpys; the host parses the 5-byte block
  headers into a segment table and one XLA gather performs the copy on
  device. Streams with Huffman-coded blocks return ``None`` from the parser —
  entropy decode is bit-serial and stays on the host (the same split
  ``ops/image_decode.py`` documents for JPEG).

The copy is an XLA gather, not a Pallas kernel: stored payloads start at
arbitrary byte offsets, and Mosaic can only slice a uint8 HBM ref at multiples
of its 1024-byte tiling, so a DMA kernel cannot express the copy.
"""

from __future__ import annotations

from typing import Any, List, Optional, Tuple

import numpy as np

# ------------------------------------------------------------------ npy unpack

def bitcast_rows(buf: Any, dtype_str: str, row_shape: Tuple[int, ...],
                 x64: Optional[bool] = None) -> Any:
    """Reinterpret a packed ``(n, stride)`` uint8 byte matrix as a typed
    ``(n,) + row_shape`` array on device.

    ``dtype_str`` is the numpy dtype string of the stored payload (little-endian
    or byteorder-free). The result matches what ``jax.device_put`` of the
    host-decoded array would produce: under x32 (``x64=False``), 8-byte integer
    payloads canonicalize to their low 4-byte word (little-endian), and
    ``float64`` payloads are rejected — the rounding conversion cannot be
    expressed without 64-bit types, so callers must keep such fields on the
    host path."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    if x64 is None:
        x64 = bool(jax.config.jax_enable_x64)
    dtype = np.dtype(dtype_str)
    n = buf.shape[0]
    if dtype.kind == 'f' and dtype.itemsize == 8 and not x64:
        raise ValueError('float64 payloads cannot be unpacked under x32; '
                         'keep this field on the host decode path')
    if dtype == np.uint8:
        arr = buf
    elif dtype == np.bool_:
        arr = buf != 0
    elif dtype.itemsize == 1:
        arr = lax.bitcast_convert_type(buf, jnp.dtype(dtype))
    elif dtype.itemsize == 8 and dtype.kind in 'iu' and not x64:
        words = lax.bitcast_convert_type(buf.reshape(n, -1, 4), jnp.uint32)
        low = words.reshape(n, -1, 2)[:, :, 0]  # little-endian low word
        target = jnp.int32 if dtype.kind == 'i' else jnp.uint32
        arr = lax.bitcast_convert_type(low, target)
    else:
        arr = lax.bitcast_convert_type(buf.reshape(n, -1, dtype.itemsize),
                                       jnp.dtype(dtype))
    return arr.reshape((n,) + tuple(row_shape))


def unpack_npy_rows(packed: Any, header_len: int, dtype_str: str,
                    row_shape: Tuple[int, ...],
                    x64: Optional[bool] = None) -> Any:
    """``(n, blob_len)`` uint8 matrix of equal-header ``.npy`` blobs -> typed
    ``(n,) + row_shape`` array: a static slice drops the shared ``header_len``
    prefix, then :func:`bitcast_rows` reinterprets the payload region. The
    header is parsed ONCE on the host (it is identical across rows for a
    fixed-shape field); the device never sees Python parsing."""
    return bitcast_rows(packed[:, header_len:], dtype_str, row_shape, x64=x64)


# ---------------------------------------------------------------- deflate-lite

def parse_stored_deflate_layout(frame: Any) -> Optional[List[Tuple[int, int]]]:
    """Scan one raw-deflate stream; if EVERY block is stored (BTYPE=00), return
    its payload segments as ``[(src_offset, length), ...]``; else None.

    Stored blocks are byte-aligned (the 3 header bits are followed by a pad to
    the next byte boundary, then LEN/NLEN and LEN literal bytes), so an
    all-stored stream is fully described by byte offsets — the on-device
    "inflate" is a gather-copy. Malformed streams (truncation, LEN/NLEN
    mismatch) also return None; the caller keeps the host zlib path, which
    raises its own precise error."""
    buf = bytes(memoryview(frame))
    pos = 0
    segments: List[Tuple[int, int]] = []
    while True:
        if pos >= len(buf):
            return None  # truncated before a final block
        header = buf[pos]
        if (header >> 1) & 0x3 != 0:
            return None  # Huffman-coded block: host inflate territory
        if pos + 5 > len(buf):
            return None
        length = int.from_bytes(buf[pos + 1:pos + 3], 'little')
        nlen = int.from_bytes(buf[pos + 3:pos + 5], 'little')
        if length ^ 0xFFFF != nlen:
            return None
        if pos + 5 + length > len(buf):
            return None
        if length:
            segments.append((pos + 5, length))
        pos += 5 + length
        if header & 0x1:
            return segments


def plan_stored_batch(
        frames: List[Any]) -> Optional[Tuple[np.ndarray, List[int]]]:
    """Build the device copy plan for a batch of raw-deflate frames that are
    ALL stored-block-only: returns ``(segments, frame_lengths)`` where
    ``segments`` is an ``(m, 3)`` int32 table of ``(src_offset, dst_offset,
    length)`` stored-block payloads, in ``dst_offset`` order, with
    ``src_offset`` indexing the CONCATENATION
    of the frames and ``dst_offset`` the concatenation of their inflated
    payloads, and ``frame_lengths`` the per-frame inflated sizes (callers
    needing a dense ``(n, len)`` view must check they are uniform — a total
    divisible by ``n`` does not imply that). Returns None when any frame
    contains a non-stored block — callers inflate on the host."""
    rows: List[Tuple[int, int, int]] = []
    frame_lengths: List[int] = []
    src_base = 0
    dst_base = 0
    for frame in frames:
        layout = parse_stored_deflate_layout(frame)
        if layout is None:
            return None
        frame_len = 0
        for src_off, length in layout:
            rows.append((src_base + src_off, dst_base, length))
            dst_base += length
            frame_len += length
        frame_lengths.append(frame_len)
        src_base += len(frame)
    if not rows:
        return np.zeros((0, 3), dtype=np.int32), frame_lengths
    return np.asarray(rows, dtype=np.int32), frame_lengths


def stored_inflate(packed_src: Any, segments: Any, out_len: int) -> Any:
    """Inflate a stored-block-only deflate batch on device: a gather over the
    :func:`plan_stored_batch` segment table (jit-traceable).

    :param packed_src: uint8 ``(s,)`` array — the concatenated raw frames
        (host or device resident).
    :param segments: int32 ``(m, 3)`` table from :func:`plan_stored_batch`;
        zero-length rows (bucket padding) are ignored.
    :param out_len: total inflated length (static).
    :returns: uint8 ``(out_len,)`` device array of the inflated payloads.
    """
    import jax.numpy as jnp

    segments = jnp.asarray(segments, dtype=jnp.int32)
    if segments.shape[0] == 0 or out_len == 0:
        return jnp.zeros((out_len,), dtype=jnp.uint8)
    src_off, dst_off, length = segments[:, 0], segments[:, 1], segments[:, 2]
    # padding rows sort past every output byte, so no byte resolves to one
    starts = jnp.where(length > 0, dst_off, out_len)
    pos = jnp.arange(out_len, dtype=jnp.int32)
    seg = jnp.searchsorted(starts, pos, side='right') - 1
    src = jnp.asarray(packed_src, dtype=jnp.uint8)
    return src[src_off[seg] + pos - dst_off[seg]]
