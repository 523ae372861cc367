"""On-chip image decode: JPEG-style DCT-domain storage with the IDCT on the MXU.

SURVEY.md §7.3 asks for a decode-as-jax-op variant of the image codec. A literal JPEG
decoder is a poor fit for TPU: Huffman/entropy decoding is bit-serial with
data-dependent control flow — exactly what XLA/the MXU cannot vectorize. The TPU-first
split keeps the *transform* FLOPs (dequantize + 8x8 inverse DCT + color conversion — the
bulk of decode compute) on-chip and removes the entropy stage entirely: images are
stored as JPEG-style quantized DCT coefficients (int16, zigzag-free) and Parquet's
page-level compression (zstd/snappy over the many zero coefficients) plays the role of
the entropy coder.

- :func:`dct_encode_image` (host, vectorized numpy): RGB->YCbCr, 8x8 blockwise DCT,
  JPEG quality-scaled quantization -> int16 coefficient blocks.
- :func:`dct_decode_image` (host, numpy): exact mirror — the
  :class:`~petastorm_tpu.codecs.DctImageCodec` host parity path.
- :func:`dct_decode_images_jax` (device, jit): batched dequant + IDCT as two 8x8
  matmul sandwiches per block (einsum -> MXU) + YCbCr->RGB, uint8 out. This is the
  codec's decode-on-device variant: the loader ships int16 coefficients
  (~= pixel bytes before page compression) and the chip does the math.

The quantization/limits match libjpeg's quality scaling, so storage cost and fidelity
are JPEG-like (without its entropy coding, recovered by Parquet page compression).
"""

import numpy as np

# Standard JPEG base quantization tables (Annex K) — luminance and chrominance.
_LUM_BASE = np.array([
    [16, 11, 10, 16, 24, 40, 51, 61],
    [12, 12, 14, 19, 26, 58, 60, 55],
    [14, 13, 16, 24, 40, 57, 69, 56],
    [14, 17, 22, 29, 51, 87, 80, 62],
    [18, 22, 37, 56, 68, 109, 103, 77],
    [24, 35, 55, 64, 81, 104, 113, 92],
    [49, 64, 78, 87, 103, 121, 120, 101],
    [72, 92, 95, 98, 112, 100, 103, 99]], dtype=np.float32)
_CHROM_BASE = np.array([
    [17, 18, 24, 47, 99, 99, 99, 99],
    [18, 21, 26, 66, 99, 99, 99, 99],
    [24, 26, 56, 99, 99, 99, 99, 99],
    [47, 66, 99, 99, 99, 99, 99, 99],
    [99, 99, 99, 99, 99, 99, 99, 99],
    [99, 99, 99, 99, 99, 99, 99, 99],
    [99, 99, 99, 99, 99, 99, 99, 99],
    [99, 99, 99, 99, 99, 99, 99, 99]], dtype=np.float32)


def _dct_matrix():
    """8x8 DCT-II basis: D = C @ F @ C.T, F = C.T @ D @ C."""
    n = np.arange(8)
    k = n[:, None]
    c = np.cos((2 * n[None, :] + 1) * k * np.pi / 16)
    c *= np.where(k == 0, np.sqrt(1.0 / 8.0), np.sqrt(2.0 / 8.0))
    return c.astype(np.float32)


_C = _dct_matrix()


def quant_tables(quality, channels):
    """libjpeg-style quality scaling of the base tables -> [8, 8, channels] float32."""
    quality = int(np.clip(quality, 1, 100))
    scale = 5000.0 / quality if quality < 50 else 200.0 - 2.0 * quality
    tables = []
    for c in range(channels):
        base = _LUM_BASE if c == 0 else _CHROM_BASE
        tables.append(np.clip(np.floor((base * scale + 50.0) / 100.0), 1, 255))
    return np.stack(tables, axis=-1).astype(np.float32)


def _rgb_to_ycbcr(x):
    r, g, b = x[..., 0], x[..., 1], x[..., 2]
    y = 0.299 * r + 0.587 * g + 0.114 * b
    cb = -0.168736 * r - 0.331264 * g + 0.5 * b + 128.0
    cr = 0.5 * r - 0.418688 * g - 0.081312 * b + 128.0
    return np.stack([y, cb, cr], axis=-1)


def _pad_to_blocks(x):
    h, w = x.shape[:2]
    ph, pw = (-h) % 8, (-w) % 8
    if ph or pw:
        x = np.pad(x, ((0, ph), (0, pw), (0, 0)), mode='edge')
    return x


def dct_encode_image(image, quality=75):
    """uint8 [H, W, 3] (or [H, W] / [H, W, 1] grayscale) -> int16 coefficient blocks
    [H8, W8, 8, 8, C] (edge-padded to /8)."""
    if image.dtype != np.uint8:
        raise ValueError('dct_encode_image expects uint8, got {}'.format(image.dtype))
    squeeze = image.ndim == 2
    if squeeze:
        image = image[..., None]
    x = image.astype(np.float32)
    channels = x.shape[-1]
    if channels == 3:
        x = _rgb_to_ycbcr(x)
    elif channels != 1:
        raise ValueError('DCT codec supports 1 or 3 channels, got {}'.format(channels))
    x = _pad_to_blocks(x) - 128.0
    h, w = x.shape[:2]
    blocks = x.reshape(h // 8, 8, w // 8, 8, channels).transpose(0, 2, 1, 3, 4)
    # D = C F C^T over the two intra-block axes
    coeffs = np.einsum('ij,hwjkc,lk->hwilc', _C, blocks, _C)
    q = quant_tables(quality, channels)
    return np.round(coeffs / q).astype(np.int16)


def dct_decode_image(coeffs, quality=75, orig_hw=None):
    """int16 [H8, W8, 8, 8, C] -> uint8 [H, W, C] (or [H, W] when C == 1), cropped to
    ``orig_hw`` when given — the host mirror of the on-chip decode."""
    h8, w8 = coeffs.shape[:2]
    channels = coeffs.shape[-1]
    q = quant_tables(quality, channels)
    deq = coeffs.astype(np.float32) * q
    blocks = np.einsum('ji,hwjkc,kl->hwilc', _C, deq, _C)
    x = blocks.transpose(0, 2, 1, 3, 4).reshape(h8 * 8, w8 * 8, channels) + 128.0
    if channels == 3:
        x = _ycbcr_to_rgb_np(x)
    out = np.clip(np.round(x), 0, 255).astype(np.uint8)
    if orig_hw is not None:
        out = out[:orig_hw[0], :orig_hw[1]]
    return out[..., 0] if channels == 1 else out


def _ycbcr_to_rgb_np(x):
    y, cb, cr = x[..., 0], x[..., 1] - 128.0, x[..., 2] - 128.0
    r = y + 1.402 * cr
    g = y - 0.344136 * cb - 0.714136 * cr
    b = y + 1.772 * cb
    return np.stack([r, g, b], axis=-1)


def dct_decode_images_jax(coeffs, quality=75):
    """Jit-friendly batched decode: int16 [B, H8, W8, 8, 8, C] -> uint8 [B, H, W, C].

    The two einsums are 8x8 matmul sandwiches batched over every block — the shape XLA
    tiles straight onto the MXU; dequant/offset/color-convert fuse around them. Use
    inside a jitted train step so decode overlaps the rest of the step and the
    host->device transfer carries coefficients instead of decoded floats."""
    import jax.numpy as jnp

    channels = coeffs.shape[-1]
    q = jnp.asarray(quant_tables(quality, channels))
    c = jnp.asarray(_C)
    deq = coeffs.astype(jnp.float32) * q
    # HIGHEST: the TPU's default f32 matmul rounds operands to bf16, which moves
    # pixels by several levels against the host decode (the parity contract)
    blocks = jnp.einsum('ji,bhwjkc,kl->bhwilc', c, deq, c, precision='highest')
    b, h8, w8 = blocks.shape[:3]
    x = blocks.transpose(0, 1, 3, 2, 4, 5).reshape(b, h8 * 8, w8 * 8, channels) + 128.0
    if channels == 3:
        y, cb, cr = x[..., 0], x[..., 1] - 128.0, x[..., 2] - 128.0
        x = jnp.stack([y + 1.402 * cr,
                       y - 0.344136 * cb - 0.714136 * cr,
                       y + 1.772 * cb], axis=-1)
    return jnp.clip(jnp.round(x), 0, 255).astype(jnp.uint8)
