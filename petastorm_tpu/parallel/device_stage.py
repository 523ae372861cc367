"""Device-resident decode tail: the loader-side stage that turns raw-shipped
codec payloads into decoded (and optionally augmented) device batches.

Counterpart of ``make_reader(device_decode_fields=...)`` (docs/performance.md
"Device-resident decode tail"): workers pass codec payloads through undecoded
(``decode_engine`` ship-raw kernels) and this stage finishes the job next to
the chip — DCT coefficient blocks run through
:func:`~petastorm_tpu.ops.image_decode.dct_decode_images_jax` (dequant + IDCT
on the MXU), packed ``.npy`` payloads become typed arrays via
:func:`~petastorm_tpu.ops.raw_decode.bitcast_rows` (static slice + bitcast XLA
fuses away), and stored-block deflate frames inflate on device through the
:func:`~petastorm_tpu.ops.raw_decode.stored_inflate` gather.
Huffman-coded deflate frames inflate on the loader's producer thread — still
off the contended worker fleet CPU, and the upload stays the packed payload.

Fallback matrix (every cell byte-identical to the host decode path):

- CPU backend, or ``device_put=False``: every device field decodes on the host
  through the same codec math the worker would have used (host mode). Declared
  ``DeviceTransform`` chains still run (same jitted math, post-upload) so a
  fallback run trains on the same data an accelerator run would.
- ``float64`` payloads under x32: per-field host mode (the bitcast cannot
  express the rounding conversion).
- accelerator backends require fully-concrete, non-nullable field shapes
  (XLA static shapes); anything else is rejected at loader construction with
  the fix named.

A small ring (``device_buffer_depth``) bounds how many decode programs may be
dispatched ahead of the train step — double buffering against device memory,
the ``prefetch_to_device`` analog. The loader reports the stage's time as the
``device_decode`` / ``d2d_wait`` telemetry stages.
"""

from __future__ import annotations

import collections
import logging
import os
import zlib
from dataclasses import dataclass
from io import BytesIO
from typing import (Any, Callable, Deque, Dict, FrozenSet, List, Mapping,
                    Optional, Tuple)

import numpy as np

from petastorm_tpu.decode_engine import (RAW_ENC_DEFLATE, RAW_ENC_NPY,
                                         RAW_ENC_SUFFIX, RAW_HW_SUFFIX,
                                         stack_if_uniform)

logger = logging.getLogger(__name__)

#: loader-private column name carrying the per-batch augment RNG key
_RNG_NAME = '__device_rng'
#: suffix of the loader-private stored-deflate segment-table column
_SEGS_SUFFIX = '__segs'


@dataclass(frozen=True)
class DeviceTransform:
    """Declarative on-device augment chain for one raw-shipped image field,
    applied INSIDE the jitted decode program (so augment cost overlaps the
    train step like the decode itself).

    :param crop: ``(h, w)`` random-crop size (``ops.image.random_crop_flip``);
        None disables cropping.
    :param random_flip: seeded random horizontal flip (requires ``crop`` —
        the two share one kernel).
    :param normalize: ``(mean, std)`` per-channel sequences; the output becomes
        ``normalize_dtype`` via ``ops.image.normalize_image``. None keeps uint8.
    :param normalize_dtype: numpy dtype string of the normalized output
        (default ``'float32'``).
    :param seed: base RNG seed; each batch folds in a running counter so
        augmentation differs per batch but replays deterministically.
    """

    crop: Optional[Tuple[int, int]] = None
    random_flip: bool = False
    normalize: Optional[Tuple[Tuple[float, ...], Tuple[float, ...]]] = None
    normalize_dtype: str = 'float32'
    seed: int = 0

    def __post_init__(self) -> None:
        if self.random_flip and self.crop is None:
            raise ValueError('DeviceTransform(random_flip=True) requires crop= '
                             '(flip rides the crop kernel)')
        # coerce sequences to tuples: the transform is part of the compiled
        # program's cache key, so it must be hashable
        if self.crop is not None:
            object.__setattr__(self, 'crop', tuple(self.crop))
        if self.normalize is not None:
            mean, std = self.normalize
            object.__setattr__(self, 'normalize',
                               (tuple(float(m) for m in mean),
                                tuple(float(s) for s in std)))

    @property
    def needs_rng(self) -> bool:
        """True when the chain consumes per-batch randomness."""
        return self.crop is not None

    def apply(self, images: Any, rng: Optional[Any]) -> Any:
        """Run the chain on a decoded uint8 ``[B, H, W, C]`` batch (jit-traceable)."""
        from petastorm_tpu.ops.image import normalize_image, random_crop_flip
        out = images
        if self.crop is not None:
            squeeze = out.ndim == 3
            if squeeze:
                out = out[..., None]
            out = random_crop_flip(rng, out, self.crop, flip=self.random_flip)
            if squeeze:
                out = out[..., 0]
        if self.normalize is not None:
            import jax.numpy as jnp
            mean, std = self.normalize
            out = normalize_image(out, mean, std,
                                  dtype=jnp.dtype(self.normalize_dtype))
        return out


@dataclass(frozen=True)
class _FieldPlan:
    """Static per-field recipe resolved from the reader's schema at loader
    construction: what raw form arrives and how to finish it."""

    name: str
    kind: str                      # 'dct' | 'npy' | 'deflate'
    dtype_str: str                 # payload dtype (npy/deflate) or 'uint8' (dct)
    shape: Tuple[int, ...]         # decoded per-row shape (may hold None dims)
    quality: int = 75              # dct quantization quality
    transform: Optional[DeviceTransform] = None
    host_only: bool = False        # per-field forced host decode (f8 under x32)

    @property
    def aux_names(self) -> Tuple[str, ...]:
        """Auxiliary columns riding alongside this field's raw payload."""
        if self.kind == 'dct':
            return (self.name + RAW_HW_SUFFIX,)
        if self.kind == 'deflate':
            return (self.name + RAW_ENC_SUFFIX,)
        return ()


def _resolve_plans(reader: Any,
                   transforms: Mapping[str, DeviceTransform]) -> Dict[str, _FieldPlan]:
    """Build the per-field recipes from the reader's ``device_decode_fields``
    and schema; rejects transforms on non-image fields."""
    from petastorm_tpu.codecs import CompressedNdarrayCodec, DctImageCodec
    import jax
    x64 = bool(jax.config.jax_enable_x64)
    plans: Dict[str, _FieldPlan] = {}
    for name in sorted(reader.device_decode_fields):
        field = reader.schema.fields[name]
        codec = field.codec
        dtype = np.dtype(field.numpy_dtype)
        if type(codec) is DctImageCodec:
            kind = 'dct'
        elif type(codec) is CompressedNdarrayCodec:
            kind = 'deflate'
        else:
            kind = 'npy'
        transform = transforms.get(name)
        if transform is not None and kind != 'dct':
            raise ValueError('device_transforms[{!r}]: transforms apply to '
                             'DctImageCodec image fields only (this field '
                             'ships as {})'.format(name, kind))
        host_only = dtype.kind == 'f' and dtype.itemsize == 8 and not x64
        plans[name] = _FieldPlan(
            name=name, kind=kind, dtype_str=dtype.str,
            shape=tuple(field.shape),
            quality=int(getattr(codec, 'quality', 75)),
            transform=transform, host_only=host_only)
    unknown = sorted(set(transforms) - set(plans))
    if unknown:
        raise ValueError('device_transforms name fields not in '
                         'device_decode_fields: {}'.format(unknown))
    return plans


def _inflate_frame(frame: Any, enc: int) -> bytes:
    """One raw frame -> its ``.npy`` member bytes (host mirror of the worker's
    stripped container): raw-deflate streams inflate, stored members pass."""
    if enc == RAW_ENC_DEFLATE:
        return zlib.decompressobj(-15).decompress(memoryview(frame))
    if enc == RAW_ENC_NPY:
        return bytes(memoryview(frame))
    raise ValueError('null cell has no payload (enc={})'.format(enc))


class DeviceDecodeStage:
    """The loader's device pipeline stage (one instance per
    :class:`~petastorm_tpu.parallel.loader.JaxDataLoader` whose reader ships
    raw fields). See the module docstring for the decode/fallback matrix."""

    def __init__(self, reader: Any,
                 transforms: Optional[Mapping[str, DeviceTransform]],
                 depth: int, device_put: bool) -> None:
        import jax
        self._plans = _resolve_plans(reader, dict(transforms or {}))
        self._schema_fields = dict(reader.schema.fields)
        self._depth = max(1, int(depth))
        self._x64 = bool(jax.config.jax_enable_x64)
        platform = jax.devices()[0].platform
        #: host mode: every device field decodes on the host, byte-identically
        #: to a reader without the knob (CPU backends, host-batch loaders).
        #: PETASTORM_TPU_DEVICE_DECODE_FORCE=1 forces the device-kernel path
        #: on a CPU backend — a test/debug hook (kernels run via XLA-CPU /
        #: Pallas interpret; DCT decode then differs from the host mirror by
        #: float rounding, which is why it is never the CPU default).
        force = os.environ.get('PETASTORM_TPU_DEVICE_DECODE_FORCE') == '1'
        self.host_mode = (not device_put) or (platform == 'cpu' and not force)
        self._programs: Dict[Tuple[Any, ...], Any] = {}
        self._transform_program: Optional[Any] = None
        self._ring: Deque[Any] = collections.deque()
        self._rng_counter = 0
        self._needs_rng = any(p.transform is not None and p.transform.needs_rng
                              for p in self._plans.values())
        if not self.host_mode:
            bad = sorted(
                name for name, plan in self._plans.items()
                if not plan.host_only
                and (any(d is None for d in plan.shape)
                     or reader.schema.fields[name].nullable))
            if bad:
                raise ValueError(
                    'device_decode_fields {} have wildcard dims or are '
                    'nullable; on-accelerator decode needs static shapes '
                    '(XLA) — make the field shapes concrete/non-nullable or '
                    'drop the fields from device_decode_fields'.format(bad))
        if transforms and self.host_mode and not device_put:
            raise ValueError('device_transforms need device batches; '
                             'construct the loader with device_put=True')

    # ------------------------------------------------------------- surfaces

    @property
    def field_names(self) -> FrozenSet[str]:
        """The raw-shipped field names this stage finishes."""
        return frozenset(self._plans)

    @property
    def passthrough_names(self) -> FrozenSet[str]:
        """Columns ``sanitize_columns`` must pass through untouched: raw
        payload columns still pending device decode, plus their auxiliaries."""
        names: List[str] = []
        for plan in self._plans.values():
            if not (self.host_mode or plan.host_only):
                names.append(plan.name)
                names.extend(plan.aux_names)
        return frozenset(names)

    @property
    def has_transforms(self) -> bool:
        """True when any field declares a device augment chain."""
        return any(p.transform is not None for p in self._plans.values())

    @property
    def depth(self) -> int:
        """Current device-buffer ring depth."""
        return self._depth

    def set_depth(self, depth: int) -> int:
        """Runtime-adjust the ring depth (autotune knob mutator); returns the
        applied value. A shrink drains lazily as the ring is throttled."""
        self._depth = max(1, int(depth))
        return self._depth

    # --------------------------------------------------------- host fallback

    def sanitize_decode(self, columns: Dict[str, Any]) -> Tuple[Dict[str, Any], bool]:
        """The ``_sanitize``-time half: decode the host-mode fields (all of
        them in host mode, only the ``host_only`` ones otherwise) and drop
        their auxiliary columns. Returns ``(columns, any_host_decoded)``."""
        decoded_any = False
        for plan in self._plans.values():
            if not (self.host_mode or plan.host_only):
                continue
            if plan.name in columns:
                columns = self._host_decode_field(columns, plan)
                decoded_any = True
        return columns, decoded_any

    def _host_decode_field(self, columns: Dict[str, Any],
                           plan: _FieldPlan) -> Dict[str, Any]:
        """Decode one raw-shipped field on the host, byte-identically to the
        codec's own decode (the parity contract the tests pin)."""
        out = dict(columns)
        col = out.pop(plan.name)
        values: List[Any]
        if plan.kind == 'dct':
            from petastorm_tpu.ops.image_decode import dct_decode_image
            hw = np.asarray(out.pop(plan.name + RAW_HW_SUFFIX))
            values = [
                None if coeffs is None else dct_decode_image(
                    np.asarray(coeffs), quality=plan.quality,
                    orig_hw=(int(hw[i, 0]), int(hw[i, 1])))
                for i, coeffs in enumerate(col)]
        elif plan.kind == 'npy':
            values = [
                None if blob is None else np.ascontiguousarray(
                    np.load(BytesIO(bytes(memoryview(blob))),
                            allow_pickle=False))
                for blob in col]
        else:
            enc = np.asarray(out.pop(plan.name + RAW_ENC_SUFFIX))
            values = [
                None if frame is None else np.ascontiguousarray(
                    np.load(BytesIO(_inflate_frame(frame, int(enc[i]))),
                            allow_pickle=False))
                for i, frame in enumerate(col)]
        out[plan.name] = stack_if_uniform(values, self._schema_fields.get(plan.name))
        return out

    # --------------------------------------------------------- device decode

    def prepare(self, columns: Dict[str, Any],
                mesh: Any) -> Tuple[Dict[str, Any], Tuple[Any, ...]]:
        """Producer-thread host half of the device path: pack/inflate raw
        payloads into upload-ready numeric arrays and build the static recipe
        the jitted finish program is compiled from. Returns
        ``(upload_columns, recipe)`` — upload them through the loader's
        normal (single-device/mesh) transfer, then call :meth:`finish`."""
        upload = dict(columns)
        recipe: List[Tuple[Any, ...]] = []
        for plan in self._plans.values():
            if plan.host_only or plan.name not in upload:
                # host_only fields were already decoded by sanitize_decode —
                # the column holds decoded values, not a raw payload
                continue
            if plan.kind == 'dct':
                coeffs = upload[plan.name]
                hw = np.asarray(upload.pop(plan.name + RAW_HW_SUFFIX))
                h = int(hw[0, 0]) if len(hw) else 0
                w = int(hw[0, 1]) if len(hw) else 0
                upload[plan.name] = np.ascontiguousarray(coeffs)
                recipe.append(('dct', plan.name, plan.quality, (h, w),
                               len(plan.shape) == 2, plan.transform))
            elif plan.kind == 'npy':
                matrix = upload[plan.name]
                header_len, dtype_str, row_shape = self._npy_meta(matrix[0])
                recipe.append(('npy', plan.name, header_len, dtype_str,
                               row_shape))
            else:
                frames = upload[plan.name]
                enc = np.asarray(upload.pop(plan.name + RAW_ENC_SUFFIX))
                packed = self._pack_deflate(frames, enc, mesh)
                if packed[0] == 'stored':
                    _, src, segs, n, blob_len, npy_meta = packed
                    upload[plan.name] = src
                    upload[plan.name + _SEGS_SUFFIX] = segs
                    header_len, dtype_str, row_shape = npy_meta
                    recipe.append(('stored', plan.name, int(n), int(blob_len),
                                   header_len, dtype_str, row_shape))
                else:
                    _, matrix = packed
                    upload[plan.name] = matrix
                    header_len, dtype_str, row_shape = self._npy_meta(matrix[0])
                    recipe.append(('npy', plan.name, header_len, dtype_str,
                                   row_shape))
        return upload, tuple(recipe)

    def _stored_header_meta(
            self, frame: Any) -> Optional[Tuple[int, str, Tuple[int, ...]]]:
        """The npy-header metadata of a stored-deflate frame, from a BOUNDED
        inflate of its prefix (the header lives in the first ~128 bytes; a
        full inflate here would duplicate the work the device kernel exists to
        take). None when the prefix does not hold a parseable device-decodable
        header — the caller then uses the host-inflate packed path."""
        try:
            prefix = zlib.decompressobj(-15).decompress(memoryview(frame), 512)
            return self._npy_meta(np.frombuffer(prefix, dtype=np.uint8))
        except (zlib.error, ValueError):
            return None

    @staticmethod
    def _npy_meta(first_blob: Any) -> Tuple[int, str, Tuple[int, ...]]:
        """Shared-header metadata of a packed npy column: (header_len,
        payload dtype string, per-row shape). The ship-raw kernel already
        verified every row shares this header byte-for-byte, so parsing row 0
        describes the whole matrix."""
        from petastorm_tpu.codecs import _parse_npy_header
        parsed = _parse_npy_header(bytes(memoryview(first_blob)))
        if parsed is None:
            raise ValueError('unparseable .npy header in a device-mode batch')
        header_len, shape, fortran, dtype = parsed
        if fortran or dtype.hasobject or dtype.byteorder not in ('=', '|', '<'):
            raise ValueError('npy payload layout is not device-decodable '
                             '(fortran/object/big-endian)')
        return header_len, dtype.str, tuple(int(d) for d in shape)

    def _pack_deflate(self, frames: List[Any], enc: np.ndarray,
                      mesh: Any) -> Tuple[Any, ...]:
        """Choose the deflate upload form for this batch: ``('stored', src,
        segs, n, blob_len, npy_meta)`` when every frame is a stored-block
        stream (the gather inflates on device; single-device only — the
        flat source has no batch dim to shard), else ``('packed', matrix)`` —
        host inflate into a ``(n, blob_len)`` npy matrix."""
        from petastorm_tpu.ops.raw_decode import plan_stored_batch
        n = len(frames)
        if mesh is None and n and (enc == RAW_ENC_DEFLATE).all():
            plan = plan_stored_batch([memoryview(f) for f in frames])
            if plan is not None:
                segs, frame_lengths = plan
                # dense (n, len) view needs truly uniform payloads — a total
                # divisible by n does not imply it
                npy_meta = (self._stored_header_meta(frames[0])
                            if len(set(frame_lengths)) == 1 and frame_lengths[0]
                            else None)
                if npy_meta is not None:
                    src = np.concatenate([np.asarray(f, dtype=np.uint8)
                                          for f in frames])
                    # pad the flat source and the segment table to power-of-two
                    # buckets: compressed sizes differ per batch, and without
                    # bucketing every batch would carry a fresh array layout —
                    # a fresh compile per batch. stored_inflate ignores the
                    # zero-length pad segments.
                    src_pad = 1 << (len(src) - 1).bit_length()
                    src = np.pad(src, (0, src_pad - len(src)))
                    seg_pad = 1 << max(0, (len(segs) - 1).bit_length())
                    segs = np.pad(segs, ((0, seg_pad - len(segs)), (0, 0)))
                    return ('stored', src, segs, n, frame_lengths[0],
                            npy_meta)
        blobs = [_inflate_frame(f, int(enc[i])) for i, f in enumerate(frames)]
        blob_len = len(blobs[0]) if blobs else 0
        matrix = np.empty((n, blob_len), dtype=np.uint8)
        for i, blob in enumerate(blobs):
            if len(blob) != blob_len:
                raise ValueError('non-uniform inflated payload lengths in a '
                                 'device-mode batch ({} vs {})'
                                 .format(len(blob), blob_len))
            matrix[i] = np.frombuffer(blob, dtype=np.uint8)
        return 'packed', matrix

    def finish(self, device_columns: Dict[str, Any],
               recipe: Tuple[Any, ...]) -> Dict[str, Any]:
        """Consumer half of the device path: run the (cached, jitted) decode +
        augment program over the uploaded columns and return the final batch
        pytree. Dispatch is async — the train step synchronizes."""
        if self._needs_rng:
            # the batch counter enters HERE, not the upload dict: the mesh
            # upload path would batch-shard it; as a scalar jit argument it is
            # transferred/replicated correctly by jax itself. Each transform
            # folds the counter into ITS OWN seed inside the program, so
            # differently-seeded transforms decorrelate and replays are
            # deterministic.
            device_columns = dict(device_columns)
            device_columns[_RNG_NAME] = np.uint32(self._rng_counter)
            self._rng_counter += 1
        program = self._programs.get(recipe)
        if program is None:
            program = self._build_program(recipe)
            self._programs[recipe] = program
        return program(device_columns)

    def apply_transforms(self, batch: Dict[str, Any]) -> Dict[str, Any]:
        """Host-mode counterpart of finish()'s augment half: the declared
        chains run as the SAME jitted math over the already-uploaded decoded
        batch, so a CPU run and an accelerator run train on identical data
        (shapes, dtypes, augmentation sequence) — transforms are never
        silently dropped on a fallback backend."""
        import jax
        program = self._transform_program
        if program is None:
            entries = [(p.name, p.transform) for p in self._plans.values()
                       if p.transform is not None]

            # named, so the device trace names its ops after it
            def device_transform(dev: Dict[str, Any],
                                 counter: Any) -> Dict[str, Any]:
                out = dict(dev)
                for name, transform in entries:
                    rng = None
                    if transform.needs_rng:
                        rng = jax.random.fold_in(
                            jax.random.PRNGKey(transform.seed), counter)
                    out[name] = transform.apply(dev[name], rng)
                return out

            program = jax.jit(device_transform)
            self._transform_program = program
        counter = np.uint32(self._rng_counter)
        self._rng_counter += 1
        return program(batch, counter)

    def _build_program(self, recipe: Tuple[Any, ...]) -> Any:
        """Compile the jitted finish program for one static recipe: every
        field's decode (and augment) in one fused program."""
        import jax
        from petastorm_tpu.ops.image_decode import dct_decode_images_jax
        from petastorm_tpu.ops.raw_decode import bitcast_rows, stored_inflate
        x64 = self._x64

        # named, so the device trace names its ops after it
        def device_decode_finish(dev: Dict[str, Any]) -> Dict[str, Any]:
            out = {name: col for name, col in dev.items()
                   if name != _RNG_NAME and not name.endswith(_SEGS_SUFFIX)}
            counter = dev.get(_RNG_NAME)
            for entry in recipe:
                if entry[0] == 'dct':
                    _, name, quality, (h, w), squeeze, transform = entry
                    images = dct_decode_images_jax(dev[name], quality=quality)
                    images = images[:, :h, :w]
                    if squeeze:
                        images = images[..., 0]
                    if transform is not None:
                        rng = None
                        if transform.needs_rng:
                            # per-field key: the transform's OWN seed folded
                            # with the per-batch counter (deterministic
                            # replay; distinct seeds decorrelate)
                            rng = jax.random.fold_in(
                                jax.random.PRNGKey(transform.seed), counter)
                        images = transform.apply(images, rng)
                    out[name] = images
                elif entry[0] == 'stored':
                    _, name, n, blob_len, header_len, dtype_str, row_shape = entry
                    flat = stored_inflate(dev[name], dev[name + _SEGS_SUFFIX],
                                          n * blob_len)
                    out[name] = bitcast_rows(
                        flat.reshape(n, blob_len)[:, header_len:], dtype_str,
                        row_shape, x64=x64)
                else:
                    _, name, header_len, dtype_str, row_shape = entry
                    out[name] = bitcast_rows(dev[name][:, header_len:],
                                             dtype_str, row_shape, x64=x64)
            return out

        return jax.jit(device_decode_finish)

    # ----------------------------------------------------------------- ring

    def throttle(self, batch: Any) -> None:
        """Bound dispatched-ahead decode work: append this batch to the ring
        and, past the configured depth, block until the OLDEST dispatched
        batch is ready (the loader times the call as the ``d2d_wait``
        stage)."""
        import jax
        leaves = jax.tree_util.tree_leaves(batch)
        if not leaves:
            return
        self._ring.append(leaves[0])
        while len(self._ring) > self._depth:
            jax.block_until_ready(self._ring.popleft())
