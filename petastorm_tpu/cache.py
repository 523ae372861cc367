"""Rowgroup cache (reference: petastorm/cache.py:21-39, petastorm/local_disk_cache.py:23-66).

The reference delegates to the ``diskcache`` package; this is a self-contained sharded
disk cache with atomic writes and size-capped LRU eviction (by file mtime), so repeated
epochs over remote storage hit local disk.

Two on-disk value formats:

- :class:`LocalDiskCache` — whole-value pickle (the reference's semantics): every hit
  pays a full unpickle round trip (read + object-graph materialization).
- :class:`ArrowIpcDiskCache` — the zero-copy format of the decoded-rowgroup data
  plane: columnar values are written as one Arrow IPC stream (the exact byte layout
  of the process-pool wire, ``workers/serializers.py``) plus a pickled sidecar for
  non-Arrow columns, in a single atomically-renamed file. A hit MEMORY-MAPS the file
  and serves the numeric columns as read-only zero-copy views straight into the
  consumer (e.g. ``JaxDataLoader``'s upload) — no Parquet read, no
  decode, no unpickle, no copy. Non-columnar values degrade to an embedded pickle
  record transparently (``stats['pickle_hits']`` makes the degradation visible).

Both keep a ``stats`` dict (hits/misses/bytes); process-pool workers hold their own
unpickled copy, so for that pool the numbers are per-worker (the per-batch
``cache_hit`` sidecar on the results channel is the cross-process aggregate —
see ``Reader.diagnostics``).
"""

import hashlib
import logging
import os
import pickle
import struct
import tempfile
import threading
import time
import zlib

from petastorm_tpu.errors import CacheCorruptionError
from petastorm_tpu.telemetry.spans import record_stage, stage_span

logger = logging.getLogger(__name__)

MB = 1 << 20

#: Arrow-IPC cache file header: magic + mode byte ('A' columnar / 'P' pickle) +
#: uint64-LE length of the IPC stream region (0 in pickle mode)
_ARROW_MAGIC = b'PTUAC001'
_HEADER = struct.Struct('<8scQ')
#: Arrow-IPC cache file footer: magic + CRC-32 of the body (everything between
#: header and footer) + uint64-LE body length. Verified on every hit BEFORE any
#: byte of the body is interpreted; entries written before the footer existed
#: fail the magic check and self-heal like any other corrupt entry
#: (docs/robustness.md "Hang detection & circuit breakers").
_FOOTER_MAGIC = b'PTUCRC01'
_FOOTER = struct.Struct('<8sIQ')

#: cache-breaker defaults: consecutive read/store failures before ``get``
#: bypasses the cache entirely (direct fills), and the cooldown before a
#: half-open probe tries the cache again
DEFAULT_CACHE_BREAKER_THRESHOLD = 5
DEFAULT_CACHE_BREAKER_RECOVERY_S = 60.0


class CacheBase(object):
    """Rowgroup-cache interface (reference: petastorm/cache.py): ``get`` with a
    fill function; implementations decide storage and eviction."""

    def get(self, key, fill_cache_func):
        """Return the cached value for ``key``, calling ``fill_cache_func()`` and storing
        its result on a miss (reference: petastorm/cache.py:24-32)."""
        raise NotImplementedError()

    def cleanup(self):
        """Remove cache resources (best effort)."""


class NullCache(CacheBase):
    """Pass-through: always calls the fill function (reference: petastorm/cache.py:35-39)."""

    def get(self, key, fill_cache_func):
        return fill_cache_func()


def _new_cache_stats():
    """Fresh cache counters: ``hits``/``misses``, ``arrow_hits`` (zero-copy mmap
    hits) vs ``pickle_hits`` (unpickle-path hits — the fallback to copy-mode),
    ``bytes_mmapped`` (bytes served as views over the mapped file),
    ``bytes_written``, ``corrupt_entries`` (unreadable entries deleted by the
    self-heal path) and ``bypass_reads`` (fills served while the cache circuit
    breaker was open)."""
    return {'hits': 0, 'misses': 0, 'arrow_hits': 0, 'pickle_hits': 0,
            'bytes_mmapped': 0, 'bytes_written': 0, 'corrupt_entries': 0,
            'bypass_reads': 0}


class LocalDiskCache(CacheBase):
    """File-per-key cache under ``path``, sharded into 256 subdirectories, bounded by
    ``size_limit_bytes`` with mtime-LRU eviction (reference: local_disk_cache.py:23-66).

    :param path: cache root directory (created if absent)
    :param size_limit_bytes: max total bytes before eviction kicks in
    :param expected_row_size_bytes: sanity check — the limit must hold many rows
    :param cleanup: remove the whole cache directory on ``cleanup()``
    """

    #: per-key file suffix; eviction scans every known suffix so differently-
    #: formatted caches sharing one directory stay bounded together
    _SUFFIX = '.pkl'
    _ALL_SUFFIXES = ('.pkl', '.arrow')

    def __init__(self, path, size_limit_bytes, expected_row_size_bytes=0, cleanup=False,
                 shards=None, breaker=None):
        if expected_row_size_bytes and size_limit_bytes < 100 * expected_row_size_bytes:
            raise ValueError('Cache size_limit_bytes={} is too small for rows of ~{} bytes'
                             .format(size_limit_bytes, expected_row_size_bytes))
        self._path = path
        self._size_limit_bytes = size_limit_bytes
        self._cleanup = cleanup
        self._lock = threading.Lock()
        self.stats = _new_cache_stats()
        self._decode_failure_logged = False
        os.makedirs(path, exist_ok=True)
        # Circuit breaker (docs/robustness.md): repeated corrupt entries or IO
        # failures open it, and get() then BYPASSES the cache (direct fills, no
        # reads, no stores) until the cooldown's half-open probe succeeds — a
        # sick disk degrades throughput, not correctness. Registered on the
        # process-local default board so its state rides the results-channel
        # breaker sidecar into Reader.diagnostics; injectable for tests.
        self._breaker = breaker if breaker is not None else self._default_breaker()
        # Runtime bypass knob (docs/autotuning.md): forces get() onto the
        # direct-fill path exactly like an open breaker, without touching the
        # breaker's failure state. Turned by the autotuner when serving hits
        # is measured slower than refilling (e.g. the pickle format's
        # per-hit unpickle on a fast store).
        self._forced_bypass = False
        # Approximate running byte total: seeded from one scan, bumped per store; the
        # expensive full rescan happens only when this crosses the limit.
        self._approx_bytes = None

    def _default_breaker(self):
        from petastorm_tpu.resilience import default_board
        return default_board().breaker(
            'cache:{}'.format(self._path),
            failure_threshold=DEFAULT_CACHE_BREAKER_THRESHOLD,
            recovery_timeout_s=DEFAULT_CACHE_BREAKER_RECOVERY_S)

    def __getstate__(self):
        # Shipped to process-pool workers; the lock is per-process state, and so
        # is the breaker (each worker re-registers on ITS default board — states
        # reach the consumer via the results-channel sidecar, not via pickle).
        state = self.__dict__.copy()
        del state['_lock']
        del state['_breaker']
        return state

    def __setstate__(self, state):
        self.__dict__.update(state)
        self._lock = threading.Lock()
        self._breaker = self._default_breaker()

    @property
    def state_home(self):
        """The cache root directory — the per-dataset local-state home the
        cost ledger and lineage manifest sidecars default into
        (``petastorm_tpu.dataset_state.cache_state_home``)."""
        return self._path

    def _key_path(self, key):
        digest = hashlib.sha1(str(key).encode('utf-8')).hexdigest()
        return os.path.join(self._path, digest[:2], digest + self._SUFFIX)

    # ------------------------------------------------------------- value codec

    def _encode_value(self, value):
        """Value -> file bytes (pickle format)."""
        return pickle.dumps(value, protocol=pickle.HIGHEST_PROTOCOL)

    def _decode_file(self, file_path):
        """File -> value; raising (corrupt/truncated entry) counts as a miss."""
        with open(file_path, 'rb') as f:
            value = pickle.load(f)
        with self._lock:
            self.stats['pickle_hits'] += 1
        return value

    # ------------------------------------------------------------------- get

    @property
    def bypass(self):
        """True while the runtime bypass knob routes ``get`` to direct fills."""
        return self._forced_bypass

    def set_bypass(self, flag):
        """Runtime cache-mode knob (docs/autotuning.md): ``True`` makes ``get``
        serve direct fills (no read, no store — counted in
        ``stats['bypass_reads']``) without touching the circuit breaker;
        ``False`` restores normal hit/miss serving. Live for in-process pools;
        process-pool workers capture the flag at spawn. Returns the flag."""
        self._forced_bypass = bool(flag)
        return self._forced_bypass

    def get(self, key, fill_cache_func):
        if self._forced_bypass or not self._breaker.allow():
            # Breaker open (or the bypass knob is set): the disk under this
            # cache keeps corrupting or erroring — bypass it entirely (no
            # read, no store) until the cooldown's half-open probe passes.
            # Degradation, never silence.
            with self._lock:
                self.stats['bypass_reads'] += 1
            return fill_cache_func()
        file_path = self._key_path(key)
        try:
            value = self._decode_file(file_path)
            # touch for LRU
            os.utime(file_path, None)
            with self._lock:
                self.stats['hits'] += 1
            self._breaker.record_success()
            return value
        except FileNotFoundError:
            pass  # plain miss
        except Exception:  # noqa: BLE001 - any unreadable entry degrades to a miss
            # Corrupt/truncated entries are expected (crash mid-eviction), but a
            # SYSTEMATIC decode failure (env/codec bug) would otherwise silently
            # turn every epoch cold — log the first one loudly, the rest quietly.
            if not self._decode_failure_logged:
                self._decode_failure_logged = True
                logger.warning('cache entry %s is unreadable; deleting it and '
                               'serving a miss (further decode failures logged '
                               'at DEBUG)', file_path, exc_info=True)
            else:
                logger.debug('cache entry %s is unreadable; deleting it and '
                             'serving a miss', file_path, exc_info=True)
            self._delete_corrupt_entry(file_path)
        with self._lock:
            self.stats['misses'] += 1
        value = fill_cache_func()
        try:
            self._store(file_path, value)
            # A successful store is breaker-neutral while closed (it must not
            # reset a corrupt-READ streak — a disk that stores fine but corrupts
            # everything it returns still needs to trip); it only counts as the
            # recovery probe's success when the breaker is half-open.
            if self._breaker.state == 'half_open':
                self._breaker.record_success()
        except OSError:
            # A failed store must not fail the read — the value is in hand. It
            # does feed the breaker: a disk that cannot store will not serve.
            self._breaker.record_failure()
            logger.warning('failed to store cache entry %s; serving the value '
                           'uncached', file_path, exc_info=True)
        return value

    def _delete_corrupt_entry(self, file_path):
        """Self-heal: a poisoned entry left on disk would re-pay the decode
        failure every warm epoch — delete it so the refill's store replaces it,
        and count it (``corrupt_entries`` stat, ``cache_corrupt`` stage — the
        latter rides the telemetry sidecar across process boundaries)."""
        delete_start = time.perf_counter()
        try:
            os.unlink(file_path)
        except OSError:
            pass  # a concurrent reader may have healed it already
        with self._lock:
            self.stats['corrupt_entries'] += 1
        self._breaker.record_failure()
        record_stage('cache_corrupt', time.perf_counter() - delete_start)

    def _store(self, file_path, value):
        # cache_store stage span (docs/observability.md): encode + write + publish
        # — first-epoch-only cost unless eviction churns
        with stage_span('cache_store'):
            os.makedirs(os.path.dirname(file_path), exist_ok=True)
            blob = self._encode_value(value)
            if len(blob) > self._size_limit_bytes:
                return  # single value larger than the cache: do not thrash
            # mkstemp + os.replace: concurrent fillers of the same key each write a
            # private temp file and atomically publish it — readers only ever see a
            # complete entry (last writer wins; both writers hold equivalent
            # values).
            fd, tmp_path = tempfile.mkstemp(dir=os.path.dirname(file_path))
            try:
                with os.fdopen(fd, 'wb') as f:
                    f.write(blob)
                os.replace(tmp_path, file_path)
            finally:
                # on the normal path os.replace already consumed the temp
                # name and this unlink is a no-op; on ANY failure (not just
                # OSError — encoding bugs included) the orphan is removed
                try:
                    os.unlink(tmp_path)
                except OSError:
                    pass
        with self._lock:
            self.stats['bytes_written'] += len(blob)
            if self._approx_bytes is None:
                self._approx_bytes = sum(size for _, size, _ in self._iter_entries())
            else:
                self._approx_bytes += len(blob)
            over_limit = self._approx_bytes > self._size_limit_bytes
        if over_limit:
            self._maybe_evict()

    def _iter_entries(self):
        for shard in os.listdir(self._path):
            shard_path = os.path.join(self._path, shard)
            if not os.path.isdir(shard_path):
                continue
            for name in os.listdir(shard_path):
                if not name.endswith(self._ALL_SUFFIXES):
                    continue  # skip other writers' in-progress mkstemp files
                full = os.path.join(shard_path, name)
                try:
                    stat = os.stat(full)
                except OSError:
                    continue
                yield full, stat.st_size, stat.st_mtime

    def _maybe_evict(self):
        with self._lock:
            entries = list(self._iter_entries())
            total = sum(size for _, size, _ in entries)
            if total > self._size_limit_bytes:
                # Evict least-recently-touched until under 90% of the limit.
                entries.sort(key=lambda e: e[2])
                target = int(self._size_limit_bytes * 0.9)
                for full, size, _ in entries:
                    if total <= target:
                        break
                    try:
                        os.unlink(full)
                        total -= size
                    except OSError:
                        continue
            self._approx_bytes = total

    @property
    def size(self):
        return sum(size for _, size, _ in self._iter_entries())

    def cleanup(self):
        if self._cleanup:
            import shutil
            shutil.rmtree(self._path, ignore_errors=True)


class ArrowIpcDiskCache(LocalDiskCache):
    """Decoded-rowgroup cache with mmap zero-copy hits (see module docstring).

    Columnar values (``{name: ndarray-or-list}`` — what the rowgroup worker caches)
    are stored as ``[header][arrow ipc stream][pickled sidecar]``; a hit memory-maps
    the file and returns numeric columns as READ-ONLY views over the map (in-place
    mutation of a warm-hit column raises numpy's read-only error — pass
    ``writable_hits=True``, or let ``make_reader`` set it when a ``transform_spec``
    is present, to receive writable copies instead: still no Parquet read, decode
    or unpickle, just one memcpy per column). Anything else (NGram payloads,
    arbitrary objects) is stored as an embedded pickle record with identical
    atomicity/eviction semantics. Constructor = :class:`LocalDiskCache` plus
    ``writable_hits`` (default False = zero-copy).
    """

    _SUFFIX = '.arrow'

    def __init__(self, path, size_limit_bytes, expected_row_size_bytes=0,
                 cleanup=False, shards=None, writable_hits=False, breaker=None):
        super().__init__(path, size_limit_bytes, expected_row_size_bytes,
                         cleanup=cleanup, shards=shards, breaker=breaker)
        self._writable_hits = writable_hits
        #: set by make_reader when the user passed an explicit
        #: cache_extra_settings={'writable_hits': ...} — a pinned hit mode is
        #: a consumer requirement, not an autotuner knob (docs/autotuning.md)
        self.writable_hits_pinned = False

    @property
    def writable_hits(self):
        """True when hits decode writable copies instead of read-only views."""
        return self._writable_hits

    def set_writable_hits(self, flag):
        """Runtime hit-mode knob (docs/autotuning.md): ``False`` serves hits as
        zero-copy read-only mmap views (fastest), ``True`` copies each column
        out writable (required by in-place ``transform_spec`` consumers — the
        autotuner only turns this knob on transform-free readers). Live for
        in-process pools; process-pool workers capture the flag at spawn.
        Returns the flag."""
        self._writable_hits = bool(flag)
        return self._writable_hits

    def _encode_value(self, value):
        from petastorm_tpu.workers.serializers import (_columns_num_rows,
                                                       encode_columnar)
        body = None
        if isinstance(value, dict):
            try:
                num_rows = _columns_num_rows(value)
                ipc_buf, sidecar_blob, _ = encode_columnar(value, num_rows)
                header = _HEADER.pack(_ARROW_MAGIC, b'A', len(ipc_buf))
                body = ipc_buf.to_pybytes() + sidecar_blob
            except Exception:  # noqa: BLE001 - non-columnar dict: pickle record
                logger.debug('value for arrow cache is not columnar; storing as '
                             'pickle record', exc_info=True)
        if body is None:
            header = _HEADER.pack(_ARROW_MAGIC, b'P', 0)
            body = pickle.dumps(value, protocol=pickle.HIGHEST_PROTOCOL)
        footer = _FOOTER.pack(_FOOTER_MAGIC, zlib.crc32(body) & 0xFFFFFFFF,
                              len(body))
        return b''.join([header, body, footer])

    def _decode_file(self, file_path):
        import pyarrow as pa
        from petastorm_tpu.workers.serializers import decode_columnar
        mm = pa.memory_map(file_path, 'r')
        buf = mm.read_buffer()
        total = len(buf)
        if total < _HEADER.size + _FOOTER.size:
            raise CacheCorruptionError(
                'cache entry {} is {} bytes — shorter than header+footer'
                .format(file_path, total))
        magic, mode, ipc_len = _HEADER.unpack_from(memoryview(buf)[:_HEADER.size])
        if magic != _ARROW_MAGIC:
            raise ValueError('not an ArrowIpcDiskCache entry: {!r}'.format(magic))
        # Footer verification BEFORE interpreting a single body byte: truncation
        # shows as a length mismatch, a bit flip as a CRC mismatch, a
        # pre-footer-format entry as a footer-magic mismatch — all three
        # self-heal through get()'s delete-on-corrupt path.
        footer_magic, crc, body_len = _FOOTER.unpack_from(
            memoryview(buf)[total - _FOOTER.size:])
        if footer_magic != _FOOTER_MAGIC:
            raise CacheCorruptionError(
                'cache entry {} has no integrity footer (truncated, or written '
                'by a pre-footer version)'.format(file_path))
        if body_len != total - _HEADER.size - _FOOTER.size or ipc_len > body_len:
            raise CacheCorruptionError(
                'cache entry {} length mismatch: footer claims {} body bytes, '
                'file holds {}'.format(file_path, body_len,
                                       total - _HEADER.size - _FOOTER.size))
        body = buf.slice(_HEADER.size, body_len)
        if zlib.crc32(memoryview(body)) & 0xFFFFFFFF != crc:
            raise CacheCorruptionError(
                'cache entry {} failed CRC verification (bit rot or torn write)'
                .format(file_path))
        if mode == b'P':
            value = pickle.loads(memoryview(body))
            with self._lock:
                self.stats['pickle_hits'] += 1
            return value
        # Zero-copy decode: numeric columns are read-only views whose base buffers
        # keep the memory map alive; sidecar columns (ragged/object) unpickle.
        # writable_hits copies each column out of the map instead (mutating
        # consumers, e.g. in-place transform_specs).
        columns, _ = decode_columnar(body.slice(0, ipc_len), body.slice(ipc_len),
                                     writable=self._writable_hits)
        with self._lock:
            self.stats['arrow_hits'] += 1
            self.stats['bytes_mmapped'] += len(buf)
        return columns
