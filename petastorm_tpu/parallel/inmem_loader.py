"""InMemJaxLoader: load a dataset once, then serve seeded epoch batches with no further
host IO — the TPU-native counterpart of the reference's ``InMemBatchedDataLoader``
(petastorm/pytorch.py:368-496: fill ≤ rows_capacity rows once, stop the reader, then
epochs of seeded ``torch.randperm`` batch sampling).

TPU-first design: on a single device (``mesh=None``) the whole dataset lives in HBM and
every batch is produced by one jitted gather — per-epoch permutations are computed with
``jax.random`` on device, so after the fill phase the input pipeline touches the host
zero times (input stall is structurally 0). With a ``mesh``, python iteration keeps the
dataset in host RAM and assembles each sampled batch into a mesh-sharded ``jax.Array``
like :class:`JaxDataLoader` (a GLOBAL per-batch permutation over HBM-resident shards
would force cross-shard gathers); ``scan_epochs`` over a mesh instead uploads the
dataset shard-blocked across device HBM and shuffles SHARD-LOCALLY, which keeps the
gathers collective-free — whole-epoch compilation composed with data parallelism.
"""

import logging
import time
import warnings

import numpy as np

from petastorm_tpu.parallel.loader import (FieldShardings, iter_reader_chunks,
                                           reader_may_be_infinite, resolve_sharding,
                                           sanitize_columns, sharding_for_field)

logger = logging.getLogger(__name__)

_FILL_SAFETY_CAP = 100_000_000
#: scan_epochs keeps this many compiled (step_fn, shuffle) programs before evicting
_SCAN_CACHE_MAX = 8


def _put_with_log(put_fn, upload_bytes, detail):
    """Run an upload and, when INFO logging is enabled, log its duration up to
    ``jax.block_until_ready``. With INFO disabled the upload stays fully async:
    no sync is paid for a discarded measurement."""
    want_log = logger.isEnabledFor(logging.INFO)
    t0 = time.perf_counter()
    data = put_fn()
    if want_log:
        import jax
        jax.block_until_ready(data)
        logger.info('uploaded %s (%.1f MB) in %.2fs', detail,
                    upload_bytes / 2**20, time.perf_counter() - t0)
    return data


class InMemJaxLoader(object):
    """Fill once from ``reader``, then iterate seeded shuffled batches for
    ``num_epochs`` (None = infinite).

    :param reader: petastorm_tpu Reader (row, batched, or NGram). NGram readers fill
        window-major: every "row" in memory is one window, each field
        ``(length, *field_shape)``, so batches are ``(batch, length, ...)`` sequence
        arrays (note overlapping windows are materialized — budget
        ``rows_capacity x length`` memory).
    :param batch_size: rows per batch on this host.
    :param num_epochs: epochs to serve from memory (None = infinite). Independent of the
        reader's own ``num_epochs``, which only governs the fill (use reader
        num_epochs=1).
    :param rows_capacity: stop filling after this many rows (required if the reader is
        infinite). The reader is stopped after the fill, mirroring the reference's
        deadlock avoidance (pytorch.py:420-424).
    :param shuffle: seeded reshuffle every epoch (default True).
    :param seed: base seed; epoch ``e`` uses fold-in of ``e``.
    :param mesh/partition_spec: as in :class:`JaxDataLoader`.
    :param pad_ragged: as in :class:`JaxDataLoader`.
    :param drop_last: drop the final partial batch (static shapes under jit).
    :param device_put: False keeps batches as host numpy (debugging).
    """

    def __init__(self, reader, batch_size, num_epochs=1, rows_capacity=None,
                 shuffle=True, seed=0, mesh=None, partition_spec=None, pad_ragged=None,
                 drop_last=True, device_put=True):
        if batch_size < 1:
            raise ValueError('batch_size must be >= 1')
        if num_epochs is not None and num_epochs < 1:
            raise ValueError('num_epochs must be >= 1 or None')
        if partition_spec is not None and mesh is None:
            raise ValueError('partition_spec requires a mesh')
        if getattr(reader, 'device_decode_fields', None):
            raise ValueError(
                'InMemJaxLoader does not support device_decode_fields (the '
                'fill materializes DECODED host columns); use JaxDataLoader '
                'for the device-resident decode tail, or drop the knob — '
                'docs/performance.md "Device-resident decode tail"')
        self.batch_size = batch_size
        self.num_epochs = num_epochs
        self._shuffle = shuffle
        self._seed = seed
        self._mesh = mesh
        self._partition_spec = partition_spec
        self._pad_ragged = dict(pad_ragged or {})
        self._drop_last = drop_last
        self._device_put = device_put
        self._columns = self._fill(reader, rows_capacity)
        self._num_rows = next(iter(self._columns.values())).shape[0] if self._columns else 0
        if self._num_rows < batch_size and drop_last:
            raise ValueError('Loaded {} rows < batch_size {} with drop_last=True — '
                             'every epoch would be empty'.format(self._num_rows, batch_size))
        self._data = None  # device-resident dataset (single-device path), built lazily
        self._sharded_meta = None  # (usable_rows, num_shards) for the mesh scan path
        self._take = None
        # scan_epochs: compiled-program cache keyed by (step_fn, shuffle) — train and
        # eval variants of the same step stay compiled side by side — plus a persistent
        # epoch cursor so repeated calls keep advancing the permutation sequence
        # instead of replaying epoch 0.
        self._scan_cache = {}
        self._scan_compile_count = 0
        self._scan_cache_warned = False
        self._scan_epoch = 0

    # ------------------------------------------------------------------ fill

    def _fill(self, reader, rows_capacity):
        if rows_capacity is None and reader_may_be_infinite(reader):
            raise ValueError(
                'rows_capacity is required with a (possibly) infinite reader: '
                'num_epochs=None, a wrapper over one, or a custom reader that does not '
                'advertise finiteness. Pass rows_capacity, or give a custom reader a '
                'num_epochs attribute (any non-None value marks it finite).')
        cap = rows_capacity if rows_capacity is not None else _FILL_SAFETY_CAP
        chunks = []
        rows = 0
        try:
            for columns, n, _ in iter_reader_chunks(reader):
                chunks.append(sanitize_columns(columns, self._pad_ragged,
                                               self._device_put))
                rows += n
                if rows >= cap:
                    if rows_capacity is None:
                        warnings.warn(
                            'InMemJaxLoader fill hit the {}-row safety cap without an '
                            'explicit rows_capacity; the dataset is TRUNCATED. Pass '
                            'rows_capacity to make the limit intentional.'
                            .format(_FILL_SAFETY_CAP))
                    break
        finally:
            # Stop regardless: an infinite reader would otherwise keep workers running
            # (reference: pytorch.py:420-424).
            reader.stop()
            reader.join()
        if not chunks:
            return {}
        columns = {name: _concat([c[name] for c in chunks])
                   for name in chunks[0]}
        if rows_capacity is not None:
            columns = {name: col[:rows_capacity] for name, col in columns.items()}
        return columns

    # ------------------------------------------------------------------ iteration

    def __len__(self):
        """Batches per epoch."""
        if self._drop_last:
            return self._num_rows // self.batch_size
        return -(-self._num_rows // self.batch_size)

    @property
    def num_rows(self):
        return self._num_rows

    def __iter__(self):
        if self._num_rows == 0:
            return
        epoch = 0
        while self.num_epochs is None or epoch < self.num_epochs:
            if self._device_put and self._mesh is None:
                yield from self._iter_epoch_on_device(epoch)
            else:
                yield from self._iter_epoch_host(epoch)
            epoch += 1

    # -- single-device: dataset in HBM, jitted gather, device-side permutation --------

    def _ensure_device_data(self):
        import jax
        if self._data is None:
            columns = self._columns
            self._data = _put_with_log(
                lambda: jax.device_put(columns),
                sum(col.nbytes for col in columns.values()),
                '{} rows'.format(self._num_rows))
            # The on-device path never reads the host copy again; holding it would
            # double the dataset's memory footprint.
            self._columns = None

            @jax.jit
            def take(data, idx):
                return {name: col[idx] for name, col in data.items()}

            self._take = take
        return self._data

    def _iter_epoch_on_device(self, epoch):
        import jax
        import jax.numpy as jnp

        from petastorm_tpu.ops.index_shuffle import random_index_shuffle
        data = self._ensure_device_data()
        n = self._num_rows
        if self._shuffle:
            # Materialization-free-in-spirit permutation: jax.random.permutation is a
            # SORT (~50ms at n=50k on a v5e — can rival a small model's whole epoch);
            # the Feistel index cipher evaluates the epoch's index vector in <1ms
            # (ops/index_shuffle.py), once per epoch.
            key = jax.random.fold_in(jax.random.PRNGKey(self._seed), epoch)
            idx_all = random_index_shuffle(jnp.arange(n), key, n)
        else:
            idx_all = jnp.arange(n)
        limit = n - self.batch_size + 1 if self._drop_last else n
        for start in range(0, limit, self.batch_size):
            yield self._take(data, idx_all[start:min(start + self.batch_size, n)])

    # -- mesh-sharded HBM residency for scan_epochs -----------------------------------

    def _batch_axis_name(self):
        """The mesh axis sharding the batch dimension. scan_epochs over a mesh
        supports the default batch-axis layout (first mesh axis) or a single-axis
        ``PartitionSpec``; per-field dict specs have no single batch layout to scan
        over and are rejected."""
        if self._partition_spec is None:
            return self._mesh.axis_names[0]
        try:
            (axis,) = tuple(self._partition_spec)
        except (TypeError, ValueError):
            axis = None
        if isinstance(axis, str) and axis in self._mesh.axis_names:
            return axis
        raise ValueError(
            'scan_epochs over a mesh supports partition_spec=None or a single-axis '
            'PartitionSpec(axis); got {!r}'.format(self._partition_spec))

    def _ensure_sharded_data(self):
        """Upload the dataset shard-blocked: each column reshaped to
        ``(num_shards, rows_per_shard, ...)`` and sharded on dim 0 over the batch
        axis, so every device holds one contiguous row block in its own HBM. Rows
        beyond ``num_shards * rows_per_shard`` are dropped (at most num_shards - 1).

        Returns ``(data, usable_rows, num_shards)``."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec
        if self._data is None:
            axis = self._batch_axis_name()
            num_shards = self._mesh.shape[axis]
            rows_per_shard = self._num_rows // num_shards
            if rows_per_shard == 0:
                raise ValueError('{} rows cannot be sharded {} ways'
                                 .format(self._num_rows, num_shards))
            usable = num_shards * rows_per_shard
            if usable < self._num_rows:
                warnings.warn('scan_epochs drops {} trailing rows so the dataset '
                              'splits evenly over the {} batch-axis shards'
                              .format(self._num_rows - usable, num_shards))
            sharding = NamedSharding(self._mesh, PartitionSpec(axis))
            blocks = {
                name: col[:usable].reshape(
                    (num_shards, rows_per_shard) + col.shape[1:])
                for name, col in self._columns.items()}
            self._data = _put_with_log(
                lambda: {name: jax.device_put(col, sharding)
                         for name, col in blocks.items()},
                # bytes of what is ACTUALLY uploaded (trailing remainder dropped)
                sum(col.nbytes for col in blocks.values()),
                '{} rows shard-blocked over {} devices'.format(
                    usable, num_shards))
            self._sharded_meta = (usable, num_shards)
            self._columns = None  # single copy: the host arrays are no longer read
        return self._data, self._sharded_meta[0], self._sharded_meta[1]

    def _build_sharded_epoch_program(self, step_fn, shuffle, seed, n, num_shards,
                                     batch_size, batches_per_epoch, index_shuffle):
        """One compiled epoch over the mesh with SHARD-LOCAL shuffling: each shard
        permutes its own rows (Feistel cipher keyed by epoch x shard), each global
        batch takes ``batch_size / num_shards`` rows from every shard, and the gather
        is a vmapped per-shard take whose batch dim is aligned-sharded on operand,
        indices, and output — XLA partitions it with NO collectives in the input
        path. Rows never migrate between shards (the same contract as sharded
        multi-host reading, reference reader.py:570-594: a shard only ever serves its
        own rows); cross-shard mixing comes from the once-at-fill row distribution.
        ``step_fn`` itself runs under plain GSPMD on the reassembled
        ``(batch_size, ...)`` batch (sharded over the batch axis), so model-side
        sharding (TP/FSDP/etc.) composes unchanged."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec
        axis = self._batch_axis_name()
        local_bs = batch_size // num_shards
        rows_per_shard = n // num_shards
        idx_sharding = NamedSharding(self._mesh, PartitionSpec(axis))

        @jax.jit
        def one_epoch(data, carry, epoch_index):
            epoch_key = jax.random.fold_in(jax.random.PRNGKey(seed), epoch_index)
            shard_keys = jax.vmap(lambda s: jax.random.fold_in(epoch_key, s))(
                jnp.arange(num_shards))
            local = jnp.arange(rows_per_shard)
            if shuffle:
                idx_all = jax.vmap(
                    lambda key: index_shuffle(local, key, rows_per_shard))(shard_keys)
            else:
                idx_all = jnp.broadcast_to(local, (num_shards, rows_per_shard))
            # Pin the per-shard index table to the batch axis so the vmapped gather
            # below partitions shard-locally instead of replicating via all-gather.
            idx_all = jax.lax.with_sharding_constraint(idx_all, idx_sharding)

            def body(carry, batch_index):
                idx = jax.lax.dynamic_slice_in_dim(
                    idx_all, batch_index * local_bs, local_bs, axis=1)
                batch = {}
                for name, col in data.items():
                    taken = jax.vmap(lambda c, i: c[i])(col, idx)
                    batch[name] = taken.reshape((batch_size,) + taken.shape[2:])
                return step_fn(carry, batch)

            return jax.lax.scan(body, carry, jnp.arange(batches_per_epoch))

        return one_epoch

    # -- fully-compiled epochs: sampling + training in ONE XLA program ----------------

    def scan_epochs(self, step_fn, carry, num_epochs=1, epoch_offset=None,
                    shuffle=None):
        """Run whole training epochs on device, each as a single compiled program.

        The idiomatic-TPU endpoint of the in-mem design: the per-epoch permutation
        (``jax.random``), the batch gather, and every training step run inside one
        ``lax.scan`` under ``jit`` — one host dispatch per epoch, so input machinery
        adds no per-batch Python overhead at all (at small batch sizes the dispatch
        costs several times the compute; see bench.py). No reference analog: petastorm's
        InMemBatchedDataLoader still crosses into Python per batch
        (petastorm/pytorch.py:464-489).

        Repeated calls with the *same* ``step_fn`` object reuse the compiled program
        and continue the epoch/permutation sequence where the previous call stopped
        (override the start with ``epoch_offset``).

        With a ``mesh``, the dataset is uploaded shard-blocked (each device holds a
        contiguous row block in its own HBM) and shuffling is SHARD-LOCAL: each
        shard permutes its own rows per epoch, every global batch takes
        ``batch_size / num_shards`` rows from each shard, and the gather partitions
        with no collectives in the input path. ``batch_size`` must be divisible by
        the batch mesh axis size; a ``partition_spec`` must be None or a single-axis
        ``PartitionSpec``.

        :param step_fn: ``step_fn(carry, batch) -> (carry, aux)`` with ``batch`` a dict
            of ``(batch_size, ...)`` arrays — a standard ``lax.scan`` body over your
            train step.
        :param carry: initial carry (e.g. ``(params, opt_state)``).
        :param num_epochs: epochs to run; the compiled program is reused across them.
        :param epoch_offset: epoch index of the first epoch (feeds the permutation
            seed fold-in); default continues the loader's internal cursor.
        :param shuffle: override the loader's shuffle setting for this call (e.g.
            ``False`` for deterministic eval epochs over the same resident data).
        :return: ``(carry, aux_per_epoch)`` where ``aux_per_epoch`` is a list of the
            stacked per-batch aux pytrees, one entry per epoch.
        """
        import jax
        import jax.numpy as jnp
        if not self._device_put:
            raise ValueError('scan_epochs requires device_put=True')
        if self._num_rows == 0:
            raise ValueError('scan_epochs on an empty dataset')
        batch_size = self.batch_size
        shuffle = self._shuffle if shuffle is None else shuffle
        seed = self._seed
        # Validate BEFORE any upload: _ensure_*_data drops the host copy, so failing
        # after it would leave the loader unusable (batch_size is fixed at __init__).
        if self._mesh is not None:
            num_shards = self._mesh.shape[self._batch_axis_name()]
            if batch_size % num_shards:
                raise ValueError(
                    'scan_epochs over a mesh needs batch_size ({}) divisible by the '
                    'batch mesh axis size ({})'.format(batch_size, num_shards))
            n = num_shards * (self._num_rows // num_shards)
        else:
            n, num_shards = self._num_rows, 1
        if n // batch_size == 0:
            raise ValueError('batch_size {} > usable dataset rows {}'
                             .format(batch_size, n))
        if not self._drop_last and self._num_rows % batch_size != 0:
            raise ValueError(
                'scan_epochs cannot serve the trailing partial batch ({} rows): '
                'lax.scan needs static batch shapes. Use drop_last=True, a divisible '
                'batch_size, or the python iterator.'.format(self._num_rows % batch_size))
        if self._mesh is not None:
            data, n, num_shards = self._ensure_sharded_data()
        else:
            data = self._ensure_device_data()
        batches_per_epoch = n // batch_size

        cache_key = (step_fn, shuffle)
        if cache_key not in self._scan_cache:
            from petastorm_tpu.ops.index_shuffle import random_index_shuffle

            if self._mesh is not None:
                one_epoch = self._build_sharded_epoch_program(
                    step_fn, shuffle, seed, n, num_shards, batch_size,
                    batches_per_epoch, random_index_shuffle)
            else:
                @jax.jit
                def one_epoch(data, carry, epoch_index):
                    # Shuffling via the Feistel index cipher, not
                    # jax.random.permutation: the sort-based permutation costs ~50ms
                    # at n=50k on a v5e while the cipher evaluates the whole epoch's
                    # indices in <1ms (ops/index_shuffle.py). Evaluated ONCE per epoch
                    # here — hoisting the cipher's cycle-walk while_loop out of the
                    # batch scan keeps the loop body free of data-dependent control
                    # flow.
                    key = jax.random.fold_in(jax.random.PRNGKey(seed), epoch_index)
                    idx_all = (random_index_shuffle(jnp.arange(n), key, n) if shuffle
                               else jnp.arange(n))

                    def body(carry, batch_index):
                        idx = jax.lax.dynamic_slice_in_dim(
                            idx_all, batch_index * batch_size, batch_size)
                        batch = {name: col[idx] for name, col in data.items()}
                        return step_fn(carry, batch)

                    return jax.lax.scan(body, carry, jnp.arange(batches_per_epoch))

            self._scan_compile_count += 1
            if len(self._scan_cache) >= _SCAN_CACHE_MAX:
                # A fresh lambda per call defeats reuse (closures cannot be safely
                # deduplicated) — warn once and evict oldest so the compiled
                # executables and their captured environments cannot accumulate.
                if not self._scan_cache_warned:
                    self._scan_cache_warned = True
                    warnings.warn(
                        'scan_epochs compiled {} distinct (step_fn, shuffle) programs; '
                        'pass a stable step_fn object to reuse compilations'
                        .format(self._scan_compile_count))
                self._scan_cache.pop(next(iter(self._scan_cache)))
            self._scan_cache[cache_key] = one_epoch
        one_epoch = self._scan_cache[cache_key]

        start = self._scan_epoch if epoch_offset is None else epoch_offset
        aux_per_epoch = []
        for epoch in range(start, start + num_epochs):
            carry, aux = one_epoch(data, carry, epoch)
            aux_per_epoch.append(aux)
        if epoch_offset is None:
            # Explicit offsets (replay/eval at a pinned epoch) must not clobber the
            # training cursor, or the next default call would reuse permutations.
            self._scan_epoch = start + num_epochs
        return carry, aux_per_epoch

    # -- mesh / host path: numpy sampling + per-batch sharded assembly ----------------

    def _iter_epoch_host(self, epoch):
        if self._columns is None:
            raise RuntimeError(
                'Python iteration is unavailable after scan_epochs moved the dataset '
                'to device HBM (the host copy is dropped to avoid double residency); '
                'keep using scan_epochs, or build a separate loader for iteration')
        if self._shuffle:
            perm = np.random.RandomState((self._seed + epoch) % (2 ** 31)).permutation(
                self._num_rows)
        else:
            perm = np.arange(self._num_rows)
        sharding = resolve_sharding(self._mesh, self._partition_spec, self._device_put)
        if isinstance(sharding, FieldShardings):
            sharding.check_unused(self._columns.keys())
        limit = (self._num_rows - self.batch_size + 1 if self._drop_last
                 else self._num_rows)
        for start in range(0, limit, self.batch_size):
            idx = perm[start:start + self.batch_size]
            batch = {name: np.ascontiguousarray(col[idx])
                     for name, col in self._columns.items()}
            if self._device_put:
                # __iter__ routes here with device_put only when a mesh is present
                # (single-device device_put takes the HBM-resident path).
                import jax
                batch = {name: jax.make_array_from_process_local_data(
                             sharding_for_field(sharding, name), col)
                         for name, col in batch.items()}
            yield batch

    # ------------------------------------------------------------------ lifecycle

    def stop(self):
        pass

    def join(self):
        pass

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc_val, exc_tb):
        pass


def _concat(parts):
    if len(parts) == 1:
        return np.ascontiguousarray(parts[0])
    return np.concatenate(parts, axis=0)
