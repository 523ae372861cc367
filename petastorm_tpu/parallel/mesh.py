"""Mesh + shard-discovery helpers.

The reference discovers the data-parallel shard from Horovod/MPI environment variables
(petastorm/spark/spark_dataset_converter.py:116-129); the TPU-native contract is the JAX
runtime itself: ``jax.process_index()/process_count()`` over an initialized
``jax.distributed`` backend, with manual ``cur_shard/shard_count`` kwargs kept as
overrides.
"""

import os

import numpy as np


def make_mesh(axis_names=('data',), axis_sizes=None, devices=None):
    """Build a :class:`jax.sharding.Mesh` over the available devices.

    :param axis_names: mesh axis names, e.g. ``('data',)`` or ``('data', 'model')``.
    :param axis_sizes: sizes per axis; None infers a single axis over all devices, or
        factors the device count with the leading axis taking the remainder.
    :param devices: explicit device list (default ``jax.devices()``).
    """
    import jax
    from jax.sharding import Mesh
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if axis_sizes is None:
        if len(axis_names) == 1:
            axis_sizes = (n,)
        else:
            axis_sizes = (n,) + (1,) * (len(axis_names) - 1)
    axis_sizes = tuple(axis_sizes)
    if int(np.prod(axis_sizes)) != n:
        raise ValueError('axis_sizes {} do not multiply to device count {}'
                         .format(axis_sizes, n))
    device_array = np.asarray(devices).reshape(axis_sizes)
    return Mesh(device_array, axis_names)


def batch_sharding(mesh, partition_spec=None, batch_axis='data'):
    """NamedSharding for batches: by default batch dim sharded over ``batch_axis``; any
    ``PartitionSpec`` is accepted so the loader can feed TP/PP/SP-sharded consumers, not
    only batch-axis DP (SURVEY.md §2.8 obligation)."""
    from jax.sharding import NamedSharding, PartitionSpec
    if partition_spec is None:
        partition_spec = PartitionSpec(batch_axis)
    return NamedSharding(mesh, partition_spec)


def distributed_shard_info(cur_shard=None, shard_count=None):
    """Resolve this process's (cur_shard, shard_count) for reader construction.

    Priority: explicit kwargs > PETASTORM_TPU_PROCESS_INDEX/_COUNT env pair (the
    topology plane's CPU-test override — parallel/topology.py) > initialized JAX
    distributed runtime > single process. Legacy Horovod/MPI env vars are honored as a
    compatibility fallback, mirroring the reference's detection
    (spark_dataset_converter.py:116-129)."""
    if cur_shard is not None or shard_count is not None:
        if (cur_shard is None) != (shard_count is None):
            raise ValueError('cur_shard and shard_count must be given together')
        return cur_shard, shard_count
    from petastorm_tpu.parallel.topology import (PROCESS_COUNT_ENV,
                                                 PROCESS_INDEX_ENV)
    if PROCESS_INDEX_ENV in os.environ and PROCESS_COUNT_ENV in os.environ:
        return (int(os.environ[PROCESS_INDEX_ENV]),
                int(os.environ[PROCESS_COUNT_ENV]))
    import jax
    if jax.process_count() > 1:
        return jax.process_index(), jax.process_count()
    for rank_var, size_var in (('HOROVOD_RANK', 'HOROVOD_SIZE'),
                               ('OMPI_COMM_WORLD_RANK', 'OMPI_COMM_WORLD_SIZE'),
                               ('PMI_RANK', 'PMI_SIZE')):
        if rank_var in os.environ and size_var in os.environ:
            return int(os.environ[rank_var]), int(os.environ[size_var])
    return None, None


def initialize_distributed(coordinator_address=None, num_processes=None, process_id=None):
    """Thin gate over ``jax.distributed.initialize`` (multi-host DCN coordination). Safe
    to call when already initialized."""
    import jax
    try:
        jax.distributed.initialize(coordinator_address=coordinator_address,
                                   num_processes=num_processes, process_id=process_id)
    except RuntimeError:
        pass  # already initialized
