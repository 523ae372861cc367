"""The one generator of every traffic mix's store.

A mix is a data file, ``benchmarks/traffic/<mix>.json``; its ``store`` block
names a ``kind`` and the sizes. A kind is a module of its own,
``benchmarks/kinds/<kind>.py``, found by that name; it has

- ``fields(store)``: the Unischema fields beside the ``id`` column;
- ``rows(store)``: the rows' values, from ``store['seed']``;
- ``COLUMNS``: the stored columns that the check compares;
- ``reader_kwargs(mix, seeds)``: what ``make_reader`` takes beyond the mix's
  ``reader`` block (a transform, device decode);
- ``plain_rows(mix, table, ids, seeds)``: the reference's own read of rows
  ``ids`` from ``table`` (:func:`read_columns`), with nothing of the program under
  test: the inputs the loader should have delivered for them;
- ``alter(batch)``: the batch with one element of row 0 changed, as a loader
  fault would (jax, for the fault tests).

The store is written once per checkout under
``benchmarks/.cache/<mix>-<store seed>-v<version>`` and reused by every later
run: the run's own ``--seed`` picks the read order, the crops and the weights,
never the stored bytes.
"""
import glob
import json
import os
import shutil

import numpy as np

#: stored ids are row numbers 0..rows-1, in this int32 column of every store
ID = 'id'


def load_mix(traffic_dir, name):
    with open(os.path.join(traffic_dir, name + '.json')) as f:
        return json.load(f)


def store_path(cache_root, mix_name, store):
    return os.path.join(cache_root, '{}-{}-v{}'.format(mix_name, store['seed'],
                                                        store['version']))


def _ided(rows):
    for i, row in enumerate(rows):
        row[ID] = np.int32(i)
        yield row


def ensure_store(cache_root, mix_name, store, kind):
    """The store's directory, and whether this call wrote it (a checkout's first
    run). Written under a temporary name and renamed, so a run cut while writing
    leaves no store that looks whole."""
    path = store_path(cache_root, mix_name, store)
    if os.path.isdir(path):
        return path, False
    from petastorm_tpu.codecs import ScalarCodec
    from petastorm_tpu.etl.dataset_metadata import write_rows
    from petastorm_tpu.unischema import Unischema, UnischemaField
    schema = Unischema('Bench_' + store['kind'],
                       [UnischemaField(ID, np.int32, (), ScalarCodec(), False)]
                       + list(kind.fields(store)))
    tmp = path + '.partial'
    shutil.rmtree(tmp, ignore_errors=True)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    write_rows('file://' + tmp, schema, _ided(kind.rows(store)),
               rowgroup_size_mb=store['rowgroup_size_mb'], n_files=store['files'],
               compression=store.get('compression', 'snappy'))
    os.rename(tmp, path)
    return path, True


def _parts(path):
    parts = sorted(glob.glob(os.path.join(path, '*.parquet')))
    if not parts:
        raise FileNotFoundError('no parquet files under {}'.format(path))
    return parts


def read_columns(path, columns):
    """Every row of ``columns`` by stored id, read with pyarrow:
    ``{column: {id: value}}``."""
    import pyarrow.parquet as pq
    out = {c: {} for c in columns}
    for part in _parts(path):
        table = pq.read_table(part, columns=[ID] + list(columns))
        ids = table[ID].to_numpy()
        for c in columns:
            for i, v in zip(ids, table[c].to_pylist()):
                out[c][int(i)] = v
    return out
