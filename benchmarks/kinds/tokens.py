"""Pre-chunked windows of token ids (``NdarrayCodec``), Zipf-distributed over the
whole vocabulary."""
import io

import numpy as np

COLUMNS = ('tokens',)


def fields(store):
    from petastorm_tpu.codecs import NdarrayCodec
    from petastorm_tpu.unischema import UnischemaField
    return [UnischemaField('tokens', np.int32, (store['seq_len'],), NdarrayCodec(), False)]


def zipf_tokens(rng, n, vocab, exponent):
    """``n`` ids from a Zipf law over the whole vocabulary, ranks scattered over ids
    by a fixed permutation."""
    ranks = np.arange(1, vocab + 1, dtype=np.float64)
    cdf = np.cumsum(ranks ** -exponent)
    cdf /= cdf[-1]
    rank = np.minimum(np.searchsorted(cdf, rng.uniform(size=n)), vocab - 1)
    return np.random.default_rng(0).permutation(vocab).astype(np.int32)[rank]


def rows(store):
    rng = np.random.default_rng(store['seed'])
    for _ in range(store['rows']):
        yield {'tokens': zipf_tokens(rng, store['seq_len'], store['vocab'],
                                     store['zipf_exponent'])}


def reader_kwargs(mix, seeds):
    return {}


def plain_rows(mix, table, ids, seeds):
    return {'tokens': np.stack([np.load(io.BytesIO(table['tokens'][i]), allow_pickle=False)
                                for i in ids])}


def alter(batch):
    """Row 0's first id moved by one, as a loader fault would."""
    x = batch['tokens']
    return dict(batch, tokens=x.at[0, 0].set((x[0, 0] + 1) % 2 ** 15))
