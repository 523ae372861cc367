"""Long-context training from Parquet: sequence-parallel attention fed by
sequence-sharded loader batches.

The end-to-end long-context story (SURVEY.md §5.7's idiomatic extension point — the
reference only *constructs* sequences via NGram; it has no compute-side sequence
parallelism):

1. tokenized documents live in a petastorm_tpu store (one ``(seq_len,)`` int32
   NdarrayCodec field per row);
2. ``JaxDataLoader`` emits batches sharded over a 2-D ``(data, seq)`` mesh with
   ``PartitionSpec('data', 'seq')`` — each device holds a [B/data, T/seq] token shard,
   assembled straight from the host pipeline (no resharding step);
3. the shared :class:`petastorm_tpu.models.TransformerLM` trains with
   ``ops.ring_attention`` injected as its attention backend (K/V shards rotate around
   the ``seq`` ring via ``ppermute`` on ICI), so sequences longer than one chip's HBM
   are trained without gathering the full sequence anywhere — and the model code is
   identical to the single-chip dense/flash configurations.

Two dataset modes:

- default: pre-tokenized fixed-length documents (one ``(seq_len,)`` row per doc);
- ``--ngram-frames N``: the store holds short token *frames* of a stream and the
  training sequence is assembled by :class:`petastorm_tpu.ngram.NGram` — N consecutive
  frames per window, gap-checked on ``frame_id`` — flowing straight into the device
  layer as ``(batch, N, frame_len)`` sequence-sharded arrays (the reference can only
  emit NGram windows as python dicts; here they feed the mesh, SURVEY.md §5.7).

Run: ``python -m examples.long_context.jax_example --seq-len 512``
     ``python -m examples.long_context.jax_example --ngram-frames 8``
"""

import argparse
import os
import tempfile

import numpy as np

VOCAB = 256
EMBED = 64
HEADS = 4


def build_dataset(url, num_docs=256, seq_len=512, seed=0):
    """Materialize synthetic tokenized documents (stand-in for a tokenized corpus)."""
    from petastorm_tpu.codecs import NdarrayCodec, ScalarCodec
    from petastorm_tpu.etl.dataset_metadata import write_rows
    from petastorm_tpu.unischema import Unischema, UnischemaField

    schema = Unischema('Docs', [
        UnischemaField('doc_id', np.int64, (), ScalarCodec(), False),
        UnischemaField('tokens', np.int32, (seq_len,), NdarrayCodec(), False),
    ])
    rng = np.random.RandomState(seed)
    # a learnable synthetic language: each doc repeats a per-doc token bigram pattern
    rows = []
    for i in range(num_docs):
        base = rng.randint(0, VOCAB, size=8, dtype=np.int32)
        tokens = np.tile(base, seq_len // 8 + 1)[:seq_len].astype(np.int32)
        rows.append({'doc_id': i, 'tokens': tokens})
    write_rows(url, schema, rows, n_files=4)
    return schema


def build_frame_dataset(url, num_frames=512, frame_len=64, seed=0):
    """Materialize a token STREAM as consecutive frames: ``frame_id`` orders them and is
    the NGram timestamp; windows of N frames become N*frame_len-token sequences. Frames
    of one stream segment live in one rowgroup (windows never cross rowgroups —
    reference caveat ngram.py:85-91), so rows_per_file bounds the window range."""
    from petastorm_tpu.codecs import NdarrayCodec, ScalarCodec
    from petastorm_tpu.etl.dataset_metadata import write_rows
    from petastorm_tpu.unischema import Unischema, UnischemaField

    schema = Unischema('Frames', [
        UnischemaField('frame_id', np.int64, (), ScalarCodec(), False),
        UnischemaField('tokens', np.int32, (frame_len,), NdarrayCodec(), False),
    ])
    rng = np.random.RandomState(seed)
    base = rng.randint(0, VOCAB, size=8, dtype=np.int32)
    stream = np.tile(base, num_frames * frame_len // 8 + 1)[:num_frames * frame_len]
    rows = [{'frame_id': i, 'tokens': stream[i * frame_len:(i + 1) * frame_len]
             .astype(np.int32)} for i in range(num_frames)]
    write_rows(url, schema, rows, rows_per_file=max(64, num_frames // 4),
               rowgroup_size_mb=64)
    return schema


def build_ragged_dataset(url, num_docs=256, max_len=48, seed=0):
    """Native Parquet list<int32> store of VARIABLE-length documents (the packed
    mode's input: no Unischema codec — ``make_batch_reader``'s native contract)."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    from petastorm_tpu.fs_utils import get_filesystem_and_path_or_paths

    fs, path = get_filesystem_and_path_or_paths(url)
    fs.create_dir(path, recursive=True)
    rng = np.random.RandomState(seed)
    docs = []
    for _ in range(num_docs):
        base = rng.randint(0, VOCAB, size=8, dtype=np.int32)
        n = int(rng.randint(8, max_len + 1))
        docs.append(np.tile(base, n // 8 + 1)[:n].astype(np.int32).tolist())
    per_file = max(1, num_docs // 4)
    for part in range(0, num_docs, per_file):
        chunk = docs[part:part + per_file]
        table = pa.table({
            'doc_id': np.arange(part, part + len(chunk), dtype=np.int64),
            'tokens': pa.array(chunk, type=pa.list_(pa.int32())),
        })
        with fs.open_output_stream('{}/part_{}.parquet'.format(path, part)) as sink:
            pq.write_table(table, sink)


def _make_data_seq_mesh(data_axis):
    """ONE definition of the example's (data, seq) device factoring: default data
    axis 2 on even device counts, seq takes the rest."""
    import jax

    from petastorm_tpu.parallel import make_mesh

    n_dev = len(jax.devices())
    if data_axis is None:
        data_axis = 2 if n_dev % 2 == 0 and n_dev > 1 else 1
    if n_dev % data_axis:
        raise ValueError('data_axis {} does not divide device count {}'
                         .format(data_axis, n_dev))
    return make_mesh(('data', 'seq'), axis_sizes=(data_axis, n_dev // data_axis))


def train_packed(dataset_url, seq_len=64, batch_size=8, epochs=2, data_axis=None,
                 learning_rate=1e-2):
    """Packed-mode training, sequence-parallel: ragged docs -> worker-side first-fit
    packing (ops.packing.make_packing_transform) -> dense [batch, seq_len] device
    batches sharded ``P('data', 'seq')`` -> TransformerLM with SEGMENT-masked RING
    attention (segment ids ring-rotate with their K/V blocks), so packing composes
    with sequences longer than one chip. The model is constructed INSIDE the jitted
    step so each batch's segment ids flow through one compiled program — the pattern
    to copy for packed training."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import PartitionSpec as P

    from petastorm_tpu import make_batch_reader
    from petastorm_tpu.models import TransformerLM
    from petastorm_tpu.ops.packing import (make_packing_transform,
                                           packed_next_token_loss)
    from petastorm_tpu.ops.ring_attention import ring_attention_sharded
    from petastorm_tpu.parallel import JaxDataLoader

    mesh = _make_data_seq_mesh(data_axis)
    if seq_len % mesh.shape['seq']:
        raise ValueError('seq_len {} not divisible by the seq mesh axis ({}); pick '
                         'a multiple or set --data-axis'
                         .format(seq_len, mesh.shape['seq']))
    if batch_size % mesh.shape['data']:
        raise ValueError('batch_size {} not divisible by the data mesh axis ({})'
                         .format(batch_size, mesh.shape['data']))
    optimizer = optax.adam(learning_rate)
    ring = ring_attention_sharded(mesh, 'seq', causal=True, with_segments=True,
                                  batch_axis='data')

    def model_for(segments):
        return TransformerLM(vocab=VOCAB, embed=EMBED, heads=HEADS, layers=1,
                             dtype=jnp.float32, max_len=seq_len,
                             attention_fn=lambda q, k, v: ring(q, k, v, segments))

    @jax.jit
    def train_step(params, opt_state, tokens, segments, positions):
        model = model_for(segments)

        def loss_fn(p):
            # positions: the packer's per-segment restart column, so every packed
            # document's position embedding starts at 0 (the attention mask alone
            # only isolates segments — it does not fix their positions).
            return packed_next_token_loss(model.apply(p, tokens, positions),
                                          tokens, segments)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    reader = make_batch_reader(
        dataset_url, transform_spec=make_packing_transform('tokens', seq_len),
        num_epochs=epochs, shuffle_row_groups=True, seed=7)
    spec = {'tokens': P('data', 'seq'), 'tokens_segments': P('data', 'seq'),
            'tokens_positions': P('data', 'seq')}
    loss = params = opt_state = None
    with mesh:
        with JaxDataLoader(reader, batch_size=batch_size, mesh=mesh,
                           partition_spec=spec) as loader:
            for step, batch in enumerate(loader):
                tokens, segments = batch['tokens'], batch['tokens_segments']
                positions = batch['tokens_positions']
                if params is None:
                    # Params are independent of the (parameter-free) attention
                    # backend: init once with any segments.
                    params = model_for(segments).init(jax.random.PRNGKey(0), tokens,
                                                      positions)
                    opt_state = optimizer.init(params)
                params, opt_state, loss = train_step(params, opt_state, tokens,
                                                     segments, positions)
                if step % 20 == 0:
                    print('step {} loss {:.4f}'.format(step, float(loss)))
            print('input pipeline stats:', loader.stats.as_dict())
    if loss is None:
        raise ValueError(
            'no batches: the corpus packs into fewer than batch_size={} bins '
            '(packing compresses docs ~seq_len/mean_len-fold) — lower the batch '
            'size or add data'.format(batch_size))
    return params, float(loss)


def make_model(mesh):
    """The shared TransformerLM with ring attention injected over the mesh's ``seq``
    axis — the model family's documented sequence-parallel injection point
    (petastorm_tpu/models/transformer.py); the model itself stays mesh-agnostic."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from petastorm_tpu.models import TransformerLM
    from petastorm_tpu.ops.ring_attention import ring_attention

    attn_spec = P('data', 'seq', None, None)
    ring = jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v, axis_name='seq', causal=True),
        mesh=mesh, in_specs=(attn_spec, attn_spec, attn_spec), out_specs=attn_spec,
        check_vma=False)
    return TransformerLM(vocab=VOCAB, embed=EMBED, heads=HEADS, layers=1,
                         dtype=jnp.float32, attention_fn=ring)


def make_train_step(mesh, model, learning_rate=1e-2):
    """Jitted train step over the (data, seq) mesh: embeddings/matmuls are GSPMD-sharded
    by the batch's PartitionSpec; attention runs as ring attention over the seq axis."""
    import jax
    import optax

    from petastorm_tpu.models import next_token_loss

    optimizer = optax.adam(learning_rate)

    @jax.jit
    def train_step(params, opt_state, tokens):
        if tokens.ndim == 3:
            # NGram window batch (batch, frames, frame_len): frames are consecutive
            # stream chunks, so flattening yields the contiguous training sequence.
            # With the frame axis sharded over 'seq' this reshape is shard-local.
            tokens = tokens.reshape(tokens.shape[0], -1)
        loss, grads = jax.value_and_grad(
            lambda p: next_token_loss(model.apply(p, tokens), tokens))(params)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    return train_step, optimizer


def train(dataset_url, batch_size=8, epochs=2, data_axis=None, ngram_frames=0):
    import jax
    from jax.sharding import PartitionSpec as P

    from petastorm_tpu import make_reader
    from petastorm_tpu.parallel import JaxDataLoader

    mesh = _make_data_seq_mesh(data_axis)
    model = make_model(mesh)
    train_step, optimizer = make_train_step(mesh, model)

    if ngram_frames:
        from petastorm_tpu.ngram import NGram
        ngram = NGram({i: ['tokens'] for i in range(ngram_frames)},
                      delta_threshold=1, timestamp_field='frame_id')
        reader = make_reader(dataset_url, schema_fields=ngram, num_epochs=epochs,
                             shuffle_row_groups=True, seed=7)
        # windows arrive (batch, frames, frame_len): shard the frame axis over 'seq'
        spec = {'tokens': P('data', 'seq'), 'frame_id': P('data', 'seq')}
    else:
        reader = make_reader(dataset_url, schema_fields=['tokens'], num_epochs=epochs,
                             shuffle_row_groups=True, seed=7)
        spec = P('data', 'seq')

    loss = None
    params = opt_state = None
    with mesh:
        with JaxDataLoader(reader, batch_size=batch_size, mesh=mesh,
                           partition_spec=spec) as loader:
            for step, batch in enumerate(loader):
                if params is None:
                    # leading dim is the GLOBAL batch (batch_size x process_count)
                    tokens = batch['tokens']
                    params = model.init(jax.random.PRNGKey(0),
                                        jax.numpy.reshape(tokens,
                                                          (tokens.shape[0], -1)))
                    opt_state = optimizer.init(params)
                params, opt_state, loss = train_step(params, opt_state,
                                                     batch['tokens'])
                if step % 20 == 0:
                    print('step {} loss {:.4f}'.format(step, float(loss)))
            print('input pipeline stats:', loader.stats.as_dict())
    return params, float(loss)


def main():
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument('--dataset-url', default=None)
    parser.add_argument('--num-docs', type=int, default=256)
    parser.add_argument('--seq-len', type=int, default=512)
    parser.add_argument('--batch-size', type=int, default=8)
    parser.add_argument('--epochs', type=int, default=2)
    parser.add_argument('--data-axis', type=int, default=None,
                        help='mesh data-axis size (default: 2 if the device count is '
                             'even, else 1; seq axis gets the rest)')
    parser.add_argument('--ngram-frames', type=int, default=0,
                        help='assemble training sequences as NGram windows of this many '
                             'consecutive token frames (0 = pre-tokenized docs mode)')
    parser.add_argument('--packed', action='store_true',
                        help='variable-length docs packed into fixed bins inside the '
                             'reader workers (segment-masked attention + loss)')
    args = parser.parse_args()

    if args.packed:
        if args.ngram_frames:
            parser.error('--packed and --ngram-frames are mutually exclusive')
        if args.dataset_url:
            # Never write synthetic data into a user-provided store: packed mode
            # only auto-generates into its own tmp default.
            url = args.dataset_url
        else:
            # Doc lengths are capped by --seq-len (a doc longer than a bin cannot
            # pack); the cache path is keyed by the full geometry.
            max_len = min(48, args.seq_len)
            url = os.path.join(tempfile.gettempdir(),
                               'long_context_ragged_{}x{}'.format(args.num_docs,
                                                                  max_len))
            fs_path = url.replace('file://', '')
            if not os.path.exists(fs_path) or not os.listdir(fs_path):
                print('materializing {} ragged docs to {}'.format(args.num_docs,
                                                                  url))
                build_ragged_dataset(url, num_docs=args.num_docs, max_len=max_len)
        _, final_loss = train_packed(url, seq_len=args.seq_len,
                                     batch_size=args.batch_size,
                                     epochs=args.epochs,
                                     data_axis=args.data_axis)
        print('final loss: {:.4f}'.format(final_loss))
        return

    if args.ngram_frames:
        if args.seq_len % args.ngram_frames or args.seq_len < args.ngram_frames:
            parser.error('--ngram-frames ({}) must divide --seq-len ({})'
                         .format(args.ngram_frames, args.seq_len))
        # cache path keyed by the geometry: changing the flags must not silently
        # reuse a store with a different frame length
        suffix = '_frames_{}x{}'.format(args.num_docs,
                                        args.seq_len // args.ngram_frames)
    else:
        suffix = ''
    url = args.dataset_url or os.path.join(tempfile.gettempdir(),
                                           'long_context_demo' + suffix)
    if not os.path.exists(os.path.join(url.replace('file://', ''), '_common_metadata')):
        if args.ngram_frames:
            frame_len = args.seq_len // args.ngram_frames
            print('materializing {} frames x {} tokens to {}'.format(
                args.num_docs, frame_len, url))
            build_frame_dataset(url, num_frames=args.num_docs, frame_len=frame_len)
        else:
            print('materializing {} docs x {} tokens to {}'.format(
                args.num_docs, args.seq_len, url))
            build_dataset(url, args.num_docs, args.seq_len)
    _, final_loss = train(url, batch_size=args.batch_size, epochs=args.epochs,
                          data_axis=args.data_axis, ngram_frames=args.ngram_frames)
    print('final loss: {:.4f}'.format(final_loss))


if __name__ == '__main__':
    main()
