"""Sequence packing (ops/packing.py): host-side bin packing, segment-masked
attention, boundary-masked loss, and the e2e ragged-store -> packed device batches
chain through make_batch_reader + TransformSpec + JaxDataLoader."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from petastorm_tpu.ops.packing import (make_packing_transform, masked_dense_attention,
                                       pack_sequences, packed_next_token_loss,
                                       segment_causal_attention, segment_mask)


class TestPackSequences(object):
    def test_round_trip_and_positions(self):
        rng = np.random.RandomState(0)
        seqs = [rng.randint(1, 100, size=n).astype(np.int32)
                for n in (5, 3, 8, 2, 7, 4)]
        packed = pack_sequences(seqs, seq_len=8)
        tokens, segments, positions = (packed['tokens'], packed['segments'],
                                       packed['positions'])
        # Every input sequence appears contiguously in exactly one (bin, segment).
        found = []
        for b in range(tokens.shape[0]):
            for seg in range(1, int(segments[b].max()) + 1):
                sel = segments[b] == seg
                found.append(tokens[b][sel].tolist())
                np.testing.assert_array_equal(positions[b][sel],
                                              np.arange(int(sel.sum())))
        assert sorted(map(tuple, found)) == sorted(tuple(s) for s in seqs)
        # Padding is segment 0 with zero tokens.
        assert np.all(tokens[segments == 0] == 0)
        # First-fit packs at least as tightly as one-bin-per-sequence.
        assert tokens.shape[0] <= len(seqs)

    def test_deterministic_first_fit(self):
        seqs = [np.arange(1, 6), np.arange(1, 4), np.arange(1, 5)]
        a = pack_sequences(seqs, 8)
        b = pack_sequences(seqs, 8)
        np.testing.assert_array_equal(a['tokens'], b['tokens'])
        # 5 + 3 share bin 0 (first fit), 4 opens bin 1.
        assert a['tokens'].shape[0] == 2
        assert int(a['segments'][0].max()) == 2

    def test_too_long_and_empty(self):
        with pytest.raises(ValueError):
            pack_sequences([np.arange(10)], 8)
        packed = pack_sequences([], 8)
        assert packed['tokens'].shape == (1, 8)
        assert np.all(packed['segments'] == 0)
        packed = pack_sequences([np.arange(0), np.arange(1, 3)], 8)
        assert int(packed['segments'].max()) == 1  # empty sequence skipped


class TestSegmentAttention(object):
    def test_segment_isolation(self):
        """The property packing exists for: tokens in one segment must be invisible
        to every other segment, through a real TransformerLM forward."""
        from petastorm_tpu.models import TransformerLM

        segments = jnp.asarray([[1, 1, 1, 2, 2, 2, 2, 0]], jnp.int32)
        rng = np.random.RandomState(1)
        tokens = jnp.asarray(rng.randint(0, 32, (1, 8)), jnp.int32)
        model = TransformerLM(vocab=32, embed=16, heads=2, layers=2,
                              dtype=jnp.float32,
                              attention_fn=segment_causal_attention(segments))
        params = model.init(jax.random.PRNGKey(0), tokens)
        base = model.apply(params, tokens)
        # Change segment 2's tokens: segment 1 logits must not move at all.
        altered = tokens.at[0, 4].set((int(tokens[0, 4]) + 7) % 32)
        out = model.apply(params, altered)
        np.testing.assert_allclose(np.asarray(out[0, :3]), np.asarray(base[0, :3]),
                                   rtol=1e-6, atol=1e-6)
        assert not np.allclose(np.asarray(out[0, 4:7]), np.asarray(base[0, 4:7]))

    def test_matches_plain_causal_for_single_segment(self):
        from petastorm_tpu.ops.ring_attention import dense_attention
        rng = np.random.RandomState(2)
        q, k, v = (jnp.asarray(rng.randn(2, 6, 2, 4), jnp.float32) for _ in range(3))
        segments = jnp.ones((2, 6), jnp.int32)
        got = masked_dense_attention(q, k, v, segment_mask(segments, segments))
        expected = dense_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                                   rtol=1e-6, atol=1e-6)

    def test_padding_positions_emit_zero(self):
        rng = np.random.RandomState(3)
        q, k, v = (jnp.asarray(rng.randn(1, 4, 1, 4), jnp.float32) for _ in range(3))
        segments = jnp.asarray([[1, 1, 0, 0]], jnp.int32)
        out = masked_dense_attention(q, k, v, segment_mask(segments, segments))
        np.testing.assert_array_equal(np.asarray(out[0, 2:]), 0.0)


class TestPackedRingAttention(object):
    """segments= on ops.ring_attention: packing composes with sequence parallelism —
    segment ids ring-rotate with their K/V blocks and the result must equal the
    dense segment-masked reference."""

    def _run_ring(self, q, k, v, segments, causal):
        from jax.sharding import Mesh

        from petastorm_tpu.ops.ring_attention import ring_attention_sharded

        mesh = Mesh(np.asarray(jax.devices()[:4]), ('seq',))
        fn = ring_attention_sharded(mesh, 'seq', causal=causal, with_segments=True)
        return fn(q, k, v, segments)

    @pytest.mark.parametrize('causal', [True, False])
    def test_matches_masked_dense(self, causal):
        rng = np.random.RandomState(5)
        q, k, v = (jnp.asarray(rng.randn(2, 16, 2, 4), jnp.float32)
                   for _ in range(3))
        # Segments span shard boundaries (shards are 4 long) — the rotating-segment
        # path is really exercised; one batch row ends in padding.
        segments = jnp.asarray([[1, 1, 1, 1, 1, 2, 2, 2, 2, 2, 2, 3, 3, 3, 3, 3],
                                [1, 1, 1, 1, 2, 2, 2, 2, 2, 2, 2, 2, 2, 0, 0, 0]],
                               jnp.int32)
        got = self._run_ring(q, k, v, segments, causal)
        expected = masked_dense_attention(
            q, k, v, segment_mask(segments, segments, causal=causal))
        np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                                   rtol=2e-5, atol=2e-6)

    def test_padding_rows_zero(self):
        rng = np.random.RandomState(6)
        q, k, v = (jnp.asarray(rng.randn(1, 8, 1, 4), jnp.float32)
                   for _ in range(3))
        segments = jnp.asarray([[1, 1, 1, 0, 0, 0, 0, 0]], jnp.int32)
        out = self._run_ring(q, k, v, segments, causal=True)
        np.testing.assert_array_equal(np.asarray(out[0, 3:]), 0.0)

    def test_none_segments_unchanged(self):
        from petastorm_tpu.ops.ring_attention import dense_attention
        from jax.sharding import Mesh, PartitionSpec as P

        from petastorm_tpu.ops.ring_attention import ring_attention

        rng = np.random.RandomState(7)
        q, k, v = (jnp.asarray(rng.randn(2, 16, 2, 4), jnp.float32)
                   for _ in range(3))
        mesh = Mesh(np.asarray(jax.devices()[:4]), ('seq',))
        qkv_spec = P(None, 'seq', None, None)
        fn = jax.shard_map(
            lambda q, k, v: ring_attention(q, k, v, axis_name='seq', causal=True),
            mesh=mesh, in_specs=(qkv_spec, qkv_spec, qkv_spec), out_specs=qkv_spec,
            check_vma=False)
        np.testing.assert_allclose(np.asarray(jax.jit(fn)(q, k, v)),
                                   np.asarray(dense_attention(q, k, v, causal=True)),
                                   rtol=2e-5, atol=2e-6)


class TestPackedLoss(object):
    def test_masks_cross_segment_and_padding(self):
        # Hand-check: only within-segment transitions count.
        segments = jnp.asarray([[1, 1, 2, 0]], jnp.int32)
        tokens = jnp.asarray([[3, 1, 2, 0]], jnp.int32)
        logits = jnp.zeros((1, 4, 5), jnp.float32)  # uniform -> nll = log(5)
        loss = packed_next_token_loss(logits, tokens, segments)
        # Valid transitions: t=0 (1->1). t=1 crosses 1->2, t=2 crosses 2->0.
        np.testing.assert_allclose(float(loss), np.log(5.0), rtol=1e-6)

    def test_all_padding_is_finite(self):
        segments = jnp.zeros((1, 4), jnp.int32)
        loss = packed_next_token_loss(jnp.zeros((1, 4, 5)), jnp.zeros((1, 4),
                                                                     jnp.int32),
                                      segments)
        assert float(loss) == 0.0


def write_ragged_store(root, n_docs, n_parts=1, seed=11, min_len=4, max_len=13):
    """Native parquet list<int32> store of variable-length docs — the ONE builder
    for every ragged-store test in this file."""
    import pyarrow as pa
    import pyarrow.parquet as pq
    rng = np.random.RandomState(seed)
    docs = [rng.randint(0, 32, size=rng.randint(min_len, max_len))
            .astype(np.int32) for _ in range(n_docs)]
    root.mkdir()
    per_part = n_docs // n_parts
    for part in range(n_parts):
        chunk = docs[part * per_part:(part + 1) * per_part]
        table = pa.table({
            'doc_id': np.arange(part * per_part, (part + 1) * per_part,
                                dtype=np.int64),
            'tokens': pa.array([d.tolist() for d in chunk],
                               type=pa.list_(pa.int32())),
        })
        pq.write_table(table, str(root / 'part_{}.parquet'.format(part)))
    return 'file://' + str(root)


class TestPackingCrossFramework(object):
    """The packing TransformSpec is framework-neutral: the same reader feeds the
    torch and TF adapters with dense packed columns."""

    def _ragged_store(self, tmp_path):
        return write_ragged_store(tmp_path / 'ragged', n_docs=32)

    def test_torch_batched_loader_gets_packed_columns(self, tmp_path):
        torch = pytest.importorskip('torch')

        from petastorm_tpu import make_batch_reader
        from petastorm_tpu.pytorch import BatchedDataLoader

        url = self._ragged_store(tmp_path)
        reader = make_batch_reader(
            url, transform_spec=make_packing_transform('tokens', 24), num_epochs=1)
        with BatchedDataLoader(reader, batch_size=4) as loader:
            batch = next(iter(loader))
        assert batch['tokens'].shape[1] == 24
        assert isinstance(batch['tokens'], torch.Tensor)
        assert int(batch['tokens_segments'].max()) >= 1

    def test_tf_dataset_gets_packed_columns(self, tmp_path):
        tf = pytest.importorskip('tensorflow')

        from petastorm_tpu import make_batch_reader
        from petastorm_tpu.tf_utils import make_petastorm_dataset

        url = self._ragged_store(tmp_path)
        with make_batch_reader(
                url, transform_spec=make_packing_transform('tokens', 24),
                num_epochs=1) as reader:
            dataset = make_petastorm_dataset(reader)
            batch = next(iter(dataset))
        assert batch.tokens.shape[1] == 24
        assert batch.tokens.dtype == tf.int32
        assert int(tf.reduce_max(batch.tokens_segments)) >= 1


class TestPackingEndToEnd(object):
    def test_ragged_store_to_packed_training_step(self, tmp_path):
        """native parquet list<int32> store -> make_batch_reader(TransformSpec=
        packing) -> JaxDataLoader -> TransformerLM steps with segment attention."""
        import optax
        from jax.sharding import PartitionSpec as P

        from petastorm_tpu import make_batch_reader
        from petastorm_tpu.models import TransformerLM
        from petastorm_tpu.parallel import JaxDataLoader, make_mesh

        url = write_ragged_store(tmp_path / 'ragged', n_docs=64, n_parts=4,
                                 seed=4, max_len=17)

        seq_len = 32
        reader = make_batch_reader(
            url, transform_spec=make_packing_transform('tokens', seq_len),
            num_epochs=2, shuffle_row_groups=False)
        mesh = make_mesh(('data',))
        optimizer = optax.adam(1e-2)
        losses = []
        with JaxDataLoader(reader, batch_size=8, mesh=mesh,
                           partition_spec=P('data'), drop_last=True) as loader:
            params = opt_state = None
            for batch in loader:
                tokens, segments = batch['tokens'], batch['tokens_segments']
                assert tokens.shape[1] == seq_len
                # Rebuild the model per batch with the batch's segment mask; params
                # are shared because the attention backend is parameter-free.
                seg_model = TransformerLM(
                    vocab=32, embed=16, heads=2, layers=1, dtype=jnp.float32,
                    max_len=seq_len,
                    attention_fn=segment_causal_attention(segments))
                if params is None:
                    params = seg_model.init(jax.random.PRNGKey(0), tokens)
                    opt_state = optimizer.init(params)
                loss, grads = jax.value_and_grad(
                    lambda p: packed_next_token_loss(
                        seg_model.apply(p, tokens), tokens, segments))(params)
                updates, opt_state = optimizer.update(grads, opt_state, params)
                params = optax.apply_updates(params, updates)
                losses.append(float(loss))
        assert len(losses) >= 2
        assert all(np.isfinite(losses))
        # Packing must actually pack: average segments per bin > 1 on this corpus.
        assert int(np.max(np.asarray(segments))) > 1
