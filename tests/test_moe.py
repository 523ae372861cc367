"""MoE expert-parallel layer tests (models/moe.py).

Numerics are checked against an independent per-token loop reference (same params,
routing recomputed with plain numpy/jnp), then the sharded path runs on the virtual
8-device mesh with expert weights partitioned over an 'expert' axis.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from petastorm_tpu.models.moe import (MoEMlp, MoETransformerLM, expert_partition_specs,
                                      moe_aux_total)


def _loop_reference(params, x, num_experts, hidden_mult):
    """Per-token top-1 routing computed the slow, obvious way (no capacity drops)."""
    router = np.asarray(params['params']['router']['kernel'], dtype=np.float32)
    w1 = np.asarray(params['params']['w1'], dtype=np.float32)
    w2 = np.asarray(params['params']['w2'], dtype=np.float32)
    batch, seqlen, d = x.shape
    tokens = np.asarray(x, dtype=np.float32).reshape(-1, d)
    logits = tokens @ router
    probs = np.asarray(jax.nn.softmax(jnp.asarray(logits), axis=-1))
    out = np.zeros_like(tokens)
    for s in range(tokens.shape[0]):
        e = int(np.argmax(probs[s]))
        h = np.asarray(jax.nn.gelu(jnp.asarray(tokens[s] @ w1[e])))
        out[s] = (h @ w2[e]) * probs[s, e]
    return out.reshape(batch, seqlen, d)


class TestMoEMlpNumerics(object):
    def test_top1_matches_loop_reference(self):
        model = MoEMlp(num_experts=4, capacity_factor=8.0, num_selected=1,
                       hidden_mult=2, dtype=jnp.float32)
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(2, 8, 16), dtype=jnp.float32)
        params = model.init(jax.random.PRNGKey(0), x)
        y, _ = model.apply(params, x, mutable='losses')
        expected = _loop_reference(params, x, 4, 2)
        np.testing.assert_allclose(np.asarray(y), expected, rtol=2e-4, atol=2e-5)

    def test_top2_gates_normalized_and_finite(self):
        model = MoEMlp(num_experts=4, capacity_factor=8.0, num_selected=2,
                       hidden_mult=2, dtype=jnp.float32)
        rng = np.random.RandomState(1)
        x = jnp.asarray(rng.randn(2, 8, 16), dtype=jnp.float32)
        params = model.init(jax.random.PRNGKey(1), x)
        y, mods = model.apply(params, x, mutable='losses')
        assert np.all(np.isfinite(np.asarray(y)))
        # With generous capacity nothing is dropped even at k=2.
        drop = float(mods['losses']['moe_drop_fraction'][0])
        assert drop == 0.0

    def test_tiny_capacity_drops_but_stays_finite(self):
        model = MoEMlp(num_experts=4, capacity_factor=0.25, num_selected=1,
                       hidden_mult=2, dtype=jnp.float32)
        rng = np.random.RandomState(2)
        x = jnp.asarray(rng.randn(2, 16, 16), dtype=jnp.float32)
        params = model.init(jax.random.PRNGKey(2), x)
        y, mods = model.apply(params, x, mutable='losses')
        assert np.all(np.isfinite(np.asarray(y)))
        drop = float(mods['losses']['moe_drop_fraction'][0])
        assert drop > 0.0
        # A dropped token contributes exactly zero from the expert branch: with
        # capacity 1 per expert at most num_experts rows are non-zero per call.
        nonzero_rows = np.count_nonzero(
            np.abs(np.asarray(y).reshape(-1, 16)).sum(axis=1))
        capacity = max(1, int(0.25 * 32 / 4))
        assert nonzero_rows <= 4 * capacity

    def test_aux_loss_uniform_floor(self):
        # The Switch aux loss X * sum f_x P_x is >= 1 and == 1 only when routing is
        # uniform; assert the sown value is sane.
        model = MoEMlp(num_experts=4, capacity_factor=4.0, dtype=jnp.float32)
        rng = np.random.RandomState(3)
        x = jnp.asarray(rng.randn(2, 16, 16), dtype=jnp.float32)
        params = model.init(jax.random.PRNGKey(3), x)
        _, mods = model.apply(params, x, mutable='losses')
        aux = moe_aux_total(mods)
        assert float(aux) >= 0.99

    def test_jittable(self):
        model = MoEMlp(num_experts=2, capacity_factor=2.0, dtype=jnp.float32)
        x = jnp.zeros((1, 8, 8), dtype=jnp.float32)
        params = model.init(jax.random.PRNGKey(0), x)
        fn = jax.jit(lambda p, x: model.apply(p, x, mutable='losses')[0])
        assert fn(params, x).shape == (1, 8, 8)


class TestMoEInvariants(object):
    def _apply(self, model, x, seed=0):
        params = model.init(jax.random.PRNGKey(seed), x)
        return params, model.apply(params, x, mutable='losses')

    def test_permutation_equivariant_with_generous_capacity(self):
        # With no capacity competition the layer is a per-token function: permuting
        # tokens must permute outputs identically.
        model = MoEMlp(num_experts=4, capacity_factor=8.0, dtype=jnp.float32)
        x = jnp.asarray(np.random.RandomState(0).randn(1, 16, 8), jnp.float32)
        params, (y, _) = self._apply(model, x)
        perm = np.random.RandomState(1).permutation(16)
        y_perm, _ = model.apply(params, x[:, perm], mutable='losses')
        np.testing.assert_allclose(np.asarray(y_perm), np.asarray(y)[:, perm],
                                   rtol=1e-5, atol=1e-6)

    def test_drop_fraction_monotone_in_capacity(self):
        x = jnp.asarray(np.random.RandomState(2).randn(2, 32, 8), jnp.float32)
        drops = []
        for cf in (0.25, 0.5, 1.0, 8.0):
            model = MoEMlp(num_experts=4, capacity_factor=cf, dtype=jnp.float32)
            _, (_, mods) = self._apply(model, x, seed=3)
            drops.append(float(mods['losses']['moe_drop_fraction'][0]))
        assert drops == sorted(drops, reverse=True), drops
        assert drops[-1] == 0.0


class TestMoEExpertParallel(object):
    def _mesh(self):
        return Mesh(np.asarray(jax.devices()[:8]).reshape(2, 4), ('data', 'expert'))

    def test_sharded_matches_unsharded(self):
        mesh = self._mesh()
        model = MoEMlp(num_experts=4, capacity_factor=4.0, dtype=jnp.float32,
                       expert_axis='expert')
        rng = np.random.RandomState(4)
        x = jnp.asarray(rng.randn(4, 8, 16), dtype=jnp.float32)
        params = model.init(jax.random.PRNGKey(4), x)
        unsharded, _ = model.apply(params, x, mutable='losses')

        specs = expert_partition_specs(params)
        shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                                 is_leaf=lambda l: isinstance(l, P))
        sharded_params = jax.device_put(params, shardings)
        x_sharded = jax.device_put(x, NamedSharding(mesh, P('data', None, None)))
        with jax.set_mesh(mesh):
            fn = jax.jit(lambda p, x: model.apply(p, x, mutable='losses')[0])
            y = fn(sharded_params, x_sharded)
        np.testing.assert_allclose(np.asarray(y), np.asarray(unsharded),
                                   rtol=2e-4, atol=2e-5)

    def test_expert_weights_actually_sharded(self):
        params = MoEMlp(num_experts=4, dtype=jnp.float32).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 4, 8)))
        specs = expert_partition_specs(params)
        flat = jax.tree_util.tree_flatten_with_path(
            specs, is_leaf=lambda l: isinstance(l, P))[0]
        by_name = {getattr(path[-1], 'key', str(path[-1])): spec for path, spec in flat}
        assert by_name['w1'] == P('expert', None, None)
        assert by_name['w2'] == P('expert', None, None)
        router = [s for p, s in flat if 'router' in str(p)]
        assert all(s == P(None, None) for s in router)

    def test_moe_lm_trains_on_expert_mesh(self):
        mesh = self._mesh()
        model = MoETransformerLM(vocab=32, embed=16, heads=2, layers=2,
                                 num_experts=4, moe_every=2, max_len=32,
                                 dtype=jnp.float32, expert_axis='expert')
        rng = np.random.RandomState(5)
        tokens = jnp.asarray(rng.randint(0, 32, (4, 16)), dtype=jnp.int32)
        # Train on the 'params' collection ONLY: init also returns the sown 'losses'
        # collection, which must never reach the optimizer.
        params = {'params': model.init(jax.random.PRNGKey(5), tokens)['params']}
        specs = expert_partition_specs(params)
        shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                                 is_leaf=lambda l: isinstance(l, P))
        params = jax.device_put(params, shardings)
        optimizer = optax.adam(1e-2)
        opt_state = optimizer.init(params)

        def loss_fn(params, tokens):
            from petastorm_tpu.models import next_token_loss
            logits, mods = model.apply(params, tokens, mutable='losses')
            return next_token_loss(logits, tokens) + moe_aux_total(mods, weight=0.01)

        @jax.jit
        def step(params, opt_state, tokens):
            loss, grads = jax.value_and_grad(loss_fn)(params, tokens)
            updates, opt_state = optimizer.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, loss

        with jax.set_mesh(mesh):
            losses = []
            for _ in range(8):
                params, opt_state, loss = step(params, opt_state, tokens)
                losses.append(float(loss))
        assert all(np.isfinite(losses))
        assert losses[-1] < losses[0]

    def test_specs_ignore_non_moe_shallow_3d_leaves(self):
        # stack_stage_params output (top-level 3-D w1/w2, no MoE scope, no 'params'
        # root) must NOT be captured as expert weights.
        stacked = {'w1': jnp.zeros((4, 8, 16)), 'w2': jnp.zeros((4, 16, 8))}
        specs = expert_partition_specs(stacked)
        assert specs['w1'] == P(None, None, None)
        assert specs['w2'] == P(None, None, None)

    def test_remat_preserves_outputs_and_sown_losses(self):
        # remat must change memory behavior only: identical logits, grads, and sown
        # aux values from the same params.
        dense = MoETransformerLM(vocab=32, embed=16, heads=2, layers=2,
                                 num_experts=2, moe_every=2, max_len=32,
                                 dtype=jnp.float32)
        remat = MoETransformerLM(vocab=32, embed=16, heads=2, layers=2,
                                 num_experts=2, moe_every=2, max_len=32,
                                 dtype=jnp.float32, remat=True)
        tokens = jnp.asarray(np.random.RandomState(7).randint(0, 32, (2, 12)),
                             jnp.int32)
        params = {'params': dense.init(jax.random.PRNGKey(7), tokens)['params']}
        out_d, mods_d = dense.apply(params, tokens, mutable='losses')
        out_r, mods_r = remat.apply(params, tokens, mutable='losses')
        np.testing.assert_allclose(np.asarray(out_d), np.asarray(out_r),
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(float(moe_aux_total(mods_d)),
                                   float(moe_aux_total(mods_r)), rtol=1e-6)

        def loss(model):
            def fn(p):
                logits, mods = model.apply(p, tokens, mutable='losses')
                from petastorm_tpu.models import next_token_loss
                return next_token_loss(logits, tokens) + moe_aux_total(mods, 0.01)
            return fn

        g_d = jax.grad(loss(dense))(params)
        g_r = jax.grad(loss(remat))(params)
        for a, b in zip(jax.tree.leaves(g_d), jax.tree.leaves(g_r)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)

    def test_aux_total_counts_only_latest_sow(self):
        # sow appends per apply; a threaded-through collection must not double-count.
        mods = {'losses': {'MoEMlp_0': {'moe_aux': (jnp.float32(2), jnp.float32(3))}}}
        assert float(moe_aux_total(mods)) == 3.0

    def test_packed_batches_through_moe_model(self):
        # Packing composes with MoE: segment-masked attention injected into
        # MoETransformerLM, boundary-masked loss, finite grads.
        from petastorm_tpu.ops.packing import (pack_sequences,
                                               packed_next_token_loss,
                                               segment_causal_attention)
        rng = np.random.RandomState(8)
        packed = pack_sequences(
            [rng.randint(1, 32, size=n).astype(np.int32)
             for n in (10, 7, 12, 5, 9, 6)], 16)
        tokens = jnp.asarray(packed['tokens'])
        segments = jnp.asarray(packed['segments'])
        model = MoETransformerLM(vocab=32, embed=16, heads=2, layers=2,
                                 num_experts=2, moe_every=2, max_len=16,
                                 dtype=jnp.float32,
                                 attention_fn=segment_causal_attention(segments))
        params = {'params': model.init(jax.random.PRNGKey(8), tokens)['params']}

        def loss_fn(p):
            logits, mods = model.apply(p, tokens, mutable='losses')
            return (packed_next_token_loss(logits, tokens, segments)
                    + moe_aux_total(mods, 0.01))

        loss, grads = jax.jit(jax.value_and_grad(loss_fn))(params)
        assert np.isfinite(float(loss))
        for leaf in jax.tree.leaves(grads):
            assert np.all(np.isfinite(np.asarray(leaf)))

    def test_capacity_guard(self):
        with pytest.raises(ValueError):
            MoEMlp(num_experts=2, num_selected=3, dtype=jnp.float32).init(
                jax.random.PRNGKey(0), jnp.zeros((1, 4, 8)))

    def test_expert_sharded_checkpoint_round_trip(self, tmp_path):
        # Expert-parallel params must survive a TrainingCheckpointer save/restore
        # with values AND shardings intact (orbax restores onto the template's
        # shardings).
        from petastorm_tpu.parallel import TrainingCheckpointer
        mesh = self._mesh()
        model = MoEMlp(num_experts=4, dtype=jnp.float32)
        x = jnp.asarray(np.random.RandomState(6).randn(2, 8, 16), jnp.float32)
        params = model.init(jax.random.PRNGKey(6), x)
        shardings = jax.tree.map(lambda s: NamedSharding(mesh, s),
                                 expert_partition_specs(params),
                                 is_leaf=lambda l: isinstance(l, P))
        params = jax.device_put(params, shardings)
        template = jax.tree.map(lambda leaf, sh: jax.device_put(
            jnp.zeros(leaf.shape, leaf.dtype), sh), params, shardings)
        with TrainingCheckpointer(str(tmp_path)) as ckpt:
            assert ckpt.save(0, params, force=True)
            ckpt.wait_until_finished()
            restored, loader_state = ckpt.restore(template)
        assert loader_state is None
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        w1 = restored['params']['w1']
        assert w1.sharding.spec == P('expert', None, None), w1.sharding
