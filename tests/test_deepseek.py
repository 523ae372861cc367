"""DeepSeek-V3-style decoder (models/deepseek.py) and its routed experts
(models/moe.py: sigmoid routing with a selection bias, dropless dispatch over the
experts a chip holds), against hand-computed values, plain per-expert loops and
the benchmark's plain reference of Moonlight-16B-A3B."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import harness
from petastorm_tpu.models import (DeepSeekConfig, DeepSeekV3LM, DroplessMoE, expert_counters,
                                  moe_aux_total, next_token_loss)
from petastorm_tpu.models.moe import (SwiGLU, held_assignments, sequence_balance_loss,
                                      sigmoid_topk_routing)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(ROOT, 'benchmarks', 'configs', 'moonlight_16b_a3b.json')
TINY = os.path.join(ROOT, 'benchmarks', 'tests', 'tiny', 'configs', 'moonlight_16b_a3b.json')


def _sigmoid(x):
    return 1.0 / (1.0 + np.exp(-np.asarray(x, np.float64)))


def _swiglu(x, w_in, w_out):
    f = w_out.shape[0]
    h = x @ w_in
    gate, up = h[..., :f], h[..., f:]
    return (gate * _sigmoid(gate) * up) @ w_out


class TestSigmoidRouting(object):
    # two tokens over four experts; the router is the identity, so x is the logits
    LOGITS = np.array([[0.0, 1.0, -1.0, 2.0], [0.5, 0.4, -2.0, 0.0]], np.float32)
    BIAS = np.array([0.0, 0.0, 0.9, -0.5], np.float32)

    def test_selection_on_biased_scores_weights_without_bias(self):
        scores, experts, weights = sigmoid_topk_routing(
            jnp.asarray(self.LOGITS), jnp.asarray(self.BIAS), 2, 2.0)
        # token 0: s + b = (0.5, 0.731, 1.169, 0.381) -> experts 2, 1;
        # token 1: s + b = (0.622, 0.599, 1.019, 0.0) -> experts 2, 0
        np.testing.assert_array_equal(np.asarray(experts), [[2, 1], [2, 0]])
        s0, s1 = _sigmoid(self.LOGITS[0]), _sigmoid(self.LOGITS[1])
        want = np.array([[s0[2], s0[1]] / (s0[2] + s0[1]),
                         [s1[2], s1[0]] / (s1[2] + s1[0])]) * 2.0
        np.testing.assert_allclose(np.asarray(weights), want, rtol=1e-6)
        # sigmoid(-1) + sigmoid(1) = 1: token 0's weights are the scores times 2
        np.testing.assert_allclose(np.asarray(weights[0]), [2 * s0[2], 2 * s0[1]], rtol=1e-6)
        np.testing.assert_allclose(np.asarray(scores), _sigmoid(self.LOGITS), rtol=1e-6)

    def test_sequence_balance_loss(self):
        scores, experts, _ = sigmoid_topk_routing(
            jnp.asarray(self.LOGITS), jnp.asarray(self.BIAS), 2, 2.0)
        # one sequence of T = 2, k = 2, X = 4: f = counts * 4 / 4 = (1, 1, 2, 0)
        share = _sigmoid(self.LOGITS) / _sigmoid(self.LOGITS).sum(-1, keepdims=True)
        want = np.sum(np.array([1.0, 1.0, 2.0, 0.0]) * share.mean(0))
        got = sequence_balance_loss(scores, experts, 1)
        np.testing.assert_allclose(float(got), want, rtol=1e-6)
        # two sequences of one token: the mean of each sequence's own loss
        per_token = [np.sum(np.bincount(np.asarray(experts[i]), minlength=4) * 4 / 2 * share[i])
                     for i in range(2)]
        np.testing.assert_allclose(float(sequence_balance_loss(scores, experts, 2)),
                                   np.mean(per_token), rtol=1e-6)

    def test_bias_update_and_counters(self):
        layer = DroplessMoE(num_experts=4, held_experts=2, num_selected=2, width=8,
                            first_expert=2, scaling=2.0, bias_step=0.001, dtype=jnp.float32)
        x = jnp.asarray(self.LOGITS)[None]                            # [1, 2, 4]
        variables = layer.init(jax.random.PRNGKey(0), x)
        params = dict(variables['params'], router={'kernel': jnp.eye(4)})
        _, mutated = layer.apply({'params': params,
                                  'router_state': {'bias': jnp.asarray(self.BIAS)}},
                                 x, mutable=['losses', 'router_state'])
        counters = expert_counters(mutated)
        # loads (1, 1, 2, 0), mean 1: the overloaded expert 2 goes down, expert 3 up
        np.testing.assert_array_equal(np.asarray(counters['expert_load'][0]), [1, 1, 2, 0])
        np.testing.assert_allclose(np.asarray(mutated['router_state']['bias']),
                                   self.BIAS + 0.001 * np.array([0, 0, -1, 1]), rtol=1e-6)
        np.testing.assert_array_equal(np.asarray(counters['router_bias'][0]), self.BIAS)
        assert int(counters['expert_rows'][0]) == 2         # experts 2 and 3 held: 2 rows
        assert moe_aux_total(mutated) == pytest.approx(
            float(sequence_balance_loss(*sigmoid_topk_routing(
                jnp.asarray(self.LOGITS), jnp.asarray(self.BIAS), 2, 2.0)[:2], 1)))
        # applied without the collection mutable, the bias stays as it was
        _, mutated = layer.apply({'params': params,
                                  'router_state': {'bias': jnp.asarray(self.BIAS)}},
                                 x, mutable=['losses'])
        assert 'router_state' not in mutated


def _moe(held, first=0, shared=0, experts=8, k=2):
    return DroplessMoE(num_experts=experts, held_experts=held, num_selected=k, width=16,
                       first_expert=first, shared_width=shared, scaling=2.446,
                       dtype=jnp.float32)


def _moe_inputs(seed=0):
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.randn(2, 16, 32), jnp.float32)


class TestDroplessDispatch(object):
    def test_held_assignments_sort_by_expert(self):
        experts = jnp.asarray([[3, 1], [2, 3], [0, 1]])
        rows, sizes, total, position = held_assignments(experts, 1, 2)
        np.testing.assert_array_equal(np.asarray(sizes), [2, 1])     # expert 1 twice, 2 once
        assert int(total) == 3 and rows.shape == (6,)
        np.testing.assert_array_equal(np.asarray(rows[:3]), [1, 5, 2])  # token * k + slot
        np.testing.assert_array_equal(np.asarray(position)[[1, 5, 2]], [0, 1, 2])
        assert np.all(np.asarray(position)[[0, 3, 4]] >= 3)

    @pytest.mark.parametrize('held,first', [(8, 0), (3, 2)])
    def test_matches_plain_per_expert_loop(self, held, first):
        """Each token's routed output is the sum, over its chosen experts among those
        held, of the gate times that expert's SwiGLU, as a plain loop computes it."""
        layer = _moe(held, first)
        x = _moe_inputs()
        variables = layer.init(jax.random.PRNGKey(1), x)
        y, mutated = layer.apply(variables, x, mutable=['losses'])
        p = jax.tree.map(lambda a: np.asarray(a, np.float64), variables['params'])
        tokens = np.asarray(x, np.float64).reshape(-1, 32)
        _, experts, weights = sigmoid_topk_routing(
            jnp.asarray(tokens @ p['router']['kernel'], jnp.float32), jnp.zeros(8), 2, 2.446)
        want = np.zeros_like(tokens)
        rows = 0
        for s in range(tokens.shape[0]):
            for j in range(2):
                e = int(experts[s, j]) - first
                if 0 <= e < held:
                    rows += 1
                    want[s] += float(weights[s, j]) * _swiglu(tokens[s], p['w_in'][e],
                                                              p['w_out'][e])
        np.testing.assert_allclose(np.asarray(y).reshape(-1, 32), want, rtol=2e-4, atol=2e-6)
        assert int(expert_counters(mutated)['expert_rows'][0]) == rows
        assert int(np.sum(expert_counters(mutated)['expert_load'][0])) == 2 * tokens.shape[0]

    def test_shares_add_up_to_the_uncut_layer(self):
        """Four chips holding two experts each: their results, with the shared
        experts (which every chip computes alike) counted once, add up to the
        layer that holds all eight."""
        uncut = _moe(8, shared=24)
        x = _moe_inputs(2)
        variables = uncut.init(jax.random.PRNGKey(3), x)
        want, _ = uncut.apply(variables, x, mutable=['losses'])
        p = variables['params']
        shared = SwiGLU(24, jnp.float32).apply({'params': p['shared']}, x)
        total = shared
        for first in range(0, 8, 2):
            share = dict(p, w_in=p['w_in'][first:first + 2], w_out=p['w_out'][first:first + 2])
            got, _ = _moe(2, first, shared=24).apply(
                {'params': share, 'router_state': variables['router_state']}, x,
                mutable=['losses'])
            total = total + (got - shared)
        np.testing.assert_allclose(np.asarray(total), np.asarray(want), rtol=1e-5, atol=1e-6)

    def test_rows_past_the_held_assignments_reach_no_token(self):
        """Whatever the grouped matmuls leave in the static buffer's rows past the
        held assignments reaches no token: a token routed to no held expert reads
        zero, and the input gradient is finite."""
        layer = _moe(1, first=5)
        x = _moe_inputs(4)
        variables = layer.init(jax.random.PRNGKey(5), x)
        grad = jax.grad(lambda a: jnp.sum(layer.apply(variables, a, mutable=['losses'])[0]
                                          ** 2))(x)
        _, experts, _ = sigmoid_topk_routing(
            x.reshape(-1, 32) @ variables['params']['router']['kernel'], jnp.zeros(8), 2, 1.0)
        elsewhere = ~np.any(np.asarray(experts) == 5, axis=-1)
        g = np.asarray(grad).reshape(-1, 32)
        assert np.all(np.isfinite(g)) and elsewhere.any() and (~elsewhere).any()
        y = np.asarray(layer.apply(variables, x, mutable=['losses'])[0]).reshape(-1, 32)
        assert np.all(y[elsewhere] == 0.0) and np.all(np.any(y[~elsewhere] != 0, axis=-1))


def _tiny_cfg():
    with open(CONFIG) as f:
        cfg = json.load(f)
    with open(TINY) as f:
        cfg.update(json.load(f))
    return cfg


@pytest.fixture(scope='module')
def moonlight():
    return harness.load_module(os.path.join(ROOT, 'benchmarks', 'configs',
                                            'moonlight_16b_a3b.py'), 'moonlight_config')


class TestAgainstTheReference(object):
    def test_model_matches_reference_in_float32(self, moonlight):
        """The program's model (flash kernels at q/k 192 and v 128, dropless
        experts, float32 compute) against the plain reference on the same seeded
        weights: loss, every gradient leaf and the updated selection biases."""
        from benchmarks import common
        cfg = dict(_tiny_cfg(), compute_dtype='float32')
        program = moonlight.Program(cfg)
        (params, router), _ = jax.jit(program.init)(common.weight_key(7))
        rng = np.random.RandomState(7)
        tokens = jnp.asarray(rng.randint(0, cfg['vocab_size'], (2, cfg['seq_len'])),
                             jnp.int32)
        alpha = cfg['seq_aux_alpha']

        def program_loss(p):
            logits, mutated = program.model.apply({'params': p, 'router_state': router},
                                                  tokens, mutable=['losses', 'router_state'])
            return (next_token_loss(logits, tokens) + moe_aux_total(mutated, alpha),
                    mutated['router_state'])

        (loss, biases), grads = jax.jit(jax.value_and_grad(program_loss, has_aux=True))(params)
        (ref_loss, loads), ref_grads = jax.jit(jax.value_and_grad(
            lambda p: moonlight._forward(cfg, p, {'tokens': tokens}, False, None, {}),
            has_aux=True))(params)
        assert float(loss) == pytest.approx(float(ref_loss), rel=1e-5)
        flat, ref_flat = common.named_leaves(grads), common.named_leaves(ref_grads)
        assert set(flat) == set(ref_flat)
        for name in flat:
            np.testing.assert_allclose(np.asarray(flat[name]), np.asarray(ref_flat[name]),
                                       rtol=2e-3, atol=2e-7, err_msg=name)
        gamma = cfg['bias_update_speed']
        for name, load in loads.items():
            want = gamma * np.sign(np.mean(np.asarray(load)) - np.asarray(load))
            np.testing.assert_allclose(np.asarray(biases[name]['moe']['bias']), want)
        assert moonlight.reference_loss(cfg, params, {'tokens': tokens}) == pytest.approx(
            float(ref_loss), rel=1e-6)

    def test_bf16_steps_stay_finite_with_kept_expert_results(self, moonlight):
        """The program's bf16 step, remat keeping the grouped matmuls' results:
        eight Adam steps keep the loss and every parameter finite. A recompute
        that chose experts anew (its scores a rounding apart) would read kept
        rows of another routing, rows the kernel never wrote."""
        from benchmarks import common
        cfg = _tiny_cfg()
        program = moonlight.Program(cfg)
        state = jax.jit(program.init)(common.weight_key(5))
        step = jax.jit(program.step)
        tokens = jnp.asarray(np.random.RandomState(0).zipf(1.1, (2, cfg['seq_len']))
                             % cfg['vocab_size'], jnp.int32)
        losses = []
        for _ in range(8):
            state, loss = step(state, {'tokens': tokens})
            losses.append(float(loss))
        assert np.all(np.isfinite(losses)), losses
        assert losses[-1] < losses[0]
        assert all(bool(jnp.all(jnp.isfinite(leaf)))
                   for leaf in jax.tree.leaves(program.params(state)))

    @pytest.mark.parametrize('low', [False, True], ids=['float32', 'fp8_control'])
    def test_reference_layer_by_layer_matches_one_graph(self, moonlight, low):
        """The reference's training steps take their gradients a layer at a time;
        they are the whole graph's, for the float32 reference and its fp8 control."""
        from benchmarks import common
        cfg = _tiny_cfg()
        params = jax.jit(lambda k: common.draw_weights(k, moonlight._param_shapes(cfg),
                                                        moonlight.weight_rule))(
            common.weight_key(3))
        tokens = jnp.asarray(np.random.RandomState(3).randint(0, cfg['vocab_size'],
                                                              (2, cfg['seq_len'])), jnp.int32)
        biases = {'layer_1': jnp.asarray(np.random.RandomState(4).randn(16) * 0.01,
                                         jnp.float32)}
        batch = {'tokens': tokens}
        (want, want_loads), want_grads = jax.jit(jax.value_and_grad(
            lambda p: moonlight._forward(cfg, p, batch, low, None, biases), has_aux=True))(
                params)
        loss, grads, loads = moonlight._layerwise(cfg, low, None)(params, biases, batch)
        assert float(loss) == pytest.approx(float(want), rel=1e-6)
        np.testing.assert_array_equal(np.asarray(loads['layer_1']),
                                      np.asarray(want_loads['layer_1']))
        flat, want_flat = common.named_leaves(grads), common.named_leaves(want_grads)
        assert set(flat) == set(want_flat)
        for name in flat:
            np.testing.assert_allclose(np.asarray(flat[name]), np.asarray(want_flat[name]),
                                       rtol=1e-4, atol=1e-7, err_msg=name)

    def test_model_runs_through_the_reader_and_loader(self, tmp_path):
        """make_reader -> JaxDataLoader -> the compiled train step, as the benchmark
        drives it: two steps, finite losses, the biases updated in the step."""
        from petastorm_tpu import make_reader
        from petastorm_tpu.codecs import NdarrayCodec, ScalarCodec
        from petastorm_tpu.etl.dataset_metadata import write_rows
        from petastorm_tpu.parallel import JaxDataLoader
        from petastorm_tpu.unischema import Unischema, UnischemaField
        cfg = DeepSeekConfig(vocab=64, hidden=32, layers=2, first_dense=1, heads=2,
                             qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16, kv_lora_rank=16,
                             dense_width=64, expert_width=16, router_experts=8,
                             held_experts=4, num_selected=2, shared_experts=1,
                             routed_scaling=2.446, rope_theta=50000.0, rms_eps=1e-5,
                             bias_step=0.001)
        model = DeepSeekV3LM(cfg, dtype=jnp.float32, remat=True)
        schema = Unischema('Tokens', [UnischemaField('id', np.int32, (), ScalarCodec(), False),
                                      UnischemaField('tokens', np.int32, (32,), NdarrayCodec(),
                                                     False)])
        rng = np.random.RandomState(0)
        rows = ({'id': np.int32(i), 'tokens': rng.randint(0, 64, 32).astype(np.int32)}
                for i in range(8))
        url = 'file://' + str(tmp_path / 'tokens')
        write_rows(url, schema, rows, rowgroup_size_mb=1, n_files=1)
        variables = model.init(jax.random.PRNGKey(0), jnp.zeros((2, 32), jnp.int32))
        state = (variables['params'], variables['router_state'])

        @jax.jit
        def step(state, tokens):
            params, router = state

            def loss_fn(p):
                logits, mutated = model.apply({'params': p, 'router_state': router}, tokens,
                                              mutable=['losses', 'router_state'])
                return next_token_loss(logits, tokens), mutated['router_state']

            (loss, router), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
            return (jax.tree.map(lambda p, g: p - 0.01 * g, params, grads), router), loss

        with make_reader(url, reader_pool_type='dummy', num_epochs=1) as reader:
            losses = []
            for batch in JaxDataLoader(reader, batch_size=2):
                state, loss = step(state, batch['tokens'])
                losses.append(float(loss))
                if len(losses) == 2:
                    break
        assert len(losses) == 2 and np.all(np.isfinite(losses))
        bias = np.asarray(state[1]['layer_1']['moe']['bias'])
        assert np.all(np.isin(np.round(bias / 0.001), [-2, -1, 0, 1, 2]))
        assert np.any(bias != 0)
