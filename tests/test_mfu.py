"""MFU accounting (benchmark/mfu.py): analytic FLOP formulas, XLA cost analysis,
peak-FLOPs detection honesty on CPU (VERDICT r3 item 2)."""
import jax
import jax.numpy as jnp
import pytest

from petastorm_tpu.benchmark.mfu import (PEAK_BF16_FLOPS, chip_generation,
                                         mfu_fields,
                                         moe_transformer_train_flops_per_step,
                                         peak_flops,
                                         transformer_train_flops_per_step,
                                         xla_cost_flops)


class TestAnalyticFormulas:
    def test_transformer_hand_computed_tiny_config(self):
        # B=1, T=2, V=4, E=2, L=1, mlp_mult=4, causal:
        # dense = (8 + 16) * 4 * 1 = 96 per token
        # attn  = 2 * 2 * 2 * 1 = 8 per token
        # unembed = 2 * 2 * 4 = 16 per token
        # fwd = 1 * 2 * (96 + 8 + 16) = 240 ; train = 3x = 720
        assert transformer_train_flops_per_step(
            1, 2, vocab=4, embed=2, layers=1) == 720

    def test_transformer_scales_linearly_in_batch_and_layers_quadratic_in_t(self):
        base = transformer_train_flops_per_step(2, 128, 256, 64, 2)
        assert transformer_train_flops_per_step(4, 128, 256, 64, 2) == 2 * base
        # attention term is quadratic in T, dense terms linear: doubling T more
        # than doubles the total
        assert transformer_train_flops_per_step(2, 256, 256, 64, 2) > 2 * base

    def test_non_causal_attention_doubles_the_attn_term(self):
        causal = transformer_train_flops_per_step(1, 64, 16, 8, 1, causal=True)
        full = transformer_train_flops_per_step(1, 64, 16, 8, 1, causal=False)
        # delta is exactly the attention term: 3 * B*T * 2*T*E
        assert full - causal == 3 * 64 * 2 * 64 * 8

    def test_moe_every_layer_selected_one_matches_dense_plus_router(self):
        # num_selected=1, hidden_mult=4: expert MLP FLOPs == dense MLP FLOPs, so
        # the only delta vs TransformerLM is the router projection.
        dense = transformer_train_flops_per_step(2, 32, 64, 16, 2)
        moe = moe_transformer_train_flops_per_step(
            2, 32, 64, 16, 2, num_experts=8, num_selected=1, moe_every=1)
        router = 3 * 2 * 32 * 2 * (2 * 16 * 8)  # 3x fwd * B*T * L_moe * 2*E*n_exp
        assert moe - dense == router

    def test_moe_every_2_mixes_dense_and_moe_layers(self):
        all_moe = moe_transformer_train_flops_per_step(
            1, 16, 32, 8, 4, num_experts=4, moe_every=1)
        half_moe = moe_transformer_train_flops_per_step(
            1, 16, 32, 8, 4, num_experts=4, moe_every=2)
        dense = transformer_train_flops_per_step(1, 16, 32, 8, 4)
        assert dense < half_moe < all_moe

    def test_moe_num_selected_scales_expert_compute(self):
        one = moe_transformer_train_flops_per_step(
            1, 16, 32, 8, 1, num_experts=4, num_selected=1)
        two = moe_transformer_train_flops_per_step(
            1, 16, 32, 8, 1, num_experts=4, num_selected=2)
        assert two > one


class TestPeakDetection:
    def test_cpu_backend_reports_no_generation(self):
        # The suite runs with JAX_PLATFORMS=cpu: a CPU run has no peak, so no
        # MFU is ever reported for it.
        assert jax.devices()[0].platform == 'cpu'
        assert chip_generation() is None
        assert peak_flops() is None

    def test_explicit_generation_lookup(self):
        assert peak_flops('v5e') == 197e12
        assert peak_flops('V5E') == 197e12
        assert peak_flops('v5p') == 459e12
        assert peak_flops('made-up-chip') is None

    def test_peak_table_is_plausible(self):
        assert PEAK_BF16_FLOPS['v4'] < PEAK_BF16_FLOPS['v5p']
        assert PEAK_BF16_FLOPS['v5e'] < PEAK_BF16_FLOPS['v6e']


class TestMfuFields:
    def test_no_flops_yields_empty(self):
        assert mfu_fields('x', None, 10, 1.0) == {}
        assert mfu_fields('x', 0, 10, 1.0) == {}
        assert mfu_fields('x', 1e9, 10, 0.0) == {}

    def test_tflops_reported_without_mfu_on_cpu(self):
        fields = mfu_fields('flash_train', 1e12, steps=10, elapsed_s=2.0)
        assert fields['flash_train_model_tflops_per_sec'] == 5.0
        assert 'flash_train_mfu' not in fields  # no fabricated MFU on CPU

    def test_mfu_with_explicit_generation(self):
        fields = mfu_fields('moe_train', 197e12, steps=1, elapsed_s=2.0,
                            generation='v5e')
        assert fields['moe_train_mfu'] == pytest.approx(0.5)
        assert fields['mfu_peak_bf16_tflops'] == 197.0


class TestXlaCostFlops:
    def test_matmul_flops_counted(self):
        f = jax.jit(lambda a, b: a @ b)
        a = jnp.zeros((64, 64), jnp.float32)
        flops = xla_cost_flops(f, a, a)
        if flops is None:
            pytest.skip('cost analysis not exposed on this backend')
        # 64^3 MACs = 2*64^3 = 524288 FLOPs; allow backend fusion slack
        assert flops >= 2 * 64 ** 3 * 0.5

    def test_bad_program_returns_none(self):
        f = jax.jit(lambda a: a)

        class NotAnArray:
            pass

        assert xla_cost_flops(f, NotAnArray()) is None
