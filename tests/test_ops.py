"""On-device op tests: ring attention exactness vs dense, image ops (CPU 8-dev mesh)."""

import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from petastorm_tpu.ops.image import normalize_image, random_crop_flip
from petastorm_tpu.ops.ring_attention import dense_attention, ring_attention_sharded
from petastorm_tpu.parallel import make_mesh


class TestRingAttention:
    @pytest.mark.parametrize('causal', [False, True])
    def test_matches_dense(self, causal):
        mesh = make_mesh(('seq',))  # 8-way sequence parallelism
        rng = np.random.RandomState(0)
        b, t, h, d = 2, 32, 4, 16  # t divisible by 8 shards
        q = jnp.asarray(rng.randn(b, t, h, d), dtype=jnp.float32)
        k = jnp.asarray(rng.randn(b, t, h, d), dtype=jnp.float32)
        v = jnp.asarray(rng.randn(b, t, h, d), dtype=jnp.float32)
        ring_fn = ring_attention_sharded(mesh, 'seq', causal=causal)
        out_ring = ring_fn(q, k, v)
        out_dense = dense_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out_ring), np.asarray(out_dense),
                                   atol=1e-4, rtol=1e-4)

    def test_output_sharded_over_seq(self):
        mesh = make_mesh(('seq',))
        q = jnp.zeros((1, 16, 2, 8))
        ring_fn = ring_attention_sharded(mesh, 'seq')
        out = ring_fn(q, q, q)
        assert out.shape == (1, 16, 2, 8)
        assert out.sharding.spec[1] == 'seq'  # sequence dim stays sharded


class TestFlashAttention:
    """The Pallas kernel runs in interpret mode on the CPU test platform — same kernel
    body as on hardware (tiling shapes only: T % block == 0, each head a multiple of
    128 lanes or wider than 128)."""

    @pytest.mark.parametrize('causal', [False, True])
    def test_matches_dense(self, causal):
        from petastorm_tpu.ops.flash_attention import flash_attention
        rng = np.random.RandomState(0)
        b, t, h, d = 1, 256, 2, 128
        q = jnp.asarray(rng.randn(b, t, h, d), dtype=jnp.float32)
        k = jnp.asarray(rng.randn(b, t, h, d), dtype=jnp.float32)
        v = jnp.asarray(rng.randn(b, t, h, d), dtype=jnp.float32)
        out = flash_attention(q, k, v, causal, 128, 128)
        expected = dense_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                                   atol=2e-4, rtol=2e-4)

    @pytest.mark.parametrize('causal', [False, True])
    def test_gradients_match_dense(self, causal):
        """Blockwise Pallas backward (multi-block: 4 q-blocks x 4 k-blocks, 2 heads)
        must reproduce dense gradients for all of dq/dk/dv."""
        from petastorm_tpu.ops.flash_attention import flash_attention
        rng = np.random.RandomState(1)
        q, k, v = (jnp.asarray(rng.randn(1, 512, 2, 128) * 0.5, dtype=jnp.float32)
                   for _ in range(3))

        def loss(fn):
            # non-uniform cotangent so dq/dk/dv all get exercised beyond ones
            return lambda a, b_, c: (fn(a, b_, c) * jnp.cos(
                jnp.arange(c.size, dtype=jnp.float32).reshape(c.shape))).sum()

        g_flash = jax.grad(loss(lambda a, b_, c: flash_attention(a, b_, c, causal,
                                                                 128, 128)),
                           argnums=(0, 1, 2))(q, k, v)
        g_dense = jax.grad(loss(lambda a, b_, c: dense_attention(a, b_, c,
                                                                 causal=causal)),
                           argnums=(0, 1, 2))(q, k, v)
        for gf, gd, name in zip(g_flash, g_dense, 'qkv'):
            np.testing.assert_allclose(np.asarray(gf), np.asarray(gd),
                                       atol=5e-4, rtol=5e-4, err_msg='d' + name)

    @pytest.mark.parametrize('causal', [False, True])
    def test_latent_heads_match_dense(self, causal):
        """Multi-head latent attention's heads: q and k 192 wide (not lane-aligned,
        taken whole by each block), v 128. The kernels run (2 x 2 blocks of 128)
        and match dense attention in value and in dq, dk, dv."""
        from petastorm_tpu.ops.flash_attention import _use_pallas, flash_attention
        rng = np.random.RandomState(5)
        q, k = (jnp.asarray(rng.randn(1, 256, 2, 192) * 0.5, dtype=jnp.float32)
                for _ in range(2))
        v = jnp.asarray(rng.randn(1, 256, 2, 128) * 0.5, dtype=jnp.float32)
        assert _use_pallas(q, k, 128, 128)
        out = flash_attention(q, k, v, causal, 128, 128)
        assert out.shape == (1, 256, 2, 128)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(dense_attention(q, k, v, causal=causal)),
                                   atol=2e-4, rtol=2e-4)

        def loss(fn):
            return lambda a, b_, c: (fn(a, b_, c) * jnp.cos(
                jnp.arange(out.size, dtype=jnp.float32).reshape(out.shape))).sum()

        g_flash = jax.grad(loss(lambda a, b_, c: flash_attention(a, b_, c, causal,
                                                                 128, 128)),
                           argnums=(0, 1, 2))(q, k, v)
        g_dense = jax.grad(loss(lambda a, b_, c: dense_attention(a, b_, c,
                                                                 causal=causal)),
                           argnums=(0, 1, 2))(q, k, v)
        for gf, gd, name in zip(g_flash, g_dense, 'qkv'):
            assert gf.shape == gd.shape, name
            np.testing.assert_allclose(np.asarray(gf), np.asarray(gd),
                                       atol=5e-4, rtol=5e-4, err_msg='d' + name)

    @pytest.mark.parametrize('d,dv', [(128, 128), (192, 128)], ids=['equal_heads', 'mla'])
    def test_pallas_call_shapes(self, d, dv):
        """The forward, dq and dk/dv kernels' operand and result shapes, as the
        trace readers match them: with equal heads exactly the three-tensor
        signatures they always had; with a value head of its own, v, o and do
        (and dv) at that head, q, k, dq and dk at theirs."""
        from petastorm_tpu.ops.flash_attention import flash_attention
        q = jax.ShapeDtypeStruct((2, 256, 2, d), jnp.bfloat16)
        v = jax.ShapeDtypeStruct((2, 256, 2, dv), jnp.bfloat16)

        def loss(a, b_, c):
            return flash_attention(a, b_, c, True, 128, 128).astype(jnp.float32).sum()

        jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, v)
        calls = [([tuple(x.aval.shape) for x in e.invars],
                  [tuple(x.aval.shape) for x in e.outvars])
                 for e in jaxpr.eqns if e.primitive.name == 'pallas_call']
        qk, vv, col = (4, 256, d), (4, 256, dv), (4, 256, 1)
        assert calls == [([qk, qk, vv], [vv, col]),
                         ([qk, qk, vv, vv, col, col], [qk]),
                         ([qk, qk, vv, vv, col, col], [qk, vv])]

    def test_head_widths_that_take_the_kernels(self):
        """Lane-aligned heads and heads wider than a lane tile take the kernels, for
        q/k and for v alike; a narrower unaligned head keeps the dense path."""
        from petastorm_tpu.ops.flash_attention import _dispatch
        mk = lambda d: jnp.zeros((1, 256, 2, d), jnp.float32)  # noqa: E731
        assert _dispatch(mk(192), mk(192), 'auto', 'auto', mk(128))[0]
        assert _dispatch(mk(128), mk(128), 'auto', 'auto', mk(192))[0]
        assert _dispatch(mk(256), mk(256), 'auto', 'auto', mk(256))[0]
        assert not _dispatch(mk(64), mk(64), 'auto', 'auto', mk(64))[0]
        assert not _dispatch(mk(192), mk(192), 'auto', 'auto', mk(96))[0]

    @pytest.mark.parametrize('causal', [False, True])
    def test_segmented_matches_masked_dense(self, causal):
        """flash_attention_segmented (segment mask fused into every Pallas block,
        incl. fully-masked blocks and padding rows) must match the dense
        segment-masked reference, forward and backward."""
        from petastorm_tpu.ops.flash_attention import flash_attention_segmented
        from petastorm_tpu.ops.packing import masked_dense_attention, segment_mask
        rng = np.random.RandomState(3)
        b, t, h, d = 1, 512, 2, 128
        q, k, v = (jnp.asarray(rng.randn(b, t, h, d) * 0.5, dtype=jnp.float32)
                   for _ in range(3))
        # Segments spanning block boundaries (blocks of 128), plus trailing padding.
        seg = np.zeros((b, t), np.int32)
        seg[0, :200] = 1
        seg[0, 200:430] = 2
        seg[0, 430:480] = 3                      # rest stays 0 = padding
        segments = jnp.asarray(seg)

        out = flash_attention_segmented(q, k, v, segments, causal, 128, 128)
        expected = masked_dense_attention(
            q, k, v, segment_mask(segments, segments, causal=causal))
        np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                                   atol=2e-4, rtol=2e-4)
        np.testing.assert_array_equal(np.asarray(out[0, 480:]), 0.0)

        def loss(fn):
            return lambda a, b_, c: (fn(a, b_, c) * jnp.cos(
                jnp.arange(c.size, dtype=jnp.float32).reshape(c.shape))).sum()

        g_flash = jax.grad(
            loss(lambda a, b_, c: flash_attention_segmented(a, b_, c, segments,
                                                            causal, 128, 128)),
            argnums=(0, 1, 2))(q, k, v)
        g_dense = jax.grad(
            loss(lambda a, b_, c: masked_dense_attention(
                a, b_, c, segment_mask(segments, segments, causal=causal))),
            argnums=(0, 1, 2))(q, k, v)
        for gf, gd, name in zip(g_flash, g_dense, 'qkv'):
            np.testing.assert_allclose(np.asarray(gf), np.asarray(gd),
                                       atol=5e-4, rtol=5e-4, err_msg='d' + name)

    def test_segmented_fallback_path(self):
        """Non-tiling shapes take the masked dense fallback — value and grads."""
        from petastorm_tpu.ops.flash_attention import flash_attention_segmented
        from petastorm_tpu.ops.packing import masked_dense_attention, segment_mask
        rng = np.random.RandomState(4)
        q, k, v = (jnp.asarray(rng.randn(1, 24, 2, 16), dtype=jnp.float32)
                   for _ in range(3))
        segments = jnp.asarray(np.r_[[1] * 10, [2] * 10, [0] * 4][None], jnp.int32)
        out = flash_attention_segmented(q, k, v, segments, True, 128, 128)
        expected = masked_dense_attention(
            q, k, v, segment_mask(segments, segments, causal=True))
        np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                                   atol=1e-5, rtol=1e-5)
        g = jax.grad(lambda a: jnp.sum(flash_attention_segmented(
            a, k, v, segments, True, 128, 128) ** 2))(q)
        assert np.all(np.isfinite(np.asarray(g)))

    def test_backward_never_materializes_txt(self):
        """The training-time memory claim (VERDICT round 1 item 7): no [T, T] tensor
        may exist anywhere in the lowered backward — scores are rematerialized
        blockwise from Q/K and the saved LSE."""
        from petastorm_tpu.ops.flash_attention import flash_attention
        t = 512
        q = jnp.zeros((1, t, 1, 128), dtype=jnp.float32)
        grad_fn = jax.jit(jax.grad(
            lambda a, b_, c: flash_attention(a, b_, c, True, 128, 128).sum(),
            argnums=(0, 1, 2)))
        hlo = grad_fn.lower(q, q, q).as_text()
        txt_patterns = ('512x512', '512,512')  # StableHLO and HLO shape spellings
        assert not any(p in hlo for p in txt_patterns), \
            'backward materialized a [T, T] intermediate'
        # sanity: the dense path DOES contain it, so the assertion is meaningful
        dense_hlo = jax.jit(jax.grad(
            lambda a, b_, c: dense_attention(a, b_, c, causal=True).sum(),
            argnums=(0, 1, 2))).lower(q, q, q).as_text()
        assert any(p in dense_hlo for p in txt_patterns)

    def test_non_tiling_shapes_fall_back(self):
        from petastorm_tpu.ops.flash_attention import flash_attention
        rng = np.random.RandomState(2)
        q, k, v = (jnp.asarray(rng.randn(1, 100, 2, 64), dtype=jnp.float32)
                   for _ in range(3))
        out = flash_attention(q, k, v)
        expected = dense_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                                   atol=1e-5, rtol=1e-5)

    def test_bf16_inputs(self):
        from petastorm_tpu.ops.flash_attention import flash_attention
        rng = np.random.RandomState(3)
        q, k, v = (jnp.asarray(rng.randn(1, 256, 1, 128), dtype=jnp.bfloat16)
                   for _ in range(3))
        out = flash_attention(q, k, v, False, 128, 128)
        assert out.dtype == jnp.bfloat16
        expected = dense_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(out, dtype=np.float32),
                                   np.asarray(expected, dtype=np.float32),
                                   atol=3e-2, rtol=3e-2)

    def test_bf16_gradients_match_dense(self):
        """The blockwise backward in the dtype the bench actually trains in
        (bf16 params, f32 VMEM accumulators)."""
        from petastorm_tpu.ops.flash_attention import flash_attention
        rng = np.random.RandomState(4)
        q, k, v = (jnp.asarray(rng.randn(1, 256, 1, 128), dtype=jnp.bfloat16)
                   for _ in range(3))

        def loss(fn):
            return lambda a, b_, c: jnp.sum(fn(a, b_, c).astype(jnp.float32) ** 2)

        g_flash = jax.grad(
            loss(lambda a, b_, c: flash_attention(a, b_, c, True, 128, 128)),
            argnums=(0, 1, 2))(q, k, v)
        g_dense = jax.grad(
            loss(lambda a, b_, c: dense_attention(a, b_, c, causal=True)),
            argnums=(0, 1, 2))(q, k, v)
        for gf, gd, name in zip(g_flash, g_dense, 'qkv'):
            assert gf.dtype == jnp.bfloat16, name
            np.testing.assert_allclose(
                np.asarray(gf, dtype=np.float32),
                np.asarray(gd, dtype=np.float32),
                atol=0.25, rtol=0.1, err_msg='d{} mismatch'.format(name))

    # Relative L2 bound for bf16 operands on the MXU (p and dS rounded to bf16,
    # outputs in bf16): the kernels read ~0.002-0.0025 here, dense attention on
    # e4m3-rounded inputs 0.04-0.06.
    BF16_REL_L2 = 0.01

    @pytest.mark.parametrize('segmented', [False, True])
    @pytest.mark.parametrize('causal', [False, True])
    def test_bf16_matches_dense_at_highest(self, causal, segmented):
        """bf16 inputs through the kernels (4 x 4 blocks of 128) against dense
        attention at float32 ``highest`` on the same values: output and
        dq/dk/dv within BF16_REL_L2, which dense attention on e4m3-rounded
        inputs exceeds."""
        from petastorm_tpu.ops.flash_attention import (flash_attention,
                                                       flash_attention_segmented)
        from petastorm_tpu.ops.packing import masked_dense_attention, segment_mask
        rng = np.random.RandomState(11)
        shape = (1, 512, 2, 128)
        q, k, v, g = (jnp.asarray(rng.randn(*shape), jnp.bfloat16) for _ in range(4))
        if segmented:
            seg = np.zeros((1, 512), np.int32)
            seg[0, :200], seg[0, 200:430], seg[0, 430:480] = 1, 2, 3
            segments = jnp.asarray(seg)
            mask = segment_mask(segments, segments, causal=causal)
            flash = lambda a, b_, c: flash_attention_segmented(  # noqa: E731
                a, b_, c, segments, causal, 128, 128)
            dense = lambda a, b_, c: masked_dense_attention(a, b_, c, mask)  # noqa: E731
        else:
            flash = lambda a, b_, c: flash_attention(a, b_, c, causal, 128, 128)  # noqa: E731
            dense = lambda a, b_, c: dense_attention(a, b_, c, causal=causal)  # noqa: E731

        def out_and_grads(fn, *xs):
            out, vjp = jax.vjp(fn, *xs[:3])
            return [np.asarray(x, np.float64) for x in (out,) + vjp(xs[3].astype(out.dtype))]

        def rel_l2(got, want):
            return [np.linalg.norm(a - b) / np.linalg.norm(b) for a, b in zip(got, want)]

        f32 = lambda xs: [x.astype(jnp.float32) for x in xs]  # noqa: E731
        with jax.default_matmul_precision('highest'):
            want = out_and_grads(dense, *f32((q, k, v, g)))
            control = rel_l2(out_and_grads(dense, *f32(
                x.astype(jnp.float8_e4m3fn) for x in (q, k, v, g))), want)
        got = out_and_grads(flash, q, k, v, g)
        assert flash(q, k, v).dtype == jnp.bfloat16
        errs = rel_l2(got, want)
        for name, err, ctl in zip(('out', 'dq', 'dk', 'dv'), errs, control):
            assert err <= self.BF16_REL_L2, (name, err)
            assert ctl > self.BF16_REL_L2, (name, ctl)

    @pytest.mark.parametrize('segmented', [False, True])
    @pytest.mark.parametrize('blocks', [(128, 128), (256, 128), (128, 256)])
    def test_clamped_index_maps_change_nothing(self, monkeypatch, blocks, segmented):
        """Causal steps above the diagonal name an already-resident block instead of
        their own; with the maps left unclamped the kernels give bit-identical
        output and gradients (interpret mode, bf16)."""
        fa = importlib.import_module('petastorm_tpu.ops.flash_attention')
        rng = np.random.RandomState(12)
        shape = (1, 512, 2, 128)
        q, k, v, g = (jnp.asarray(rng.randn(*shape), jnp.bfloat16) for _ in range(4))
        seg = np.ones((1, 512), np.int32)
        seg[0, 300:] = 2
        segments = jnp.asarray(seg)

        def run():
            if segmented:
                fn = lambda a, b_, c: fa.flash_attention_segmented(  # noqa: E731
                    a, b_, c, segments, True, *blocks)
            else:
                fn = lambda a, b_, c: fa.flash_attention(a, b_, c, True, *blocks)  # noqa: E731
            out, vjp = jax.vjp(fn, q, k, v)
            return [np.asarray(x, np.float32) for x in (out,) + vjp(g)]

        clamped = run()
        monkeypatch.setattr(fa, '_causal_kv_block', lambda i, j, bq, bk: j)
        monkeypatch.setattr(fa, '_causal_q_block', lambda i, j, bq, bk: j)
        for a, b_, name in zip(clamped, run(), ('out', 'dq', 'dk', 'dv')):
            np.testing.assert_array_equal(a, b_, err_msg=name)

    @pytest.mark.parametrize('blocks', [(128, 128), (256, 128), (128, 256)])
    def test_causal_index_maps_refetch_nothing_dead(self, blocks):
        """Along each grid row's inner axis, a step above the diagonal names the
        block its neighbour on the live side already holds; a live step names its
        own block."""
        fa = importlib.import_module('petastorm_tpu.ops.flash_attention')
        bq, bk = blocks
        t = 1024
        nq, nk = t // bq, t // bk
        for i in range(nq):           # forward and dq: (q-block i, k-block j)
            kv = [int(fa._causal_kv_block(i, j, bq, bk)) for j in range(nk)]
            live = [j for j in range(nk) if j * bk <= i * bq + bq - 1]
            assert kv[:len(live)] == live
            assert set(kv[len(live):]) <= {live[-1]}
        for i in range(nk):           # dk/dv: (k-block i, q-block j)
            qb = [int(fa._causal_q_block(i, j, bq, bk)) for j in range(nq)]
            live = [j for j in range(nq) if j * bq + bq - 1 >= i * bk]
            dead = nq - len(live)
            assert qb[dead:] == live
            assert set(qb[:dead]) <= {live[0]}

    @pytest.mark.parametrize('blocks', [(128, 128), (256, 128), (128, 256)])
    def test_causal_steps_split_below_and_diagonal(self, blocks):
        """Each causal grid step folds without the mask when all its keys precede
        all its queries, with it when it straddles the diagonal, and not at all
        above it (a Pallas grid in interpret mode records which)."""
        from jax.experimental import pallas as pl
        fa = importlib.import_module('petastorm_tpu.ops.flash_attention')
        bq, bk = blocks
        t = 1024
        nq, nk = t // bq, t // bk

        def kernel(o_ref):
            qi, ki = pl.program_id(0), pl.program_id(1)
            o_ref[...] = jnp.zeros_like(o_ref)

            def fold(masked):
                o_ref[...] = jnp.full_like(o_ref, 2 if masked else 1)
            fa._fold_live_blocks(fold, True, qi * bq, ki * bk, bq, bk)

        out = pl.pallas_call(
            kernel, out_shape=jax.ShapeDtypeStruct((8 * nq, 128 * nk), jnp.int32),
            grid=(nq, nk), out_specs=pl.BlockSpec((8, 128), lambda i, j: (i, j)),
            interpret=True)()
        steps = np.asarray(out)[::8, ::128]
        for i in range(nq):
            for j in range(nk):
                q_lo, q_hi, k_lo, k_hi = i * bq, i * bq + bq - 1, j * bk, j * bk + bk - 1
                want = 0 if k_lo > q_hi else 1 if k_hi <= q_lo else 2
                assert steps[i, j] == want, (i, j)
        assert {1, 2} <= set(steps.ravel().tolist())


class TestImageOps:
    def test_normalize(self):
        images = np.full((2, 4, 4, 3), 255, dtype=np.uint8)
        out = normalize_image(jnp.asarray(images), mean=[1.0, 1.0, 1.0],
                              std=[1.0, 1.0, 1.0], dtype=jnp.float32)
        np.testing.assert_allclose(np.asarray(out), 0.0, atol=1e-6)

    def test_random_crop_flip_shapes(self):
        rng = jax.random.PRNGKey(0)
        images = jnp.zeros((4, 32, 32, 3), dtype=jnp.uint8)
        out = random_crop_flip(rng, images, (28, 28))
        assert out.shape == (4, 28, 28, 3)

    def test_crop_is_jittable(self):
        rng = jax.random.PRNGKey(0)
        images = jnp.zeros((2, 8, 8, 1), dtype=jnp.uint8)
        jitted = jax.jit(lambda r, im: random_crop_flip(r, im, (6, 6)))
        assert jitted(rng, images).shape == (2, 6, 6, 1)


class TestRandomIndexShuffle:
    """Feistel index cipher: a seeded bijection on [0, n) evaluated pointwise
    (ops/index_shuffle.py) — replaces sort-based jax.random.permutation."""

    @pytest.mark.parametrize('n', [1, 2, 3, 7, 16, 100, 1000, 49152])
    def test_is_a_bijection(self, n):
        import jax
        from petastorm_tpu.ops.index_shuffle import random_index_shuffle
        out = np.asarray(random_index_shuffle(
            jnp.arange(n), jax.random.PRNGKey(0), n))
        assert sorted(out.tolist()) == list(range(n))

    def test_not_identity_and_decorrelated(self):
        import jax
        from petastorm_tpu.ops.index_shuffle import random_index_shuffle
        n = 4096
        out = np.asarray(random_index_shuffle(
            jnp.arange(n), jax.random.PRNGKey(3), n))
        assert out.tolist() != list(range(n))
        corr = abs(float(np.corrcoef(np.arange(n), out)[0, 1]))
        assert corr < 0.1

    def test_seeded_reproducible_and_key_sensitive(self):
        import jax
        from petastorm_tpu.ops.index_shuffle import random_index_shuffle
        pos = jnp.arange(256)
        a = np.asarray(random_index_shuffle(pos, jax.random.PRNGKey(1), 256))
        b = np.asarray(random_index_shuffle(pos, jax.random.PRNGKey(1), 256))
        c = np.asarray(random_index_shuffle(pos, jax.random.PRNGKey(2), 256))
        assert a.tolist() == b.tolist()
        assert a.tolist() != c.tolist()

    def test_pointwise_matches_full_evaluation(self):
        # perm[positions] computed lane-wise must agree with evaluating the whole
        # permutation — the property that lets batches shuffle without materialization.
        import jax
        from petastorm_tpu.ops.index_shuffle import random_index_shuffle
        n = 1000
        key = jax.random.PRNGKey(9)
        full = np.asarray(random_index_shuffle(jnp.arange(n), key, n))
        window = np.asarray(random_index_shuffle(jnp.arange(200, 300), key, n))
        assert window.tolist() == full[200:300].tolist()

    def test_works_under_jit_and_scan(self):
        import jax
        from petastorm_tpu.ops.index_shuffle import random_index_shuffle
        n, batch = 64, 16

        @jax.jit
        def gather_epoch(key):
            def body(carry, b):
                idx = random_index_shuffle(b * batch + jnp.arange(batch), key, n)
                return carry, idx
            _, idxs = jax.lax.scan(body, None, jnp.arange(n // batch))
            return idxs.ravel()

        out = np.asarray(gather_epoch(jax.random.PRNGKey(0)))
        assert sorted(out.tolist()) == list(range(n))


class TestFlashAutoBlocks:
    """'auto' block resolution: the largest of 512, 256 and 128 that divides T
    (128 widens Pallas coverage to shapes a fixed 256 sent down the dense
    path); non-tiling shapes still take the dense path."""

    def test_resolution_preference(self):
        from petastorm_tpu.ops.flash_attention import _resolve_blocks
        assert _resolve_blocks(512, 'auto', 'auto') == (512, 512)
        assert _resolve_blocks(8192, 'auto', 'auto') == (512, 512)
        assert _resolve_blocks(768, 'auto', 'auto') == (256, 256)
        assert _resolve_blocks(384, 'auto', 'auto') == (128, 128)
        assert _resolve_blocks(100, 'auto', 'auto') == (512, 512)  # -> dense
        assert _resolve_blocks(384, 64, 'auto') == (64, 128)  # ints pass through

    def test_t1536_takes_512(self):
        """512 divides 1,536 (three blocks); 256 is never reached."""
        from petastorm_tpu.ops.flash_attention import _resolve_blocks, _use_pallas
        assert _resolve_blocks(1536, 'auto', 'auto') == (512, 512)
        assert _resolve_blocks(1536, 'auto', 256) == (512, 256)
        x = jnp.zeros((1, 1536, 2, 128), jnp.bfloat16)
        assert _use_pallas(x, x, 'auto', 'auto')

    def test_dispatch_predicate(self):
        from petastorm_tpu.ops.flash_attention import _use_pallas
        mk = lambda t: jnp.zeros((1, t, 2, 128), jnp.float32)
        assert _use_pallas(mk(384), mk(384), 'auto', 'auto')       # 128 tiles
        assert not _use_pallas(mk(384), mk(384), 256, 256)         # old default
        assert not _use_pallas(mk(100), mk(100), 'auto', 'auto')   # nothing tiles

    @pytest.mark.parametrize('causal', [False, True])
    def test_auto_t384_matches_dense(self, causal):
        """T=384 took the dense path under the fixed-256 default; under 'auto'
        it must run the Pallas kernels (asserted via the dispatch predicate)
        and still match dense in values and gradients."""
        from petastorm_tpu.ops.flash_attention import flash_attention
        rng = np.random.RandomState(7)
        b, t, h, d = 1, 384, 2, 128
        q = jnp.asarray(rng.randn(b, t, h, d), dtype=jnp.float32)
        k = jnp.asarray(rng.randn(b, t, h, d), dtype=jnp.float32)
        v = jnp.asarray(rng.randn(b, t, h, d), dtype=jnp.float32)
        out = flash_attention(q, k, v, causal)
        expected = dense_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                                   atol=2e-4, rtol=2e-4)
        g_flash = jax.grad(lambda a: jnp.sum(flash_attention(a, k, v, causal)))(q)
        g_dense = jax.grad(
            lambda a: jnp.sum(dense_attention(a, k, v, causal=causal)))(q)
        np.testing.assert_allclose(np.asarray(g_flash), np.asarray(g_dense),
                                   atol=2e-3, rtol=2e-3)

    def test_segmented_auto_t384_matches_masked_dense(self):
        from petastorm_tpu.ops.flash_attention import flash_attention_segmented
        from petastorm_tpu.ops.packing import (masked_dense_attention,
                                               segment_mask)
        rng = np.random.RandomState(8)
        b, t, h, d = 1, 384, 2, 128
        q = jnp.asarray(rng.randn(b, t, h, d), dtype=jnp.float32)
        k = jnp.asarray(rng.randn(b, t, h, d), dtype=jnp.float32)
        v = jnp.asarray(rng.randn(b, t, h, d), dtype=jnp.float32)
        segments = jnp.asarray(
            np.concatenate([np.full(200, 1), np.full(120, 2), np.zeros(64)])[None, :]
            .astype(np.int32))
        out = flash_attention_segmented(q, k, v, segments, True)
        mask = segment_mask(segments, segments, causal=True)
        expected = masked_dense_attention(q, k, v, mask)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                                   atol=2e-4, rtol=2e-4)
