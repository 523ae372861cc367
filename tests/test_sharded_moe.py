"""Explicit all-to-all MoE (ops/sharded_moe.py): must match the dense einsum
reference computed with the same routing function and global weights."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from petastorm_tpu.models.moe import _capacity, switch_routing
from petastorm_tpu.ops.sharded_moe import expert_alltoall_ffn, sharded_moe_ffn

N_EXPERTS = 8
DIM = 16
HID = 32
S = 32  # global tokens; 16 per data shard


def params(seed):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(DIM, N_EXPERTS) * 0.5, jnp.float32),
            jnp.asarray(rng.randn(N_EXPERTS, DIM, HID) * 0.3, jnp.float32),
            jnp.asarray(rng.randn(N_EXPERTS, HID, DIM) * 0.3, jnp.float32))


def shard_reference(tokens, router_kernel, w1, w2, capacity_factor=8.0,
                    num_selected=1):
    """ONE shard's route->dispatch->FFN->combine, the slow unsharded way — the
    reference body for every equivalence test in this file."""
    n_exp = router_kernel.shape[1]
    probs = jax.nn.softmax(tokens @ router_kernel, axis=-1)
    cap = _capacity(tokens.shape[0], n_exp, num_selected, capacity_factor)
    dispatch, combine, _, _ = switch_routing(probs, cap, num_selected)
    expert_in = jnp.einsum('sxc,sd->xcd', dispatch, tokens)
    h = jax.nn.gelu(jnp.einsum('xcd,xdf->xcf', expert_in, w1))
    out = jnp.einsum('xcf,xfd->xcd', h, w2)
    return jnp.einsum('xcd,sxc->sd', out, combine)


def dense_reference(tokens, router_kernel, w1, w2, capacity_factor=8.0,
                    num_selected=1):
    """Unsharded reference with routing computed per data shard of 16 tokens
    (matching what each shard_map instance sees)."""
    return jnp.concatenate(
        [shard_reference(shard, router_kernel, w1, w2, capacity_factor,
                         num_selected)
         for shard in (tokens[:16], tokens[16:])], axis=0)


def mesh_2x4():
    return Mesh(np.asarray(jax.devices()[:8]).reshape(2, 4), ('data', 'expert'))


def sharded_fn(mesh, capacity_factor=8.0, num_selected=1):
    return jax.shard_map(
        lambda t, rk, w1, w2: sharded_moe_ffn(
            t, rk, w1, w2, 'expert', capacity_factor=capacity_factor,
            num_selected=num_selected)[0],
        mesh=mesh,
        in_specs=(P('data', None), P(None, None), P('expert', None, None),
         P('expert', None, None)),
        out_specs=P('data', None), check_vma=False)


class TestShardedMoE(object):
    def test_matches_dense_reference(self):
        router_kernel, w1, w2 = params(0)
        tokens = jnp.asarray(np.random.RandomState(1).randn(S, DIM), jnp.float32)
        expected = dense_reference(tokens, router_kernel, w1, w2)
        got = jax.jit(sharded_fn(mesh_2x4()))(tokens, router_kernel, w1, w2)
        np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                                   rtol=2e-5, atol=2e-6)

    def test_top2_matches_dense_reference(self):
        router_kernel, w1, w2 = params(2)
        tokens = jnp.asarray(np.random.RandomState(3).randn(S, DIM), jnp.float32)
        expected = dense_reference(tokens, router_kernel, w1, w2, num_selected=2)
        got = jax.jit(sharded_fn(mesh_2x4(), num_selected=2))(
            tokens, router_kernel, w1, w2)
        np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                                   rtol=2e-5, atol=2e-6)

    def test_gradients_match_dense_reference(self):
        router_kernel, w1, w2 = params(4)
        tokens = jnp.asarray(np.random.RandomState(5).randn(S, DIM), jnp.float32)
        pipe = sharded_fn(mesh_2x4())

        g_sharded = jax.jit(jax.grad(
            lambda w1, w2: jnp.sum(pipe(tokens, router_kernel, w1, w2) ** 2),
            argnums=(0, 1)))(w1, w2)
        g_dense = jax.jit(jax.grad(
            lambda w1, w2: jnp.sum(
                dense_reference(tokens, router_kernel, w1, w2) ** 2),
            argnums=(0, 1)))(w1, w2)
        for a, b in zip(g_sharded, g_dense):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-5, atol=5e-6)

    def test_bf16_tokens_supported(self):
        router_kernel, w1, w2 = params(6)
        tokens = jnp.asarray(np.random.RandomState(7).randn(S, DIM), jnp.bfloat16)
        got = jax.jit(sharded_fn(mesh_2x4()))(tokens, router_kernel, w1, w2)
        assert got.dtype == jnp.bfloat16
        assert np.all(np.isfinite(np.asarray(got, dtype=np.float32)))

    def test_composes_with_ring_attention_in_one_shard_map(self):
        """The reason this op exists: sp + ep inside ONE shard_map region (the
        annotation-based MoEMlp cannot run there). A mini layer — ring attention
        over 'seq', expert FFN over 'expert' — on a (data, seq, expert) mesh."""
        from petastorm_tpu.ops.ring_attention import dense_attention, ring_attention

        mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(2, 2, 2),
                    ('data', 'seq', 'expert'))
        B, T, H, D = 4, 16, 2, 8
        E = H * D
        rng = np.random.RandomState(10)
        x = jnp.asarray(rng.randn(B, T, H, D), jnp.float32)
        router_kernel = jnp.asarray(rng.randn(E, 4) * 0.5, jnp.float32)
        w1 = jnp.asarray(rng.randn(4, E, 2 * E) * 0.3, jnp.float32)
        w2 = jnp.asarray(rng.randn(4, 2 * E, E) * 0.3, jnp.float32)

        def layer(x, rk, w1, w2):
            attn = ring_attention(x, x, x, axis_name='seq', causal=True)
            tokens = attn.reshape(-1, E)
            out, _, _ = sharded_moe_ffn(tokens, rk, w1, w2, 'expert',
                                        capacity_factor=8.0)
            return (tokens + out).reshape(attn.shape)

        x_spec = P('data', 'seq', None, None)
        fn = jax.shard_map(
            layer, mesh=mesh,
            in_specs=(x_spec, P(None, None), P('expert', None, None),
             P('expert', None, None)), out_specs=x_spec, check_vma=False)
        got = jax.jit(fn)(x, router_kernel, w1, w2)

        # Reference: dense attention, then per-(data, seq)-shard routing + FFN on
        # the same weights — each of the 4 (data, seq) shard cells routes its own
        # B/2 x T/2 token block independently, exactly as the sharded layer does.
        attn = dense_attention(x, x, x, causal=True)
        expected = np.empty((B, T, E), np.float32)
        for bi in range(2):
            for si in range(2):
                blk = attn[bi * 2:(bi + 1) * 2, si * 8:(si + 1) * 8]
                tokens = jnp.asarray(blk.reshape(-1, E))
                y = tokens + shard_reference(tokens, router_kernel, w1, w2)
                expected[bi * 2:(bi + 1) * 2, si * 8:(si + 1) * 8] = (
                    np.asarray(y).reshape(2, 8, E))
        np.testing.assert_allclose(np.asarray(got.reshape(B, T, E)), expected,
                                   rtol=2e-5, atol=2e-5)

    def test_indivisible_experts_rejected(self):
        rng = np.random.RandomState(8)
        mesh = mesh_2x4()
        tokens = jnp.zeros((S, DIM), jnp.float32)
        # 6 experts over a 4-device expert axis: must fail loudly at trace time.
        w1 = jnp.asarray(rng.randn(6, DIM, HID), jnp.float32)
        w2 = jnp.asarray(rng.randn(6, HID, DIM), jnp.float32)
        dispatch = jnp.zeros((16, 6, 4), jnp.float32)
        fn = jax.shard_map(
            lambda t, d, w1, w2: expert_alltoall_ffn(t, d, d, w1, w2, 'expert'),
            mesh=mesh, in_specs=(P('data', None), P('data', None, None),
                   P(None, None, None), P(None, None, None)),
            out_specs=P('data', None), check_vma=False)
        with pytest.raises(ValueError):
            jax.jit(fn)(tokens, dispatch, w1, w2)

    def test_wrong_local_slice_rejected(self):
        mesh = mesh_2x4()
        rng = np.random.RandomState(9)
        tokens = jnp.zeros((S, DIM), jnp.float32)
        dispatch = jnp.zeros((16, N_EXPERTS, 4), jnp.float32)
        # Full (global) expert weights passed where the local slice is expected:
        # replicated in_spec leaves leading dim 8 != 8/4 local experts.
        w1 = jnp.asarray(rng.randn(N_EXPERTS, DIM, HID), jnp.float32)
        w2 = jnp.asarray(rng.randn(N_EXPERTS, HID, DIM), jnp.float32)
        fn = jax.shard_map(
            lambda t, d, w1, w2: expert_alltoall_ffn(t, d, d, w1, w2, 'expert'),
            mesh=mesh, in_specs=(P('data', None), P('data', None, None),
                   P(None, None, None), P(None, None, None)),
            out_specs=P('data', None), check_vma=False)
        with pytest.raises(ValueError):
            jax.jit(fn)(tokens, dispatch, w1, w2)
