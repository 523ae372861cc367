"""DeepSeek-V3-style decoder: multi-head latent attention and routed experts.

The block of DeepSeek-V3 and of the models built on it (Moonlight-16B-A3B among
them), in flax: RMSNorm, multi-head latent attention (:class:`MLAttention`),
dense SwiGLU MLPs in the first ``first_dense`` layers and, after them, routed
experts with shared experts (:class:`~petastorm_tpu.models.moe.DroplessMoE`),
an untied embedding and head. bf16 compute with float32 norms, router and
logits.

A layer of experts holds ``[first_expert, first_expert + held_experts)`` of
``num_experts`` and routes over all of them, as one chip of an expert-parallel
deployment does; the selection bias of each routed layer lives in the
``'router_state'`` collection (apply with it mutable to take the bias update).
"""

import dataclasses
from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from petastorm_tpu.models.moe import EXPERT_RESIDUALS, DroplessMoE, SwiGLU


@dataclasses.dataclass(frozen=True)
class DeepSeekConfig(object):
    """The widths and settings of a DeepSeek-V3-style decoder, and the chip's
    share of its experts: ``held_experts`` of the router's ``router_experts``,
    from ``first_expert``. ``bias_step`` is gamma of the selection bias's update;
    ``kv_norm_eps`` the latent norm's epsilon."""

    vocab: int
    hidden: int
    layers: int
    first_dense: int
    heads: int
    qk_nope_dim: int
    qk_rope_dim: int
    v_head_dim: int
    kv_lora_rank: int
    dense_width: int
    expert_width: int
    router_experts: int
    held_experts: int
    num_selected: int
    shared_experts: int
    routed_scaling: float
    rope_theta: float
    rms_eps: float
    kv_norm_eps: float = 1e-6
    first_expert: int = 0
    bias_step: float = 0.0

    @classmethod
    def from_hf(cls, c, **overrides):
        """From a Hugging Face ``deepseek_v3`` config's keys (``n_routed_experts``
        read as the experts held; ``router_experts`` gives the router's width and
        defaults to it)."""
        fields = dict(
            vocab=c['vocab_size'], hidden=c['hidden_size'], layers=c['num_hidden_layers'],
            first_dense=c['first_k_dense_replace'], heads=c['num_attention_heads'],
            qk_nope_dim=c['qk_nope_head_dim'], qk_rope_dim=c['qk_rope_head_dim'],
            v_head_dim=c['v_head_dim'], kv_lora_rank=c['kv_lora_rank'],
            dense_width=c['intermediate_size'], expert_width=c['moe_intermediate_size'],
            router_experts=c.get('router_experts', c['n_routed_experts']),
            held_experts=c['n_routed_experts'], num_selected=c['num_experts_per_tok'],
            shared_experts=c['n_shared_experts'],
            routed_scaling=c['routed_scaling_factor'], rope_theta=c['rope_theta'],
            rms_eps=c['rms_norm_eps'])
        fields.update(overrides)
        return cls(**fields)


class RMSNorm(nn.Module):
    """``x / rms(x) * scale`` in float32, returned in ``dtype``."""

    eps: float = 1e-6
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        scale = self.param('scale', nn.initializers.ones, (x.shape[-1],), jnp.float32)
        x = x.astype(jnp.float32)
        x = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + self.eps)
        return (x * scale).astype(self.dtype)


def rope(x, positions, theta):
    """Rotary embedding of ``x [B, T, H, R]`` at ``positions [T]``, with the
    published DeepSeek-V3 modeling code's pairing: dims ``(2i, 2i + 1)`` rotate
    together by ``positions / theta**(2i / R)``, and the result holds the pairs'
    first members, then their second members."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / x.shape[-1])
    angle = positions.astype(jnp.float32)[:, None] * freqs[None]        # [T, R/2]
    cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]         # [T, 1, R/2]
    xf = x.astype(jnp.float32)
    even, odd = xf[..., 0::2], xf[..., 1::2]
    return jnp.concatenate([even * cos - odd * sin, odd * cos + even * sin],
                           axis=-1).astype(x.dtype)


class MLAttention(nn.Module):
    """Multi-head latent attention without query compression: ``[B, T, D] ->
    [B, T, D]``.

    q = W_q h (heads x (nope + rope)); [c_kv ; k_pe] = W_kv_a h (latent + rope);
    c_kv is RMS-normed and W_kv_b maps it to each head's k_nope and v; RoPE turns
    q_pe and k_pe, k_pe shared by every head; the softmax scale is
    ``(nope + rope) ** -0.5``. ``attention_fn(q, k, v)`` takes q, k
    ``[B, T, H, nope + rope]`` and v ``[B, T, H, v_head]`` (causal)."""

    heads: int
    qk_nope_dim: int
    qk_rope_dim: int
    v_head_dim: int
    kv_lora_rank: int
    rope_theta: float
    attention_fn: Callable
    kv_norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        b, t, d = x.shape
        h, nope, rot, dv = self.heads, self.qk_nope_dim, self.qk_rope_dim, self.v_head_dim
        dense = lambda n, name: nn.Dense(n, use_bias=False, dtype=self.dtype,  # noqa: E731
                                         name=name)
        with jax.named_scope('mla'):
            positions = jnp.arange(t)
            q = dense(h * (nope + rot), 'q_proj')(x).reshape(b, t, h, nope + rot)
            q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], positions,
                                                     self.rope_theta)], axis=-1)
            kv_a = dense(self.kv_lora_rank + rot, 'kv_a_proj')(x)
            latent = RMSNorm(self.kv_norm_eps, self.dtype, name='kv_a_norm')(
                kv_a[..., :self.kv_lora_rank])
            k_pe = rope(kv_a[..., None, self.kv_lora_rank:], positions, self.rope_theta)
            kv = dense(h * (nope + dv), 'kv_b_proj')(latent).reshape(b, t, h, nope + dv)
            k = jnp.concatenate([kv[..., :nope],
                                 jnp.broadcast_to(k_pe, (b, t, h, rot))], axis=-1)
            attn = self.attention_fn(q, k, kv[..., nope:])
            return dense(d, 'o_proj')(attn.reshape(b, t, h * dv))


class DeepSeekBlock(nn.Module):
    """Pre-norm block: MLA, then a dense SwiGLU (``dense``) or routed experts
    with shared experts."""

    cfg: DeepSeekConfig
    attention_fn: Callable
    dense: bool
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        h = RMSNorm(c.rms_eps, self.dtype, name='input_norm')(x)
        x = x + MLAttention(
            heads=c.heads, qk_nope_dim=c.qk_nope_dim, qk_rope_dim=c.qk_rope_dim,
            v_head_dim=c.v_head_dim, kv_lora_rank=c.kv_lora_rank,
            rope_theta=c.rope_theta, attention_fn=self.attention_fn,
            kv_norm_eps=c.kv_norm_eps, dtype=self.dtype, name='attention')(h).astype(
                x.dtype)
        h = RMSNorm(c.rms_eps, self.dtype, name='post_attention_norm')(x)
        if self.dense:
            mlp = SwiGLU(c.dense_width, self.dtype, name='mlp')
        else:
            mlp = DroplessMoE(
                num_experts=c.router_experts, held_experts=c.held_experts,
                num_selected=c.num_selected, width=c.expert_width,
                first_expert=c.first_expert, shared_width=c.shared_experts * c.expert_width,
                scaling=c.routed_scaling, bias_step=c.bias_step, dtype=self.dtype,
                name='moe')
        return x + mlp(h).astype(x.dtype)


class DeepSeekV3LM(nn.Module):
    """DeepSeek-V3-style decoder (:class:`DeepSeekConfig`): tokens ``[B, T]`` ->
    logits ``[B, T, vocab]`` float32. Layers are named ``layer_<i>``, the first
    ``first_dense`` dense; ``remat`` recomputes each block in the backward with
    the same param tree, all but the routed layers' chosen experts and
    grouped-matmul results (:data:`~petastorm_tpu.models.moe.EXPERT_RESIDUALS`),
    which it keeps;
    ``attention_fn`` defaults to dense causal attention."""

    cfg: DeepSeekConfig
    dtype: Any = jnp.bfloat16
    attention_fn: Optional[Callable] = None
    remat: bool = False

    @nn.compact
    def __call__(self, tokens):
        from petastorm_tpu.models.transformer import dense_causal_attention
        c = self.cfg
        attention_fn = self.attention_fn or dense_causal_attention
        block_cls = DeepSeekBlock
        if self.remat:
            block_cls = nn.remat(DeepSeekBlock, policy=jax.checkpoint_policies.
                                 save_only_these_names(*EXPERT_RESIDUALS))
        x = nn.Embed(c.vocab, c.hidden, dtype=self.dtype, name='embed')(tokens)
        for i in range(c.layers):
            x = block_cls(cfg=c, attention_fn=attention_fn, dense=i < c.first_dense,
                          dtype=self.dtype, name='layer_{}'.format(i))(x)
        x = RMSNorm(c.rms_eps, jnp.float32, name='final_norm')(x)
        return nn.Dense(c.vocab, use_bias=False, dtype=jnp.float32, name='lm_head')(x)
