"""Multi-head Latent Attention (DeepSeek-V2, arXiv:2405.04434).

KV activations are compressed into a rank-`kv_lora_rank` latent c_kv, taken
through an RMSNorm (the published ``kv_a_layernorm``), plus a shared
(per-token, head-agnostic) rope key.  The decode cache stores only the
normalised (c_kv, k_rope): cache bytes per token = kv_lora_rank +
qk_rope_dim, the paper's headline 93% KV-cache reduction.

Training and prefill go through the flash-attention kernel seam
(``attention_grad``): q = [q_nope | q_rope] and k = [k_nope | k_rope
broadcast over the heads] of ``qk_nope_dim + qk_rope_dim``, v of
``v_head_dim``, with the softmax scale of ``softmax_scale``.  Decode
re-materialises K/V from the latent cache ("naive" formulation; the
absorbed-matmul variant is a hillclimb candidate).

Rotary pairs are rotated in halves (``apply_rope``).  DeepSeek-V2's
published checkpoints pair interleaved columns; that is a fixed permutation
of the rope columns of ``w_q`` and of ``w_krope`` and changes nothing else.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as FA
from repro.kernels.backend import kernel_interpret, resolve_backend
from repro.models.common import (dense, dense_init, apply_rope, norm_apply,
                                 norm_init, yarn_mscale)


def mla_init(key, cfg, dtype=jnp.float32):
    d, H = cfg.d_model, cfg.num_heads
    r, rd, nd, vd = cfg.kv_lora_rank, cfg.qk_rope_dim, cfg.qk_nope_dim, cfg.v_head_dim
    ks = jax.random.split(key, 6)
    return {
        "w_q": dense_init(ks[0], d, H * (nd + rd), cfg.use_bias, dtype),
        "w_dkv": dense_init(ks[1], d, r, cfg.use_bias, dtype),
        "w_krope": dense_init(ks[2], d, rd, cfg.use_bias, dtype),
        "kv_norm": norm_init("rmsnorm", r),
        "w_uk": dense_init(ks[3], r, H * nd, cfg.use_bias, dtype),
        "w_uv": dense_init(ks[4], r, H * vd, cfg.use_bias, dtype),
        "w_o": dense_init(ks[5], H * vd, d, cfg.use_bias, dtype),
    }


def softmax_scale(cfg) -> float:
    """1/sqrt(q/k head dim), times YaRN's ``mscale(mscale_all_dim)**2``."""
    s = (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5
    if cfg.yarn is not None and cfg.yarn.mscale_all_dim:
        s *= yarn_mscale(cfg.yarn.factor, cfg.yarn.mscale_all_dim) ** 2
    return s


def _project_q(p, x, positions, cfg):
    H, nd, rd = cfg.num_heads, cfg.qk_nope_dim, cfg.qk_rope_dim
    q = dense(p["w_q"], x).reshape(x.shape[:2] + (H, nd + rd))
    q_nope, q_rope = q[..., :nd], q[..., nd:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta, cfg.yarn)
    return q_nope, q_rope


def _latent(p, x, positions, cfg):
    """The normalised latent c_kv [B, S, r] and the rotated shared rope key
    k_rope [B, S, rd] of x."""
    with jax.named_scope("latent"):
        c_kv = norm_apply("rmsnorm", p["kv_norm"], dense(p["w_dkv"], x),
                          cfg.norm_eps)
        k_rope = apply_rope(dense(p["w_krope"], x)[..., None, :], positions,
                            cfg.rope_theta, cfg.yarn)[..., 0, :]
    return c_kv, k_rope


def _expand_kv(p, c_kv, cfg):
    H, nd, vd = cfg.num_heads, cfg.qk_nope_dim, cfg.v_head_dim
    with jax.named_scope("latent"):
        k_nope = dense(p["w_uk"], c_kv).reshape(c_kv.shape[:2] + (H, nd))
        v = dense(p["w_uv"], c_kv).reshape(c_kv.shape[:2] + (H, vd))
    return k_nope, v


def mla_forward(p, x, positions, cfg, backend=None):
    """Training / prefill forward.  Returns (out, cache={c_kv, k_rope}).
    ``backend`` is the kernel seam's (None reads ``cfg.attn_backend``)."""
    H, vd = cfg.num_heads, cfg.v_head_dim
    q_nope, q_rope = _project_q(p, x, positions, cfg)
    c_kv, k_rope = _latent(p, x, positions, cfg)
    k_nope, v = _expand_kv(p, c_kv, cfg)
    q = jnp.concatenate([q_nope, q_rope], -1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_rope[:, :, None], k_nope.shape[:3] + k_rope.shape[-1:])], -1)
    if resolve_backend(backend or cfg.attn_backend) == "kernel":
        out = FA.attention_grad(q, k, v, causal=True,
                                scale=softmax_scale(cfg),
                                interpret=kernel_interpret())
    else:
        out = FA.attention_ref(q, k, v, causal=True, scale=softmax_scale(cfg))
    out = dense(p["w_o"], out.reshape(x.shape[:2] + (H * vd,)))
    return out, {"c_kv": c_kv, "k_rope": k_rope}


def mla_init_cache(cfg, batch: int, max_len: int, dtype):
    return {"c_kv": jnp.zeros((batch, max_len, cfg.kv_lora_rank), dtype=dtype),
            "k_rope": jnp.zeros((batch, max_len, cfg.qk_rope_dim), dtype=dtype)}


def mla_decode(p, x, pos, cache, cfg):
    """One-token decode.  Cache holds latents only."""
    H, vd = cfg.num_heads, cfg.v_head_dim
    B = x.shape[0]
    posb = jnp.broadcast_to(jnp.asarray(pos)[None, None], (B, 1))
    q_nope, q_rope = _project_q(p, x, posb, cfg)
    c_new, kr_new = _latent(p, x, posb, cfg)           # [B, 1, r], [B, 1, rd]
    c_kv = jax.lax.dynamic_update_slice(
        cache["c_kv"], c_new.astype(cache["c_kv"].dtype), (0, pos, 0))
    k_rope = jax.lax.dynamic_update_slice(
        cache["k_rope"], kr_new.astype(cache["k_rope"].dtype), (0, pos, 0))
    k_nope, v = _expand_kv(p, c_kv, cfg)
    s_nope = jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope)
    s_rope = jnp.einsum("bqhd,bkd->bhqk", q_rope, k_rope)
    scores = (s_nope + s_rope).astype(jnp.float32) * softmax_scale(cfg)
    L = c_kv.shape[1]
    mask = (jnp.arange(L) <= pos)[None, None, None, :]
    scores = jnp.where(mask, scores, -1e9)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
    out = dense(p["w_o"], out.reshape(B, 1, H * vd))
    return out, {"c_kv": c_kv, "k_rope": k_rope}
