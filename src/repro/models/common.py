"""Shared functional building blocks (pure-jnp, eval_shape friendly).

All modules are (init, apply) pairs over plain dict pytrees so that
``jax.eval_shape`` can abstract-init trillion-parameter configs without
allocating.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


# --------------------------------------------------------------------- dense
def dense_init(key, in_dim: int, out_dim: int, use_bias: bool = False,
               dtype=jnp.float32, scale: float | None = None):
    scale = scale if scale is not None else 1.0 / np.sqrt(in_dim)
    p = {"w": (jax.random.normal(key, (in_dim, out_dim), dtype=jnp.float32)
               * scale).astype(dtype)}
    if use_bias:
        p["b"] = jnp.zeros((out_dim,), dtype=dtype)
    return p


def dense(p, x):
    y = x @ p["w"].astype(x.dtype)
    if "b" in p:
        y = y + p["b"].astype(x.dtype)
    return y


# ---------------------------------------------------------------------- norm
def norm_init(kind: str, dim: int, dtype=jnp.float32):
    p = {"scale": jnp.ones((dim,), dtype=dtype)}
    if kind == "layernorm":
        p["bias"] = jnp.zeros((dim,), dtype=dtype)
    return p


def norm_apply(kind: str, p, x, eps: float = 1e-5):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    if kind == "rmsnorm":
        x32 = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    else:  # layernorm
        mu = jnp.mean(x32, -1, keepdims=True)
        var = jnp.mean(jnp.square(x32 - mu), -1, keepdims=True)
        x32 = (x32 - mu) * jax.lax.rsqrt(var + eps)
    y = x32 * p["scale"].astype(jnp.float32)
    if "bias" in p:
        y = y + p["bias"].astype(jnp.float32)
    return y.astype(dt)


# ---------------------------------------------------------------- activation
def activation(name: str, x):
    if name == "gelu":
        return jax.nn.gelu(x)
    if name == "silu":
        return jax.nn.silu(x)
    if name == "relu_sq":
        return jnp.square(jax.nn.relu(x))
    raise ValueError(name)


# ---------------------------------------------------------------------- RoPE
def rope_inv_freq(half_dim: int, theta: float, yarn=None):
    """The rotary frequencies of ``half_dim`` pairs; with a ``configs.Yarn``
    the YaRN blend: interpolated by ``factor`` below the correction range,
    kept above it, and ramped linearly between."""
    if yarn is None:
        return 1.0 / (theta ** (jnp.arange(half_dim, dtype=jnp.float32)
                                / half_dim))
    f = 1.0 / theta ** (np.arange(half_dim, dtype=np.float64) / half_dim)
    dim = 2 * half_dim

    def corr(rot):     # the pair index that turns ``rot`` times
        return dim * np.log(yarn.original_max_position
                            / (rot * 2 * np.pi)) / (2 * np.log(theta))
    low = max(np.floor(corr(yarn.beta_fast)), 0)
    high = min(np.ceil(corr(yarn.beta_slow)), dim - 1)
    ramp = np.clip((np.arange(half_dim) - low) / max(high - low, 1e-3), 0, 1)
    return jnp.asarray(f / yarn.factor * ramp + f * (1 - ramp), jnp.float32)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * float(np.log(factor)) + 1.0 if factor > 1 else 1.0


def _rope_cos_sin(positions, half_dim: int, theta: float, yarn=None):
    """positions [...]; returns cos/sin of shape positions.shape + (half_dim,)."""
    freqs = rope_inv_freq(half_dim, theta, yarn)
    angles = positions[..., None].astype(jnp.float32) * freqs
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x, positions, theta: float, yarn=None):
    """x [B, S, H, hd]; positions [B, S] -> rotated x (llama half-split
    style).  A ``configs.Yarn`` scales the frequencies, and cos and sin by
    ``mscale(mscale) / mscale(mscale_all_dim)`` where that is not 1."""
    hd = x.shape[-1]
    cos, sin = _rope_cos_sin(positions, hd // 2, theta, yarn)   # [B, S, hd/2]
    if yarn is not None:
        m = yarn_mscale(yarn.factor, yarn.mscale) / yarn_mscale(
            yarn.factor, yarn.mscale_all_dim)
        if m != 1.0:
            cos, sin = cos * m, sin * m
    cos = cos[:, :, None, :].astype(jnp.float32)
    sin = sin[:, :, None, :].astype(jnp.float32)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x, positions3, sections, theta: float):
    """Qwen2-VL M-RoPE.  x [B,S,H,hd]; positions3 [B,3,S]; sections half-dims
    (t, h, w) summing to hd//2 — each frequency band is driven by its own
    position row (temporal / height / width)."""
    hd = x.shape[-1]
    half = hd // 2
    assert sum(sections) == half, (sections, half)
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    # select the position row per frequency band
    sec_ids = jnp.repeat(jnp.arange(len(sections)),
                         jnp.array(sections), total_repeat_length=half)  # [half]
    pos = jnp.take_along_axis(
        positions3.astype(jnp.float32),                    # [B, 3, S]
        jnp.broadcast_to(sec_ids[None, :, None],
                         (positions3.shape[0], half, positions3.shape[2])).astype(jnp.int32),
        axis=1)                                            # [B, half, S]
    angles = jnp.einsum("bfs,f->bsf", pos, freqs)          # [B, S, half]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------- loss
def cross_entropy(logits, labels, mask=None, vocab_size: int | None = None):
    """Mean next-token CE.  logits [..., Vpad]; labels [...] int32.

    ``vocab_size`` masks padded vocab entries (Vpad >= V)."""
    logits = logits.astype(jnp.float32)
    if vocab_size is not None and logits.shape[-1] > vocab_size:
        pad = logits.shape[-1] - vocab_size
        neg = jnp.full((pad,), -1e9, dtype=jnp.float32)
        logits = logits.at[..., vocab_size:].set(neg)
    logz = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - ll
    if mask is None:
        return jnp.mean(nll)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


# ------------------------------------------------------------------ mlp
def mlp_init(key, d_model: int, d_ff: int, act: str, use_bias: bool,
             dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    if act == "swiglu":
        return {"w_gate": dense_init(ks[0], d_model, d_ff, use_bias, dtype),
                "w_up": dense_init(ks[1], d_model, d_ff, use_bias, dtype),
                "w_down": dense_init(ks[2], d_ff, d_model, use_bias, dtype)}
    return {"w_up": dense_init(ks[0], d_model, d_ff, use_bias, dtype),
            "w_down": dense_init(ks[1], d_ff, d_model, use_bias, dtype)}


def mlp_apply(p, x, act: str):
    if act == "swiglu":
        h = jax.nn.silu(dense(p["w_gate"], x)) * dense(p["w_up"], x)
    else:
        h = activation(act, dense(p["w_up"], x))
    return dense(p["w_down"], h)
