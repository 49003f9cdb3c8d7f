"""Pure-jnp oracle for the flash-attention kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def attention_ref(q, k, v, *, causal: bool = True, window: int = 0,
                  scale: float | None = None):
    """q, k [B, S, H or KV, hd]; v [B, S, KV, hdv] (KV divides H).  fp32
    math; scores scaled by ``scale``, 1/sqrt(hd) by default."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    rep = H // KV
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32))
    s = s / jnp.sqrt(jnp.float32(hd)) if scale is None else s * scale
    qi = jnp.arange(S)[:, None]
    kj = jnp.arange(S)[None, :]
    if causal:
        mask = kj <= qi
        if window:
            mask &= kj > qi - window
    else:
        mask = jnp.ones((S, S), dtype=bool)
    s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32)).astype(q.dtype)


def decode_ref(q, ck, cv, pos, *, window: int = 0):
    """One-token decode oracle, repeat-free grouped einsum over the cache.

    q [B, 1, H, hd]; ck, cv [B, L, KV, hd]; pos scalar int32 (traced).
    Mirrors ``models.attention._gqa_decode_sdpa`` masking: ``window > 0``
    treats the cache as a ring buffer and masks slots by age."""
    B, _, H, hd = q.shape
    L, KV = ck.shape[1], ck.shape[2]
    G = H // KV
    idx = jnp.arange(L)
    if window:
        age = (pos - idx) % window
        mask1d = (pos - age) >= 0
    else:
        mask1d = idx <= pos
    qg = q.reshape(B, 1, KV, G, hd)
    s = jnp.einsum("bqkgd,blkd->bkgql", qg.astype(jnp.float32),
                   ck.astype(jnp.float32)) / jnp.sqrt(jnp.float32(hd))
    s = jnp.where(mask1d[None, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgql,blkd->bqkgd", p, cv.astype(jnp.float32))
    return out.reshape(B, 1, H, hd).astype(q.dtype)
