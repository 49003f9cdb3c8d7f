"""GQA / MQA / MHA attention with full, sliding-window, and cross variants.

Pure-jnp reference math is the oracle; the Pallas kernels in
``repro.kernels.flash_attention`` are routed in through the kernel backend
seam.  ``attention_forward`` / ``attention_decode`` take a ``backend``
argument (default: the model config's ``attn_backend`` field, ``"auto"``)
resolved by ``repro.kernels.backend.resolve_backend`` — ``kernel`` runs the
flash forward (with the flash backward as its VJP for training) and the
streaming decode kernel; ``ref`` keeps the jnp expressions below
bit-for-bit.
Cross-attention (``kv_x`` / ``cross_kv``) always uses the reference path:
its keys come from a different sequence length.

Cache layouts
-------------
full   : {"k": [B, Smax, KV, hd], "v": [B, Smax, KV, hd]}  write at position t
window : {"k": [B, W,    KV, hd], "v": ...}                ring buffer, write at t % W
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as FA
from repro.kernels.backend import kernel_interpret, resolve_backend
from repro.models.common import dense, dense_init, apply_rope, apply_mrope

NEG_INF = -1e9


def attn_init(key, cfg, dtype=jnp.float32, cross: bool = False):
    d, H, KV, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], d, H * hd, cfg.use_bias, dtype),
        "wk": dense_init(ks[1], d, KV * hd, cfg.use_bias, dtype),
        "wv": dense_init(ks[2], d, KV * hd, cfg.use_bias, dtype),
        "wo": dense_init(ks[3], H * hd, d, cfg.use_bias, dtype),
    }


def _split_heads(x, n, hd):
    return x.reshape(x.shape[:-1] + (n, hd))


def _repeat_kv(k, n_rep):
    if n_rep == 1:
        return k
    return jnp.repeat(k, n_rep, axis=2)


def _sdpa(q, k, v, mask):
    """q [B,Sq,H,hd] k/v [B,Sk,H,hd] mask [B,1,Sq,Sk] or broadcastable."""
    hd = q.shape[-1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    scores = scores / jnp.sqrt(jnp.float32(hd))
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)


def _causal_mask(sq, sk, offset=0):
    """query i (global pos offset+i) may see key j<=offset+i."""
    qi = jnp.arange(sq)[:, None] + offset
    kj = jnp.arange(sk)[None, :]
    return (kj <= qi)[None, None]


def _window_mask(sq, sk, window, offset=0):
    qi = jnp.arange(sq)[:, None] + offset
    kj = jnp.arange(sk)[None, :]
    return ((kj <= qi) & (kj > qi - window))[None, None]


def attention_forward(p, x, positions, cfg, *, causal=True, window=0,
                      kv_x=None, use_rope=True, backend=None):
    """Training / prefill / encoder forward.

    kv_x: if given, cross-attention keys/values come from kv_x (no rope).
    backend: kernel backend ("auto" | "kernel" | "ref"); None reads the
    config's ``attn_backend``.  The kernel path feeds the *unrepeated* k/v
    straight to the flash kernel (GQA folds in the BlockSpec index map).
    Returns (out, cache) where cache has the full k/v (for prefill reuse).
    """
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    if backend is None:
        backend = getattr(cfg, "attn_backend", "auto")
    src = x if kv_x is None else kv_x
    q = _split_heads(dense(p["wq"], x), H, hd)
    k = _split_heads(dense(p["wk"], src), KV, hd)
    v = _split_heads(dense(p["wv"], src), KV, hd)
    if use_rope and kv_x is None:
        if cfg.mrope_sections:
            q = apply_mrope(q, positions, cfg.mrope_sections, cfg.rope_theta)
            k = apply_mrope(k, positions, cfg.mrope_sections, cfg.rope_theta)
        else:
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
    if kv_x is None and resolve_backend(backend) == "kernel":
        out = FA.attention_grad(q, k, v, causal=causal,
                                window=window if causal else 0,
                                interpret=kernel_interpret())
    else:
        kr = _repeat_kv(k, H // KV)
        vr = _repeat_kv(v, H // KV)
        sq, sk = q.shape[1], kr.shape[1]
        if kv_x is not None:
            mask = jnp.ones((1, 1, sq, sk), dtype=bool)
        elif not causal:
            mask = jnp.ones((1, 1, sq, sk), dtype=bool)
        elif window:
            mask = _window_mask(sq, sk, window)
        else:
            mask = _causal_mask(sq, sk)
        out = _sdpa(q, kr, vr, mask)
    out = dense(p["wo"], out.reshape(out.shape[:2] + (H * hd,)))
    return out, {"k": k, "v": v}


def init_cache(cfg, batch: int, max_len: int, dtype, window: int = 0):
    KV, hd = cfg.num_kv_heads, cfg.head_dim
    L = window if window else max_len
    return {"k": jnp.zeros((batch, L, KV, hd), dtype=dtype),
            "v": jnp.zeros((batch, L, KV, hd), dtype=dtype)}


def attention_decode(p, x, pos, cache, cfg, *, window=0, cross_kv=None,
                     use_rope=True, backend=None):
    """One-token decode step.  x [B,1,d]; pos scalar int32 (same for batch).

    window > 0 -> ring-buffer cache of that length (sub-quadratic decode).
    cross_kv -> (k, v) precomputed encoder keys/values; cache unused.
    backend: kernel backend seam (None reads the config's ``attn_backend``);
    the kernel path streams the cache through ``flash_decode``.
    Returns (out [B,1,d], new_cache).
    """
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    if backend is None:
        backend = getattr(cfg, "attn_backend", "auto")
    B = x.shape[0]
    q = _split_heads(dense(p["wq"], x), H, hd)
    if cross_kv is not None:
        kr = _repeat_kv(cross_kv["k"], H // KV)
        vr = _repeat_kv(cross_kv["v"], H // KV)
        mask = jnp.ones((1, 1, 1, kr.shape[1]), dtype=bool)
        out = _sdpa(q, kr, vr, mask)
        out = dense(p["wo"], out.reshape(B, 1, H * hd))
        return out, cache

    k = _split_heads(dense(p["wk"], x), KV, hd)
    v = _split_heads(dense(p["wv"], x), KV, hd)
    posb = jnp.broadcast_to(jnp.asarray(pos)[None, None], (B, 1))
    if use_rope:
        if cfg.mrope_sections:
            pos3 = jnp.broadcast_to(jnp.asarray(pos)[None, None, None],
                                    (B, 3, 1))
            q = apply_mrope(q, pos3, cfg.mrope_sections, cfg.rope_theta)
            k = apply_mrope(k, pos3, cfg.mrope_sections, cfg.rope_theta)
        else:
            q = apply_rope(q, posb, cfg.rope_theta)
            k = apply_rope(k, posb, cfg.rope_theta)

    L = cache["k"].shape[1]
    slot = (pos % window) if window else pos
    ck = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                      (0, slot, 0, 0))
    cv = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                      (0, slot, 0, 0))
    if resolve_backend(backend) == "kernel":
        out = FA.decode(q, ck, cv, jnp.asarray(pos, jnp.int32),
                        window=window, interpret=kernel_interpret())
    else:
        idx = jnp.arange(L)
        if window:
            # slot j holds global position p_j with p_j % W == j and
            # p_j <= pos; valid iff pos - p_j < W <=> p_j > pos - W, >= 0.
            age = (pos - idx) % window        # steps since slot was written
            mask1d = (pos - age) >= 0
        else:
            mask1d = idx <= pos
        out = _gqa_decode_sdpa(q, ck, cv, mask1d)
    out = dense(p["wo"], out.reshape(B, 1, H * hd))
    return out, {"k": ck, "v": cv}


def _gqa_decode_sdpa(q, ck, cv, mask1d):
    """Grouped-query decode attention WITHOUT materializing repeated K/V.

    q [B,1,H,hd]; ck/cv [B,L,KV,hd]; mask1d [L].  The repeat-free grouped
    einsum keeps the cache in its stored layout — on TPU this avoids an
    H/KV-fold HBM blow-up."""
    B, _, H, hd = q.shape
    KV = ck.shape[2]
    G = H // KV
    qg = q.reshape(B, 1, KV, G, hd)
    scores = jnp.einsum("bqkgd,blkd->bkgql", qg.astype(jnp.float32),
                        ck.astype(jnp.float32))       # [B,KV,G,1,L]
    scores = scores / jnp.sqrt(jnp.float32(hd))
    scores = jnp.where(mask1d[None, None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgql,blkd->bqkgd", probs.astype(cv.dtype), cv)
    return out.reshape(B, 1, H, hd)
