"""Unified decoder-only LM covering dense / moe / vlm / hybrid / ssm families.

Layer stacks are grouped into homogeneous segments and executed with
``jax.lax.scan`` so that compile time and HLO size stay bounded for the
61-layer / trillion-parameter configs.  Heterogeneous block patterns
(recurrentgemma's rglru-rglru-local) scan over *groups* of the pattern.

Everything is eval_shape friendly: params can be abstract-initialised
with ``jax.eval_shape`` and lowered against ShapeDtypeStructs only.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import mla as mla_mod
from repro.models import rglru as rglru_mod
from repro.models import rwkv6 as rwkv_mod
from repro.models.common import (cross_entropy, dense, mlp_apply, mlp_init,
                                 norm_apply, norm_init)
from repro.models.moe import moe_apply, moe_init


# --------------------------------------------------------------- segment plan
def plan_segments(cfg: ModelConfig) -> List[Tuple[str, Any]]:
    """Returns [("plain", sig) | ("scan", (sig, ...), n_groups), ...] where a
    sig is (kind, use_moe)."""
    sigs = []
    for i, kind in enumerate(cfg.layer_kinds):
        use_moe = bool(cfg.moe and i >= cfg.first_k_dense
                       and kind in ("attn", "local"))
        sigs.append((kind, use_moe))
    segments: List[Tuple[str, Any]] = []
    i = 0
    # plain prefix (dense-before-MoE layers)
    while i < len(sigs) and cfg.moe and i < cfg.first_k_dense:
        segments.append(("plain", sigs[i]))
        i += 1
    pat_len = len(cfg.block_pattern)
    remaining = sigs[i:]
    pattern = tuple(remaining[:pat_len]) if remaining else ()
    n_groups = 0
    while (n_groups + 1) * pat_len <= len(remaining) and all(
            remaining[n_groups * pat_len + j] == pattern[j]
            for j in range(pat_len)):
        n_groups += 1
    if n_groups:
        segments.append(("scan", pattern, n_groups))
        i += n_groups * pat_len
    for sig in sigs[i:]:
        segments.append(("plain", sig))
    return segments


# ------------------------------------------------------------------ layer ops
def _layer_init(key, cfg: ModelConfig, sig, dtype):
    kind, use_moe = sig
    ks = jax.random.split(key, 4)
    p: Dict[str, Any] = {"ln1": norm_init(cfg.norm, cfg.d_model),
                         "ln2": norm_init(cfg.norm, cfg.d_model)}
    if kind in ("attn", "local"):
        if cfg.attn_type == "mla":
            p["mixer"] = mla_mod.mla_init(ks[0], cfg, dtype)
        else:
            p["mixer"] = attn.attn_init(ks[0], cfg, dtype)
    elif kind == "rglru":
        p["mixer"] = rglru_mod.rglru_init(ks[0], cfg, dtype)
    elif kind == "rwkv":
        p["mixer"] = rwkv_mod.rwkv_init(ks[0], cfg, dtype)
    else:
        raise ValueError(kind)
    if kind == "rwkv":
        pass  # channel mix lives inside rwkv params
    elif use_moe:
        p["moe"] = moe_init(ks[1], cfg, dtype)
    else:
        p["mlp"] = mlp_init(ks[1], cfg.d_model, cfg.d_ff, cfg.act,
                            cfg.use_bias, dtype)
    return p


def _no_moe():
    return {"aux": jnp.float32(0.0), "dropped": jnp.float32(0.0),
            "held_load": jnp.float32(0.0)}


def _add_moe(a, b):
    """Two layers' MoE stats: losses and drops add, the load is the most."""
    return {"aux": a["aux"] + b["aux"], "dropped": a["dropped"] + b["dropped"],
            "held_load": jnp.maximum(a["held_load"], b["held_load"])}


def _layer_forward(p, cfg: ModelConfig, sig, x, positions, state=None,
                   window_override=0):
    """Full-sequence forward for one layer.  Returns (x, moe_stats,
    new_state), the stats of ``moe_apply`` (zeros without a MoE).  state is
    only used/returned for stateful kinds (cache build in prefill)."""
    kind, use_moe = sig
    stats = _no_moe()
    with jax.named_scope("norm"):
        h = norm_apply(cfg.norm, p["ln1"], x, cfg.norm_eps)
    new_state = None
    if kind in ("attn", "local"):
        with jax.named_scope("attention"):
            if cfg.attn_type == "mla":
                out, new_state = mla_mod.mla_forward(p["mixer"], h,
                                                     positions, cfg)
            else:
                window = cfg.window if kind == "local" else window_override
                out, new_state = attn.attention_forward(
                    p["mixer"], h, positions, cfg, causal=True,
                    window=window)
    elif kind == "rglru":
        out, (h_last, conv_buf) = rglru_mod.rglru_forward(p["mixer"], h)
        new_state = {"h": h_last, "conv": conv_buf}
    elif kind == "rwkv":
        out, new_state_tm = rwkv_mod.time_mix_forward(p["mixer"], h, cfg)
        x = x + out
        h2 = norm_apply(cfg.norm, p["ln2"], x, cfg.norm_eps)
        out2, shift_cm = rwkv_mod.channel_mix_forward(p["mixer"], h2, cfg)
        new_state = {"S": new_state_tm["S"], "shift_tm": new_state_tm["shift"],
                     "shift_cm": shift_cm}
        return x + out2, stats, new_state
    x = x + out
    with jax.named_scope("norm"):
        h = norm_apply(cfg.norm, p["ln2"], x, cfg.norm_eps)
    with jax.named_scope("mlp"):
        if use_moe:
            out, stats = moe_apply(p["moe"], h, cfg)
        else:
            out = mlp_apply(p["mlp"], h, cfg.act)
    return x + out, stats, new_state


def _layer_decode(p, cfg: ModelConfig, sig, x, pos, cache, window_override=0,
                  tp_axis=None):
    """One-token decode for one layer.  Returns (x, new_cache).

    tp_axis: when set (tensor-parallel decode under shard_map), the mixer
    and MLP outputs are row-parallel partial products — sum them across
    the tensor axis with ``tensor_reduce`` before each residual add.
    Only plain GQA attention layers support this (the serving engine
    gates admission accordingly)."""
    kind, use_moe = sig
    if tp_axis is not None and (use_moe or kind not in ("attn", "local")
                                or cfg.attn_type == "mla"):
        raise ValueError(
            f"tensor-parallel decode supports dense GQA layers only "
            f"(got kind={kind}, moe={use_moe}, attn_type={cfg.attn_type})")
    if tp_axis is not None:
        from repro.parallel.staged import tensor_copy, tensor_reduce
        t_copy, t_reduce = tensor_copy(tp_axis), tensor_reduce(tp_axis)
    else:
        t_copy = t_reduce = lambda y: y
    if kind == "rwkv":
        return rwkv_mod.rwkv_block_decode(
            p["mixer"], p["mixer"], p["ln1"], p["ln2"], cfg, x, cache)
    with jax.named_scope("norm"):
        h = norm_apply(cfg.norm, p["ln1"], x, cfg.norm_eps)
    if kind in ("attn", "local"):
        with jax.named_scope("attention"):
            if cfg.attn_type == "mla":
                out, new_cache = mla_mod.mla_decode(p["mixer"], h, pos,
                                                    cache, cfg)
            else:
                window = cfg.window if kind == "local" else window_override
                out, new_cache = attn.attention_decode(
                    p["mixer"], t_copy(h), pos, cache, cfg, window=window)
                out = t_reduce(out)
    elif kind == "rglru":
        out, new_cache = rglru_mod.rglru_decode(p["mixer"], h, cache)
    else:
        raise ValueError(kind)
    x = x + out
    with jax.named_scope("norm"):
        h = norm_apply(cfg.norm, p["ln2"], x, cfg.norm_eps)
    with jax.named_scope("mlp"):
        if use_moe:
            out, _ = moe_apply(p["moe"], h, cfg)
        else:
            out = t_reduce(mlp_apply(p["mlp"], t_copy(h), cfg.act))
    return x + out, new_cache


def _layer_cache(cfg: ModelConfig, sig, batch, max_len, dtype,
                 window_override=0):
    kind, _ = sig
    if kind in ("attn", "local"):
        if cfg.attn_type == "mla":
            return mla_mod.mla_init_cache(cfg, batch, max_len, dtype)
        window = cfg.window if kind == "local" else window_override
        return attn.init_cache(cfg, batch, max_len, dtype, window=window)
    if kind == "rglru":
        return rglru_mod.rglru_init_state(cfg, batch, dtype)
    if kind == "rwkv":
        return rwkv_mod.rwkv_init_state(cfg, batch, dtype)
    raise ValueError(kind)


# ----------------------------------------------------------------- model init
def init_params(key, cfg: ModelConfig, dtype=jnp.float32,
                vocab_pad_multiple: int = 1):
    vpad = cfg.padded_vocab(vocab_pad_multiple)
    segs = plan_segments(cfg)
    keys = jax.random.split(key, len(segs) + 2)
    params: Dict[str, Any] = {
        "embed": (jax.random.normal(keys[0], (vpad, cfg.d_model))
                  * 0.02).astype(dtype),
        "final_norm": norm_init(cfg.norm, cfg.d_model),
        "segments": [],
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = (jax.random.normal(keys[1], (cfg.d_model, vpad))
                             / np.sqrt(cfg.d_model)).astype(dtype)
    for si, seg in enumerate(segs):
        k = keys[2 + si]
        if seg[0] == "plain":
            params["segments"].append(_layer_init(k, cfg, seg[1], dtype))
        else:
            _, pattern, n_groups = seg

            def group_init(gk, _pattern=pattern):
                gks = jax.random.split(gk, len(_pattern))
                return tuple(_layer_init(gks[j], cfg, _pattern[j], dtype)
                             for j in range(len(_pattern)))
            params["segments"].append(
                jax.vmap(group_init)(jax.random.split(k, n_groups)))
    return params


# ------------------------------------------------------------------- forward
def forward(params, cfg: ModelConfig, tokens, positions=None,
            vision_embeds=None, compute_dtype=jnp.bfloat16,
            return_cache: bool = False, cache_len: int = 0,
            remat: bool = False, unroll: bool = False,
            window_override: int = 0):
    """Full-sequence forward.  Returns (logits, moe_stats, caches|None):
    the MoE layers' balance loss and drops summed (``aux``, ``dropped``)
    and their most loaded held expert (``held_load``).

    tokens [B, S] int32.  positions: [B, S] (or [B, 3, S] for M-RoPE).
    vision_embeds [B, P, d]: merged into the leading P token slots (vlm stub).
    window_override: sliding-window mask for plain attention layers — the
    prefill-side twin of ``decode_step``'s ring-buffer override, so a
    windowed serve's batched prefill attends exactly what its decode would.
    """
    B, S = tokens.shape
    segs = plan_segments(cfg)
    with jax.named_scope("embed"):
        x = params["embed"].astype(compute_dtype)[tokens]
        if vision_embeds is not None:
            x = jax.lax.dynamic_update_slice(
                x, vision_embeds.astype(compute_dtype), (0, 0, 0))
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        if cfg.mrope_sections:
            positions = jnp.broadcast_to(positions[:, None], (B, 3, S))
    moe_total = _no_moe()
    caches: List[Any] = []

    seg_i = 0
    for seg in segs:
        p_seg = params["segments"][seg_i]
        seg_i += 1
        if seg[0] == "plain":
            x, stats, st = _layer_forward(p_seg, cfg, seg[1], x, positions,
                                          window_override=window_override)
            moe_total = _add_moe(moe_total, stats)
            if return_cache:
                caches.append(st)
        else:
            _, pattern, n_groups = seg

            def body(carry, g_params, _pattern=pattern):
                xc, moec = carry
                sts = []
                for j, sig in enumerate(_pattern):
                    xc, stats_j, st_j = _layer_forward(
                        g_params[j], cfg, sig, xc, positions,
                        window_override=window_override)
                    moec = _add_moe(moec, stats_j)
                    sts.append(st_j)
                return (xc, moec), tuple(sts)

            if remat and not return_cache:
                body = jax.checkpoint(body)   # per-layer-group activation remat
            if unroll:
                # analysis-only path: XLA cost_analysis counts while-loop
                # bodies once, so a roofline count unrolls the stack
                seg_states_l = []
                carry = (x, moe_total)
                for gi in range(n_groups):
                    g_params = jax.tree.map(lambda a, _g=gi: a[_g], p_seg)
                    carry, sts = body(carry, g_params)
                    seg_states_l.append(sts)
                (x, moe_total) = carry
                seg_states = jax.tree.map(
                    lambda *xs: jnp.stack(xs), *seg_states_l)
            else:
                (x, moe_total), seg_states = jax.lax.scan(
                    body, (x, moe_total), p_seg)
            if return_cache:
                caches.append(seg_states)
    logits = _head(params, cfg, x, compute_dtype)
    return logits, moe_total, (caches if return_cache else None)


def _head(params, cfg: ModelConfig, x, compute_dtype):
    """Final norm and the (tied or untied) vocabulary projection."""
    with jax.named_scope("norm"):
        x = norm_apply(cfg.norm, params["final_norm"], x, cfg.norm_eps)
    with jax.named_scope("head"):
        if cfg.tie_embeddings:
            return x @ params["embed"].astype(compute_dtype).T
        return dense({"w": params["lm_head"]}, x)


def loss_fn(params, cfg: ModelConfig, batch, compute_dtype=jnp.bfloat16,
            remat: bool = False, unroll: bool = False):
    """Next-token CE + MoE aux.  batch: {tokens, labels[, mask, positions,
    vision_embeds]}.  Metrics: ``ce``, ``aux``, and for a MoE
    ``moe_dropped`` (assignments left uncomputed) and ``moe_held_load``
    (the most tokens a held expert got over the balanced T*K/E)."""
    logits, moe, _ = forward(
        params, cfg, batch["tokens"], positions=batch.get("positions"),
        vision_embeds=batch.get("vision_embeds"), compute_dtype=compute_dtype,
        remat=remat, unroll=unroll)
    with jax.named_scope("loss"):
        ce = cross_entropy(logits, batch["labels"], batch.get("mask"),
                           vocab_size=cfg.vocab_size)
    metrics = {"ce": ce, "aux": moe["aux"]}
    if cfg.moe:
        metrics.update(moe_dropped=moe["dropped"],
                       moe_held_load=moe["held_load"])
    return ce + moe["aux"], metrics


# --------------------------------------------------------------------- decode
def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16, window_override: int = 0):
    """Cache pytree mirroring the segment plan.  For scan segments the
    per-layer caches are stacked on a leading group axis."""
    segs = plan_segments(cfg)
    caches: List[Any] = []
    for seg in segs:
        if seg[0] == "plain":
            caches.append(_layer_cache(cfg, seg[1], batch, max_len, dtype,
                                       window_override))
        else:
            _, pattern, n_groups = seg
            group = tuple(_layer_cache(cfg, sig, batch, max_len, dtype,
                                       window_override) for sig in pattern)
            caches.append(jax.tree.map(
                lambda a: jnp.broadcast_to(a[None], (n_groups,) + a.shape),
                group))
    return caches


def decode_step(params, cfg: ModelConfig, caches, token, pos,
                compute_dtype=jnp.bfloat16, window_override: int = 0,
                unroll: bool = False, tp_axis: Optional[str] = None):
    """One decode step.  token [B, 1] int32; pos scalar int32 (position of
    this token).  Returns (logits [B, 1, Vpad], new_caches).

    tp_axis: tensor-parallel decode (serving).  Inside a ``shard_map``
    over mesh axis ``tp_axis`` with head-sharded attention weights and
    column/row-sharded MLP weights, each rank computes its head/ff shard
    and the two row-parallel products (wo, w_down) are combined with
    ``tensor_reduce`` before the residual adds — Megatron's f/g pair from
    ``repro.parallel.staged``, reused for inference."""
    segs = plan_segments(cfg)
    with jax.named_scope("embed"):
        x = params["embed"].astype(compute_dtype)[token]
    new_caches: List[Any] = []
    for seg, p_seg, c_seg in zip(segs, params["segments"], caches):
        if seg[0] == "plain":
            x, nc = _layer_decode(p_seg, cfg, seg[1], x, pos, c_seg,
                                  window_override, tp_axis)
            new_caches.append(nc)
        else:
            _, pattern, n_groups = seg

            def body(xc, inp, _pattern=pattern):
                g_params, g_cache = inp
                ncs = []
                for j, sig in enumerate(_pattern):
                    xc, nc_j = _layer_decode(g_params[j], cfg, sig, xc, pos,
                                             g_cache[j], window_override,
                                             tp_axis)
                    ncs.append(nc_j)
                return xc, tuple(ncs)

            if unroll:
                caches_l = []
                for gi in range(n_groups):
                    inp = jax.tree.map(lambda a, _g=gi: a[_g],
                                       (p_seg, c_seg))
                    x, ncs = body(x, inp)
                    caches_l.append(ncs)
                seg_caches = jax.tree.map(lambda *xs: jnp.stack(xs),
                                          *caches_l)
            else:
                x, seg_caches = jax.lax.scan(body, x, (p_seg, c_seg))
            new_caches.append(seg_caches)
    return _head(params, cfg, x, compute_dtype), new_caches


def prefill(params, cfg: ModelConfig, tokens, positions=None,
            vision_embeds=None, compute_dtype=jnp.bfloat16,
            unroll: bool = False, window_override: int = 0):
    """Prefill: forward over the prompt, returning last-token logits and the
    populated caches (full-length attention caches / final recurrent states)."""
    logits, _, caches = forward(params, cfg, tokens, positions=positions,
                                vision_embeds=vision_embeds,
                                compute_dtype=compute_dtype,
                                return_cache=True, unroll=unroll,
                                window_override=window_override)
    return logits[:, -1:], caches


def _state_to_cache(cfg: ModelConfig, sig, st, max_len: int, dtype,
                    window_override: int = 0):
    """Convert one layer's prefill state into its ``init_cache`` decode
    layout.  Leaves may carry leading stacked dims (scan groups) — the
    sequence axis is located from the *end* per kind, so the same rule
    maps plain and group-stacked states."""
    kind, _ = sig
    if kind in ("attn", "local"):
        if cfg.attn_type == "mla":
            seq_from_end, window = 2, 0          # [.., B, S, r]
        else:
            seq_from_end = 3                     # [.., B, S, KV, hd]
            window = cfg.window if kind == "local" else window_override
        L = window if window else max_len

        def fill(a):
            ax = a.ndim - seq_from_end
            S = a.shape[ax]
            if not window and S > max_len:
                raise ValueError(f"prompt length {S} > max_len {max_len}")
            # position t lives at slot t (full) / t % window (ring buffer);
            # only the last `window` positions survive in a ring cache
            start = max(0, S - window) if window else 0
            ts = np.arange(start, S)
            slots = ts % window if window else ts
            am = jnp.moveaxis(a.astype(dtype), ax, 0)
            om = jnp.zeros((L,) + am.shape[1:], dtype=dtype)
            om = om.at[slots].set(am[ts])
            return jnp.moveaxis(om, 0, ax)

        return jax.tree.map(fill, st)
    # recurrent kinds (rglru / rwkv): the final forward state *is* the
    # decode cache — align each leaf's dtype with the init_cache template
    # (e.g. rwkv keeps its S matrix in float32 regardless of cache dtype)
    tmpl = _layer_cache(cfg, sig, 1, max_len, dtype, window_override)
    return jax.tree.map(lambda t, s: s.astype(t.dtype), tmpl, st)


def cache_from_prefill(cfg: ModelConfig, fwd_caches, max_len: int,
                       dtype=jnp.bfloat16, window_override: int = 0):
    """Cache-page plumbing for the serving plane: convert the states of
    ``forward(..., return_cache=True)`` / ``prefill`` into the decode-cache
    pytree ``init_cache`` lays out (attention k/v scattered to their
    full-length or ring-buffer slots, recurrent states passed through), so
    a prompt is consumed by ONE batched forward pass instead of a
    token-by-token warm-up loop."""
    segs = plan_segments(cfg)
    out: List[Any] = []
    for seg, st in zip(segs, fwd_caches):
        if seg[0] == "plain":
            out.append(_state_to_cache(cfg, seg[1], st, max_len, dtype,
                                       window_override))
        else:
            _, pattern, _n = seg
            out.append(tuple(
                _state_to_cache(cfg, pattern[j], st[j], max_len, dtype,
                                window_override)
                for j in range(len(pattern))))
    return out
