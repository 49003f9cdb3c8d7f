"""Plain reference of DeepSeek-V2 (arXiv:2405.04434; hf
deepseek-ai/DeepSeek-V2-Lite) on one chip's share of experts.
Straightforward ``jax.numpy`` in float32, no kernels, no cache, no
batching tricks; it imports nothing of the program.

Each layer: RMSNorm, multi-head latent attention, RMSNorm, then a SwiGLU
MLP (the first ``first_k_dense_replace`` layers) or a mixture of experts.

Attention: q = x W_q split per head into 128 "nope" and 64 "rope" columns;
the latent c = RMSNorm(x W_dkv) (the published ``kv_a_layernorm``), k_nope
= c W_uk and v = c W_uv per head, and one rope key x W_krope shared by the
heads.  The rope columns are rotated with YaRN frequencies (``rope_scaling``:
interpolated by ``factor`` below the correction range of ``beta_fast`` /
``beta_slow`` rotations at ``original_max_position_embeddings``, ramped
linearly between); cos and sin carry mscale(mscale) / mscale(mscale_all_dim),
which is 1 here.  Scores are q.k over 192 dims times mscale(mscale_all_dim)^2
/ sqrt(192), causal softmax, then v.  Rotary pairs are taken in halves; the
published checkpoints pair interleaved columns, a fixed permutation of the
rope columns of W_q and W_krope (stated in the configuration file).

Experts: softmax over all ``expert_share.of`` router outputs in float32,
greedy top-k, gates the raw scores (``norm_topk_prob`` false) times
``routed_scaling_factor``.  This chip holds ``n_routed_experts`` of them,
``expert_share.first`` onwards; each is computed here on every token and
masked by its gate, so the layer gives the held experts' part of the
result.  Plus the shared experts, one SwiGLU of ``n_shared_experts`` x
``moe_intermediate_size``.  Balance loss per sequence:
alpha * sum_i f_i P_i with f_i = E / (K S) x (selections of i) and P_i the
mean score of i, added for each MoE layer.

``init`` makes the weights from a key in the parameter layout the program
takes (a leading dense layer, then one stacked segment of identical MoE
layers), so the same function gives the program its weights and the
reference its own copy.  ``q`` is applied to every matmul operand: the
identity for the reference, a rounding to a lower precision for the control.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


def _dims(c):
    return dict(
        d=c["hidden_size"], H=c["num_attention_heads"], r=c["kv_lora_rank"],
        nd=c["qk_nope_head_dim"], rd=c["qk_rope_head_dim"],
        vd=c["v_head_dim"], ff=c["intermediate_size"],
        eff=c["moe_intermediate_size"], E=c["n_routed_experts"],
        shared=c["n_shared_experts"], V=c["vocab_size"],
        L=c["num_hidden_layers"], dense=c["first_k_dense_replace"],
        router=c["expert_share"]["of"])


def layout(c):
    """{path: (shape, kind)} of the program's parameter tree."""
    k = _dims(c)
    d, H, r = k["d"], k["H"], k["r"]

    def layer(L, moe):
        lead = (L,) if L else ()
        mat = lambda *s: {"w": (lead + s, "matrix")}
        scale = lambda n: {"scale": (lead + (n,), "scale")}
        p = {"ln1": scale(d), "ln2": scale(d),
             "mixer": {"w_q": mat(d, H * (k["nd"] + k["rd"])),
                       "w_dkv": mat(d, r), "w_krope": mat(d, k["rd"]),
                       "kv_norm": scale(r), "w_uk": mat(r, H * k["nd"]),
                       "w_uv": mat(r, H * k["vd"]),
                       "w_o": mat(H * k["vd"], d)}}
        if moe:
            E, f, sf = k["E"], k["eff"], k["shared"] * k["eff"]
            p["moe"] = {"router": mat(d, k["router"]),
                        "w_gate": (lead + (E, d, f), "matrix"),
                        "w_up": (lead + (E, d, f), "matrix"),
                        "w_down": (lead + (E, f, d), "matrix"),
                        "shared": {"w_gate": mat(d, sf), "w_up": mat(d, sf),
                                   "w_down": mat(sf, d)}}
        else:
            p["mlp"] = {"w_gate": mat(d, k["ff"]), "w_up": mat(d, k["ff"]),
                        "w_down": mat(k["ff"], d)}
        return p

    segs = [layer(0, False) for _ in range(k["dense"])]
    return {"embed": ((k["V"], d), "embed"),
            "final_norm": {"scale": ((d,), "scale")},
            "lm_head": ((d, k["V"]), "matrix"),
            "segments": segs + [(layer(k["L"] - k["dense"], True),)]}


def _is_spec(x):
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[1], str)


def _leaf(key, shape, kind):
    z = jax.random.normal(key, shape, jnp.float32)
    if kind == "scale":
        return 1.0 + 0.1 * z
    if kind == "embed":
        return 0.02 * z
    return z / np.sqrt(shape[-2])          # matrix: 1 / sqrt(fan_in)


@functools.partial(jax.jit, static_argnums=(0, 2))
def _init(dims, key, dtype):
    c = dict(dims)
    c["expert_share"] = {"of": c.pop("router")}
    specs, tree = jax.tree.flatten(layout(c), is_leaf=_is_spec)
    leaves = [_leaf(jax.random.fold_in(key, i), s, k).astype(dtype)
              for i, (s, k) in enumerate(specs)]
    return jax.tree.unflatten(tree, leaves)


_KEYS = ("hidden_size", "num_attention_heads", "kv_lora_rank",
         "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
         "intermediate_size", "moe_intermediate_size", "n_routed_experts",
         "n_shared_experts", "vocab_size", "num_hidden_layers",
         "first_k_dense_replace")


def init(c, key, dtype=jnp.float32):
    """The weights, made on the device in one jitted call."""
    dims = tuple(sorted([(k, c[k]) for k in _KEYS]
                        + [("router", c["expert_share"]["of"])]))
    return _init(dims, key, jnp.dtype(dtype))


def identity(x):
    return x


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _yarn_mscale(factor, m):
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def _yarn(c):
    """(inverse frequencies [rd/2], cos/sin factor, softmax scale)."""
    rd, base = c["qk_rope_head_dim"], c["rope_theta"]
    ys = c["rope_scaling"]
    fac, orig = ys["factor"], ys["original_max_position_embeddings"]
    extra = 1.0 / base ** (np.arange(0, rd, 2, dtype=np.float64) / rd)
    inter = extra / fac

    def corr_dim(rot):
        return rd * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))
    low = max(math.floor(corr_dim(ys["beta_fast"])), 0)
    high = min(math.ceil(corr_dim(ys["beta_slow"])), rd - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(rd // 2) - low) / (high - low), 0, 1)
    keep = 1.0 - ramp
    inv = inter * (1 - keep) + extra * keep
    m_all = _yarn_mscale(fac, ys["mscale_all_dim"])
    cos_m = _yarn_mscale(fac, ys["mscale"]) / m_all
    scale = m_all ** 2 / math.sqrt(c["qk_nope_head_dim"] + rd)
    return jnp.asarray(inv, jnp.float32), cos_m, scale


def _rope(x, inv, m):
    """x [B, S, H, rd]; pairs (i, i + rd/2) rotated by position * inv."""
    S, half = x.shape[1], x.shape[-1] // 2
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv
    cos = (jnp.cos(ang) * m)[None, :, None]
    sin = (jnp.sin(ang) * m)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(c, lp, h, q):
    k = _dims(c)
    B, S, _ = h.shape
    H, nd, rd, vd = k["H"], k["nd"], k["rd"], k["vd"]
    inv, cos_m, scale = _yarn(c)
    mm = lambda x, w: q(x) @ q(w)
    qh = mm(h, lp["w_q"]["w"]).reshape(B, S, H, nd + rd)
    q_nope, q_rope = qh[..., :nd], _rope(qh[..., nd:], inv, cos_m)
    lat = _rmsnorm(mm(h, lp["w_dkv"]["w"]), lp["kv_norm"]["scale"],
                   c["rms_norm_eps"])
    k_nope = mm(lat, lp["w_uk"]["w"]).reshape(B, S, H, nd)
    v = mm(lat, lp["w_uv"]["w"]).reshape(B, S, H, vd)
    k_rope = _rope(mm(h, lp["w_krope"]["w"])[:, :, None], inv, cos_m)
    s = (jnp.einsum("bqhd,bkhd->bhqk", q(q_nope), q(k_nope))
         + jnp.einsum("bqhd,bkd->bhqk", q(q_rope), q(k_rope[:, :, 0])))
    causal = jnp.tril(jnp.ones((S, S), bool))
    a = jax.nn.softmax(jnp.where(causal, s * scale, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", q(a), q(v)).reshape(B, S, H * vd)
    return mm(o, lp["w_o"]["w"])


def _swiglu(x, w_gate, w_up, w_down, q):
    mm = lambda a, w: q(a) @ q(w)
    return mm(jax.nn.silu(mm(x, w_gate)) * mm(x, w_up), w_down)


def _moe(c, mp, h, q):
    """(held experts' part + shared experts [B, S, d], balance loss)."""
    k = _dims(c)
    B, S, d = h.shape
    E, K, first = k["router"], c["num_experts_per_tok"], \
        c["expert_share"]["first"]
    scores = jax.nn.softmax(q(h) @ q(mp["router"]["w"]), axis=-1)  # [B,S,E]
    top, ids = jax.lax.top_k(scores, K)
    if c["norm_topk_prob"]:
        top = top / jnp.sum(top, -1, keepdims=True)
    top = top * c["routed_scaling_factor"]
    out = _swiglu(h, mp["shared"]["w_gate"]["w"], mp["shared"]["w_up"]["w"],
                  mp["shared"]["w_down"]["w"], q)
    for e in range(k["E"]):
        g = jnp.sum(jnp.where(ids == first + e, top, 0.0), -1)      # [B, S]
        out = out + g[..., None] * _swiglu(h, mp["w_gate"][e], mp["w_up"][e],
                                           mp["w_down"][e], q)
    counts = jnp.sum(jax.nn.one_hot(ids, E, dtype=jnp.float32), (1, 2))
    f = counts * E / (K * S)                                        # [B, E]
    aux = c["aux_loss_alpha"] * jnp.mean(jnp.sum(f * scores.mean(1), -1))
    return out, aux


def forward(c, params, tokens, q=identity, remat=False):
    """(logits [B, S, V] in float32, summed balance loss) for tokens
    [B, S]; ``remat`` recomputes each layer in the backward pass instead of
    keeping its scores."""
    eps = c["rms_norm_eps"]
    p = params        # cast to float32 layer by layer, where each is used

    def layer(x, lp, moe):
        lp = _f32(lp)
        x = x + _attention(c, lp["mixer"],
                           _rmsnorm(x, lp["ln1"]["scale"], eps), q)
        h = _rmsnorm(x, lp["ln2"]["scale"], eps)
        if moe:
            out, aux = _moe(c, lp["moe"], h, q)
        else:
            out, aux = _swiglu(h, lp["mlp"]["w_gate"]["w"],
                               lp["mlp"]["w_up"]["w"],
                               lp["mlp"]["w_down"]["w"], q), 0.0
        return x + out, aux

    wrap = jax.checkpoint if remat else (lambda f, **kw: f)
    x = p["embed"][tokens].astype(jnp.float32)
    aux = jnp.float32(0.0)
    for lp in p["segments"][:-1]:
        x, a = wrap(functools.partial(layer, moe=False))(x, lp)
        aux = aux + a

    def body(carry, lp):
        x, a = wrap(functools.partial(layer, moe=True))(carry[0], lp)
        return (x, carry[1] + a), None
    (x, aux), _ = jax.lax.scan(body, (x, aux), p["segments"][-1][0])
    x = _rmsnorm(x, p["final_norm"]["scale"].astype(jnp.float32), eps)
    return q(x) @ q(p["lm_head"].astype(jnp.float32)), aux


def loss(c, params, batch, q=identity):
    """Mean next-token cross-entropy over every position, plus the balance
    loss of every MoE layer."""
    logits, aux = forward(c, params, batch["tokens"], q, remat=True)
    lz = jax.nn.logsumexp(logits, -1)
    ll = jnp.take_along_axis(logits, batch["labels"][..., None], -1)[..., 0]
    return jnp.mean(lz - ll) + aux
