"""Plain reference of a dense decoder LM (StableLM-2 style): LayerNorm with
bias, multi-head attention with rotary embeddings, SwiGLU MLP, untied LM
head.  Straightforward ``jax.numpy`` in float32, no kernels, no cache, no
batching tricks; it imports nothing of the program.

Departures from the published StableLM-2-1.6B, each stated in the
configuration file's ``reduced``: rotary embeddings cover the whole head
(published: the first 25% of it) and the q/k/v projections carry no bias
(published: a bias each).  The reference computes what the configuration
states, and the program is measured against it.

``init`` makes the weights from a key in the parameter layout the program
takes (one stacked segment of ``L`` identical layers), so the same
function gives the program its weights and the reference its own copy.
``q`` is applied to every matmul operand: the identity for the reference,
a rounding to a lower precision for the control.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _dims(c):
    d, H = c["hidden_size"], c["num_attention_heads"]
    return (d, H, c.get("num_key_value_heads", H), d // H,
            c["intermediate_size"], c["vocab_size"], c["num_hidden_layers"])


def layout(c):
    """{path: (shape, kind)} of the program's parameter tree."""
    d, H, KV, hd, ff, V, L = _dims(c)
    norm = lambda: {"scale": ((L, d), "scale"), "bias": ((L, d), "bias")}
    mat = lambda i, o: {"w": ((L, i, o), "matrix")}
    layer = {"ln1": norm(), "ln2": norm(),
             "mixer": {"wq": mat(d, H * hd), "wk": mat(d, KV * hd),
                       "wv": mat(d, KV * hd), "wo": mat(H * hd, d)},
             "mlp": {"w_gate": mat(d, ff), "w_up": mat(d, ff),
                     "w_down": mat(ff, d)}}
    return {"embed": ((V, d), "embed"),
            "final_norm": {"scale": ((d,), "scale"), "bias": ((d,), "bias")},
            "lm_head": ((d, V), "matrix"),
            "segments": [(layer,)]}


def _is_spec(x):
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[1], str)


def _leaf(key, shape, kind):
    z = jax.random.normal(key, shape, jnp.float32)
    if kind == "scale":
        return 1.0 + 0.1 * z
    if kind == "bias":
        return 0.05 * z
    if kind == "embed":
        return 0.02 * z
    return z / np.sqrt(shape[-2])          # matrix: 1 / sqrt(fan_in)


@functools.partial(jax.jit, static_argnums=(0, 2))
def _init(c_items, key, dtype):
    c = dict(c_items)
    specs, tree = jax.tree.flatten(layout(c), is_leaf=_is_spec)
    leaves = [_leaf(jax.random.fold_in(key, i), s, k).astype(dtype)
              for i, (s, k) in enumerate(specs)]
    return jax.tree.unflatten(tree, leaves)


def init(c, key, dtype=jnp.float32):
    """The weights, made on the device in one jitted call."""
    return _init(tuple(sorted((k, v) for k, v in c.items()
                              if isinstance(v, (int, float, str)))),
                 key, jnp.dtype(dtype))


def _layernorm(x, p, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _rope(x, theta):
    """x [B, S, H, hd]; rotary over the whole head, halves rotated."""
    S, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def identity(x):
    return x


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def forward(c, params, tokens, q=identity, remat=False):
    """Logits [B, S, V] in float32 for tokens [B, S]; ``remat`` recomputes
    each layer in the backward pass instead of keeping its scores."""
    d, H, KV, hd, ff, V, L = _dims(c)
    eps, theta = c["layer_norm_eps"], c["rope_theta"]
    p = params        # cast to float32 layer by layer, where each is used
    B, S = tokens.shape
    mm = lambda x, w: q(x) @ q(w)
    causal = jnp.tril(jnp.ones((S, S), bool))

    def layer(x, lp):
        lp = _f32(lp)
        h = _layernorm(x, lp["ln1"], eps)
        qh = _rope(mm(h, lp["mixer"]["wq"]["w"]).reshape(B, S, H, hd), theta)
        kh = _rope(mm(h, lp["mixer"]["wk"]["w"]).reshape(B, S, KV, hd), theta)
        vh = mm(h, lp["mixer"]["wv"]["w"]).reshape(B, S, KV, hd)
        kh = jnp.repeat(kh, H // KV, axis=2)
        vh = jnp.repeat(vh, H // KV, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q(qh), q(kh)) / np.sqrt(hd)
        a = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", q(a), q(vh)).reshape(B, S, H * hd)
        x = x + mm(o, lp["mixer"]["wo"]["w"])
        h = _layernorm(x, lp["ln2"], eps)
        m = jax.nn.silu(mm(h, lp["mlp"]["w_gate"]["w"])) \
            * mm(h, lp["mlp"]["w_up"]["w"])
        return x + mm(m, lp["mlp"]["w_down"]["w"]), None

    x = p["embed"][tokens].astype(jnp.float32)
    x, _ = jax.lax.scan(jax.checkpoint(layer) if remat else layer, x,
                        p["segments"][0][0])
    x = _layernorm(x, _f32(p["final_norm"]), eps)
    return mm(x, p["lm_head"].astype(jnp.float32))


def loss(c, params, batch, q=identity):
    """Mean next-token cross-entropy over every position."""
    logits = forward(c, params, batch["tokens"], q, remat=True)
    lz = jax.nn.logsumexp(logits, -1)
    ll = jnp.take_along_axis(logits, batch["labels"][..., None], -1)[..., 0]
    return jnp.mean(lz - ll)
