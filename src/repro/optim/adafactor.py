"""Adafactor (factored second moments) — the memory-frugal optimizer that
makes trillion-parameter optimizer state representable
(state is O(rows + cols) per matrix instead of O(rows * cols))."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Adafactor:
    decay: float = 0.8
    eps: float = 1e-30
    clip_threshold: float = 1.0

    def init(self, params):
        def one(p):
            if p.ndim >= 2:
                return {"vr": jnp.zeros(p.shape[:-1], jnp.float32),
                        "vc": jnp.zeros(p.shape[:-2] + p.shape[-1:],
                                        jnp.float32)}
            return {"v": jnp.zeros_like(p, jnp.float32)}
        return {"f": jax.tree.map(one, params), "t": jnp.zeros((), jnp.int32)}

    def step(self, params, grads, state, lr):
        t = state["t"] + 1
        beta = 1.0 - (t.astype(jnp.float32) + 1.0) ** (-self.decay)

        def one(p, g, st):
            g32 = g.astype(jnp.float32)
            g2 = jnp.square(g32) + self.eps
            if p.ndim >= 2:
                vr = beta * st["vr"] + (1 - beta) * g2.mean(-1)
                vc = beta * st["vc"] + (1 - beta) * g2.mean(-2)
                denom = jnp.maximum(vr.mean(-1, keepdims=True), self.eps)
                prec = (vr[..., None] / denom[..., None]) * vc[..., None, :]
                u = g32 * jax.lax.rsqrt(prec + self.eps)
                new_st = {"vr": vr, "vc": vc}
            else:
                v = beta * st["v"] + (1 - beta) * g2
                u = g32 * jax.lax.rsqrt(v + self.eps)
                new_st = {"v": v}
            # update clipping (RMS)
            rms = jnp.sqrt(jnp.mean(jnp.square(u)) + self.eps)
            u = u / jnp.maximum(1.0, rms / self.clip_threshold)
            return (p.astype(jnp.float32) - lr * u).astype(p.dtype), new_st

        flat_p, treedef = jax.tree.flatten(params)
        flat_g = treedef.flatten_up_to(grads)
        flat_s = treedef.flatten_up_to(state["f"])
        outs = [one(p, g, s) for p, g, s in zip(flat_p, flat_g, flat_s)]
        new_p = jax.tree.unflatten(treedef, [o[0] for o in outs])
        new_f = jax.tree.unflatten(treedef, [o[1] for o in outs])
        return new_p, {"f": new_f, "t": t}
