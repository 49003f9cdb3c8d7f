"""Jit'd public wrappers for the flash-attention kernels.

``attention`` / ``decode`` run the Pallas kernels directly.
``attention_grad`` is the trainable entry the model layer routes through:
its forward is the flash kernel and its VJP replays the pure-jnp oracle
(Pallas kernels do not differentiate), so gradients match the reference
math the models were validated against.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels.flash_attention.flash_attention import (flash_attention,
                                                           flash_decode)
from repro.kernels.flash_attention.ref import attention_ref, decode_ref


@functools.partial(jax.jit, static_argnames=("causal", "window", "scale",
                                             "block_q", "block_k",
                                             "interpret"))
def attention(q, k, v, *, causal: bool = True, window: int = 0,
              scale: float | None = None, block_q: int | None = None,
              block_k: int | None = None, interpret: bool = True):
    return flash_attention(q, k, v, causal=causal, window=window,
                           scale=scale, block_q=block_q, block_k=block_k,
                           interpret=interpret)


@functools.partial(jax.jit, static_argnames=("window", "block_k",
                                             "interpret"))
def decode(q, ck, cv, pos, *, window: int = 0, block_k: int = 128,
           interpret: bool = True):
    return flash_decode(q, ck, cv, pos, window=window, block_k=block_k,
                        interpret=interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _attention_grad(q, k, v, causal, window, scale, interpret):
    return flash_attention(q, k, v, causal=causal, window=window,
                           scale=scale, interpret=interpret)


def _attention_grad_fwd(q, k, v, causal, window, scale, interpret):
    return (_attention_grad(q, k, v, causal, window, scale, interpret),
            (q, k, v))


def _attention_grad_bwd(causal, window, scale, interpret, res, g):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda qq, kk, vv: attention_ref(qq, kk, vv, causal=causal,
                                         window=window, scale=scale),
        q, k, v)
    return vjp(g)


_attention_grad.defvjp(_attention_grad_fwd, _attention_grad_bwd)


@functools.partial(jax.jit, static_argnames=("causal", "window", "scale",
                                             "interpret"))
def attention_grad(q, k, v, *, causal: bool = True, window: int = 0,
                   scale: float | None = None, interpret: bool = True):
    """Flash forward with a reference-math VJP (safe under value_and_grad);
    v may have its own head dim, and ``scale`` defaults to 1/sqrt(hd)."""
    return _attention_grad(q, k, v, causal, window, scale, interpret)


__all__ = ["attention", "attention_grad", "attention_ref", "decode",
           "decode_ref", "flash_attention", "flash_decode"]
