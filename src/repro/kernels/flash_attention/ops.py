"""Jit'd public wrappers for the flash-attention kernels.

``attention`` / ``decode`` run the Pallas kernels directly.
``attention_grad`` is the trainable entry the model layer routes through.
Its primal is the flash forward.  Under differentiation the forward also
saves each row's log-sum-exp, and the VJP is the Pallas flash backward
(``flash_attention_bwd``: a dK/dV kernel and a dQ kernel on the
forward's tiling), which recomputes the probabilities block by block.

The backward runs under a jit of its own named ``flash_attention_bwd``:
its kernels' instructions in a step's HLO are then named after it, and
only the forward's are named ``attention_grad``, the name the benchmark's
forward rooflines look for.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels.flash_attention import flash_attention as _fa
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


flash_attention_bwd = jax.jit(
    _fa.flash_attention_bwd,
    static_argnames=("causal", "window", "scale", "interpret"))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _attention_grad(q, k, v, causal, window, scale, interpret):
    return flash_attention(q, k, v, causal=causal, window=window,
                           scale=scale, interpret=interpret)


def _attention_grad_fwd(q, k, v, causal, window, scale, interpret):
    o, lse = _fa.flash_attention_fwd(q, k, v, causal=causal, window=window,
                                     scale=scale, interpret=interpret)
    return o, (q, k, v, o, lse)


def _attention_grad_bwd(causal, window, scale, interpret, res, g):
    return flash_attention_bwd(*res, g, causal=causal, window=window,
                               scale=scale, interpret=interpret)


_attention_grad.defvjp(_attention_grad_fwd, _attention_grad_bwd)


@functools.partial(jax.jit, static_argnames=("causal", "window", "scale",
                                             "interpret"))
def attention_grad(q, k, v, *, causal: bool = True, window: int = 0,
                   scale: float | None = None, interpret: bool = True):
    """Flash forward with the flash backward as its VJP (safe under
    value_and_grad); v may have its own head dim, and ``scale`` defaults
    to 1/sqrt(hd)."""
    return _attention_grad(q, k, v, causal, window, scale, interpret)


__all__ = ["attention", "attention_grad", "attention_ref", "decode",
           "decode_ref", "flash_attention", "flash_attention_bwd",
           "flash_decode"]
