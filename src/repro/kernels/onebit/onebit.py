"""Pallas TPU kernel: 1-bit quantize + error feedback (Seide et al. [159]).

This runs on every gradient byte every step, which makes it the bandwidth
hot-spot the survey's §3.3.3 is about.  Gradients are reshaped to [R, C]
rows; each grid step processes a (block_r, C) VMEM tile and emits the sign
plane, the per-row scale, and the updated error-feedback residual in one
fused pass (one HBM read of g/e, one write of each output — arithmetic
intensity is too low for anything but a fused elementwise kernel, so the
win over unfused jnp is purely avoided HBM traffic).

TPU has no 1-bit dtype; signs leave the kernel as int8 and are bit-packed
into int32 words (32x) by ``ops.pack_bits`` for the wire-format byte count.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(g_ref, e_ref, s_ref, scale_ref, ne_ref):
    c = g_ref[...].astype(jnp.float32) + e_ref[...].astype(jnp.float32)
    # f32 sign plane, narrowed to int8 only at the store (an i1 -> int8
    # select does not lower on Mosaic).
    signs = jnp.where(c >= 0, 1.0, -1.0)
    scale = jnp.mean(jnp.abs(c), axis=-1, keepdims=True)
    s_ref[...] = signs.astype(jnp.int8)
    scale_ref[...] = scale
    ne_ref[...] = c - signs * scale


def onebit_compress(g, e, *, block_r: int = 256, interpret: bool = True):
    """g, e [R, C] -> (signs int8 [R, C], scale f32 [R, 1], new_e f32 [R, C])."""
    R, C = g.shape
    br = min(block_r, R)
    r_pad = (R + br - 1) // br * br
    gp = jnp.pad(g, ((0, r_pad - R), (0, 0)))
    ep = jnp.pad(e, ((0, r_pad - R), (0, 0)))
    grid = (r_pad // br,)
    signs, scale, new_e = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((br, C), lambda i: (i, 0)),
                  pl.BlockSpec((br, C), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((br, C), lambda i: (i, 0)),
                   pl.BlockSpec((br, 1), lambda i: (i, 0)),
                   pl.BlockSpec((br, C), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((r_pad, C), jnp.int8),
                   jax.ShapeDtypeStruct((r_pad, 1), jnp.float32),
                   jax.ShapeDtypeStruct((r_pad, C), jnp.float32)],
        interpret=interpret,
    )(gp, ep)
    return signs[:R], scale[:R], new_e[:R]
