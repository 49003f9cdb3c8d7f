"""Pallas TPU kernel: TernGrad stochastic ternarization (Wen et al. [190]).

The per-tensor statistics (std for clipping, max|g| for the scale) are
reductions computed once outside and passed in as (1,1) SMEM-style operands;
the kernel then does the bandwidth-bound elementwise ternarize in VMEM
tiles.  Uniform random bits are an explicit input so the pure-jnp oracle is
bit-identical (and interpret mode needs no TPU PRNG).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(g_ref, u_ref, stats_ref, t_ref):
    g = g_ref[...].astype(jnp.float32)
    sigma_clip = stats_ref[0, 0]
    s = stats_ref[0, 1]
    g = jnp.where(sigma_clip > 0,
                  jnp.clip(g, -sigma_clip, sigma_clip), g)
    p = jnp.abs(g) / jnp.maximum(s, 1e-30)
    # Product in f32 ({-1, 0, 1} exactly), narrowed to int8 at the store:
    # Mosaic has no int8 multiply.
    b = (u_ref[...] < p).astype(jnp.float32)
    t_ref[...] = (jnp.sign(g) * b).astype(jnp.int8)


def terngrad_ternarize(gc, u, s, *, block_r: int = 256,
                       interpret: bool = True):
    """Ternarize pre-clipped rows against a precomputed scale ``s``.

    The segment codec computes its statistics on the *unpadded* payload
    before row-padding, so the kernel cannot re-derive them from the rows
    it sees; passing ``stats = [0, s]`` skips the in-kernel clip branch
    and reuses the same fused elementwise pass.  gc, u [R, C] -> int8."""
    stats = jnp.stack([jnp.float32(0.0), jnp.asarray(s, jnp.float32)]
                      ).reshape(1, 2)
    R, C = gc.shape
    br = min(block_r, R)
    r_pad = (R + br - 1) // br * br
    gp = jnp.pad(gc.astype(jnp.float32), ((0, r_pad - R), (0, 0)))
    up = jnp.pad(u, ((0, r_pad - R), (0, 0)), constant_values=1.0)
    tern = pl.pallas_call(
        _kernel,
        grid=(r_pad // br,),
        in_specs=[pl.BlockSpec((br, C), lambda i: (i, 0)),
                  pl.BlockSpec((br, C), lambda i: (i, 0)),
                  pl.BlockSpec((1, 2), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((br, C), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r_pad, C), jnp.int8),
        interpret=interpret,
    )(gp, up, stats)
    return tern[:R]


def terngrad_compress(g, u, *, clip_sigma: float = 2.5, block_r: int = 256,
                      interpret: bool = True):
    """g, u [R, C] -> (tern int8 [R, C], scale scalar f32)."""
    g32 = g.astype(jnp.float32)
    sigma = jnp.std(g32) * clip_sigma if clip_sigma else jnp.float32(0.0)
    gc = jnp.where(sigma > 0, jnp.clip(g32, -sigma, sigma), g32)
    s = jnp.max(jnp.abs(gc))
    stats = jnp.stack([sigma, s]).reshape(1, 2)

    R, C = g.shape
    br = min(block_r, R)
    r_pad = (R + br - 1) // br * br
    gp = jnp.pad(g32, ((0, r_pad - R), (0, 0)))
    up = jnp.pad(u, ((0, r_pad - R), (0, 0)), constant_values=1.0)
    tern = pl.pallas_call(
        _kernel,
        grid=(r_pad // br,),
        in_specs=[pl.BlockSpec((br, C), lambda i: (i, 0)),
                  pl.BlockSpec((br, C), lambda i: (i, 0)),
                  pl.BlockSpec((1, 2), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((br, C), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r_pad, C), jnp.int8),
        interpret=interpret,
    )(gp, up, stats)
    return tern[:R], s
