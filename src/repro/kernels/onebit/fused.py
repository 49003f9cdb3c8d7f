"""Pallas TPU kernel: fused 1-bit encode + error-feedback residual.

The seed ``onebit.py`` kernel emits the symmetric ``sign * mean|c|``
plane; production (``comm/codecs.py`` and ``core/compression.py``) since
grew the Seide two-bin reconstruction, per-row valid masks for lane
padding / dgc's already-sent slots, and the ``ef_gain`` over-relaxation —
all as separate jnp passes, so one encode touches each gradient byte
four-plus times.  This kernel is the fusion of the whole sequence: one
grid step reads a ``(block_r, C)`` tile of ``g`` (and optionally ``e``
and a valid mask) from HBM once and writes every output of the
encode+EF contract:

    c_in   = g + gain * e        (what the quantizer sees)
    c_true = g + e               (what the residual is measured against)
    signs  = sign(c_in)                       -> the 1-bit wire plane
    sp,sn  = per-row bin means of c_in        -> 8 B/row side info
             (or both = mean|c_in| when symmetric=True, the seed format)
    out    = valid ? decode(signs, sp, sn) : 0
    new_e  = c_true - out                     -> next step's EF residual

Arithmetic intensity is far below the TPU ridge, so the win is purely
the avoided HBM round-trips of the unfused jnp passes; the math is kept
expression-identical to the oracles so backend parity is bitwise, not
just allclose (asserted by tests/test_kernels.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Mosaic's default scoped VMEM is 16 MiB.  The double-buffered blocks of
# one grid step are held under this budget by taking fewer rows per step;
# a row too wide for even one 8-row tile (an untied LM head's rows are
# vocab-long) raises the kernel's VMEM limit instead.
_VMEM_BLOCK_BUDGET = 12 << 20
_VMEM_HEADROOM = 16 << 20


def _kernel(*refs, gain: float, has_e: bool, has_valid: bool,
            symmetric: bool):
    it = iter(refs)
    g_ref = next(it)
    e_ref = next(it) if has_e else None
    v_ref = next(it) if has_valid else None
    s_ref, sp_ref, sn_ref, o_ref, ne_ref = it

    g = g_ref[...].astype(jnp.float32)
    if has_e:
        e = e_ref[...].astype(jnp.float32)
        cin = g + gain * e
        ctrue = g + e
    else:
        cin = ctrue = g
    # The sign plane stays f32 inside the kernel and is narrowed to int8
    # only at the store: Mosaic cannot relayout an i1 mask into int8
    # tiling.  Same values as the oracle's int8 plane, so parity holds.
    signs = jnp.where(cin >= 0, 1.0, -1.0)
    valid = (v_ref[...] != 0) if has_valid else None

    if symmetric:
        sp = sn = jnp.mean(jnp.abs(cin), axis=-1, keepdims=True)
    else:
        pos = signs > 0
        neg = ~pos
        if valid is not None:
            pos = pos & valid
            neg = neg & valid
        npos = jnp.maximum(jnp.sum(pos, axis=-1, keepdims=True), 1)
        nneg = jnp.maximum(jnp.sum(neg, axis=-1, keepdims=True), 1)
        sp = jnp.sum(jnp.where(pos, cin, 0.0), axis=-1, keepdims=True) / npos
        sn = jnp.sum(jnp.where(neg, -cin, 0.0), axis=-1, keepdims=True) / nneg

    recon = jnp.where(signs > 0, sp, -sn)
    out = recon if valid is None else jnp.where(valid, recon, 0.0)
    s_ref[...] = signs.astype(jnp.int8)
    sp_ref[...] = sp
    sn_ref[...] = sn
    o_ref[...] = out
    ne_ref[...] = ctrue - out


def onebit_encode_ef(g, e=None, valid=None, *, gain: float = 1.0,
                     symmetric: bool = False, block_r: int = 256,
                     interpret: bool = True):
    """g [R, C]; e, valid optional [R, C] (valid: nonzero = real element).

    Returns ``(signs int8 [R,C], sp f32 [R,1], sn f32 [R,1],
    out f32 [R,C], new_e f32 [R,C])`` per the module contract.  ``e=None``
    means no error feedback (``c_in = c_true = g``, the segment-codec
    case); ``valid=None`` means every element is real."""
    R, C = g.shape
    # bytes of one row across every block: f32 g (and e), int8 valid,
    # int8 signs, f32 out and new_e, and the lane-padded sp/sn columns
    row_bytes = (C * (4 + 4 * (e is not None) + (valid is not None)
                      + 1 + 4 + 4) + 2 * 4 * 128)
    fit = max(8, _VMEM_BLOCK_BUDGET // (2 * row_bytes) // 8 * 8)
    br = min(block_r, R, fit)
    r_pad = (R + br - 1) // br * br

    def rpad(x, fill=0):
        return jnp.pad(x, ((0, r_pad - R), (0, 0)), constant_values=fill)

    operands = [rpad(g.astype(jnp.float32))]
    if e is not None:
        operands.append(rpad(e.astype(jnp.float32)))
    if valid is not None:
        operands.append(rpad(valid.astype(jnp.int8)))
    row_spec = pl.BlockSpec((br, C), lambda i: (i, 0))
    col_spec = pl.BlockSpec((br, 1), lambda i: (i, 0))

    signs, sp, sn, out, new_e = pl.pallas_call(
        functools.partial(_kernel, gain=gain, has_e=e is not None,
                          has_valid=valid is not None, symmetric=symmetric),
        grid=(r_pad // br,),
        in_specs=[row_spec] * len(operands),
        out_specs=[row_spec, col_spec, col_spec, row_spec, row_spec],
        out_shape=[jax.ShapeDtypeStruct((r_pad, C), jnp.int8),
                   jax.ShapeDtypeStruct((r_pad, 1), jnp.float32),
                   jax.ShapeDtypeStruct((r_pad, 1), jnp.float32),
                   jax.ShapeDtypeStruct((r_pad, C), jnp.float32),
                   jax.ShapeDtypeStruct((r_pad, C), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=2 * br * row_bytes + _VMEM_HEADROOM),
        interpret=interpret,
    )(*operands)
    return signs[:R], sp[:R], sn[:R], out[:R], new_e[:R]
