"""Block-tiled online-softmax (flash) attention Pallas TPU kernel.

Grid (B, H, nq, nk): the innermost nk axis streams K/V blocks through VMEM
while float32 VMEM scratch accumulators (running max m, normalizer l, output
acc) persist across nk steps — the canonical TPU flash schedule.  GQA is
free: the K/V BlockSpec index_map folds the query head onto its KV head, so
no repeated K/V ever materializes in VMEM.  Block shapes default to the
MXU-aligned (128, 128); head_dim is the minor (lane) dimension.

Causal / sliding-window masking is applied per-block from global positions.
``interpret=True`` executes the kernel body on the CPU; on TPU hardware
pass interpret=False.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale: float, causal: bool, window: int, bq: int, bk: int,
            nk: int, seq_len: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale          # [bq, hd]
    k = k_ref[0, 0].astype(jnp.float32)                  # [bk, hd]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [bq, bk]

    qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = kpos < seq_len                                # key padding
    if causal:
        mask &= kpos <= qpos
        if window:
            mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                                  # [bq, 1]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                               # [bq, bk]
    v = v_ref[0, 0].astype(jnp.float32)                  # [bk, hd]
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = True):
    """q [B, S, H, hd]; k, v [B, S, KV, hd] (KV divides H) -> [B, S, H, hd]."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    group = H // KV
    scale = 1.0 / (hd ** 0.5)

    # [B, H, S, hd] layout, pad S to block multiples
    qt = jnp.moveaxis(q, 2, 1)
    kt = jnp.moveaxis(k, 2, 1)
    vt = jnp.moveaxis(v, 2, 1)
    bq = min(block_q, max(8, S))
    bk = min(block_k, max(8, S))
    sq_pad = (S + bq - 1) // bq * bq
    sk_pad = (S + bk - 1) // bk * bk
    qt = jnp.pad(qt, ((0, 0), (0, 0), (0, sq_pad - S), (0, 0)))
    kt = jnp.pad(kt, ((0, 0), (0, 0), (0, sk_pad - S), (0, 0)))
    vt = jnp.pad(vt, ((0, 0), (0, 0), (0, sk_pad - S), (0, 0)))
    nq, nk = sq_pad // bq, sk_pad // bk

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal, window=window,
                          bq=bq, bk=bk, nk=nk, seq_len=S),
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, iq, ik, _g=group: (b, h // _g, ik, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, iq, ik, _g=group: (b, h // _g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, sq_pad, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, hd), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt)
    return jnp.moveaxis(out[:, :, :S, :], 1, 2)


def _decode_kernel(q_ref, k_ref, v_ref, pos_ref, o_ref, acc_ref, m_ref,
                   l_ref, *, scale: float, window: int, bk: int, nk: int,
                   kv_len: int):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale          # [1, hd]
    k = k_ref[0, 0].astype(jnp.float32)                  # [bk, hd]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)   # [1, bk]

    pos = pos_ref[0, 0]                                  # traced scalar
    kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
    mask = kpos < kv_len                                 # cache padding
    if window:
        # ring buffer: slot j holds global position p_j with p_j % W == j
        # and p_j <= pos; valid iff that position has been written (>= 0).
        age = (pos - kpos) % window
        mask &= (pos - age) >= 0
    else:
        mask &= kpos <= pos
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                                  # [1, 1]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                               # [1, bk]
    v = v_ref[0, 0].astype(jnp.float32)                  # [bk, hd]
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_decode(q, ck, cv, pos, *, window: int = 0, block_k: int = 128,
                 interpret: bool = True):
    """One-token grouped-query decode against the stored cache layout.

    q [B, 1, H, hd]; ck, cv [B, L, KV, hd] (KV divides H); pos scalar int32
    (traced — same decode step for the whole batch) -> [B, 1, H, hd].

    The grid streams K/V cache blocks through VMEM with the same online-
    softmax scratch as the training kernel, but the query block is a single
    row and the K/V BlockSpec folds query heads onto their KV head, so the
    cache is never repeated H/KV-fold (the repeat-free property of
    ``models.attention._gqa_decode_sdpa``).  ``window > 0`` masks the ring
    buffer by slot age exactly like the jnp decode path.
    """
    B, _, H, hd = q.shape
    L, KV = ck.shape[1], ck.shape[2]
    group = H // KV
    scale = 1.0 / (hd ** 0.5)

    qt = jnp.moveaxis(q, 2, 1)                           # [B, H, 1, hd]
    kt = jnp.moveaxis(ck, 2, 1)                          # [B, KV, L, hd]
    vt = jnp.moveaxis(cv, 2, 1)
    bk = min(block_k, max(8, L))
    lp = (L + bk - 1) // bk * bk
    kt = jnp.pad(kt, ((0, 0), (0, 0), (0, lp - L), (0, 0)))
    vt = jnp.pad(vt, ((0, 0), (0, 0), (0, lp - L), (0, 0)))
    nk = lp // bk
    posb = jnp.asarray(pos, jnp.int32).reshape(1, 1)

    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, window=window,
                          bk=bk, nk=nk, kv_len=L),
        grid=(B, H, nk),
        in_specs=[
            pl.BlockSpec((1, 1, 1, hd), lambda b, h, ik: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, ik, _g=group: (b, h // _g, ik, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, ik, _g=group: (b, h // _g, ik, 0)),
            pl.BlockSpec((1, 1), lambda b, h, ik: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, hd), lambda b, h, ik: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, 1, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1, hd), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt, posb)
    return jnp.moveaxis(out, 1, 2)
