"""Block-tiled online-softmax (flash) attention Pallas TPU kernels.

Training / prefill forward (``flash_attention``)
    q, k and v are read in the model's own layout, viewed for free as
    ``[B, S, H*hd]`` and ``[B, S, KV*hd]`` (v and the output with a head
    dim ``hdv`` of their own, as MLA's 128 against q/k's 192): one block
    holds ``heads`` query heads side by side on the lane axis, and the KV
    heads they attend to, so GQA folds into the K/V index map and no
    repeated K/V ever reaches VMEM.  Grid ``(B, H / heads, nq, nk)``: the innermost nk axis streams
    K/V blocks through VMEM while float32 scratch (the output accumulator,
    and the running max m and normaliser l in a lane-dense ``(bq, 128)``
    per head) persists across it.

    Blocks come from the input's shape (``plan``): ``heads`` gives a block
    at least 256 lanes wide where the heads allow it; the sequence block is
    512 for long sequences (fewer rows where the window is short, or where
    VMEM would not hold the step), and the sequence itself, rounded up to
    the sublane tile, when it is shorter.  Explicit ``block_q`` /
    ``block_k`` override the sequence blocks.

    Causal and window block skipping: a (q-block, k-block) pair wholly
    above the diagonal, or wholly before the window, runs no compute, and
    the K/V index map is clamped to the blocks the q-block needs, so a
    skipped step issues no DMA.  The mask is built only on blocks that
    straddle the diagonal, the window's edge or the padded key tail.
    ``plan(...).pairs`` counts the pairs that compute.

    Operands reach the MXU in their own dtype with float32 accumulation:
    QK^T with q pre-scaled by the softmax scale (1/sqrt(hd) unless one is
    given; exact where hd is a power of 4),
    PV with the probabilities cast to V's dtype.  m, l, the accumulator
    and the softmax stay float32; float32 inputs give float32 dots.

One-token decode (``flash_decode``)
    Streams ``[B, L, KV, hd]`` cache blocks through VMEM with the same
    online-softmax scratch; see its docstring.

``interpret=True`` executes a kernel body on the CPU; on TPU hardware pass
interpret=False.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128
MAX_BLOCK = 512                 # longest sequence block the plan picks
VMEM_BUDGET = 12 * 2 ** 20      # bytes one forward grid step may hold


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


@dataclasses.dataclass(frozen=True)
class FlashPlan:
    """The forward kernel's schedule for one input shape."""
    block_q: int
    block_k: int
    heads: int       # query heads per grid step
    kv_heads: int    # KV heads per grid step
    nq: int
    nk: int
    pairs: int       # (q-block, k-block) pairs that compute, of nq * nk


def _kv_range(iq, *, bq: int, bk: int, nk: int, causal: bool, window: int,
              lo=max, hi=min):
    """First and last k-block that q-block ``iq`` attends to.  ``lo`` /
    ``hi`` are ``max`` / ``min`` for Python ints and ``jnp.maximum`` /
    ``jnp.minimum`` for a traced block index."""
    if not causal:
        return 0, nk - 1
    first = lo(iq * bq - window + 1, 0) // bk if window else 0
    return first, hi((iq * bq + bq - 1) // bk, nk - 1)


def _head_block(H: int, KV: int, hd: int, hdv: int) -> tuple:
    """Fewest query heads per block (with the KV heads they read) whose
    lane widths, of q/k heads of ``hd`` and v heads of ``hdv``, tile by
    128, or span the array, reaching 256 lanes."""
    group = H // KV
    for hb in (d for d in range(1, H) if H % d == 0):
        if hb % group and group % hb:
            continue
        kvb = max(1, hb // group)
        if hb * hd >= 2 * LANES and all(
                n == total or n * w % LANES == 0
                for n, total in ((hb, H), (kvb, KV)) for w in (hd, hdv)):
            return hb, kvb
    return H, KV                  # every head: the blocks span the arrays


def _vmem_bytes(bq: int, bk: int, hb: int, kvb: int, hd: int, hdv: int,
                itemsize: int) -> int:
    io = 2 * (bq * hb + bk * kvb) * (hd + hdv) * itemsize  # q, o, k, v x2
    scratch = hb * bq * (max(hdv, LANES) + 2 * LANES) * 4  # acc, m, l
    return io + scratch + 4 * bq * bk * 4                  # one head's s, p


def plan(S: int, H: int, KV: int, hd: int, dtype, *, causal: bool = True,
         window: int = 0, block_q: int | None = None,
         block_k: int | None = None, hdv: int | None = None) -> FlashPlan:
    """Blocks for a ``[B, S, H, hd]`` forward from the shape alone; ``hdv``
    is the v head dim where it differs from q/k's ``hd``."""
    hdv = hdv or hd
    itemsize = jnp.dtype(dtype).itemsize
    sub = 8 * 4 // itemsize                  # sublane tile: 8 f32, 16 bf16
    hb, kvb = _head_block(H, KV, hd, hdv)
    target = MAX_BLOCK
    if causal and window:
        target = min(target, max(LANES, _round_up(window, LANES)))
    while True:
        if S <= target:
            b = _round_up(S, sub)
        else:   # least padding, then the largest block
            b = min((c for c in (512, 256, 128) if c <= target),
                    key=lambda c: (_round_up(S, c), -c))
        if target == LANES or \
                _vmem_bytes(b, b, hb, kvb, hd, hdv, itemsize) <= VMEM_BUDGET:
            break
        target = max(LANES, target // 2)
    bq = min(block_q, _round_up(S, sub)) if block_q else b
    bk = min(block_k, _round_up(S, sub)) if block_k else b
    nq, nk = -(-S // bq), -(-S // bk)
    kw = dict(bq=bq, bk=bk, nk=nk, causal=causal, window=window)
    pairs = sum(last - first + 1 for first, last in
                (_kv_range(iq, **kw) for iq in range(nq)))
    return FlashPlan(bq, bk, hb, kvb, nq, nk, pairs)


def _lanes(x, n: int):
    """A ``[rows, 128]`` array whose lanes are equal, as ``[rows, n]``."""
    if n % LANES == 0:
        return jnp.tile(x, (1, n // LANES))
    if n < LANES:
        return x[:, :n]
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _update(q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref, *, masked: bool,
            q0, k0, scale: float, causal: bool, window: int, bq: int,
            bk: int, seq_len: int, heads: int, group: int, hd: int,
            hdv: int):
    """One (q-block, k-block) pair for every head of the block."""
    if masked:
        qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = kpos < seq_len                            # key padding
        if causal:
            mask &= kpos <= qpos
            if window:
                mask &= kpos > qpos - window
    for h in range(heads):
        c = h // group                                   # its KV head
        q = q_ref[0, :, h * hd:(h + 1) * hd]
        q = (q.astype(jnp.float32) * scale).astype(q.dtype)
        k = k_ref[0, :, c * hd:(c + 1) * hd]
        v = v_ref[0, :, c * hdv:(c + 1) * hdv]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if masked:
            s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[h]                                # [bq, 128]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - _lanes(m_new, bk))               # [bq, bk]
        l_ref[h] = alpha * l_ref[h] + jnp.sum(p, axis=1, keepdims=True)
        m_ref[h] = m_new
        acc_ref[h] = acc_ref[h] * _lanes(alpha, hdv) + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale: float, causal: bool, window: int, bq: int, bk: int,
            nk: int, seq_len: int, heads: int, group: int, hd: int,
            hdv: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    first, last = _kv_range(iq, bq=bq, bk=bk, nk=nk, causal=causal,
                            window=window, lo=jnp.maximum, hi=jnp.minimum)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q0, k0 = iq * bq, ik * bk
    run = (ik >= first) & (ik <= last)
    clean = k0 + bk <= seq_len                # every pair of it is visible
    if causal:
        clean &= k0 + bk - 1 <= q0
        if window:
            clean &= k0 > q0 + bq - 1 - window
    update = functools.partial(
        _update, q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref, q0=q0, k0=k0,
        scale=scale, causal=causal, window=window, bq=bq, bk=bk,
        seq_len=seq_len, heads=heads, group=group, hd=hd, hdv=hdv)

    @pl.when(run & clean)
    def _whole():
        update(masked=False)

    @pl.when(run & jnp.logical_not(clean))
    def _edge():
        update(masked=True)

    @pl.when(ik == last)
    def _finalize():
        for h in range(heads):
            l = jnp.maximum(l_ref[h], 1e-30)
            o_ref[0, :, h * hdv:(h + 1) * hdv] = (
                acc_ref[h] / _lanes(l, hdv)).astype(o_ref.dtype)


def _pad_seq(x, n: int):
    return x if x.shape[1] == n else \
        jnp.pad(x, ((0, 0), (0, n - x.shape[1]), (0, 0)))


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    scale: float | None = None, block_q: int | None = None,
                    block_k: int | None = None, interpret: bool = True):
    """q, k [B, S, H or KV, hd]; v [B, S, KV, hdv] (KV divides H) ->
    [B, S, H, hdv].  Scores are scaled by ``scale``, 1/sqrt(hd) by default.

    Blocks come from ``plan``; ``block_q`` / ``block_k`` override its
    sequence blocks."""
    B, S, H, hd = q.shape
    KV, hdv = k.shape[2], v.shape[3]
    group = H // KV
    scale = 1.0 / hd ** 0.5 if scale is None else float(scale)
    pn = plan(S, H, KV, hd, q.dtype, causal=causal, window=window,
              block_q=block_q, block_k=block_k, hdv=hdv)
    bq, bk, hb, kvb, nq, nk = (pn.block_q, pn.block_k, pn.heads,
                               pn.kv_heads, pn.nq, pn.nk)
    # free views [B, S, heads*hd], padded to whole blocks
    qf = _pad_seq(q.reshape(B, S, H * hd), nq * bq)
    kf = _pad_seq(k.reshape(B, S, KV * hd), nk * bk)
    vf = _pad_seq(v.reshape(B, S, KV * hdv), nk * bk)

    def q_map(b, h, iq, ik):
        return b, iq, h

    def kv_map(b, h, iq, ik):
        # a skipped step maps to a block the q-block needs: no new DMA
        first, last = _kv_range(iq, bq=bq, bk=bk, nk=nk, causal=causal,
                                window=window, lo=jnp.maximum,
                                hi=jnp.minimum)
        return b, jnp.minimum(jnp.maximum(ik, first), last), \
            h * hb // (group * kvb)

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal,
                          window=window, bq=bq, bk=bk, nk=nk, seq_len=S,
                          heads=hb, group=group, hd=hd, hdv=hdv),
        grid=(B, H // hb, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, hb * hd), q_map),
            pl.BlockSpec((1, bk, kvb * hd), kv_map),
            pl.BlockSpec((1, bk, kvb * hdv), kv_map),
        ],
        out_specs=pl.BlockSpec((1, bq, hb * hdv), q_map),
        out_shape=jax.ShapeDtypeStruct((B, nq * bq, H * hdv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((hb, bq, hdv), jnp.float32),
            pltpu.VMEM((hb, bq, LANES), jnp.float32),
            pltpu.VMEM((hb, bq, LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf)
    return out[:, :S].reshape(B, S, H, hdv)


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
