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

Training backward (``flash_attention_bwd``)
    Under differentiation the forward (``flash_attention_fwd``) also writes
    each row's float32 log-sum-exp ``m + log l``, ``[B, H, S]``; the
    primal and the serving paths keep the one-output kernel.  The backward
    is the FlashAttention-2 split on the forward's tiling: with
    ``D = rowsum(dO * O)`` in float32, a dK/dV kernel (k-block outer, the
    q-blocks that see it streamed innermost, keys on the sublanes so the
    lse and D rows broadcast as they are) and a dQ kernel (q-block outer,
    k-blocks innermost) each recompute ``P = exp(s - lse)`` and
    ``dS = P * (dP - D)`` block by block, accumulating in float32 VMEM.
    GQA sums dK/dV over each KV head's query group inside the kernel, over
    head blocks too where a group spans several.  ``plan(...,
    backward=True)`` gives its blocks and pairs, against a VMEM budget of
    its own; pairs are skipped, their DMA elided and edges masked as in
    the forward.  P and dS reach the MXU in the input dtype.

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
BWD_VMEM_BUDGET = 24 * 2 ** 20  # bytes one backward grid step may hold
BWD_VMEM_LIMIT = 64 * 2 ** 20   # the backward kernels' scoped VMEM limit


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


@dataclasses.dataclass(frozen=True)
class FlashPlan:
    """The forward's or the backward's schedule for one input shape."""
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


def _q_range(ik, *, bq: int, bk: int, nq: int, causal: bool, window: int,
             hi=min):
    """First and last q-block that attends to k-block ``ik``: the same
    pairs as ``_kv_range``, enumerated by k-block."""
    if not causal:
        return 0, nq - 1
    last = hi((ik * bk + bk + window - 2) // bq, nq - 1) if window \
        else nq - 1
    return ik * bk // bq, last


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
                itemsize: int, backward: bool) -> int:
    """What one grid step holds: double-buffered blocks, float32 scratch
    and one head's [bq, bk] temporaries."""
    io = 2 * (bq * hb + bk * kvb) * (hd + hdv) * itemsize  # q, o, k, v x2
    if not backward:
        scratch = hb * bq * (max(hdv, LANES) + 2 * LANES) * 4  # acc, m, l
        return io + scratch + 4 * bq * bk * 4                  # s, p
    # dK/dV: + dk, dv blocks and their float32 accumulators, the lse and D
    # rows; dQ's blocks and accumulator are no larger; s, p, dp, ds
    io += 2 * bk * kvb * (hd + hdv) * itemsize + 2 * 2 * hb * 8 * bq * 4
    scratch = kvb * bk * (max(hd, LANES) + max(hdv, LANES)) * 4
    return io + scratch + 8 * bq * bk * 4


def plan(S: int, H: int, KV: int, hd: int, dtype, *, causal: bool = True,
         window: int = 0, block_q: int | None = None,
         block_k: int | None = None, hdv: int | None = None,
         backward: bool = False) -> FlashPlan:
    """Blocks for a ``[B, S, H, hd]`` forward, or its backward, from the
    shape alone; ``hdv`` is the v head dim where it differs from q/k's
    ``hd``.  The backward's pairs are enumerated by k-block."""
    hdv = hdv or hd
    itemsize = jnp.dtype(dtype).itemsize
    sub = 8 * 4 // itemsize                  # sublane tile: 8 f32, 16 bf16
    hb, kvb = _head_block(H, KV, hd, hdv)
    budget = BWD_VMEM_BUDGET if backward else VMEM_BUDGET
    target = MAX_BLOCK
    if causal and window:
        target = min(target, max(LANES, _round_up(window, LANES)))
    while True:
        if S <= target:
            b = _round_up(S, sub)
        else:   # least padding, then the largest block
            b = min((c for c in (512, 256, 128) if c <= target),
                    key=lambda c: (_round_up(S, c), -c))
        if target == LANES or _vmem_bytes(b, b, hb, kvb, hd, hdv, itemsize,
                                          backward) <= budget:
            break
        target = max(LANES, target // 2)
    bq = min(block_q, _round_up(S, sub)) if block_q else b
    bk = min(block_k, _round_up(S, sub)) if block_k else b
    nq, nk = -(-S // bq), -(-S // bk)
    kw = dict(bq=bq, bk=bk, causal=causal, window=window)
    if backward:
        ranges = (_q_range(ik, nq=nq, **kw) for ik in range(nk))
    else:
        ranges = (_kv_range(iq, nk=nk, **kw) for iq in range(nq))
    pairs = sum(last - first + 1 for first, last in ranges)
    return FlashPlan(bq, bk, hb, kvb, nq, nk, pairs)


def _lanes(x, n: int):
    """A ``[rows, 128]`` array whose lanes are equal, as ``[rows, n]``."""
    if n % LANES == 0:
        return jnp.tile(x, (1, n // LANES))
    if n < LANES:
        return x[:, :n]
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


NT = (((1,), (1,)), ((), ()))       # a @ b.T
NN = (((1,), (0,)), ((), ()))       # a @ b


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _pair(update, run, q0, k0, *, bq: int, bk: int, seq_len: int,
          causal: bool, window: int):
    """Runs ``update`` on a block pair that computes: unmasked where every
    pair of it is visible, masked where it straddles the diagonal, the
    window's edge or the padded key tail."""
    clean = k0 + bk <= seq_len
    if causal:
        clean &= k0 + bk - 1 <= q0
        if window:
            clean &= k0 > q0 + bq - 1 - window

    @pl.when(run & clean)
    def _whole():
        update(masked=False)

    @pl.when(run & jnp.logical_not(clean))
    def _edge():
        update(masked=True)


def _mask(q0, k0, shape, q_axis: int, *, seq_len: int, causal: bool,
          window: int):
    """Which (query, key) pairs of a [bq, bk] (``q_axis`` 0) or [bk, bq]
    (1) block attend: not the padded key tail, and within the causal
    window."""
    qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
    kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    mask = kpos < seq_len
    if causal:
        mask &= kpos <= qpos
        if window:
            mask &= kpos > qpos - window
    return mask


def _head(ref, h: int, w: int):
    """Head ``h`` (``w`` lanes wide) of a ``[1, rows, heads * w]`` block."""
    return ref[0, :, h * w:(h + 1) * w]


def _scaled(q, scale: float):
    return (q.astype(jnp.float32) * scale).astype(q.dtype)


def _update(q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref, *, masked: bool,
            q0, k0, scale: float, causal: bool, window: int, bq: int,
            bk: int, seq_len: int, heads: int, group: int, hd: int,
            hdv: int):
    """One (q-block, k-block) pair for every head of the block."""
    if masked:
        mask = _mask(q0, k0, (bq, bk), 0, seq_len=seq_len, causal=causal,
                     window=window)
    for h in range(heads):
        c = h // group                                   # its KV head
        q = _scaled(_head(q_ref, h, hd), scale)
        k, v = _head(k_ref, c, hd), _head(v_ref, c, hdv)
        s = _dot(q, k, NT)
        if masked:
            s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[h]                                # [bq, 128]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - _lanes(m_new, bk))               # [bq, bk]
        l_ref[h] = alpha * l_ref[h] + jnp.sum(p, axis=1, keepdims=True)
        m_ref[h] = m_new
        acc_ref[h] = acc_ref[h] * _lanes(alpha, hdv) + _dot(
            p.astype(v.dtype), v, NN)


def _kernel(q_ref, k_ref, v_ref, o_ref, *rest, scale: float, causal: bool,
            window: int, bq: int, bk: int, nk: int, seq_len: int,
            heads: int, group: int, hd: int, hdv: int):
    lse_ref = rest[0] if len(rest) == 4 else None
    acc_ref, m_ref, l_ref = rest[-3:]
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
    update = functools.partial(
        _update, q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref, q0=q0, k0=k0,
        scale=scale, causal=causal, window=window, bq=bq, bk=bk,
        seq_len=seq_len, heads=heads, group=group, hd=hd, hdv=hdv)
    _pair(update, (ik >= first) & (ik <= last), q0, k0, bq=bq, bk=bk,
          seq_len=seq_len, causal=causal, window=window)

    @pl.when(ik == last)
    def _finalize():
        for h in range(heads):
            l = jnp.maximum(l_ref[h], 1e-30)
            o_ref[0, :, h * hdv:(h + 1) * hdv] = (
                acc_ref[h] / _lanes(l, hdv)).astype(o_ref.dtype)
            if lse_ref is not None:      # one row of bq lanes a head
                lse_ref[0, h] = (m_ref[h] + jnp.log(l)).T[:1]


def _pad_seq(x, n: int, axis: int = 1):
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, n - x.shape[axis])
    return x if x.shape[axis] == n else jnp.pad(x, pad)


def _q_major_maps(*, bq: int, bk: int, nk: int, causal: bool, window: int,
                  hb: int, group: int, kvb: int):
    """Index maps of a grid ``(B, head blocks, nq, nk)``: q-side blocks of
    ``[B, S, heads * w]``, K/V blocks, and per-row ``[B, H, 1, S]``
    blocks."""
    def q_map(b, h, iq, ik):
        return b, iq, h

    def kv_map(b, h, iq, ik):
        # a skipped step maps to a block the q-block needs: no new DMA
        first, last = _kv_range(iq, bq=bq, bk=bk, nk=nk, causal=causal,
                                window=window, lo=jnp.maximum,
                                hi=jnp.minimum)
        return b, jnp.minimum(jnp.maximum(ik, first), last), \
            h * hb // (group * kvb)

    def rows_map(b, h, iq, ik):
        return b, h, 0, iq

    return q_map, kv_map, rows_map


def _forward(q, k, v, *, causal: bool, window: int, scale, block_q,
             block_k, interpret: bool, lse: bool):
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

    q_map, kv_map, rows_map = _q_major_maps(
        bq=bq, bk=bk, nk=nk, causal=causal, window=window, hb=hb,
        group=group, kvb=kvb)
    out_specs = pl.BlockSpec((1, bq, hb * hdv), q_map)
    out_shape = jax.ShapeDtypeStruct((B, nq * bq, H * hdv), q.dtype)
    if lse:     # [B, H, 1, S]: each head's rows on the lanes
        out_specs = [out_specs, pl.BlockSpec((1, hb, 1, bq), rows_map)]
        out_shape = [out_shape, jax.ShapeDtypeStruct((B, H, 1, nq * bq),
                                                     jnp.float32)]
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
        out_specs=out_specs,
        out_shape=out_shape,
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
    if not lse:
        return out[:, :S].reshape(B, S, H, hdv)
    return out[0][:, :S].reshape(B, S, H, hdv), out[1][:, :, 0, :S]


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    scale: float | None = None, block_q: int | None = None,
                    block_k: int | None = None, interpret: bool = True):
    """q, k [B, S, H or KV, hd]; v [B, S, KV, hdv] (KV divides H) ->
    [B, S, H, hdv].  Scores are scaled by ``scale``, 1/sqrt(hd) by default.

    Blocks come from ``plan``; ``block_q`` / ``block_k`` override its
    sequence blocks."""
    return _forward(q, k, v, causal=causal, window=window, scale=scale,
                    block_q=block_q, block_k=block_k, interpret=interpret,
                    lse=False)


def flash_attention_fwd(q, k, v, *, causal: bool = True, window: int = 0,
                        scale: float | None = None, interpret: bool = True):
    """The training forward: ``flash_attention``'s output and each row's
    float32 log-sum-exp of its scaled scores, ``[B, H, S]``, which
    ``flash_attention_bwd`` takes back."""
    return _forward(q, k, v, causal=causal, window=window, scale=scale,
                    block_q=None, block_k=None, interpret=interpret,
                    lse=True)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref, dk_ref, dv_ref,
                dk_acc, dv_acc, *, scale: float, causal: bool, window: int,
                bq: int, bk: int, nq: int, nsub: int, seq_len: int,
                heads: int, group: int, hd: int, hdv: int):
    """dK and dV of one k-block, summed over the q-blocks that see it
    (innermost) and, where a KV head's query group spans several head
    blocks, over those (``nsub``).  Keys on the sublanes, queries on the
    lanes: the lse and D rows broadcast down the sublanes, and every
    matmul takes its operands as they are."""
    ik, j, iq = pl.program_id(2), pl.program_id(3), pl.program_id(4)
    first, last = _q_range(ik, bq=bq, bk=bk, nq=nq, causal=causal,
                           window=window, hi=jnp.minimum)

    @pl.when((j == 0) & (iq == 0))
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q0, k0 = iq * bq, ik * bk

    def update(masked: bool):
        if masked:
            mask = _mask(q0, k0, (bk, bq), 1, seq_len=seq_len,
                         causal=causal, window=window)
        for h in range(heads):
            c = h // group
            q = _scaled(_head(q_ref, h, hd), scale)
            do = _head(do_ref, h, hdv)
            st = _dot(_head(k_ref, c, hd), q, NT)        # [bk, bq]
            if masked:
                st = jnp.where(mask, st, NEG_INF)
            pt = jnp.exp(st - lse_ref[0, h])
            dv_acc[c] += _dot(pt.astype(do.dtype), do, NN)
            dpt = _dot(_head(v_ref, c, hdv), do, NT)
            dst = pt * (dpt - d_ref[0, h])
            dk_acc[c] += _dot(dst.astype(q.dtype), q, NN)   # q carries scale

    _pair(update, (iq >= first) & (iq <= last), q0, k0, bq=bq, bk=bk,
          seq_len=seq_len, causal=causal, window=window)

    @pl.when((j == nsub - 1) & (iq == nq - 1))
    def _finalize():
        for c in range(dk_acc.shape[0]):
            dk_ref[0, :, c * hd:(c + 1) * hd] = dk_acc[c].astype(dk_ref.dtype)
            dv_ref[0, :, c * hdv:(c + 1) * hdv] = \
                dv_acc[c].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref, dq_ref, dq_acc,
               *, scale: float, causal: bool, window: int, bq: int, bk: int,
               nk: int, seq_len: int, heads: int, group: int, hd: int,
               hdv: int):
    """dQ of one q-block, summed over the k-blocks it sees (innermost)."""
    iq, ik = pl.program_id(2), pl.program_id(3)
    first, last = _kv_range(iq, bq=bq, bk=bk, nk=nk, causal=causal,
                            window=window, lo=jnp.maximum, hi=jnp.minimum)

    @pl.when(ik == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    q0, k0 = iq * bq, ik * bk

    def update(masked: bool):
        if masked:
            mask = _mask(q0, k0, (bq, bk), 0, seq_len=seq_len,
                         causal=causal, window=window)
        for h in range(heads):
            c = h // group
            k = _head(k_ref, c, hd)
            s = _dot(_scaled(_head(q_ref, h, hd), scale), k, NT)
            if masked:
                s = jnp.where(mask, s, NEG_INF)
            p = jnp.exp(s - lse_ref[0, h, 0][:, None])     # [bq, bk]
            dp = _dot(_head(do_ref, h, hdv), _head(v_ref, c, hdv), NT)
            ds = p * (dp - d_ref[0, h, 0][:, None])
            dq_acc[h] += _dot(ds.astype(k.dtype), k, NN)

    _pair(update, (ik >= first) & (ik <= last), q0, k0, bq=bq, bk=bk,
          seq_len=seq_len, causal=causal, window=window)

    @pl.when(ik == last)
    def _finalize():
        for h in range(heads):
            dq_ref[0, :, h * hd:(h + 1) * hd] = \
                (dq_acc[h] * scale).astype(dq_ref.dtype)


def flash_attention_bwd(q, k, v, o, lse, do, *, causal: bool = True,
                        window: int = 0, scale: float | None = None,
                        interpret: bool = True):
    """dq, dk, dv of ``flash_attention`` at (q, k, v), from its output
    ``o``, the ``lse`` of ``flash_attention_fwd`` and the output's
    cotangent ``do``.  P = exp(s - lse) is recomputed block by block and
    dS = P * (dP - D), D = rowsum(do * o); no [S, S] array is formed."""
    B, S, H, hd = q.shape
    KV, hdv = k.shape[2], v.shape[3]
    group = H // KV
    scale = 1.0 / hd ** 0.5 if scale is None else float(scale)
    pn = plan(S, H, KV, hd, q.dtype, causal=causal, window=window, hdv=hdv,
              backward=True)
    bq, bk, hb, kvb, nq, nk = (pn.block_q, pn.block_k, pn.heads,
                               pn.kv_heads, pn.nq, pn.nk)
    nsub = max(1, group // hb)          # head blocks of one KV head block
    d = jnp.einsum("bshd,bshd->bhs", do.astype(jnp.float32),
                   o.astype(jnp.float32))
    lse = _pad_seq(lse, nq * bq, axis=2)[:, :, None]   # [B, H, 1, S]
    d = _pad_seq(d, nq * bq, axis=2)[:, :, None]
    qf = _pad_seq(q.reshape(B, S, H * hd), nq * bq)
    dof = _pad_seq(do.reshape(B, S, H * hdv), nq * bq)
    kf = _pad_seq(k.reshape(B, S, KV * hd), nk * bk)
    vf = _pad_seq(v.reshape(B, S, KV * hdv), nk * bk)
    kw = dict(scale=scale, causal=causal, window=window, bq=bq, bk=bk,
              seq_len=S, heads=hb, group=group, hd=hd, hdv=hdv)
    params = functools.partial(pltpu.CompilerParams,
                               vmem_limit_bytes=BWD_VMEM_LIMIT)

    # dK, dV: grid (B, KV blocks, nk, nsub, nq), the q-blocks innermost
    def q_at(b, g, ik, j, iq):
        # a skipped step maps to a q-block that sees ik: no new DMA
        first, last = _q_range(ik, bq=bq, bk=bk, nq=nq, causal=causal,
                               window=window, hi=jnp.minimum)
        return b, jnp.minimum(jnp.maximum(iq, first), last), g * nsub + j

    def q_rows(*idx):
        b, iq, h = q_at(*idx)
        return b, h, 0, iq

    def kv_at(b, g, ik, j, iq):
        return b, ik, g

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, nq=nq, nsub=nsub, **kw),
        grid=(B, KV // kvb, nk, nsub, nq),
        in_specs=[
            pl.BlockSpec((1, bq, hb * hd), q_at),
            pl.BlockSpec((1, bk, kvb * hd), kv_at),
            pl.BlockSpec((1, bk, kvb * hdv), kv_at),
            pl.BlockSpec((1, bq, hb * hdv), q_at),
            pl.BlockSpec((1, hb, 1, bq), q_rows),
            pl.BlockSpec((1, hb, 1, bq), q_rows),
        ],
        out_specs=[pl.BlockSpec((1, bk, kvb * hd), kv_at),
                   pl.BlockSpec((1, bk, kvb * hdv), kv_at)],
        out_shape=[jax.ShapeDtypeStruct((B, nk * bk, KV * hd), k.dtype),
                   jax.ShapeDtypeStruct((B, nk * bk, KV * hdv), v.dtype)],
        scratch_shapes=[pltpu.VMEM((kvb, bk, hd), jnp.float32),
                        pltpu.VMEM((kvb, bk, hdv), jnp.float32)],
        compiler_params=params(dimension_semantics=(
            "parallel", "parallel", "parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf, dof, lse, d)

    # dQ: grid (B, head blocks, nq, nk), the k-blocks innermost
    q_map, kv_map, rows_map = _q_major_maps(
        bq=bq, bk=bk, nk=nk, causal=causal, window=window, hb=hb,
        group=group, kvb=kvb)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, nk=nk, **kw),
        grid=(B, H // hb, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, hb * hd), q_map),
            pl.BlockSpec((1, bk, kvb * hd), kv_map),
            pl.BlockSpec((1, bk, kvb * hdv), kv_map),
            pl.BlockSpec((1, bq, hb * hdv), q_map),
            pl.BlockSpec((1, hb, 1, bq), rows_map),
            pl.BlockSpec((1, hb, 1, bq), rows_map),
        ],
        out_specs=pl.BlockSpec((1, bq, hb * hd), q_map),
        out_shape=jax.ShapeDtypeStruct((B, nq * bq, H * hd), q.dtype),
        scratch_shapes=[pltpu.VMEM((hb, bq, hd), jnp.float32)],
        compiler_params=params(dimension_semantics=(
            "parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf, dof, lse, d)
    return (dq[:, :S].reshape(B, S, H, hd), dk[:, :S].reshape(B, S, KV, hd),
            dv[:, :S].reshape(B, S, KV, hdv))


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
