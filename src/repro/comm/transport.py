"""Topology schedule generators: collective schedules whose steps carry
*encoded* segment payloads (survey §3.3.1(2) × §3.3.3 co-design).

Two families live here:

1. The **exact** schedules (the pre-refactor ``core/allreduce.py``
   topologies, moved verbatim): full-precision ppermute schedules,
   numerically equal to ``psum``.  ``core/allreduce.py`` re-exports them,
   and every ``none``-codec exchange runs them unchanged — that is the
   bitwise-compatibility contract of the refactor.

2. The **codec** schedules (``compressed_allreduce`` /
   ``compressed_reduce_scatter``): the same topologies, but every
   transmission is ``encode → ppermute the planes → decode``:

   * ring reduce-scatter: each hop encodes the *partial sum* it forwards;
     the hop's quantization error is accumulated into the sender's
     error-feedback residual at that chunk position (per-link EF — the
     residual re-enters the sender's own gradient next step).
   * ring all-gather: the chunk's owner encodes its reduced chunk *once*
     (owner EF) and the planes are relayed unchanged around the ring, so
     every worker decodes identical bytes — replicated parameters cannot
     drift.
   * tree: re-encode up the reduce tree (sender EF per hop); the root
     encodes the total once and the planes broadcast down unchanged.
   * butterfly: lossy butterfly runs *halving-doubling* (recursive-halving
     reduce-scatter with hop EF + an all-gather of the owner-encoded
     planes).  A lossy recursive-doubling exchange would hand every
     worker a differently-quantized sum — inconsistent replicas — so the
     exact and lossy butterfly schedules intentionally differ; the byte
     models below account for both.
   * fully-connected: every worker encodes its own contribution once and
     all-gathers the planes; everyone decodes the same n payloads.

   All generators return ``(result, residual, sent_elems)`` where
   ``residual`` is the flat per-worker EF contribution of every encode
   this worker performed and ``sent_elems`` is the traced count of
   data-dependent sparse elements shipped (dgc; 0 otherwise).

Byte accounting: ``schedule_tx_bytes`` is the *mean per-worker* bytes a
schedule puts on the wire (total transmissions / n) for the shape-static
part of the payloads; ``model_error_factor`` documents the exact ratio
between the legacy critical-path model ``per_device_bytes`` and this
mean-tx measure per topology (see docs/comm.md).
"""
from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.comm.codecs import NoneCodec, SegmentCodec


# ======================================================= chunk layout
# The ring and halving schedules address chunk ``(me - i) % n`` of a
# buffer split into ``n`` chunks.  Stored as ``[n, m]``, the chunk axis
# falls inside the TPU's (8, 128) tile, so every dynamic chunk read or
# update cuts across all tiles, and the TPU compiler's codegen time grew
# with the bytes reduced (compiling a 1-layer tinyllama grad step with a
# per-leaf ring for four v5e chips: 65 s, against 6.5 s with psum and
# 8.6 s in this layout).  Chunks are kept as ``[n, R, 128]``
# rows instead, each zero-padded to whole rows: a chunk is one
# contiguous block, and the padding never leaves the device.
LANES = 128


def _rows(flat, count: int):
    """``count`` chunks, flat ``[count * m]``, as ``[count, R, LANES]``."""
    x = flat.reshape(count, -1)
    x = jnp.pad(x, ((0, 0), (0, (-x.shape[1]) % LANES)))
    return x.reshape(count, -1, LANES)


def _flat(rows, m: int):
    """Inverse of ``_rows``: ``[count, R, LANES]`` -> ``[count * m]``."""
    return rows.reshape(rows.shape[0], -1)[:, :m].reshape(-1)


def _take(rows, start, count: int, m: int):
    """Chunks ``start .. start + count - 1`` of ``rows``, flat."""
    return _flat(lax.dynamic_slice_in_dim(rows, start, count), m)


# ===================================================== exact schedules
# (the pre-refactor core/allreduce.py implementations; core/allreduce.py
# re-exports them so existing call sites — and bitwise behaviour — are
# unchanged)
def ring_allreduce(x, axis_name: str):
    """Bandwidth-optimal ring: reduce-scatter then all-gather, 2(n-1) steps."""
    n = jax.lax.axis_size(axis_name)
    if n == 1:
        return x
    me = lax.axis_index(axis_name)
    shape, dtype = x.shape, x.dtype
    flat = x.reshape(-1)
    m = -(-flat.shape[0] // n)
    chunks = _rows(jnp.pad(flat, (0, n * m - flat.shape[0])), n)
    fwd = [(i, (i + 1) % n) for i in range(n)]

    def rs_step(i, c):
        send = _take(c, (me - i) % n, 1, m)
        recv = lax.ppermute(send, axis_name, fwd)
        return c.at[(me - i - 1) % n].add(_rows(recv, 1)[0])

    chunks = lax.fori_loop(0, n - 1, rs_step, chunks)
    # rank r now owns reduced chunk (r + 1) % n

    def ag_step(i, c):
        send = _take(c, (me + 1 - i) % n, 1, m)
        recv = lax.ppermute(send, axis_name, fwd)
        return c.at[(me - i) % n].set(_rows(recv, 1)[0])

    chunks = lax.fori_loop(0, n - 1, ag_step, chunks)
    return _flat(chunks, m)[:flat.shape[0]].reshape(shape).astype(dtype)


def butterfly_allreduce(x, axis_name: str):
    """Recursive doubling: log2(n) exchange-and-add rounds (n power of 2)."""
    n = jax.lax.axis_size(axis_name)
    if n == 1:
        return x
    assert n & (n - 1) == 0, "butterfly requires power-of-two workers"
    acc = x
    for k in range(int(math.log2(n))):
        d = 1 << k
        perm = [(i, i ^ d) for i in range(n)]
        acc = acc + lax.ppermute(acc, axis_name, perm)
    return acc


def tree_allreduce(x, axis_name: str):
    """Binomial tree: reduce to rank 0, then broadcast back down."""
    n = jax.lax.axis_size(axis_name)
    if n == 1:
        return x
    me = lax.axis_index(axis_name)
    levels = int(math.log2(n))
    assert 1 << levels == n, "tree requires power-of-two workers"
    acc = x
    # reduce phase: at level k, ranks with me % 2^(k+1) == 2^k send down
    for k in range(levels):
        d = 1 << k
        perm = [(i, i - d) for i in range(n) if i % (2 * d) == d]
        recv = lax.ppermute(acc, axis_name, perm)
        is_receiver = (me % (2 * d)) == 0
        acc = jnp.where(is_receiver, acc + recv, acc)
    # broadcast phase
    for k in reversed(range(levels)):
        d = 1 << k
        perm = [(i, i + d) for i in range(n) if i % (2 * d) == 0]
        recv = lax.ppermute(acc, axis_name, perm)
        is_receiver = (me % (2 * d)) == d
        acc = jnp.where(is_receiver, recv, acc)
    return acc


def fully_connected_allreduce(x, axis_name: str):
    """Every worker sends its full tensor to every other (the O(n^2) traffic
    case the survey warns about); numerically an all_gather + sum."""
    g = lax.all_gather(x, axis_name)
    return jnp.sum(g, axis=0).astype(x.dtype)


def psum_allreduce(x, axis_name: str):
    return lax.psum(x, axis_name)


SCHEDULES = {
    "ring": ring_allreduce,
    "butterfly": butterfly_allreduce,
    "tree": tree_allreduce,
    "fully_connected": fully_connected_allreduce,
    "psum": psum_allreduce,
}


# ===================================================== codec schedules
def _permute(planes: Dict[str, Any], axis_name: str, perm):
    return jax.tree.map(
        lambda p: lax.ppermute(p, axis_name, perm), planes)


def _where_planes(cond, new: Dict[str, Any], old: Dict[str, Any]):
    return jax.tree.map(lambda a, b: jnp.where(cond, a, b), new, old)


def _ring_rs(flat, axis_name: str, codec: SegmentCodec, key, n: int):
    """Compressed ring reduce-scatter: rank r ends owning reduced chunk r.
    Returns (chunks as ``_rows`` with chunk me reduced, residual as
    ``_rows``, sent_elems, key)."""
    me = lax.axis_index(axis_name)
    m = flat.shape[0] // n
    c = _rows(flat, n)
    res = jnp.zeros_like(c)
    sent = jnp.zeros((), jnp.int32)
    fwd = [(i, (i + 1) % n) for i in range(n)]

    def step(i, carry):
        c, res, sent, key = carry
        key, sub = jax.random.split(key)
        pos = (me - i - 1) % n
        send = _take(c, pos, 1, m)
        # fused encode + hop EF: one read of the chunk yields the planes
        # and the quantization residual (send - decode) together
        planes, r = codec.encode_ef(send, sub)
        res = res.at[pos].add(_rows(r, 1)[0])
        sent = sent + codec.sent_elems(planes)
        planes = _permute(planes, axis_name, fwd)
        recv = codec.decode(planes)[:m]
        return (c.at[(me - i - 2) % n].add(_rows(recv, 1)[0]), res, sent,
                key)

    return lax.fori_loop(0, n - 1, step, (c, res, sent, key))


def _owner_encode(c, res, pos, m: int, codec: SegmentCodec, key):
    """Encode chunk ``pos`` (of length ``m``, in ``_rows`` layout) once at
    its owner (EF the encode error) and replace it with its own decode so
    every worker — owner included — consumes identical bytes.  Encoding
    is not itself a transmission: the caller's distribution loop counts
    every send of these planes."""
    x = _take(c, pos, 1, m)
    planes = codec.encode(x, key)
    dec = codec.decode(planes)[:m]
    res = res.at[pos].add(_rows(x - dec, 1)[0])
    return c.at[pos].set(_rows(dec, 1)[0]), res, planes


def _ring_exchange(flat, axis_name: str, codec: SegmentCodec, key):
    n = jax.lax.axis_size(axis_name)
    me = lax.axis_index(axis_name)
    m = flat.shape[0] // n
    fwd = [(i, (i + 1) % n) for i in range(n)]
    c, res, sent, key = _ring_rs(flat, axis_name, codec, key, n)
    key, sub = jax.random.split(key)
    c, res, planes = _owner_encode(c, res, me, m, codec, sub)

    def ag_step(i, carry):
        c, planes, sent = carry
        # one transmission per hop: i=0 is the owner's own send, later
        # iterations are relays — n-1 sends total per plane
        sent = sent + codec.sent_elems(planes)
        planes = _permute(planes, axis_name, fwd)
        c = c.at[(me - 1 - i) % n].set(_rows(codec.decode(planes)[:m],
                                             1)[0])
        return c, planes, sent

    c, _, sent = lax.fori_loop(0, n - 1, ag_step, (c, planes, sent))
    return _flat(c, m), _flat(res, m), sent


def _butterfly_exchange(flat, axis_name: str, codec: SegmentCodec, key):
    """Halving-doubling: recursive-halving RS (hop EF) + an all-gather of
    the owner-encoded chunk planes (consistent decode everywhere)."""
    n = jax.lax.axis_size(axis_name)
    assert n & (n - 1) == 0, "butterfly requires power-of-two workers"
    me = lax.axis_index(axis_name)
    m = flat.shape[0] // n
    acc = _rows(flat, n)
    res = jnp.zeros_like(acc)
    sent = jnp.zeros((), jnp.int32)
    levels = int(math.log2(n))
    for k in range(levels):
        d = n >> (k + 1)                      # rank and chunk distance
        base = me & ~((n >> k) - 1)
        has_upper = (me & d) != 0
        my_start = base + jnp.where(has_upper, d, 0)
        send_start = base + jnp.where(has_upper, 0, d)
        send = _take(acc, send_start, d, m)
        key, sub = jax.random.split(key)
        planes, r = codec.encode_ef(send, sub)
        res_slice = lax.dynamic_slice_in_dim(res, send_start, d)
        res = lax.dynamic_update_slice_in_dim(
            res, res_slice + _rows(r, d), send_start, 0)
        sent = sent + codec.sent_elems(planes)
        planes = _permute(planes, axis_name, [(i, i ^ d) for i in range(n)])
        recv = _rows(codec.decode(planes)[:d * m], d)
        mine = lax.dynamic_slice_in_dim(acc, my_start, d)
        acc = lax.dynamic_update_slice_in_dim(acc, mine + recv, my_start, 0)
    key, sub = jax.random.split(key)
    acc, res, planes = _owner_encode(acc, res, me, m, codec, sub)
    sent = sent + codec.sent_elems(planes) * (n - 1)   # AG transmissions
    gathered = jax.tree.map(lambda p: lax.all_gather(p, axis_name), planes)
    chunks = jax.vmap(codec.decode)(gathered)[:, :m]   # [n, m], identical
    return chunks.reshape(-1), _flat(res, m), sent


def _tree_exchange(flat, axis_name: str, codec: SegmentCodec, key):
    n = jax.lax.axis_size(axis_name)
    me = lax.axis_index(axis_name)
    L = flat.shape[0]
    levels = int(math.log2(n))
    assert 1 << levels == n, "tree requires power-of-two workers"
    acc = flat
    res = jnp.zeros_like(flat)
    sent = jnp.zeros((), jnp.int32)
    # reduce: senders re-encode their partial and EF the encode error
    for k in range(levels):
        d = 1 << k
        is_sender = (me % (2 * d)) == d
        is_receiver = (me % (2 * d)) == 0
        key, sub = jax.random.split(key)
        planes, r = codec.encode_ef(acc, sub)
        res = res + jnp.where(is_sender, r, 0.0)
        sent = sent + jnp.where(is_sender, codec.sent_elems(planes), 0)
        perm = [(i, i - d) for i in range(n) if i % (2 * d) == d]
        recv = codec.decode(_permute(planes, axis_name, perm))[:L]
        acc = jnp.where(is_receiver, acc + recv, acc)
    # root encodes the total once; the planes broadcast down *unchanged*
    # (the broadcast loop counts each of the n-1 forwards — encoding
    # itself is not a transmission)
    key, sub = jax.random.split(key)
    planes, r = codec.encode_ef(acc, sub)
    res = res + jnp.where(me == 0, r, 0.0)
    for k in reversed(range(levels)):
        d = 1 << k
        is_sender = (me % (2 * d)) == 0
        is_receiver = (me % (2 * d)) == d
        sent = sent + jnp.where(is_sender, codec.sent_elems(planes), 0)
        perm = [(i, i + d) for i in range(n) if i % (2 * d) == 0]
        recv = _permute(planes, axis_name, perm)
        planes = _where_planes(is_receiver, recv, planes)
    return codec.decode(planes)[:L], res, sent


def _fully_connected_exchange(flat, axis_name: str, codec: SegmentCodec,
                              key):
    n = jax.lax.axis_size(axis_name)
    L = flat.shape[0]
    key, sub = jax.random.split(key)
    planes, res = codec.encode_ef(flat, sub)
    sent = codec.sent_elems(planes) * (n - 1)
    gathered = jax.tree.map(lambda p: lax.all_gather(p, axis_name), planes)
    out = jnp.sum(jax.vmap(codec.decode)(gathered)[:, :L], axis=0)
    return out, res, sent


_CODEC_EXCHANGES = {
    "ring": _ring_exchange,
    "psum": _ring_exchange,        # psum ring-schedules on the torus
    "butterfly": _butterfly_exchange,
    "tree": _tree_exchange,
    "fully_connected": _fully_connected_exchange,
}


def compressed_allreduce(flat, axis_name: str, topology: str,
                         codec: SegmentCodec, key
                         ) -> Tuple[Any, Any, Any]:
    """Sum-allreduce a flat fp32 vector with encoded payloads inside the
    ``topology`` schedule.  ``flat`` must be padded so every chunk is a
    whole number of LANE-wide rows (``pad_for_schedule``).  Returns
    ``(reduced_sum, ef_residual, sent_elems)``; callers divide by the
    axis size for mean semantics and fold the residual into per-worker
    error-feedback state."""
    return _CODEC_EXCHANGES[topology](flat, axis_name, codec, key)


def compressed_reduce_scatter(flat, axis_name: str, codec: SegmentCodec,
                              key) -> Tuple[Any, Any, Any]:
    """Compressed ring reduce-scatter: rank r receives the reduced chunk
    r of ``flat`` (shape [len/n]).  Returns (my_shard_sum, residual,
    sent_elems) — the gradient-push half of the PS / ZeRO exchange."""
    n = jax.lax.axis_size(axis_name)
    me = lax.axis_index(axis_name)
    m = flat.shape[0] // n
    c, res, sent, _ = _ring_rs(flat, axis_name, codec, key, n)
    return _take(c, me, 1, m), _flat(res, m), sent


def compressed_allreduce_ef(flat, ef, axis_name: str, topology: str,
                            codec: SegmentCodec, key, *, gain: float = 1.0
                            ) -> Tuple[Any, Any, Any]:
    """EF-compensated exchange: the transport owns the whole residual
    lifecycle — compensate ``c_in = flat + gain*ef``, run the codec
    schedule (every hop's encode is the fused ``encode_ef``), and fold
    the hop residuals into the returned next-step EF vector, measured
    against the true compensated gradient ``flat + ef`` so the
    telescoping invariant holds for any over-relaxation gain.  Callers
    (``CommPlan``) hand the residual down instead of applying EF as
    separate jnp passes around the schedule.  Returns
    ``(reduced_sum, new_ef, sent_elems)``."""
    cin = flat + gain * ef
    red, res, sent = _CODEC_EXCHANGES[topology](cin, axis_name, codec, key)
    return red, (flat + ef) - cin + res, sent


def compressed_reduce_scatter_ef(flat, ef, axis_name: str,
                                 codec: SegmentCodec, key, *,
                                 gain: float = 1.0) -> Tuple[Any, Any, Any]:
    """EF-compensated ring reduce-scatter (see ``compressed_allreduce_ef``
    — the PS/ZeRO gradient-push counterpart)."""
    cin = flat + gain * ef
    shard, res, sent = compressed_reduce_scatter(cin, axis_name, codec, key)
    return shard, (flat + ef) - cin + res, sent


def pad_for_schedule(length: int, n: int) -> int:
    """Padded flat length for a chunked schedule: a whole number of 1/n
    chunks (codecs row-pad each payload internally)."""
    return n * (-(-length // n))


# ======================================================== byte models
def per_device_bytes(topology: str, n: int, size_bytes: float) -> float:
    """Analytic critical-path traffic for one exchange (the pre-refactor
    benchmark model, unchanged): the bytes crossing the busiest device's
    links.  See ``model_error_factor`` for how it relates to the measured
    mean per-worker tx bytes."""
    if n == 1:
        return 0.0
    if topology in ("ring", "psum"):
        return 2 * (n - 1) / n * size_bytes
    if topology == "butterfly":
        return math.log2(n) * size_bytes
    if topology == "tree":
        return 2 * math.log2(n) * size_bytes
    if topology == "fully_connected":
        return (n - 1) * size_bytes
    raise ValueError(topology)


def schedule_tx_bytes(topology: str, n: int, length: int,
                      codec: SegmentCodec) -> float:
    """Mean per-worker bytes one exchange of a padded length-``length``
    segment puts on the wire (total schedule transmissions / n), for the
    shape-static part of the codec's payloads.  This is what the measured
    wire accounting reports for static codecs; dgc adds 8 B per traced
    ``sent_elems``."""
    if n == 1:
        return 0.0
    m = -(-length // n)
    e = codec.static_tx_bytes
    if topology in ("ring", "psum"):
        # RS: n-1 hop encodes; AG: owner encode relayed n-1 hops
        return (n - 1) * e(m) + (n - 1) * e(m)
    if topology == "butterfly":
        if codec.exact:
            return math.log2(n) * e(length)       # recursive doubling
        rs = sum(e((n >> (k + 1)) * m) for k in range(int(math.log2(n))))
        return rs + (n - 1) * e(m)                # halving + plane AG
    if topology == "tree":
        # n-1 reduce sends + n-1 broadcast forwards of the full payload
        return 2 * (n - 1) / n * e(length)
    if topology == "fully_connected":
        return (n - 1) * e(length)
    raise ValueError(topology)


def fp32_schedule_bytes(topology: str, n: int, length: int) -> float:
    """Mean per-worker tx bytes of the full-precision schedule — the
    baseline the compressed-payload ratios are quoted against."""
    return schedule_tx_bytes(topology, n, length, NoneCodec())


def model_error_factor(topology: str, n: int, exact: bool = True) -> float:
    """The documented ratio ``per_device_bytes / schedule_tx_bytes`` per
    topology (docs/comm.md): the critical-path model counts the busiest
    device (tree: the root's rx+tx), the measured accounting counts the
    mean per-worker tx.  Divide ``per_device_bytes`` by this factor to
    predict the measured value."""
    if n == 1:
        return 1.0
    if topology in ("ring", "psum", "fully_connected"):
        return 1.0
    if topology == "tree":
        return math.log2(n) * n / (n - 1)
    if topology == "butterfly":
        if exact:
            return 1.0
        return math.log2(n) * n / (2 * (n - 1))
    raise ValueError(topology)
