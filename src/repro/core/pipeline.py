"""Pipeline parallelism (survey §3.2.3): GPipe-style micro-batch pipeline
[Huang et al., 70] over a dedicated 'stage' mesh axis.

Each device along the stage axis holds one stage's parameters; activations
flow stage-to-stage with ``jax.lax.ppermute`` while micro-batches stream
through — at tick t, stage s processes micro-batch (t - s).  The schedule
runs inside ``lax.scan`` so it is differentiable (ppermute has a transpose
rule), giving real pipelined training, and the bubble fraction
(S-1)/(M+S-1) is observable in the tick count.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax



def gpipe_forward(stage_fn: Callable, stage_params, x_micro, axis_name: str):
    """Run inside shard_map over ``axis_name``.

    stage_fn(params, x) -> y with x/y of identical shape [mb, ...].
    stage_params: this device's stage parameters (already sharded).
    x_micro [n_micro, mb, ...]: full micro-batched input (replicated; only
    stage 0 reads it).
    Returns [n_micro, mb, ...]: outputs (nonzero only on the last stage —
    psum over the axis to broadcast if needed).
    """
    n = jax.lax.axis_size(axis_name)
    me = lax.axis_index(axis_name)
    n_micro = x_micro.shape[0]
    mb_shape = x_micro.shape[1:]
    fwd = [(i, i + 1) for i in range(n - 1)]

    def tick(carry, t):
        inbox, outputs = carry
        mb_idx = t - me
        active = (mb_idx >= 0) & (mb_idx < n_micro)
        src = lax.dynamic_index_in_dim(
            x_micro, jnp.clip(t, 0, n_micro - 1), 0, keepdims=False)
        x_in = jnp.where(me == 0, src, inbox)
        y = stage_fn(stage_params, x_in)
        y = jnp.where(active, y, jnp.zeros_like(y))
        inbox_next = lax.ppermute(y, axis_name, fwd)
        is_last = me == n - 1
        idx = jnp.clip(mb_idx, 0, n_micro - 1)
        upd = lax.dynamic_update_index_in_dim(outputs, y, idx, 0)
        outputs = jnp.where(active & is_last, upd, outputs)
        return (inbox_next, outputs), None

    inbox0 = jnp.zeros(mb_shape, dtype=x_micro.dtype)
    outputs0 = jnp.zeros_like(x_micro)
    # mark the carries as device-varying along the stage axis (scan-vma rule)
    try:
        inbox0 = lax.pcast(inbox0, (axis_name,), to="varying")
        outputs0 = lax.pcast(outputs0, (axis_name,), to="varying")
    except (AttributeError, TypeError):
        pass  # older jax: carries infer vma automatically
    (_, outputs), _ = lax.scan(tick, (inbox0, outputs0),
                               jnp.arange(n_micro + n - 1))
    return outputs


def onefb_forward(stage_fn: Callable, stage_params, x_micro, axis_name: str,
                  interleave: int = 2):
    """Interleaved 1F1B schedule (PipeDream-flush / Megatron-style virtual
    stages).  Run inside shard_map over ``axis_name``.

    Each of the S stage devices holds ``interleave`` (= v) **virtual
    stages**: its local stacked parameter block is split into v contiguous
    chunks of ``layers_local / v`` layers, and chunk c on device i is
    global virtual stage ``c*S + i`` (the engine lays params out so this
    round-robin placement holds).  Device i computes (chunk c, micro k)
    at tick ``c*m + k + i``; activations hop the ring ``i -> (i+1) % S``
    every tick, with the wrap link (S-1 -> 0) feeding a FIFO that device 0
    drains m - S ticks later for the next chunk.  The schedule runs
    ``v*m + S - 1`` ticks of ``1/v`` the per-tick work, so the bubble
    fraction drops from GPipe's (S-1)/(m+S-1) to (S-1)/(v*m+S-1).

    Requires ``n_micro >= S`` (the wrap FIFO gap m - S must be >= 0) and
    the local layer count divisible by ``interleave``.  ``interleave=1``
    is plain non-interleaved 1F1B — same bubble as GPipe at uniform tick
    cost, scheduled via the ring.  Fully differentiable: dynamic_slice /
    ppermute / scan all have transpose rules, so the backward pass runs
    the reverse schedule and gradients accumulate across micro-batches
    and chunks inside the scan, exactly as in ``gpipe_forward``.

    stage_fn(chunk_params, x) -> y applies ONE chunk (leading dim
    ``layers_local / v``) to x of shape [mb, ...].
    Returns [n_micro, mb, ...], nonzero only on the last stage device.
    """
    n = jax.lax.axis_size(axis_name)
    me = lax.axis_index(axis_name)
    v = int(interleave)
    n_micro = x_micro.shape[0]
    mb_shape = x_micro.shape[1:]
    if n_micro < n:
        raise ValueError(
            f"1f1b needs micro_batches >= stages (got m={n_micro} < s={n})")
    layers_local = jax.tree.leaves(stage_params)[0].shape[0]
    if layers_local % v:
        raise ValueError(
            f"local layer count {layers_local} not divisible by "
            f"interleave={v}")
    cl = layers_local // v
    ring = [(i, (i + 1) % n) for i in range(n)]

    def tick(carry, t):
        inbox, fifo, outputs = carry
        rel = t - me
        c = jnp.clip(rel // n_micro, 0, v - 1)
        k = rel % n_micro
        active = (rel >= 0) & (rel < v * n_micro)
        # the wrap link delivered stage S-1's tick-(t-1) output for
        # (chunk c', micro k') with k' = (t - S) mod m: bank it first so
        # a gap-0 consume (m == S) still sees it this tick
        slot = (t - n) % n_micro
        fifo = jnp.where(me == 0,
                         lax.dynamic_update_index_in_dim(fifo, inbox, slot, 0),
                         fifo)
        src = lax.dynamic_index_in_dim(x_micro, k, 0, keepdims=False)
        buf = lax.dynamic_index_in_dim(fifo, k, 0, keepdims=False)
        x_in = jnp.where(me == 0, jnp.where(c == 0, src, buf), inbox)
        sp = jax.tree.map(
            lambda leaf: lax.dynamic_slice_in_dim(leaf, c * cl, cl, 0),
            stage_params)
        y = stage_fn(sp, x_in)
        y = jnp.where(active, y, jnp.zeros_like(y))
        inbox_next = lax.ppermute(y, axis_name, ring)
        is_out = active & (me == n - 1) & (c == v - 1)
        upd = lax.dynamic_update_index_in_dim(outputs, y, k, 0)
        outputs = jnp.where(is_out, upd, outputs)
        return (inbox_next, fifo, outputs), None

    inbox0 = jnp.zeros(mb_shape, dtype=x_micro.dtype)
    fifo0 = jnp.zeros((n_micro,) + mb_shape, dtype=x_micro.dtype)
    outputs0 = jnp.zeros_like(x_micro)
    try:
        inbox0 = lax.pcast(inbox0, (axis_name,), to="varying")
        fifo0 = lax.pcast(fifo0, (axis_name,), to="varying")
        outputs0 = lax.pcast(outputs0, (axis_name,), to="varying")
    except (AttributeError, TypeError):
        pass  # older jax: carries infer vma automatically
    (_, _, outputs), _ = lax.scan(tick, (inbox0, fifo0, outputs0),
                                  jnp.arange(v * n_micro + n - 1))
    return outputs


def gpipe_ticks(n_stages: int, n_micro: int) -> int:
    """Ticks the schedule runs for: the last micro-batch enters at tick
    ``n_micro - 1`` and drains through ``n_stages - 1`` more hops.  Every
    device executes exactly this many stage calls, so the tick count is
    also the per-stage compute (and ppermute) multiplier the hybrid
    engine's modeled accounting uses."""
    return n_micro + n_stages - 1


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    """GPipe pipeline bubble: idle fraction of the schedule."""
    return (n_stages - 1) / gpipe_ticks(n_stages, n_micro)


def onefb_ticks(n_stages: int, n_micro: int, interleave: int = 2) -> int:
    """Interleaved-1F1B tick count: v*m chunk-calls per device plus the
    S-1 fill/drain.  Each tick costs 1/v of a GPipe tick (one chunk of
    ``layers_local / v`` layers), so total work is unchanged while the
    fill/drain overhead shrinks by v."""
    return interleave * n_micro + n_stages - 1


def onefb_bubble_fraction(n_stages: int, n_micro: int,
                          interleave: int = 2) -> float:
    """Interleaved-1F1B bubble: (S-1)/(v*m + S-1) — strictly below
    GPipe's (S-1)/(m + S-1) whenever v > 1."""
    return (n_stages - 1) / onefb_ticks(n_stages, n_micro, interleave)


def stacked_forward(stage_fn: Callable, stage_params, x_micro):
    """Unpipelined single-device reference for ``gpipe_forward``: apply
    the S stacked stages sequentially to every micro-batch.  The pipeline
    loss/grad tests assert the scan+ppermute schedule reproduces this to
    float tolerance — including micro-batch counts that do not divide the
    stage count (the bubble just grows)."""
    n_stages = jax.tree.leaves(stage_params)[0].shape[0]
    y = x_micro
    for s in range(n_stages):
        sp = jax.tree.map(lambda leaf: leaf[s], stage_params)
        y = jax.vmap(lambda mb: stage_fn(sp, mb))(y)
    return y
