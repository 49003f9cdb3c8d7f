"""Topology-explicit allreduce schedules (survey §3.3.1(2)) built from
``jax.lax.ppermute`` inside ``shard_map``.

On the GPU clusters the survey describes, the topology (ring / tree /
butterfly / fully-connected) is a software overlay on a switched network.
On a TPU torus the ICI *is* the topology, so these become collective
*schedules*; the benchmark compares their per-device traffic against XLA's
native ``psum`` (which ring-schedules on the torus already) — quantifying
when a hand-rolled schedule loses to the compiler's.

As of the communication-plane refactor the schedules live in
``repro.comm.transport`` as *schedule generators*: the same topologies
can carry **encoded segment payloads** (encode → ppermute the planes →
decode-accumulate, per-worker error feedback) when a ``CommPlan`` runs
with ``wire="measured"``.  This module re-exports the exact
full-precision forms — unchanged, still numerically equal to ``psum``
(tested on 8 host devices) — and the legacy analytic traffic model.

Per-device bytes moved for an n-worker reduce of a size-S tensor:
  ring            2 (n-1)/n S        (bandwidth-optimal)
  butterfly       log2(n) S          (recursive doubling / halving-doubling)
  tree            2 log2(n) S        (reduce to root + broadcast)
  fully-connected (n-1) S            (every worker sends its full tensor)
"""
from __future__ import annotations

import jax

from repro.comm.transport import (SCHEDULES, butterfly_allreduce,
                                  fully_connected_allreduce, per_device_bytes,
                                  psum_allreduce, ring_allreduce,
                                  tree_allreduce)

TOPOLOGIES = SCHEDULES

__all__ = ["TOPOLOGIES", "ring_allreduce", "butterfly_allreduce",
           "tree_allreduce", "fully_connected_allreduce", "psum_allreduce",
           "per_device_bytes", "make_allreduce"]


# ------------------------------------------------------------------- frontend
def make_allreduce(topology: str, axis_name: str, mean: bool = True):
    """Returns f(pytree) -> pytree applying the chosen schedule per leaf.
    Must be called inside shard_map over ``axis_name``."""
    fn = TOPOLOGIES[topology]

    def reduce_tree(tree):
        def one(x):
            y = fn(x, axis_name)
            if mean:
                y = y / jax.lax.axis_size(axis_name)
            return y.astype(x.dtype)
        return jax.tree.map(one, tree)

    return reduce_tree
