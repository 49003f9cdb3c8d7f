"""Centralized architecture (survey §3.3.1(1)) adapted to TPU SPMD.

A literal parameter server (separate processes + RPC) has no TPU-pod
analogue; the faithful adaptation (DESIGN.md §2.2) keeps the PS's defining
property — *the optimizer state for each parameter shard lives in exactly
one place* — by sharding parameters/optimizer state across workers and
expressing push/pull as reduce-scatter / all-gather:

  push(grads)  : reduce_scatter over the worker axis -> my shard's grads
  update       : optimizer step on my 1/n shard only (the "server" work)
  pull(params) : all_gather my updated shard back to all workers

vs. the decentralized architecture where update work is replicated after an
all-reduce.  Traffic per device is identical (RS + AG == ring AR) but update
FLOPs/memory drop by n — exactly the ZeRO observation, and the TPU-native
form of the survey's PS-vs-allreduce dichotomy.  The benchmark quantifies
this trade-off.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax



def _pad_to(x, n):
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % n
    return jnp.pad(flat, (0, pad)), flat.shape[0]


def push_reduce_scatter(g, axis_name: str):
    """Gradient pytree -> my shard of the summed gradient (flat per leaf)."""
    n = jax.lax.axis_size(axis_name)

    def one(x):
        flat, _ = _pad_to(x, n)
        return lax.psum_scatter(flat.reshape(n, -1), axis_name,
                                scatter_dimension=0, tiled=False)
    return jax.tree.map(one, g)


def pull_all_gather(shard, shapes, axis_name: str):
    """My updated shards -> full parameter pytree on every worker."""
    def one(s, ref):
        full = lax.all_gather(s, axis_name).reshape(-1)[:ref.size]
        return full.reshape(ref.shape).astype(ref.dtype)
    return jax.tree.map(one, shard, shapes)


def sgd_update_fn(lr: float, mean_over=1) -> Callable:
    """The plain-SGD ``update_fn`` for ``make_ps_step``: each worker
    updates its own shard (the "server" work), optionally dividing the
    pushed gradient *sum* by ``mean_over`` workers.  This is the update
    the Strategy device backend (train/data_parallel.py) routes through
    for ``arch=ps`` — bucketed BSP pushes pass ``mean_over=axis_size``,
    single-worker SSP/ASP pushes use the raw sum."""
    def update(p_shard, g_shard, opt_shard):
        return (jax.tree.map(lambda p, g: p - lr * (g / mean_over),
                             p_shard, g_shard), opt_shard)
    return update


def make_ps_step(update_fn: Callable, axis_name: str):
    """update_fn(param_shard, grad_shard, opt_shard) ->
    (new_param_shard, new_opt_shard).

    Returns ps_step(params, grads, opt_state) to be used inside shard_map:
    each worker plays parameter-server for its 1/n shard."""
    def ps_step(params, grads, opt_state):
        n = jax.lax.axis_size(axis_name)
        g_shards = push_reduce_scatter(grads, axis_name)
        p_shards = jax.tree.map(
            lambda x: _shard_of(x, axis_name, n), params)
        new_p, new_opt = update_fn(p_shards, g_shards, opt_state)
        new_params = pull_all_gather(new_p, params, axis_name)
        return new_params, new_opt
    return ps_step


def _shard_of(x, axis_name: str, n: int):
    me = lax.axis_index(axis_name)
    flat, _ = _pad_to(x, n)
    m = flat.shape[0] // n
    return lax.dynamic_slice(flat, (me * m,), (m,))


# ----------------------------------------------- flat-shard public surface
# (the ZeRO optimizer-state sharding of repro.parallel routes through
# these, so the PS path and the ZeRO path cannot diverge)
def pad_to_multiple(x, n: int):
    """Flatten ``x`` and zero-pad to a multiple of ``n``.  Returns
    (padded_flat, original_flat_length)."""
    return _pad_to(x, n)


def shard_of_flat(x, axis_name: str):
    """My rank's 1/n shard of ``x`` (flattened, zero-padded) over
    ``axis_name`` — the PS "my parameters" view."""
    return _shard_of(x, axis_name, jax.lax.axis_size(axis_name))


def reduce_scatter_flat(flat, axis_name: str):
    """Sum-reduce a (padded) flat vector over ``axis_name``, delivering
    each rank its own contiguous shard — the PS push."""
    n = jax.lax.axis_size(axis_name)
    return lax.psum_scatter(flat.reshape(n, -1), axis_name,
                            scatter_dimension=0, tiled=False)


def all_gather_flat(shard, axis_name: str, length: int):
    """Concatenate per-rank shards back into the first ``length`` elements
    of the flat vector — the PS pull."""
    return lax.all_gather(shard, axis_name).reshape(-1)[:length]


def init_opt_shards(params, n: int, init_leaf: Callable):
    """Per-worker optimizer shard sizes (flat, padded length // n)."""
    def one(x):
        size = x.size
        m = (size + (-size) % n) // n
        return init_leaf(m)
    return jax.tree.map(one, params)
