"""ZeRO optimizer-state sharding over the data axis (Rajbhandari et al.),
as a first-class Strategy dimension (``z1``/``z2``/``z3`` mesh tokens).

The survey's PS-vs-allreduce dichotomy already gave this repo the
reduce-scatter / shard-update / all-gather path (core/parameter_server.py,
``arch=ps``); ZeRO is that path with the *persistent* state progressively
sharded over the D data-parallel ranks:

  level  persistent per-rank state          data-axis exchange per step
  z0     params + opt                       allreduce(grads)
  z1     params + opt/D                     allreduce(grads) + allgather(params)
  z2     params + opt/D                     reduce-scatter(grads) + allgather(params)
  z3     params/D + opt/D                   allgather(params) + reduce-scatter(grads)

z1 and z2 hold the same persistent state; they differ in the gradient
exchange (z1 materializes the full reduced gradient on every rank, z2
reduce-scatters so each rank only ever owns its shard) and therefore in
wire/transient-memory accounting.  z3 additionally shards the parameters
themselves: each step starts by all-gathering the param shards for
compute and ends by updating only the local shard.

Everything here operates on *flat per-bucket vectors* over the same
fused-bucket plan (``MeshPlan``) the data-parallel engine executes, and
is meant to run inside ``shard_map`` with a ``data`` mesh axis.  The
optimizer step works on shard pytrees, so ``repro.optim.adam.Adam`` (and
plain SGD) apply unchanged — the Adam moments simply live sharded.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.parameter_server import (all_gather_flat, pad_to_multiple,
                                         reduce_scatter_flat, shard_of_flat)
from repro.optim.adam import AdamW
from repro.parallel.mesh_plan import MeshPlan

ZERO_LEVELS = (0, 1, 2, 3)


def make_optimizer_step(optimizer: str, lr: float,
                        moment_dtype: str = "float32") -> Callable:
    """(params, grads, opt_state) -> (new_params, new_opt_state) on any
    pytree — full leaves (z0) or flat shards (z1-z3) alike.
    ``moment_dtype="bfloat16"`` stores the AdamW EMA buffers quantized
    (olmax-style); math stays fp32."""
    if optimizer == "sgd":
        def sgd_step(p, g, opt):
            return jax.tree.map(lambda a, b: a - lr * b, p, g), opt
        return sgd_step
    if optimizer == "adamw":
        adam = AdamW(moment_dtype=moment_dtype)

        def adam_step(p, g, opt):
            return adam.step(p, g, opt, lr)
        return adam_step
    raise ValueError(f"optimizer={optimizer!r} (want sgd | adamw)")


def init_opt_state(optimizer: str, params_like,
                   moment_dtype: str = "float32"):
    """Optimizer state matching ``params_like`` (full leaves or shards);
    None for stateless SGD."""
    if optimizer == "sgd":
        return None
    return AdamW(moment_dtype=moment_dtype).init(params_like)


def flatten_bucket(leaves: List[Any], idxs: List[int]) -> Any:
    """Concatenate the chosen leaves into one fp32 flat vector."""
    return jnp.concatenate(
        [leaves[i].astype(jnp.float32).reshape(-1) for i in idxs])


def make_zero_bucket_update(plan: MeshPlan, zero: int, optimizer: str,
                            lr: float, axis: str = "data",
                            moment_dtype: str = "float32") -> Callable:
    """Build the per-step ZeRO-1/2/3 update over ``plan``'s buckets.

    Returns ``update(p_buckets, g_buckets, opt, grad_reduce=None) ->
    (new_p_buckets, new_opt)`` where the bucket lists follow
    ``plan.order`` issue order; for z1/z2 ``p_buckets`` are full flat
    buckets in and out, for z3 they are per-rank shards in and out (the
    engine owns the gather-for-compute side).  ``opt`` is the sharded
    optimizer state ({"m","v","t"} of per-bucket shards for adamw, None
    for sgd).  Gradient buckets are summed over ``axis`` and divided by
    the axis size (mean semantics, matching the allreduce path).

    ``grad_reduce(padded_flat, bucket_pos) -> my_shard_sum`` replaces the
    default full-precision psum / reduce-scatter with a caller-supplied
    exchange — the hook the hybrid engine uses to route the gradient push
    through the compressed-payload schedules of ``repro.comm`` under
    ``wire="measured"`` (parameters still travel exact)."""
    if zero not in (1, 2, 3):
        raise ValueError(f"zero={zero} (bucket update is for levels 1-3)")
    opt_step = make_optimizer_step(optimizer, lr, moment_dtype)
    n_data = plan.mesh.data
    sizes = [plan.bucket_sizes[b] for b in plan.order]

    def update(p_buckets, g_buckets, opt, grad_reduce=None):
        g_shards = []
        for j, (g, n_b) in enumerate(zip(g_buckets, sizes)):
            padded, _ = pad_to_multiple(g, n_data)
            if grad_reduce is not None:
                g_shards.append(grad_reduce(padded, j))
            elif zero == 1:
                # full allreduce, then slice my shard (grads materialize
                # everywhere — ZeRO-1 only shards the *optimizer* state)
                g_shards.append(shard_of_flat(lax.psum(padded, axis), axis))
            else:
                g_shards.append(reduce_scatter_flat(padded, axis))
        g_shards = [g / n_data for g in g_shards]
        if zero == 3:
            p_shards = list(p_buckets)
        else:
            p_shards = [shard_of_flat(pad_to_multiple(p, n_data)[0], axis)
                        for p in p_buckets]
        new_shards, new_opt = opt_step(p_shards, g_shards, opt)
        if zero == 3:
            return new_shards, new_opt
        return [all_gather_flat(s, axis, n_b)
                for s, n_b in zip(new_shards, sizes)], new_opt

    return update


# --------------------------------------------------------- memory model
def state_bytes_per_device(plan: MeshPlan, zero: int, optimizer: str,
                           moment_dtype: str = "float32") -> Dict[str, int]:
    """Analytic persistent param+optimizer bytes per device for the mesh
    — the memory math of docs/hybrid.md (fp32 params; moments at
    ``moment_dtype`` width, 2 B when quantized to bf16)."""
    n_local = plan.n_local_params
    shard = sum(plan.shard_sizes)        # padded 1/D of the local block
    params = shard if zero == 3 else n_local
    adam = AdamW(moment_dtype=moment_dtype)
    moments = adam.moments_per_param if optimizer == "adamw" else 0
    mb = adam.moment_bytes
    opt = moments * (shard if zero >= 1 else n_local)
    return {"params": 4 * params, "opt": mb * opt,
            "total": 4 * params + mb * opt}


def wire_bytes_per_device(plan: MeshPlan, zero: int,
                          grad_bytes: Optional[int] = None) -> int:
    """Modeled data-axis bytes one device moves per step under the ZeRO
    exchange schedule (ring collectives: AR = 2(D-1)/D, RS = AG =
    (D-1)/D of the payload).  ``grad_bytes`` defaults to the dense local
    gradient size; pass the compressor's accounting for compressed runs."""
    d = plan.mesh.data
    if d == 1:
        return 0
    n_local = 4 * plan.n_local_params
    g = n_local if grad_bytes is None else grad_bytes
    ar, rs = 2 * (d - 1) / d, (d - 1) / d
    if zero == 0:
        return int(ar * g)
    if zero == 1:
        return int(ar * g + rs * n_local)          # AR grads + AG params
    return int(rs * g + rs * n_local)              # RS grads + AG params
