"""Stage-decomposable models for the hybrid (tensor × pipeline) axes.

``gpipe_forward`` (core/pipeline.py) needs a model expressed as a
shape-preserving per-stage function; Megatron-style tensor parallelism
additionally needs the stage function to know the tensor mesh axis so it
can place the two collectives of the column→row parallel pair:

  * forward of the row-parallel matmul: psum of the partial products,
    whose backward must be the *identity* (the cotangent is replicated);
  * backward of the column-parallel matmul: the input is replicated over
    the tensor axis, so its cotangent must be summed across tensor ranks
    — ``tensor_copy`` is the identity-forward / psum-backward operator
    (Megatron's conjugate "g" to the forward "f" = ``tensor_reduce``).

Both are ``custom_vjp``-wrapped: under ``shard_map(check_vma=False)``
(the mode these programs run in) a raw ``lax.psum`` transposes to
another psum — pmap semantics — which over-counts the cotangent by the
axis size.  The custom rules pin the correct transposes
(psum ↔ identity), which is exactly Megatron's f/g conjugate pair.

``StagedModel`` is the contract the hybrid engine consumes; the tiny
transformer-FFN block model below is the reference instance (residual
``x + gelu(x @ w_up) @ w_down`` blocks — leaf names chosen so
``core/parallelism.py``'s role table classifies ``w_up`` column-parallel
and ``w_down`` row-parallel).  ``stacked_loss`` runs the same parameters
unpipelined and unsharded — the single-device reference every mesh cell
is validated against.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax


def tensor_copy(axis_name: str):
    """Identity forward, psum-over-``axis_name`` backward — apply to the
    (tensor-replicated) input of a column-parallel matmul so its cotangent
    sums the per-rank partials."""
    @jax.custom_vjp
    def f(x):
        return x

    def fwd(x):
        return x, None

    def bwd(_, g):
        return (lax.psum(g, axis_name),)

    f.defvjp(fwd, bwd)
    return f


def tensor_reduce(axis_name: str):
    """psum-over-``axis_name`` forward, *identity* backward — combine the
    partial products of a row-parallel matmul (the replicated output's
    cotangent flows back to each rank unchanged)."""
    @jax.custom_vjp
    def f(x):
        return lax.psum(x, axis_name)

    def fwd(x):
        return lax.psum(x, axis_name), None

    def bwd(_, g):
        return (g,)

    f.defvjp(fwd, bwd)
    return f


@dataclasses.dataclass(frozen=True)
class StagedModel:
    """A model the hybrid engine can pipeline and tensor-shard.

    stage_fn(stage_params, x, tensor_axis=None) -> y
        Shape-preserving per-stage transform.  When ``tensor_axis`` is a
        mesh axis name, ``stage_params`` arrive tensor-sharded on their
        role dimension and stage_fn must place the Megatron collectives
        (see module docstring); with ``tensor_axis=None`` it computes on
        full weights.
    inputs(batch) -> x [B, ...]
        The activation entering stage 0.
    readout(y, batch) -> scalar
        The loss head, applied to the last stage's outputs.

    Params are NOT carried here — they flow through ``engine.init`` like
    every other engine's, with each leaf carrying a leading stage dim.
    """
    stage_fn: Callable
    inputs: Callable
    readout: Callable


def is_staged_model(obj: Any) -> bool:
    return isinstance(obj, StagedModel)


def stacked_loss(model: StagedModel, params, batch,
                 tensor_axis: Optional[str] = None):
    """Unpipelined reference: run the S stacked stages sequentially on one
    device and apply the loss head — the trajectory every mesh cell must
    reproduce."""
    x = model.inputs(batch)
    n_stages = jax.tree.leaves(params)[0].shape[0]
    for s in range(n_stages):
        sp = jax.tree.map(lambda leaf: leaf[s], params)
        x = model.stage_fn(sp, x, tensor_axis=tensor_axis)
    return model.readout(x, batch)


def stacked_grad_fn(model: StagedModel) -> Callable:
    """(params, batch) -> (loss, grads) over the unpipelined stacked model
    — plugs a StagedModel into any data-parallel-only engine or the
    simulator as a reference."""
    def grad_fn(params, batch):
        return jax.value_and_grad(
            lambda p: stacked_loss(model, p, batch))(params)
    return grad_fn


# ------------------------------------------------- reference tiny model
def make_tiny_transformer(stages: int, d_model: int = 8, d_ff: int = 16,
                          seed: int = 0):
    """Residual transformer-FFN blocks (the tiny cross-check model of the
    hybrid acceptance tests): ``stages`` blocks of
    ``x + gelu(x @ w_up) @ w_down``, stacked on a leading stage dim.

    Returns ``(params, model)``; targets live in ``batch["y"]`` and the
    loss is mean squared error on the final activations."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    scale_up = 1.0 / jnp.sqrt(d_model)
    scale_dn = 1.0 / jnp.sqrt(d_ff)
    params = {
        "w_up": jax.random.normal(k1, (stages, d_model, d_ff)) * scale_up,
        "w_down": jax.random.normal(k2, (stages, d_ff, d_model)) * scale_dn,
    }

    def stage_fn(sp, x, tensor_axis=None):
        xin = x
        if tensor_axis is not None:
            x = tensor_copy(tensor_axis)(x)
        h = jax.nn.gelu(x @ sp["w_up"])      # column-parallel: local cols
        y = h @ sp["w_down"]                 # row-parallel: partial product
        if tensor_axis is not None:
            y = tensor_reduce(tensor_axis)(y)
        return xin + y

    def inputs(batch):
        return batch["x"]

    def readout(y, batch):
        return jnp.mean((y - batch["y"]) ** 2)

    return params, StagedModel(stage_fn=stage_fn, inputs=inputs,
                               readout=readout)
