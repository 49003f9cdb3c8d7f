"""The trainer: composes model loss, optimizer, LR schedule, precision
policy, and (optionally) a gradient compressor — the full data-parallel
step the survey's Figure 4 describes, in one jitted function.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.core.compression import Compressor, EF_METHODS
from repro.core.precision import PrecisionPolicy, DEFAULT
from repro.obs.trace import span
from repro.optim.schedule import constant


class TrainState:
    """Factory for the train-state pytree (a plain dict with keys
    params / opt_state / step / ef)."""
    @staticmethod
    def create(params, opt, compressor: Optional[Compressor] = None):
        return dict(
            params=params,
            opt_state=opt.init(params),
            step=jnp.zeros((), jnp.int32),
            ef=(compressor.init_state(params)
                if compressor and compressor.method in EF_METHODS
                else None),
        )


def make_train_step(loss_fn: Callable, opt, lr_schedule=None,
                    precision: PrecisionPolicy = DEFAULT,
                    compressor: Optional[Compressor] = None,
                    remat: bool = False,
                    reduce_fn: Optional[Callable] = None):
    """loss_fn(params, batch, compute_dtype) -> (loss, metrics).

    ``reduce_fn(grads) -> grads`` runs after compression roundtrip — a
    data-parallel caller passes the bucketed topology allreduce here (the
    step is then used inside ``shard_map``; see train/data_parallel.py).

    Returns train_step(state, batch, rng) -> (state, metrics)."""
    lr_schedule = lr_schedule or constant(1e-3)

    def train_step(state: Dict, batch, rng=None):
        def lf(p):
            return loss_fn(p, batch, compute_dtype=precision.cdt)
        if remat:
            lf = jax.checkpoint(lf)
        (loss, mets), grads = jax.value_and_grad(lf, has_aux=True)(
            state["params"])
        grads = precision.cast_for_reduce(grads)
        wire = jnp.int32(0)
        ef = state["ef"]
        if compressor is not None and compressor.method != "none":
            grads, ef, wire_py = compressor.roundtrip(grads, ef, rng)
            wire = jnp.int32(wire_py % (2**31 - 1))
        if reduce_fn is not None:
            grads = reduce_fn(grads)
        lr = lr_schedule(state["step"])
        params, opt_state = opt.step(state["params"], grads,
                                     state["opt_state"], lr)
        new_state = dict(params=params, opt_state=opt_state,
                         step=state["step"] + 1, ef=ef)
        mets = dict(mets)
        mets.update(loss=loss, lr=lr, wire_bytes=wire)
        return new_state, mets

    return train_step


def train_loop(train_step, state, batch_fn: Callable[[int], Any],
               steps: int, log_every: int = 10, jit: bool = True,
               rng=None):
    """The single host driver loop: drives ``make_train_step`` steps in
    the examples AND every Strategy engine (``repro.train.strategy.fit``
    adapts the Engine protocol onto this same contract, with ``batch_fn``
    yielding the global-step index).  Returns (state, history)."""
    step_fn = jax.jit(train_step) if jit else train_step
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    hist = []
    t0 = time.time()
    for t in range(steps):
        # one ``train.step`` per global step on the device trace's clock,
        # and the recorder's ``step`` span on the deterministic step
        # clock; engines emit their sub-spans inside it
        # (docs/observability.md)
        with span("train.step", "step", pid="train", tid="loop",
                  cat="train", clock=("train_step", t), step=t):
            rng, sub = jax.random.split(rng)
            state, mets = step_fn(state, batch_fn(t), sub)
            if t % log_every == 0 or t == steps - 1:
                rec = {k: float(v) for k, v in mets.items()}
                rec["step"] = t
                rec["wall_s"] = time.time() - t0
                hist.append(rec)
    return state, hist
