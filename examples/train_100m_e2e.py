"""End-to-end driver (deliverable b): train a ~100M-param llama-family
model for a few hundred steps on the synthetic pipeline, with cosine
schedule, checkpointing every N steps, and a final registry entry.

  PYTHONPATH=src python examples/train_100m_e2e.py --steps 300
(CPU: ~1-4 s/step at the default batch; use --steps 30 for a quick pass.)

The parallel-training strategy is one declarative spec string
(``Strategy.parse``; see docs/strategies.md for the grammar and matrix):

  --strategy bsp/allreduce/onebit@8   8-worker BSP, TicTac-bucketed ring
                                      allreduce, 1-bit EF compression,
                                      AdamW + cosine schedule under
                                      shard_map (the full trainer path)
  --strategy bsp/ps/dgc:0.05@8        centralized ZeRO-style PS arch
  --strategy ssp:3/allreduce/onebit@8 bounded-staleness on devices
                                      (Strategy engine path, SGD)
  --strategy bsp/ps/none@4:d4.z3.adamw  ZeRO-3-sharded AdamW over the
                                      data axis (hybrid engine; the
                                      tensor/stage mesh axes need a
                                      StagedModel — docs/hybrid.md)

Under ``JAX_PLATFORMS=cpu``, multi-worker specs re-exec with that many
virtual host devices; on an accelerator host the workers are its chips.

``--failure-plan "crash:w1@5,resize:4@10"`` demonstrates elastic
fault-tolerant training end to end: the run snapshots through
repro.checkpoint, loses worker 1 before step 5, recovers from the latest
checkpoint, reshards to the survivors, and grows back to 4 workers at
step 10 — all in one process (docs/elasticity.md):

  PYTHONPATH=src python examples/train_100m_e2e.py --steps 30 \
      --strategy ssp:2/allreduce/onebit@4 --failure-plan crash:w1@5 \
      --checkpoint-every 5
"""
import argparse
import dataclasses
import json
import os
import sys
import time


def _spec_workers(spec: str) -> int:
    """Worker count from a strategy spec string, pre-jax-import (the full
    parse lives in repro.train.strategy, which imports jax).  The worker
    segment may carry a mesh suffix: ``@8:d2.t2.s2`` (docs/hybrid.md)."""
    if "@" not in spec:
        return 1
    return int(spec.rsplit("@", 1)[1].split(":", 1)[0])


def _maybe_reexec_with_devices():
    """Virtual host devices must be configured before jax import.  Only a
    CPU run gets them: a chip host's devices are its chips, and a child
    process could not reach a chip this one holds."""
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        return
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--strategy", default="bsp/allreduce/none@1")
    n = _spec_workers(ap.parse_known_args()[0].strategy)
    if n > 1 and "xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={n}").strip()
        os.execv(sys.executable, [sys.executable] + sys.argv)


_maybe_reexec_with_devices()

import jax                                        # noqa: E402
import jax.numpy as jnp                           # noqa: E402
import numpy as np                                # noqa: E402
from jax.sharding import Mesh                     # noqa: E402

from repro.checkpoint import ModelRegistry, save_checkpoint   # noqa: E402
from repro.configs import get_config              # noqa: E402
from repro.core.precision import PrecisionPolicy  # noqa: E402
from repro.data import LMDataConfig, make_lm_batches  # noqa: E402
from repro.launch.env import use_compile_cache  # noqa: E402
from repro.models import build_model              # noqa: E402
from repro.optim import AdamW                     # noqa: E402
from repro.optim.schedule import cosine_warmup    # noqa: E402
from repro.train import (Strategy, Trainer, TrainState,  # noqa: E402
                         make_train_step, train_loop,
                         make_bucketed_allreduce, make_sharded_train_step)
from repro.train.data_parallel import AXIS        # noqa: E402


def _fit_with_optimizer(strat, model, params, batches, args):
    """The full trainer path (AdamW + cosine + checkpointable TrainState)
    for bsp/allreduce specs — compression and worker count come from the
    strategy; K>1 lifts the step under shard_map."""
    opt = AdamW(0.01)
    compressor = strat.compressor
    K = strat.workers
    if K > 1:
        reduce_fn = make_bucketed_allreduce(
            params, topology=strat.topology, bucket_mb=strat.bucket_mb,
            order=strat.order)
        step = make_train_step(
            model.loss_fn, opt, cosine_warmup(args.lr, 20, args.steps),
            precision=PrecisionPolicy(compute_dtype="float32"),
            compressor=compressor, reduce_fn=reduce_fn)
        state = TrainState.create(params, opt, compressor)
        if state["ef"] is not None:     # per-worker error-feedback state
            state["ef"] = jax.tree.map(
                lambda x: jnp.zeros((K,) + x.shape, x.dtype), state["ef"])
        if len(jax.devices()) < K:      # e.g. caller pre-set XLA_FLAGS low
            raise SystemExit(
                f"need {K} devices, have {len(jax.devices())}; unset "
                "XLA_FLAGS or set --xla_force_host_platform_device_count")
        mesh = Mesh(np.array(jax.devices()[:K]), (AXIS,))
        sharded = make_sharded_train_step(step, mesh,
                                          compressed=state["ef"] is not None)
        print(f"data-parallel: {strat.spec()}, "
              f"{len(reduce_fn.fused_layers)} buckets ({strat.order} order)")

        def stacked_batch(t):
            per = [batches(t, w) for w in range(K)]
            return jax.tree.map(lambda *xs: jnp.stack(xs), *per)

        state, hist = train_loop(sharded, state, stacked_batch,
                                 args.steps, log_every=10, jit=False)
    else:
        step = make_train_step(
            model.loss_fn, opt, cosine_warmup(args.lr, 20, args.steps),
            precision=PrecisionPolicy(compute_dtype="float32"),
            compressor=compressor)
        state = TrainState.create(params, opt, compressor)
        state, hist = train_loop(step, state, lambda t: batches(t, 0),
                                 args.steps, log_every=10)
    return state["params"], hist


def _fit_with_strategy_engine(strat, model, params, batches, args):
    """Every other cell (ssp/asp staleness replay, arch=ps, sma) goes
    through the Strategy engine (SGD at --engine-lr) via Trainer.fit.
    With --failure-plan the run goes through the elastic trainer: the
    engine is snapshotted every --checkpoint-every steps and survives the
    plan's crashes/resizes/stragglers in process (docs/elasticity.md)."""
    def grad_fn(p, batch):
        (loss, _), g = jax.value_and_grad(
            lambda pp: model.loss_fn(pp, batch, compute_dtype=jnp.float32),
            has_aux=True)(p)
        return loss, g

    strat = dataclasses.replace(strat, lr=args.engine_lr)
    trainer = Trainer(strat)
    if args.failure_plan:
        params, hist, mets = trainer.fit(
            grad_fn, params, batches, args.steps, plan=args.failure_plan,
            checkpoint_dir=os.path.join(args.out, "elastic_ckpts"),
            checkpoint_every=args.checkpoint_every)
        for r in mets["recoveries"]:
            print(f"  {r['kind']} at step {r['at']}: restored step "
                  f"{r['restored_step']} ({r['lost_steps']} steps lost, "
                  f"{r['wall_s']:.2f}s), now {r['workers']} workers")
        print(f"elastic: {len(mets['recoveries'])} recoveries, "
              f"{mets['resizes']} resizes, "
              f"{mets['executed_steps']} steps executed for "
              f"{args.steps} committed "
              f"(goodput {args.steps / mets['executed_steps']:.2f}), "
              f"{mets.get('dropped_updates', 0)} straggler pushes dropped")
    else:
        params, hist, mets = trainer.fit(grad_fn, params, batches,
                                         args.steps)
    print(f"strategy engine: {mets['spec']} on {mets['backend']} backend, "
          f"{mets['wire_bytes']} wire B total")
    return params, hist


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch-size", type=int, default=4)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--lr", type=float, default=6e-4)
    ap.add_argument("--strategy", default="bsp/allreduce/none@1",
                    help="parallel-training spec: "
                         "sync[:staleness]/arch/comp[:density]@workers, "
                         "e.g. bsp/allreduce/onebit@8 (docs/strategies.md)")
    ap.add_argument("--engine-lr", type=float, default=0.05,
                    help="SGD lr for non-bsp/allreduce cells, which train "
                         "through the Strategy engine instead of AdamW")
    ap.add_argument("--failure-plan", default="",
                    help="elastic event plan, e.g. 'crash:w1@5,resize:4@10'"
                         " — inject a mid-run crash + recovery (grammar in"
                         " docs/elasticity.md; routes through the Strategy"
                         " engine + elastic trainer)")
    ap.add_argument("--checkpoint-every", type=int, default=10,
                    help="elastic snapshot cadence (global steps)")
    ap.add_argument("--wire", choices=("modeled", "measured"),
                    default="modeled",
                    help="wire accounting / exchange mode (docs/comm.md):"
                         " 'measured' moves the encoded payloads inside"
                         " the collective schedule and counts the planes"
                         " actually exchanged (device cells only)")
    ap.add_argument("--out", default="results/train_100m")
    args = ap.parse_args()
    use_compile_cache()
    # workers default must agree with the pre-jax re-exec hook, which
    # reads only the "@N" suffix (no "@N" -> 1 worker, not Strategy's 4)
    strat = Strategy.parse(args.strategy,
                           workers=_spec_workers(args.strategy),
                           wire=args.wire)

    # ~100M-param member of the tinyllama (llama2) family
    cfg = dataclasses.replace(
        get_config("tinyllama-1.1b"),
        name="tinyllama-100m", num_layers=10, d_model=640, d_ff=2560,
        num_heads=10, num_kv_heads=2, head_dim=64, vocab_size=32000)
    print(f"{cfg.name}: {cfg.param_count() / 1e6:.1f}M params")

    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    data = LMDataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq_len,
                        batch_size=args.batch_size)
    batches = make_lm_batches(data)

    os.makedirs(args.out, exist_ok=True)
    t0 = time.time()
    if args.failure_plan:
        params, hist = _fit_with_strategy_engine(strat, model, params,
                                                 batches, args)
        trainer_used, lr_used = "strategy-engine-elastic", args.engine_lr
    elif strat.sync == "bsp" and strat.arch == "allreduce" \
            and not strat.is_hybrid and strat.wire == "modeled":
        # measured-wire cells route through the Strategy engine below —
        # the in-schedule codec exchange lives in the engines
        params, hist = _fit_with_optimizer(strat, model, params, batches,
                                           args)
        trainer_used, lr_used = "adamw+cosine", args.lr
    else:
        params, hist = _fit_with_strategy_engine(strat, model, params,
                                                 batches, args)
        trainer_used, lr_used = "strategy-engine-sgd", args.engine_lr
    wall = time.time() - t0
    with open(os.path.join(args.out, "history.json"), "w") as f:
        json.dump(hist, f, indent=1)
    ck = os.path.join(args.out, "ckpt_final")
    save_checkpoint(ck, params, step=args.steps)
    reg = ModelRegistry(os.path.join(args.out, "registry"))
    reg.register("tinyllama-100m", ck, arch=cfg.name,
                 hyperparams={"lr": lr_used, "trainer": trainer_used,
                              "steps": args.steps,
                              "strategy": strat.spec()},
                 metrics={"final_loss": hist[-1]["loss"]})
    print(f"loss {hist[0]['loss']:.3f} -> {hist[-1]['loss']:.3f} "
          f"in {wall:.0f}s ({wall / args.steps:.2f}s/step)")


if __name__ == "__main__":
    main()
