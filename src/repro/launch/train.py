"""Training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
      --smoke --steps 20

runs a reduced config on the process's default device: a TPU chip where
JAX finds one, the CPU under ``JAX_PLATFORMS=cpu`` (Pallas kernels then
run in interpret mode).  ``chip_smoke.py`` at the repo root drives the
Strategy trainer and the serving engine at published widths on a chip.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core.compression import Compressor
from repro.core.precision import PrecisionPolicy
from repro.data import LMDataConfig, make_lm_batches
from repro.launch.env import use_compile_cache
from repro.models import build_model
from repro.optim import OPTIMIZERS
from repro.optim.schedule import cosine_warmup
from repro.train import TrainState, make_train_step, train_loop


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config on host devices")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--optimizer", default="adam", choices=list(OPTIMIZERS))
    ap.add_argument("--compress", default="none",
                    choices=["none", "onebit", "terngrad", "qsgd", "dgc"])
    ap.add_argument("--compute-dtype", default="float32")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="write a Chrome trace (Perfetto-loadable) of the "
                         "run; see docs/observability.md")
    ap.add_argument("--report", action="store_true",
                    help="print the trace analysis (step-time "
                         "attribution etc.) after the run; implies "
                         "tracing even without --trace")
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.reduced()
    model = build_model(cfg)
    key = jax.random.PRNGKey(0)
    params = model.init(key)

    data_cfg = LMDataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq_len,
                            batch_size=args.batch_size)
    batches = make_lm_batches(data_cfg)

    if cfg.is_encoder_decoder:
        F = cfg.max_source_positions
        fkey = jax.random.PRNGKey(7)

        def batch_fn(t):
            b = batches(t)
            return {"frames": jax.random.normal(
                        jax.random.fold_in(fkey, t),
                        (args.batch_size, F, cfg.d_model)),
                    "tokens": b["tokens"], "labels": b["labels"]}
    else:
        def batch_fn(t):
            return batches(t)

    opt = OPTIMIZERS[args.optimizer]()
    comp = Compressor(args.compress)
    precision = PrecisionPolicy(compute_dtype=args.compute_dtype)
    step = make_train_step(model.loss_fn, opt,
                           cosine_warmup(args.lr, 5, args.steps),
                           precision=precision, compressor=comp)
    state = TrainState.create(params, opt, comp)
    t0 = time.time()
    rec = None
    with contextlib.ExitStack() as stack:
        if args.trace or args.report:
            from repro.obs.trace import tracing
            rec = stack.enter_context(tracing(args.trace))
        state, hist = train_loop(step, state, batch_fn, args.steps,
                                 log_every=max(1, args.steps // 10))
    if args.trace:
        print(f"trace written to {args.trace}")
    if args.report and rec is not None:
        from repro.obs.report import render
        print(render(rec.to_chrome()))
    for rec in hist:
        print(json.dumps({k: round(v, 5) for k, v in rec.items()}))
    print(f"done in {time.time() - t0:.1f}s; "
          f"loss {hist[0]['loss']:.3f} -> {hist[-1]['loss']:.3f}")


if __name__ == "__main__":
    main()
