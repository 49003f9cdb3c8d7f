"""Quickstart: build an assigned architecture, train it on the synthetic
pipeline, checkpoint + register it, and decode from it — the whole public
API in ~80 lines.

The parallel-training strategy is one declarative spec string
(``Strategy.parse``; grammar and matrix in docs/strategies.md):

  PYTHONPATH=src python examples/quickstart.py                # 1-bit EF BSP
  PYTHONPATH=src python examples/quickstart.py --strategy ssp:2/ps/onebit@4

The default single-worker BSP spec trains through ``make_train_step``
(Adam); any other cell trains through the Strategy engine — on this
single-device process the ``auto`` backend picks the deterministic
simulator, so multi-worker specs need no device re-exec here.
"""
import argparse
import os
import tempfile

import jax

from repro.checkpoint import ModelRegistry, load_checkpoint, save_checkpoint
from repro.configs import get_config
from repro.core.precision import PrecisionPolicy
from repro.data import LMDataConfig, make_lm_batches
from repro.launch.env import use_compile_cache
from repro.models import build_model
from repro.optim import Adam
from repro.serve import generate
from repro.train import Strategy, Trainer, TrainState, make_train_step, \
    train_loop


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--strategy", default="bsp/allreduce/onebit@1",
                    help="sync[:staleness]/arch/comp[:density]@workers")
    args = ap.parse_args()
    use_compile_cache()
    # like train_100m_e2e: a spec without "@N" means 1 worker here, not
    # Strategy's default of 4 — keeps --strategy bsp/allreduce/dgc on the
    # single-worker Adam path
    strat = Strategy.parse(args.strategy, lr=0.05, workers=1)

    # 1. model (reduced variant of the assigned config, CPU-sized)
    cfg = get_config(args.arch).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    # 2. data pipeline (deterministic synthetic LM stream)
    data = LMDataConfig(vocab_size=cfg.vocab_size, seq_len=64, batch_size=8)
    batches = make_lm_batches(data)

    # 3. trainer, configured by the strategy spec
    comp = strat.compressor
    if strat.workers == 1 and strat.sync == "bsp" and \
            strat.arch == "allreduce":
        # single-worker BSP: the jitted Adam train step
        step = make_train_step(
            model.loss_fn, Adam(),
            precision=PrecisionPolicy(compute_dtype="float32"),
            compressor=comp)
        state = TrainState.create(params, Adam(), comp)
        state, hist = train_loop(step, state, lambda t: batches(t, 0),
                                 args.steps,
                                 log_every=max(1, args.steps // 5))
        trained = state["params"]
        print(f"loss {hist[0]['loss']:.3f} -> {hist[-1]['loss']:.3f} "
              f"({hist[-1]['wire_bytes']:.0f} wire B/step, "
              f"{comp.method} compression)")
    else:
        # any other cell: the declarative Strategy engine (SGD)
        def grad_fn(p, batch):
            (loss, _), g = jax.value_and_grad(
                lambda pp: model.loss_fn(pp, batch,
                                         compute_dtype=jax.numpy.float32),
                has_aux=True)(p)
            return loss, g

        trained, hist, mets = Trainer(strat).fit(
            grad_fn, params, batches, args.steps)
        print(f"{mets['spec']} on {mets['backend']} backend: loss "
              f"{hist[0]['loss']:.3f} -> {hist[-1]['loss']:.3f} "
              f"({mets['wire_bytes']} wire B total)")

    # 4. checkpoint + registry (ModelDB-style)
    root = tempfile.mkdtemp(prefix="repro-quickstart-")
    ck = os.path.join(root, "ckpt")
    save_checkpoint(ck, trained, step=args.steps)
    reg = ModelRegistry(os.path.join(root, "registry"))
    mid = reg.register("quickstart", ck, arch=cfg.name,
                       metrics={"loss": hist[-1]["loss"]})
    print("registered:", mid)

    # 5. reload + decode
    restored, _ = load_checkpoint(ck, trained)
    prompt = jax.numpy.asarray([[1, 2, 3, 4]])
    out = generate(model, restored, prompt, max_new_tokens=12)
    print("decoded:", out[0].tolist())


if __name__ == "__main__":
    main()
