"""Smoke run of the training and serving paths on TPU chips.

  python chip_smoke.py             # one chip: train + serve phases
  python chip_smoke.py --chips 4   # four chips: data-parallel and
                                   # tensor-parallel decode vs references

One chip drives the repo's normal entry points at stablelm-1.6b's
published widths (d_model 2048, 32x64 MHA, d_ff 5632, vocab 100352) with
random weights from ``--seed``:

  train  ``Trainer(Strategy.parse("bsp/allreduce/onebit@1",
         backend="device")).fit`` for a few steps, depth cut so fp32
         params, onebit error feedback, grads and activations fit 16 GB;
  serve  ``ServeEngine`` with all 24 layers, bf16, paged KV cache,
         continuous batching over requests of 128-512 prompt tokens.

``--chips 4`` runs only the paths that exist across chips: the
data-parallel ``bsp/ring`` engine at tinyllama-1.1b's published widths
against the simulator oracle plus the measured-vs-modelled wire bytes of
``bsp/ring/onebit@4``, and ``ServeConfig(tp=4)`` serving against
``tp=1``.

Each check prints a line and raises on failure.  Times printed are smoke
timings (compiles included, with whatever the compile cache held), not
benchmark metrics.  Without a TPU the script exits non-zero before
printing any result.  The last stdout line is ``{"ok": true, "device":
{"platform", "kind", "count"}}``, ``count`` being the chips the run used.
"""
import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

STABLELM = "stablelm-1.6b"
TRAIN_LAYERS = 4            # of 24
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 1, 1024, 3
SERVE_PROMPTS = (128, 256, 384, 512) * 2
SERVE_NEW_TOKENS = 32
DP_ARCH, DP_LAYERS = "tinyllama-1.1b", 1   # of 22
DP_BATCH, DP_SEQ, DP_STEPS = 1, 512, 3
TP = 4

# Tolerances, each beside the reason it is what it is.
#
# Train loss, kernel vs ref attention, fp32 compute at "highest" matmul
# precision: the paths differ only in how the flash kernel orders the
# softmax sums, an fp32 rounding effect near 1e-6 of the loss.
LOSS_RTOL = 1e-4
# Serve: relative L2 distance of bf16 prefill logits (flash kernel) from
# the fp32 ref-path forward.  bf16 keeps 8 mantissa bits (2^-9 relative
# per rounding) and every layer's activations are rounded; a wrong mask,
# a dropped head or a mis-tiled block moves the logits by O(1).
SERVE_LOGIT_RTOL = 5e-2
# Data parallel, device engine vs the simulator: the same per-worker
# gradients at "highest" precision, summed in another order (ring
# reduce-scatter vs the simulator's mean) -- fp32 rounding; 1e-4 is the
# bar the CPU tests have held since the engine was written.
DP_LOSS_RTOL = 1e-4
# Measured vs modelled wire bytes: exact.  Every tinyllama leaf is a
# multiple of 2048 elements, so each bucket's four ring chunks are whole
# 256-wide codec rows and the critical-path model has no row padding to
# miss (`make comm`'s 25% covers chunks that end mid-row).
# TP serving, engine path: fp32 params, compute and cache at "highest"
# matmul precision, where tp=4 differs from tp=1 only in the order its
# psum adds four fp32 partial products (~1e-7 relative) -- far below the
# gap between a random model's two best logits, so every greedy token
# must be equal.
# TP decode logits, bf16: tp=4 rounds each rank's row-parallel partial
# product to bf16 before the psum where tp=1 rounds once after an fp32
# accumulate.  Its logits must agree with tp=1 about as well as bf16
# agrees with fp32: within TP_GAP_FACTOR times the tp=1 step's own
# bf16-vs-fp32 distance.
TP_GAP_FACTOR = 3.0


def check(ok, what):
    print(f"check {what}: {'ok' if ok else 'FAILED'}", flush=True)
    if not ok:
        raise SystemExit(f"chip_smoke: check failed: {what}")


def rel_l2(a, b):
    import numpy as np
    a = np.asarray(a, np.float64).ravel()
    b = np.asarray(b, np.float64).ravel()
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def has_kernel(jitted, *args):
    """Whether the compiled program of ``jitted`` at ``args`` calls a
    Mosaic (Pallas) kernel."""
    return "tpu_custom_call" in jitted.lower(*args).compile().as_text()


def smoke_time(name, t0):
    print(f"smoke timing (not a benchmark metric): {name} "
          f"{time.perf_counter() - t0:.1f} s", flush=True)


def peak_memory(name):
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        print(f"{name}: peak device memory so far "
              f"{stats['peak_bytes_in_use'] / 2**30:.2f} GiB of "
              f"{stats['bytes_limit'] / 2**30:.2f} GiB", flush=True)


def requests(vocab, prompt_lens, new_tokens, seed):
    import numpy as np
    from repro.serve.request import Request
    rng = np.random.RandomState(seed)
    return [Request(rid=i, prompt=[int(t) for t in
                                   rng.randint(1, vocab, size=n)],
                    max_new_tokens=new_tokens)
            for i, n in enumerate(prompt_lens)]


def lm_grad_fn(model):
    import jax
    import jax.numpy as jnp

    def grad_fn(p, batch):
        (loss, _), g = jax.value_and_grad(
            lambda pp: model.loss_fn(pp, batch, compute_dtype=jnp.float32),
            has_aux=True)(p)
        return loss, g
    return grad_fn


# ------------------------------------------------------------- one chip
def train_phase(seed):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_config
    from repro.data import LMDataConfig, make_lm_batches
    from repro.models import build_model
    from repro.train import Strategy, Trainer

    cfg = dataclasses.replace(get_config(STABLELM), num_layers=TRAIN_LAYERS)
    print(f"train: {STABLELM} at published widths (d_model {cfg.d_model}, "
          f"{cfg.num_heads}x{cfg.head_dim} heads, d_ff {cfg.d_ff}, vocab "
          f"{cfg.vocab_size}); depth cut 24 -> {TRAIN_LAYERS} layers "
          f"({cfg.param_count() / 1e6:.0f}M params) so fp32 params + "
          f"onebit EF + grads + activations fit one chip; batch "
          f"{TRAIN_BATCH} x seq {TRAIN_SEQ}, {TRAIN_STEPS} steps",
          flush=True)
    model = build_model(cfg)
    model_ref = build_model(dataclasses.replace(cfg, attn_backend="ref"))
    params = model.init(jax.random.PRNGKey(seed))
    batches = make_lm_batches(LMDataConfig(
        vocab_size=cfg.vocab_size, seq_len=TRAIN_SEQ,
        batch_size=TRAIN_BATCH, seed=seed))

    t0 = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        losses = [float(jax.jit(lambda p, b, _m=m: _m.loss_fn(
            p, b, compute_dtype=jnp.float32)[0])(params, batches(0, 0)))
            for m in (model, model_ref)]
    err = abs(losses[0] - losses[1]) / abs(losses[1])
    print(f"train: first-batch loss kernel {losses[0]!r} ref {losses[1]!r} "
          f"(rel diff {err:.3g}, tol {LOSS_RTOL})")
    check(err <= LOSS_RTOL, "train loss kernel attention == ref attention")
    smoke_time("train kernel-vs-ref loss", t0)

    t0 = time.perf_counter()
    trainer = Trainer(Strategy.parse("bsp/allreduce/onebit@1",
                                     backend="device"))
    params, hist, mets = trainer.fit(lm_grad_fn(model), params, batches,
                                     TRAIN_STEPS)
    losses = [e["loss"] for e in hist]
    print(f"train: losses {losses}, backend {mets['backend']}, "
          f"wire {mets['wire_bytes']} B")
    check(len(losses) == TRAIN_STEPS and all(np.isfinite(losses)),
          "train loss finite at every step")
    check(mets["backend"] == "device", "train engine backend is device")
    smoke_time("train fit (compile + steps)", t0)
    peak_memory("train")

    # the engine's jitted BSP step, at the shapes it ran with (a worker
    # axis of 1 leads every per-worker argument)
    def per_worker(x, dtype=None):
        return jax.ShapeDtypeStruct((1,) + x.shape, dtype or x.dtype)
    args = (jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                         params),
            jax.tree.map(lambda x: per_worker(x, jnp.float32), params),
            jax.tree.map(per_worker, batches(0, 0)),
            jax.ShapeDtypeStruct((1, 2), jnp.uint32),
            jax.ShapeDtypeStruct((1,), jnp.float32))
    check(has_kernel(trainer.engine.inner.bsp_step, *args),
          "train step calls a Pallas kernel (tpu_custom_call)")


def serve_phase(seed):
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.models import build_model
    from repro.serve.engine import ServeConfig, ServeEngine
    from repro.serve.request import RequestState

    cfg = get_config(STABLELM)
    max_len = max(SERVE_PROMPTS) + SERVE_NEW_TOKENS
    print(f"serve: {STABLELM}, all {cfg.num_layers} layers, bf16 params/"
          f"compute/cache, page_size 16, 4 slots, {len(SERVE_PROMPTS)} "
          f"requests of {min(SERVE_PROMPTS)}-{max(SERVE_PROMPTS)} prompt "
          f"tokens + {SERVE_NEW_TOKENS} new", flush=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed), dtype=jnp.bfloat16)
    reqs = requests(cfg.vocab_size, SERVE_PROMPTS, SERVE_NEW_TOKENS, seed)

    t0 = time.perf_counter()
    eng = ServeEngine(model, params, ServeConfig(
        slots=4, max_len=max_len, page_size=16,
        cache_dtype=jnp.bfloat16, compute_dtype=jnp.bfloat16))
    m = eng.run(reqs)
    print(f"serve: {m['completed']} requests, {m['generated_tokens']} "
          f"tokens, {m['decode_iterations']} decode iterations, "
          f"{m['prefill_groups']} prefill groups")
    check(all(r.state is RequestState.DONE
              and len(r.output) == SERVE_NEW_TOKENS for r in reqs),
          f"serve: all {len(reqs)} requests DONE with "
          f"{SERVE_NEW_TOKENS} tokens each")
    smoke_time("serve run (compile + prefill + decode)", t0)
    peak_memory("serve")

    check("tpu_custom_call" in eng.lower_step().compile().as_text(),
          "serve decode step calls a Pallas kernel (tpu_custom_call)")

    t0 = time.perf_counter()
    toks = jnp.asarray([reqs[0].prompt], jnp.int32)
    got = model.prefill(params, toks, compute_dtype=jnp.bfloat16)[0]
    model_ref = build_model(dataclasses.replace(cfg, attn_backend="ref"))
    with jax.default_matmul_precision("highest"):
        want = model_ref.forward(params, toks,
                                 compute_dtype=jnp.float32)[0][:, -1:]
    V = cfg.vocab_size
    err = rel_l2(got[..., :V], want[..., :V])
    print(f"serve: prefill last-token logits (bf16, flash kernel) vs "
          f"fp32 ref forward: rel L2 {err:.4g} (tol {SERVE_LOGIT_RTOL})")
    check(err <= SERVE_LOGIT_RTOL, "serve prefill logits == ref forward")
    smoke_time("serve logit check", t0)


# ----------------------------------------------------------- four chips
def dp_phase(seed):
    import jax
    import numpy as np
    from repro.comm.codecs import LANE
    from repro.comm.transport import (model_error_factor, pad_for_schedule,
                                      per_device_bytes)
    from repro.configs import get_config
    from repro.data import LMDataConfig, make_lm_batches
    from repro.models import build_model
    from repro.train import Strategy, Trainer

    full = get_config(DP_ARCH)
    cfg = dataclasses.replace(full, num_layers=DP_LAYERS)
    print(f"dp: {DP_ARCH} at published widths (d_model {cfg.d_model}, "
          f"{cfg.num_heads}x{cfg.head_dim} heads, {cfg.num_kv_heads} kv, "
          f"d_ff {cfg.d_ff}, vocab {cfg.vocab_size}); depth cut "
          f"{full.num_layers} -> {DP_LAYERS} layer "
          f"({cfg.param_count() / 1e6:.0f}M params); per-worker batch "
          f"{DP_BATCH} x seq {DP_SEQ}, {DP_STEPS} steps", flush=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    batches = make_lm_batches(LMDataConfig(
        vocab_size=cfg.vocab_size, seq_len=DP_SEQ, batch_size=DP_BATCH,
        seed=seed))
    grad_fn = lm_grad_fn(model)

    t0 = time.perf_counter()
    spec = f"bsp/ring/none@{TP}"
    runs = {}
    with jax.default_matmul_precision("highest"):
        for backend in ("device", "sim"):
            _, hist, mets = Trainer(Strategy.parse(
                spec, backend=backend)).fit(grad_fn, params, batches,
                                            DP_STEPS)
            runs[backend] = [e["loss"] for e in hist]
            print(f"dp: {spec} on {mets['backend']}: losses "
                  f"{runs[backend]}")
    dev, sim = np.asarray(runs["device"]), np.asarray(runs["sim"])
    err = float(np.max(np.abs(dev - sim) / np.abs(sim)))
    print(f"dp: device vs sim max rel loss diff {err:.3g} "
          f"(tol {DP_LOSS_RTOL})")
    check(len(dev) == DP_STEPS and np.all(np.isfinite(dev))
          and err <= DP_LOSS_RTOL, f"dp {spec}: device losses == sim")
    smoke_time("dp device vs sim", t0)

    t0 = time.perf_counter()
    spec = f"bsp/ring/onebit@{TP}"
    trainer = Trainer(Strategy.parse(spec, backend="device",
                                     wire="measured"))
    _, hist, mets = trainer.fit(grad_fn, params, batches, DP_STEPS)
    losses = [e["loss"] for e in hist]
    plan = trainer.engine.inner.comm_plan
    # `make comm`'s model: per bucket, the topology's critical-path bytes
    # for one encoded segment, over the documented error factor
    modelled = sum(
        per_device_bytes("ring", TP, plan.codec.static_tx_bytes(
            pad_for_schedule(plan.bucket_len(b), TP)))
        / model_error_factor("ring", TP, exact=False)
        for b in range(len(plan.buckets)))
    measured = mets["measured_step_tx_bytes"]
    print(f"dp: {spec} wire=measured losses {losses}; step tx bytes "
          f"measured {measured} modelled {modelled!r}; fp32 would be "
          f"{mets['fp32_step_tx_bytes']}")
    check(all(np.isfinite(losses)), f"dp {spec}: losses finite")
    check(all(plan.bucket_len(b) % (TP * LANE) == 0
              for b in range(len(plan.buckets))),
          f"dp {spec}: every ring chunk is whole codec rows")
    check(measured == modelled, f"dp {spec}: measured wire bytes == "
          f"modelled")
    check(measured < mets["fp32_step_tx_bytes"],
          f"dp {spec}: fewer bytes than fp32")
    smoke_time("dp onebit measured wire", t0)


def tp_phase(seed):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.configs import get_config
    from repro.models import build_model
    from repro.models import transformer as T
    from repro.serve.engine import ServeConfig, ServeEngine
    from repro.serve.request import RequestState
    from repro.serve.tp import TPContext, param_specs, store_specs

    cfg = get_config(STABLELM)
    prompts = SERVE_PROMPTS[:4]
    max_len = max(prompts) + SERVE_NEW_TOKENS
    print(f"tp: {STABLELM}, all {cfg.num_layers} layers; ServeConfig(tp="
          f"{TP}) vs tp=1 on the same {len(prompts)} requests (fp32, "
          f"\"highest\"), then one bf16 decode step's logits", flush=True)
    model = build_model(cfg)
    key = jax.random.PRNGKey(seed)

    t0 = time.perf_counter()
    params = model.init(key)
    outputs = {}
    with jax.default_matmul_precision("highest"):
        for tp in (TP, 1):
            reqs = requests(cfg.vocab_size, prompts, SERVE_NEW_TOKENS, seed)
            ServeEngine(model, params, ServeConfig(
                slots=4, max_len=max_len, page_size=16, tp=tp,
                cache_dtype=jnp.float32,
                compute_dtype=jnp.float32)).run(reqs)
            check(all(r.state is RequestState.DONE
                      and len(r.output) == SERVE_NEW_TOKENS for r in reqs),
                  f"tp={tp}: all {len(reqs)} requests DONE")
            outputs[tp] = [r.output for r in reqs]
    del params
    same = sum(a == b for o4, o1 in zip(outputs[TP], outputs[1])
               for a, b in zip(o4, o1))
    print(f"tp: greedy tokens equal between tp={TP} and tp=1: {same}/"
          f"{len(prompts) * SERVE_NEW_TOKENS}")
    check(outputs[TP] == outputs[1], f"tp={TP} engine serves tp=1's tokens")
    smoke_time("tp serve runs", t0)

    # one bf16 decode step after request 0's prefill, through the same
    # decode_step(tp_axis=...) the engine's shard_map wraps.  Prefill
    # itself runs on one device at every tp, so its logits are shared.
    t0 = time.perf_counter()
    params = model.init(key, dtype=jnp.bfloat16)
    plen = prompts[0]
    toks = jnp.asarray([requests(cfg.vocab_size, prompts, SERVE_NEW_TOKENS,
                                 seed)[0].prompt], jnp.int32)
    logits, states = model.prefill(params, toks, compute_dtype=jnp.bfloat16)
    caches = model.cache_from_prefill(states, max_len, dtype=jnp.bfloat16)
    nxt = jnp.argmax(logits[..., :cfg.vocab_size], -1).astype(jnp.int32)

    def step(cfg_used, dtype, tp_axis=None):
        return lambda p, c, t: T.decode_step(
            p, cfg_used, c, t, plen, compute_dtype=dtype, tp_axis=tp_axis)[0]
    one = {dt: jax.jit(step(cfg, dt))(params, caches, nxt)
           for dt in (jnp.bfloat16, jnp.float32)}
    ctx = TPContext(cfg, TP)
    p4, c4 = ctx.place(params, caches)
    four = jax.jit(jax.shard_map(
        step(ctx.cfg_local, jnp.bfloat16, "model"), mesh=ctx.mesh,
        in_specs=(param_specs(params), store_specs(caches), P()),
        out_specs=P(), check_vma=False))(p4, c4, nxt)
    V = cfg.vocab_size
    gap = rel_l2(one[jnp.bfloat16][..., :V], one[jnp.float32][..., :V])
    err = rel_l2(four[..., :V], one[jnp.bfloat16][..., :V])
    print(f"tp: decode logits tp={TP} vs tp=1 rel L2 {err:.4g}; tp=1 bf16 "
          f"vs fp32 {gap:.4g}; tol {TP_GAP_FACTOR} x {gap:.4g}")
    check(err <= TP_GAP_FACTOR * gap, f"tp={TP} decode logits == tp=1")
    smoke_time("tp logit check", t0)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, TP), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found platform "
                         f"{devices[0].platform!r}")
    if len(devices) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} but JAX found "
                         f"{len(devices)} device(s)")
    used = devices[:args.chips]
    print(f"device: {devices[0].device_kind} x{len(devices)}, using "
          f"{len(used)}", flush=True)

    from repro.kernels.backend import kernel_interpret, resolve_backend
    from repro.launch.env import use_compile_cache
    print(f"compile cache: {use_compile_cache()}", flush=True)
    check(resolve_backend("auto") == "kernel" and not kernel_interpret(),
          "kernel backend resolves to compiled Pallas (not interpret)")

    t0 = time.perf_counter()
    if args.chips == 1:
        train_phase(args.seed)
        serve_phase(args.seed)
    else:
        dp_phase(args.seed)
        tp_phase(args.seed)
    smoke_time("total", t0)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(used)}}))


if __name__ == "__main__":
    main()
