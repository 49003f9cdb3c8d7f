"""`make serve` tier-1 gate: the serving plane end to end on the host
device, plus one tensor-parallel cell on two devices (in process on a
multi-device host, in a 2-virtual-device child on a CPU host).

Five checks, all on reduced configs:

  equivalence   paged (page_size=4) and contiguous engines produce the
                seed loop's exact greedy tokens on tinyllama + the
                mixed rglru/ring recurrentgemma stack
  continuous    on a staggered arrival trace with mixed decode budgets,
                continuous batching beats one-shot static batching on
                p99 time-to-first-token AND tokens/s (virtual clock)
  exhaustion    a page pool sized under the working set serves the same
                tokens by stalling admission (no allocation failure)
  autoscale     the Poisson trace -> rate estimate -> replica schedule ->
                sched TraceEvents -> elastic EventPlan loop emits resize
                events and cuts simulated p99 queueing delay
  tp decode     ServeConfig(tp=2) on 2 devices matches the
                single-device token stream bitwise

  PYTHONPATH=src python tools/serve_smoke.py
"""
import os
import sys

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src"))

import jax                      # noqa: E402
import jax.numpy as jnp         # noqa: E402
import numpy as np              # noqa: E402

from repro.configs import get_config                        # noqa: E402
from repro.launch.env import call_on_devices                # noqa: E402
from repro.models import build_model                        # noqa: E402
from repro.serve.autoscale import (AutoscalePolicy,         # noqa: E402
                                   Autoscaler, ScaleDecision,
                                   poisson_trace, simulate_queue)
from repro.serve.engine import ServeConfig, ServeEngine     # noqa: E402
from repro.serve.request import Request                     # noqa: E402


def seed_loop(model, params, prompt, max_new, max_len):
    B, S0 = prompt.shape
    caches = model.init_cache(B, max_len, dtype=jnp.float32)
    step = jax.jit(lambda p, c, tok, pos: model.decode_step(
        p, c, tok, pos, compute_dtype=jnp.float32))
    tokens = jnp.asarray(prompt)
    logits = None
    for t in range(S0):
        logits, caches = step(params, caches, tokens[:, t:t + 1], t)
    V = model.cfg.vocab_size
    for t in range(S0, S0 + max_new):
        nxt = jnp.argmax(logits[..., :V], axis=-1).astype(jnp.int32)
        tokens = jnp.concatenate([tokens, nxt], axis=1)
        logits, caches = step(params, caches, nxt, t)
    return np.asarray(tokens)[:, S0:].tolist()


def tp_decode_matches() -> bool:
    """ServeConfig(tp=2) serves the single-device token stream."""
    cfg = get_config("tinyllama-1.1b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts = np.random.RandomState(0).randint(1, cfg.vocab_size,
                                                size=(3, 5))

    def go(tp):
        reqs = [Request(rid=i, prompt=[int(t) for t in prompts[i]],
                        max_new_tokens=6) for i in range(3)]
        ServeEngine(model, params, ServeConfig(
            slots=2, max_len=16, page_size=4, tp=tp,
            cache_dtype=jnp.float32, compute_dtype=jnp.float32)).run(reqs)
        return [r.output for r in reqs]
    return go(2) == go(1)


def run(model, params, prompts, budgets, arrivals, **scfg):
    reqs = [Request(rid=i, prompt=[int(t) for t in prompts[i]],
                    max_new_tokens=budgets[i], arrival=arrivals[i])
            for i in range(len(prompts))]
    eng = ServeEngine(model, params, ServeConfig(
        cache_dtype=jnp.float32, compute_dtype=jnp.float32, **scfg))
    m = eng.run(reqs)
    return [r.output for r in reqs], m


def main() -> int:
    failures = []

    # ---------------------------------------------------- equivalence
    for arch in ("tinyllama-1.1b", "recurrentgemma-9b"):
        cfg = get_config(arch).reduced()
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.RandomState(0)
        prompts = rng.randint(1, cfg.vocab_size, size=(3, 5))
        ref = seed_loop(model, params, prompts, 6, 16)
        for page in (0, 4):
            out, _ = run(model, params, prompts, [6] * 3, [0.0] * 3,
                         slots=2, max_len=16, page_size=page)
            tag = f"equivalence[{arch},page={page}]"
            ok = out == ref
            print(f"{tag:48s} {'OK' if ok else 'FAIL'}")
            if not ok:
                failures.append(tag)

    # ------------------------------------------- continuous vs oneshot
    cfg = get_config("tinyllama-1.1b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(1)
    prompts = rng.randint(1, cfg.vocab_size, size=(6, 5))
    budgets = [3, 10, 4, 9, 5, 8]
    arrivals = [0.0, 0.0, 1.0, 2.0, 3.0, 8.0]
    out1, m1 = run(model, params, prompts, budgets, arrivals,
                   slots=2, max_len=16, page_size=4, policy="oneshot")
    outc, mc = run(model, params, prompts, budgets, arrivals,
                   slots=2, max_len=16, page_size=4, policy="continuous")
    ok = (outc == out1
          and mc["p99_first_token"] < m1["p99_first_token"]
          and mc["tokens_per_s"] >= m1["tokens_per_s"])
    print(f"{'continuous beats oneshot':48s} {'OK' if ok else 'FAIL'} "
          f"(p99 ttft {mc['p99_first_token']:.0f} vs "
          f"{m1['p99_first_token']:.0f}, tok/s {mc['tokens_per_s']:.2f} "
          f"vs {m1['tokens_per_s']:.2f})")
    if not ok:
        failures.append("continuous")

    # ------------------------------------------------------ exhaustion
    ref, _ = run(model, params, prompts[:4], [6] * 4, [0.0] * 4,
                 slots=4, max_len=16, page_size=4)
    out, m = run(model, params, prompts[:4], [6] * 4, [0.0] * 4,
                 slots=4, max_len=16, page_size=4, num_pages=6)
    ok = out == ref and m["admission_stalls"] > 0
    print(f"{'pool exhaustion stalls, same tokens':48s} "
          f"{'OK' if ok else 'FAIL'} ({m['admission_stalls']} stalls)")
    if not ok:
        failures.append("exhaustion")

    # ------------------------------------------------------- autoscale
    arrivals_t = poisson_trace(rate=2.0, horizon=60.0, seed=0)
    pol = AutoscalePolicy(replica_rate=0.5, max_replicas=8, interval=5.0)
    plan, decisions = Autoscaler(pol, jid=0).plan(arrivals_t, horizon=60.0)
    q_fixed = simulate_queue(arrivals_t, [ScaleDecision(0.0, 0.0, 1)],
                             service_time=1.0, horizon=60.0)
    q_auto = simulate_queue(arrivals_t, decisions, service_time=1.0,
                            horizon=60.0)
    ok = (any(e.kind == "resize" for e in plan)
          and q_auto["p99_wait"] < q_fixed["p99_wait"])
    print(f"{'autoscale: resize plan + p99 wait cut':48s} "
          f"{'OK' if ok else 'FAIL'} "
          f"({q_fixed['p99_wait']:.1f}s -> {q_auto['p99_wait']:.1f}s)")
    if not ok:
        failures.append("autoscale")

    # ------------------------------------------------------- tp decode
    name = "tp=2 decode == single device"
    ok = call_on_devices(2, tp_decode_matches)
    if ok is None:
        print(f"{name:48s} SKIP (needs 2 devices)")
    else:
        print(f"{name:48s} {'OK' if ok else 'FAIL'}")
        if not ok:
            failures.append("tp")

    if failures:
        print(f"\nserve gate FAILED: {failures}")
        return 1
    print("\nserve gate OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
