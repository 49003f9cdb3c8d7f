"""Serving-plane benchmark: continuous batching vs one-shot static
batching under an open-loop Poisson arrival trace (survey §5: serving as
a first-class workload).

One JSON row per (arch, policy, page_size, tp) cell: throughput and
first-token / per-token latency percentiles on the engine's virtual
iteration clock (deterministic — wall seconds are recorded alongside).
The tp=2 cell re-runs the continuous+paged config under tensor-parallel
decode (in process on a multi-device host, in a 2-virtual-device
subprocess on a CPU host) and must reproduce the single-device token
stream.

Asserts the headline claim the gate also checks: continuous batching
beats one-shot on BOTH tokens/s and p99 time-to-first-token for every
arch (iteration-level admission fills freed slots immediately instead of
gating each wave on its slowest member).

  PYTHONPATH=src python -m benchmarks.run serve
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit_json
from repro.configs import get_config
from repro.launch.env import call_on_devices
from repro.models import build_model
from repro.serve.autoscale import poisson_trace
from repro.serve.engine import ServeConfig, ServeEngine
from repro.serve.request import Request

SLOTS = 4
MAX_LEN = 24
PROMPT_LEN = 5
RATE = 0.6          # requests per virtual iteration (open loop)
HORIZON = 30.0
SEED = 0


def make_trace(vocab):
    arrivals = [0.0] + poisson_trace(RATE, HORIZON, seed=SEED)
    rng = np.random.RandomState(SEED)
    prompts = rng.randint(1, vocab, size=(len(arrivals), PROMPT_LEN))
    budgets = rng.choice([3, 6, 10, 14], size=len(arrivals))
    return arrivals, prompts, budgets


def requests(arrivals, prompts, budgets):
    return [Request(rid=i, prompt=[int(t) for t in prompts[i]],
                    max_new_tokens=int(budgets[i]), arrival=arrivals[i])
            for i in range(len(arrivals))]


def run_cell(arch, model, params, arrivals, prompts, budgets,
             policy, page_size, tp=1):
    reqs = requests(arrivals, prompts, budgets)
    eng = ServeEngine(model, params, ServeConfig(
        slots=SLOTS, max_len=MAX_LEN, page_size=page_size, policy=policy,
        tp=tp, cache_dtype=jnp.float32, compute_dtype=jnp.float32))
    m = eng.run(reqs)
    row = {"bench": "serve", "arch": arch, "policy": policy,
           "page_size": page_size, "tp": tp, "slots": SLOTS,
           "requests": len(reqs)}
    row.update({k: (round(v, 4) if isinstance(v, float) else v)
                for k, v in m.items()})
    return row, [r.output for r in reqs]


def tp_cell():
    """The continuous+paged tinyllama cell at tp=2 (on two devices)."""
    cfg = get_config("tinyllama-1.1b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    arrivals, prompts, budgets = make_trace(cfg.vocab_size)
    return run_cell("tinyllama-1.1b", model, params, arrivals, prompts,
                    budgets, "continuous", 4, tp=2)


def main() -> None:
    rows = []
    token_streams = {}
    for arch in ("tinyllama-1.1b", "recurrentgemma-9b"):
        cfg = get_config(arch).reduced()
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        arrivals, prompts, budgets = make_trace(cfg.vocab_size)
        for page_size in (0, 4):
            per_policy = {}
            for policy in ("oneshot", "continuous"):
                row, outs = run_cell(arch, model, params, arrivals, prompts,
                                     budgets, policy, page_size)
                rows.append(row)
                per_policy[policy] = row
                token_streams[(arch, policy, page_size)] = outs
            c, o = per_policy["continuous"], per_policy["oneshot"]
            assert c["tokens_per_s"] >= o["tokens_per_s"], (arch, page_size)
            assert c["p99_first_token"] < o["p99_first_token"], \
                (arch, page_size)
        # layout must never change tokens
        for policy in ("oneshot", "continuous"):
            assert (token_streams[(arch, policy, 0)]
                    == token_streams[(arch, policy, 4)]), (arch, policy)
        # admission must never change tokens
        assert (token_streams[(arch, "oneshot", 4)]
                == token_streams[(arch, "continuous", 4)]), arch

    tp = call_on_devices(2, tp_cell)
    if tp is None:
        print("tp=2 cell: SKIP (needs 2 devices; this process holds its "
              "only accelerator)")
    else:
        row_tp, outs_tp = tp
        rows.append(row_tp)
        assert outs_tp == token_streams[("tinyllama-1.1b", "continuous",
                                         4)], \
            "tp=2 token stream diverged from single-device"

    emit_json(rows)


if __name__ == "__main__":
    main()
