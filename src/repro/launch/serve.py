"""Serving launcher: the continuous-batching engine on this host
(reduced config), driven by an open-loop arrival trace.

  PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
      --smoke --requests 8 --rate 0.5 --policy continuous --pages 4

  # static-batching baseline on the same trace
  PYTHONPATH=src python -m repro.launch.serve --smoke --policy oneshot

  # tensor-parallel decode (needs >= tp devices)
  PYTHONPATH=src python -m repro.launch.serve --smoke --tp 2
"""
from __future__ import annotations

import argparse
import contextlib

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.launch.env import use_compile_cache
from repro.models import build_model
from repro.serve.autoscale import poisson_trace
from repro.serve.batcher import POLICIES
from repro.serve.engine import ServeConfig, ServeEngine
from repro.serve.request import Request, SamplingParams


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--slots", type=int, default=4,
                    help="max concurrent batch slots")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--rate", type=float, default=0.0,
                    help="open-loop Poisson arrival rate (req per engine "
                         "iteration); 0 = all requests arrive at t=0")
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=0,
                    help="cache capacity (0 = prompt+max_new)")
    ap.add_argument("--policy", choices=POLICIES, default="continuous")
    ap.add_argument("--pages", type=int, default=0,
                    help="KV page size (0 = contiguous per-slot cache)")
    ap.add_argument("--num-pages", type=int, default=0,
                    help="page-pool cap (0 = size for all slots full)")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel decode degree")
    ap.add_argument("--window", type=int, default=0,
                    help="sliding-window override (sub-quadratic decode)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="<= 0 is greedy argmax")
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="write a Chrome trace (request lifecycles + KV "
                         "occupancy); see docs/observability.md")
    ap.add_argument("--report", action="store_true",
                    help="print the trace analysis (latency summary, SLO "
                         "burn) after the run; implies tracing even "
                         "without --trace")
    ap.add_argument("--slo", action="append", default=[], metavar="SPEC",
                    help="attach an SLO objective, e.g. ttft_p99<8 "
                         "(repeatable); burning SLOs emit slo_burn "
                         "instants (docs/serving.md)")
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.reduced()
    if cfg.is_encoder_decoder:
        raise SystemExit("use examples/whisper_decode.py for enc-dec serving")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    max_len = args.max_len or (args.prompt_len + args.max_new)
    horizon = max(1.0, args.requests / args.rate) if args.rate > 0 else 1.0
    arrivals = ([0.0] + poisson_trace(args.rate, horizon, seed=args.seed,
                                      max_requests=args.requests - 1)
                if args.rate > 0 else [0.0] * args.requests)
    rng = np.random.RandomState(args.seed + 1)
    prompts = rng.randint(1, cfg.vocab_size,
                          size=(len(arrivals), args.prompt_len))
    reqs = [Request(rid=i, prompt=[int(t) for t in prompts[i]],
                    max_new_tokens=args.max_new, arrival=arrivals[i],
                    sampling=SamplingParams(temperature=args.temperature,
                                            top_k=args.top_k,
                                            seed=args.seed + i))
            for i in range(len(arrivals))]

    slo = None
    if args.slo:
        from repro.obs.slo import SLOMonitor
        slo = SLOMonitor(args.slo)
    eng = ServeEngine(model, params, ServeConfig(
        slots=args.slots, max_len=max_len, page_size=args.pages,
        num_pages=args.num_pages or None, policy=args.policy, tp=args.tp,
        window_override=args.window,
        cache_dtype=jnp.float32, compute_dtype=jnp.float32), slo=slo)
    rec = None
    with contextlib.ExitStack() as stack:
        if args.trace or args.report:
            from repro.obs.trace import tracing
            rec = stack.enter_context(tracing(args.trace))
        metrics = eng.run(reqs)
    if args.trace:
        print(f"trace written to {args.trace}")
    if args.report and rec is not None:
        from repro.obs.report import render
        print(render(rec.to_chrome(), slos=args.slo))
    if slo is not None:
        print(f"slo alerts: {len(eng.slo_alerts)}"
              + (f" (first at t={eng.slo_alerts[0]['t']})"
                 if eng.slo_alerts else ""))

    for r in reqs[:4]:
        print(f"req {r.rid}: arrival={r.arrival:5.1f} "
              f"ttft={r.first_token_latency():5.1f} "
              f"output={r.output[:8]}{'...' if len(r.output) > 8 else ''}")
    if len(reqs) > 4:
        print(f"... {len(reqs) - 4} more")
    print(f"policy={metrics['policy']} paged={metrics['paged']} "
          f"tp={metrics['tp']}")
    print(f"{metrics['completed']} requests, "
          f"{metrics['generated_tokens']} tokens in "
          f"{metrics['clock']:.0f} iterations "
          f"({metrics['tokens_per_s']:.2f} tok/iter, "
          f"{metrics['wall_s']:.2f}s wall)")
    print(f"first-token p50/p99: {metrics['p50_first_token']:.1f}/"
          f"{metrics['p99_first_token']:.1f} iters   per-token p50/p99: "
          f"{metrics['p50_per_token']:.2f}/{metrics['p99_per_token']:.2f}"
          f"   stalls: {metrics['admission_stalls']}")


if __name__ == "__main__":
    main()
