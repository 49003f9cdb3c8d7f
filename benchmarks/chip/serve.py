"""Serve cells: open-loop traffic through ``ServeEngine.submit`` /
``step_iteration`` on the host's wall clock, then a greedy-token check
against the configuration's plain reference.

Set-up makes the weights on the device, builds the engine, and drives
the engine's own path once for every (prompt bucket, group size) the
cell warms, so that the eager prefill has compiled every shape the
window can meet.  The window sends request ``i`` at its scheduled time
``due[i]``; each output token is stamped when the engine call that
produced it returns.  TTFT is measured from the scheduled send time, so
a stall that delays later sends is charged to them.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List

import numpy as np

from chip import stats, traffic, work
from chip.harness import Span, Outcome


@dataclasses.dataclass
class Iter:
    t0: float
    t1: float
    n_active: int        # slots the decode step advanced
    live: int            # cache positions those slots attended
    prefill_flops: float
    prefills: int


def _engine(ctx, params):
    import jax.numpy as jnp
    from repro.serve.engine import ServeConfig, ServeEngine
    e = ctx.cell_params["engine"]
    dt = jnp.dtype(ctx.config["dtype"])
    return ServeEngine(ctx.model, params, ServeConfig(
        slots=e["slots"], max_len=e["max_len"], page_size=e["page_size"],
        cache_dtype=dt, compute_dtype=dt))


def _warm(ctx, eng, rng):
    """Every prompt bucket at every group size up to ``warm_groups``, then
    one decode step, through the engine's own calls."""
    from repro.serve.request import Request
    V = ctx.config["vocab_size"]
    rid = -1
    for b in ctx.mix["prompt"]["buckets"]:
        for g in range(1, ctx.cell_params["warm_groups"] + 1):
            for _ in range(g):
                eng.submit(Request(rid=rid, prompt=rng.integers(
                    0, V, b).tolist(), max_new_tokens=1))
                rid -= 1
            while not eng.batcher.idle:
                eng.step_iteration()
    eng.submit(Request(rid=rid, prompt=rng.integers(0, V, 64).tolist(),
                       max_new_tokens=3))
    while not eng.batcher.idle:
        eng.step_iteration()


def _window(ctx, eng, sched, reqs, spans, iters, tracer):
    """Send, step and stamp until every request due in the window is
    done or the drain allowance after the window has passed."""
    seconds = ctx.seconds
    drain_s = ctx.cell_params.get("drain_s", 60)
    stamps: Dict[int, List[float]] = {r.rid: [] for r in reqs}
    nxt, inflight = 0, []
    t0 = time.perf_counter()
    while True:
        now = time.perf_counter() - t0
        tracer.poll(now)
        if now > seconds + drain_s:
            break
        while nxt < sched.n and sched.due_s[nxt] <= now:
            eng.submit(reqs[nxt])
            inflight.append(reqs[nxt])
            nxt += 1
        if inflight:
            seen = [len(r.output) for r in inflight]
            with Span(spans, "serve.iter", i=len(iters)):
                a = time.perf_counter() - t0
                eng.step_iteration()
                b = time.perf_counter() - t0
            it = Iter(a, b, 0, 0, 0.0, 0)
            for r, s in zip(inflight, seen):
                k = len(r.output)
                stamps[r.rid].extend([b] * (k - s))
                if s == 0 and k:
                    it.prefills += 1
                    it.prefill_flops += work.prefill_flops(ctx.config,
                                                           r.prompt_len)
                if k - s - (s == 0) == 1:      # the decode step advanced r
                    it.n_active += 1
                    it.live += r.prompt_len + k - 1
            iters.append(it)
            inflight = [r for r in inflight if not r.done]
        elif nxt < sched.n:
            with Span(spans, "serve.wait"):
                time.sleep(max(0.0, min(sched.due_s[nxt] - now, 0.05)))
        else:
            break
    tracer.poll(float("inf"))
    return stamps


def run(ctx) -> Outcome:
    import jax
    from repro.serve.request import Request

    ref = ctx.reference
    c = ctx.config
    rate = ctx.rate if ctx.rate else ctx.cell_params["rate_per_s"]
    sched = traffic.serve_schedule(ctx.mix, rate, ctx.seconds, ctx.seed,
                                   c["vocab_size"])
    params = ref.init(c, ctx.key, c["dtype"])
    ctx.check_layout(params)
    eng = _engine(ctx, params)
    _warm(ctx, eng, np.random.default_rng(ctx.seed ^ 0x5EED))
    reqs = [Request(rid=i, prompt=sched.prompts[i].tolist(),
                    max_new_tokens=int(sched.output_len[i]))
            for i in range(sched.n)]
    spans, iters = [], []
    ctx.setup_done()
    with ctx.window():
        stamps = _window(ctx, eng, sched, reqs, spans, iters, ctx.tracer)
    memory_peak = ctx.memory_peak()
    eng.kv.store = None
    del eng, params
    ctx.free_device()

    done = [r for r in reqs if r.done]
    failed = sched.n - len(done)
    ttft = [stamps[r.rid][0] - sched.due_s[r.rid] for r in done]
    itl = [b - a for r in done for a, b in zip(stamps[r.rid],
                                                stamps[r.rid][1:])]
    in_window = sum(t < ctx.seconds for r in reqs for t in stamps[r.rid])
    e2e = {"ttft_p95_ms": 1e3 * stats.percentile(ttft, 95) if ttft else None,
           "itl_p95_ms": 1e3 * stats.percentile(itl, 95) if itl else None,
           "serve_tokens_per_s": in_window / ctx.seconds}
    # a backlog that grows through the window shows in the requests still
    # without a first token at its close, and in late requests' TTFT
    waiting = sum(1 for r in reqs if not stamps[r.rid]
                  or stamps[r.rid][0] >= ctx.seconds)
    third = max(1, len(ttft) // 3)
    ctx.note(f"backlog at the close {waiting}; mean TTFT first third "
             f"{np.mean(ttft[:third]) if ttft else 0:.3f} s, last third "
             f"{np.mean(ttft[-third:]) if ttft else 0:.3f} s")
    ctx.note(f"served {len(done)}/{sched.n} requests due in the window, "
             f"{sum(len(r.output) for r in done)} tokens "
             f"({in_window} inside it), {len(itl)} token gaps, "
             f"{len(iters)} engine iterations")

    checks = _check(ctx, done)
    return Outcome(e2e=e2e, attempted=sched.n, failed=failed,
                   memory_peak=memory_peak, checks=checks, spans=spans,
                   extra={"iters": iters, "ttft_s": ttft})


def _sample(ctx, done):
    """The longest finished request and then others drawn from the seed,
    until ``check_tokens`` served tokens are covered."""
    rng = np.random.default_rng(ctx.seed + 1)
    order = sorted(done, key=lambda r: -(r.prompt_len + len(r.output)))
    pick, rest = order[:1], order[1:]
    rng.shuffle(rest)
    want = ctx.cell_params["check"]["tokens"]
    for r in rest:
        if sum(len(p.output) for p in pick) >= want:
            break
        pick.append(r)
    return pick


def _check(ctx, done):
    """The widest gap by which a served (greedy) token's logit lies below
    the reference's best at its position; with ``--control 1``, the same
    gap of the token the lower-precision reference puts first."""
    import jax
    import jax.numpy as jnp
    if not done:
        return [("served_requests", 0, 1, False)]
    ref, c = ctx.reference, ctx.config
    chk = ctx.cell_params["check"]
    pick = _sample(ctx, done)
    L = ctx.cell_params["engine"]["max_len"]
    blk = int(chk.get("rows_per_block", 4))
    # whole blocks of max_len rows: one shape, so one compiled reference
    rows = np.zeros((-(-len(pick) // blk) * blk, L), np.int32)
    for i, r in enumerate(pick):
        seq = list(r.prompt) + list(r.output[:-1])
        rows[i, :len(seq)] = seq
    t0 = time.perf_counter()
    params = ref.init(c, ctx.key, c["dtype"])     # the served values
    fwd = jax.jit(lambda p, t: ref.forward(c, p, t))
    ctl = jax.jit(lambda p, t: jnp.argmax(
        ref.forward(c, p, t, ctx.lower_precision()), -1))
    gaps, ctl_gaps = [], []
    with jax.default_matmul_precision("highest"):
        for s in range(0, len(pick), blk):
            toks = jnp.asarray(rows[s:s + blk])
            lg = fwd(params, toks)
            best = np.asarray(jnp.max(lg, -1))
            if ctx.control:
                first = np.asarray(ctl(params, toks))
            for i, r in enumerate(pick[s:s + blk]):
                P, out = r.prompt_len, np.asarray(r.output)
                pos = np.arange(P - 1, P - 1 + len(out))
                got = np.asarray(lg[i, pos, out])
                gaps.append(float(np.max(best[i, pos] - got)))
                if ctx.control:
                    alt = np.asarray(lg[i, pos, first[i, pos]])
                    ctl_gaps.append(float(np.max(best[i, pos] - alt)))
            del lg
    n_tok = sum(len(r.output) for r in pick)
    ctx.note(f"reference over {len(pick)} requests, {n_tok} served tokens, "
             f"{time.perf_counter() - t0:.1f} s")
    limit = chk["max_logit_gap"]
    out = [("max_logit_gap", max(gaps), limit, max(gaps) <= limit)]
    if ctx.control:
        # the control stands in the program's place: its number decides
        out = [("control_logit_gap", max(ctl_gaps), limit,
                max(ctl_gaps) <= limit)] + [o[:3] + (True,) for o in out]
    return out
