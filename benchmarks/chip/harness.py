"""The harness: finds a cell's configuration, traffic mix, cell file and
metric readers by name, runs it, and prints the result line.

Everything that belongs to one configuration, mix, cell or per-layer
metric is a file of its own:

  configs/<config>.json     sizes as run, published source, cuts, reference
  reference/<name>.py       the configuration's plain reference
  traffic/<mix>.json        a serve or train mix (``"kind"``)
  cells/<workload>.json     the cell's rate, engine or strategy, limits
  metrics/<metric>.py       ``read(run)`` -> a number, or None

so a later change adds a model, a mix, a cell or a counter by adding
files and entries.
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import importlib
import json
import os
import shutil
import sys
import time
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
OUT = os.path.join(ROOT, ".bench_out")


def fail(msg: str) -> None:
    print(f"benchmark: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(2)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


# ----------------------------------------------------------------- spans
class Span:
    """A host span of the benchmark's own: kept in ``sink`` as
    (name, start, end, args) on ``time.perf_counter`` seconds, and written
    into the profiler's trace as a ``TraceAnnotation`` when one runs."""

    def __init__(self, sink: list, name: str, **args):
        self.sink, self.name, self.args = sink, name, args

    def __enter__(self):
        import jax
        self.ann = jax.profiler.TraceAnnotation(self.name, **self.args)
        self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self.ann.__exit__(*exc)
        self.sink.append((self.name, self.t0, t1, self.args))
        return False


@dataclasses.dataclass
class Outcome:
    e2e: Dict[str, Optional[float]]
    attempted: int
    failed: int
    memory_peak: int
    checks: List[tuple]            # (name, value, limit, ok)
    spans: list
    extra: Dict[str, Any]


class Tracer:
    """Takes one profiler trace of about ``length`` seconds from ``at``
    seconds into the window when the run is traced; ``poll(now)`` is
    called from the window's loop, once per engine iteration or training
    step, with the seconds since the window opened.  The traced window
    (the ``bench.window`` annotation) opens at the poll after the one that
    started the profiler: the first program run under a fresh profiler
    stalls the host for seconds, and that stall is the profiler's, not
    the system's."""

    def __init__(self, enabled: bool, at: float, length: float):
        self.enabled, self.at, self.length = enabled, at, length
        self.state, self.dir = "idle", os.path.join(OUT, "trace")
        self.t_start = self.t_stop = None

    def poll(self, now: float) -> None:
        if not self.enabled:
            return
        import jax
        if self.state == "idle" and self.at <= now < float("inf"):
            shutil.rmtree(self.dir, ignore_errors=True)
            jax.profiler.start_trace(self.dir)
            self.state = "armed"
        elif self.state == "armed" and now < float("inf"):
            self.ann = jax.profiler.TraceAnnotation("bench.window")
            self.ann.__enter__()
            self.t_start, self.on_at = time.perf_counter(), now
            self.state = "on"
        elif self.state == "armed":
            jax.profiler.stop_trace()     # the window closed first
            self.state = "done"
        elif self.state == "on" and now >= self.on_at + self.length:
            self.t_stop = time.perf_counter()
            self.ann.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.state = "done"

    def path(self) -> Optional[str]:
        hits = sorted(glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb")))
        return hits[-1] if hits else None


# --------------------------------------------------------------- context
class Context:
    """What one run of one cell needs, found from its name."""

    def __init__(self, spec: dict, workload: str, seed: int, seconds: int,
                 trace: bool, control: bool = False,
                 rate: Optional[float] = None, overrides=None,
                 keep_trace: Optional[str] = None):
        cells = {w["name"]: w for w in spec["workloads"]}
        if workload not in cells:
            fail(f"unknown workload {workload!r}; known: {sorted(cells)}")
        self.cell = cells[workload]
        cfg_entry = {c["name"]: c for c in spec["configs"]}[
            self.cell["config"]]
        self.config = load_json(os.path.join(ROOT, cfg_entry["file"]))
        self.mix = load_json(os.path.join(
            HERE, "traffic", self.cell["traffic"] + ".json"))
        self.cell_params = load_json(os.path.join(
            HERE, "cells", workload + ".json"))
        for name, over in (overrides or {}).items():
            getattr(self, name).update(over)
        self.spec, self.workload = spec, workload
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.control, self.rate, self.keep_trace = control, rate, keep_trace
        self.reference = importlib.import_module(
            "chip.reference." + self.config["reference"])
        self.compiles = {"traces": 0, "compiles": 0}
        self.window_compiles = None
        self.t_setup = None
        self.tracer = Tracer(trace, self.mix.get("trace_at", 0.4) * seconds,
                             self.mix.get("trace_seconds", 3.0))

    # --- the system under test, and its inputs
    @property
    def key(self):
        """The run's PRNG key from a seed of any size."""
        import jax
        import jax.numpy as jnp
        import numpy as np
        words = np.random.SeedSequence(self.seed).generate_state(2)
        return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                        impl="threefry2x32")

    @property
    def model(self):
        if not hasattr(self, "_model"):
            from repro.configs import get_config
            from repro.models import build_model
            prog = dict(self.config["program"])
            cfg = dataclasses.replace(get_config(prog.pop("arch")), **prog)
            c = self.config
            want = dict(d_model=c["hidden_size"], d_ff=c["intermediate_size"],
                        num_heads=c["num_attention_heads"],
                        num_layers=c["num_hidden_layers"],
                        vocab_size=c["vocab_size"])
            got = {k: getattr(cfg, k) for k in want}
            if got != want:
                fail(f"program config {got} is not the file's {want}")
            self._model = build_model(cfg)
        return self._model

    def check_layout(self, params) -> None:
        """The benchmark's weights have the tree and shapes the program's
        own ``init`` gives."""
        import jax
        want = jax.eval_shape(self.model.init, jax.random.PRNGKey(0))
        got_s, got_t = jax.tree.flatten(jax.tree.map(lambda a: a.shape,
                                                     params))
        want_s, want_t = jax.tree.flatten(jax.tree.map(lambda a: a.shape,
                                                       want))
        if got_t != want_t or got_s != want_s:
            fail(f"weights layout differs from the program's: {got_t} "
                 f"{got_s[:4]} vs {want_t} {want_s[:4]}")

    def lower_precision(self):
        """The control's rounding: every matmul operand through float8
        (e4m3) with a per-tensor scale, the step below bfloat16."""
        import jax
        import jax.numpy as jnp

        def fp8(x):
            # the forward rounds; the gradient passes straight through, as
            # a float8 training recipe keeps its cotangents wider
            s = jax.lax.stop_gradient(jnp.max(jnp.abs(x)) / 448.0)
            s = jnp.where(s > 0, s, 1.0)
            r = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
            return x + jax.lax.stop_gradient(r - x)
        return fp8

    # --- phases
    def note(self, msg: str) -> None:
        print(f"note: {msg}", file=sys.stderr, flush=True)

    def setup_done(self) -> None:
        self.t_setup = time.perf_counter()

    @contextlib.contextmanager
    def window(self):
        before = dict(self.compiles)
        yield
        self.window_compiles = {k: self.compiles[k] - before[k]
                                for k in before}

    def free_device(self) -> None:
        """Drop the program's compiled programs with its state, so that
        the reference that follows has the chip's memory."""
        import gc
        import jax
        gc.collect()
        jax.clear_caches()
        gc.collect()
        live = sorted(jax.live_arrays(), key=lambda a: -a.nbytes)
        self.note(f"after freeing the program: {len(live)} live arrays, "
                  f"{sum(a.nbytes for a in live) / 2**30:.2f} GiB; largest "
                  f"{[(a.shape, str(a.dtype)) for a in live[:4]]}")

    def memory_peak(self) -> int:
        import jax
        devs = jax.devices()[:self.cell["chips"]]
        return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in devs)


def _count_compiles(ctx: Context) -> None:
    from jax._src import monitoring

    def on(name, *a, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            ctx.compiles["compiles"] += 1
        elif name == "/jax/core/compile/jaxpr_trace_duration":
            ctx.compiles["traces"] += 1
    monitoring.register_event_duration_secs_listener(on)


def device_or_fail(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"needs a TPU; JAX found platform {devs[0].platform!r}")
    if len(devs) < chips:
        fail(f"cell needs {chips} chips; JAX found {len(devs)}")
    return devs[:chips]


def read_per_layer(ctx: Context, out: Outcome, devs) -> tuple:
    """Reduce the trace and run each per-layer metric's reader."""
    from chip import tracing
    from chip.peaks import peak
    path = ctx.tracer.path()
    if path is None:
        fail("the traced run wrote no trace")
    if ctx.keep_trace:
        tracing.describe(path, ctx.keep_trace + ".lines.txt")
    tr = tracing.load(path)
    if ctx.keep_trace:
        tracing.save(tr, ctx.keep_trace + ".json")
        with open(ctx.keep_trace + ".extra.json", "w") as f:
            json.dump({k: [dataclasses.asdict(x) if dataclasses.is_dataclass(x)
                           else x for x in v] if isinstance(v, list) else v
                       for k, v in out.extra.items()}, f)
    run = tracing.Run(trace=tr, ctx=ctx, outcome=out,
                      peak=peak(devs[0].device_kind))
    metrics = {}
    for m in ctx.spec["per_layer"]:
        if not _reports(ctx, m):
            continue
        mod = importlib.import_module("chip.metrics." + m["name"])
        v = mod.read(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    busy = sum(tr.busy_s(d) for d in tr.devices) / max(1, len(tr.devices))
    return metrics, busy, tr.window_s, tracing.breakdown(tr)


def _reports(ctx: Context, m: dict) -> bool:
    if "workloads" in m:
        return ctx.workload in m["workloads"]
    e2e = {e["name"]: e for e in ctx.spec["end_to_end"]}
    moved = e2e.get(m["moves"], {})
    return "workloads" not in moved or ctx.workload in moved["workloads"]


def main(args, t_start: float) -> None:
    spec = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    ctx = Context(spec, args.workload, args.seed, args.seconds,
                  bool(args.trace), bool(args.control), args.rate,
                  keep_trace=args.keep_trace)
    devs = device_or_fail(ctx.cell["chips"])
    from repro.launch.env import use_compile_cache
    import jax
    cache = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    ctx.note(f"device {devs[0].device_kind} x{len(jax.devices())}, using "
             f"{len(devs)}; compile cache {cache}")
    result = execute(ctx, devs, t_start)
    for n, c in result["checks"].items():
        print(f"check {n}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def execute(ctx: Context, devs, t_start: float) -> dict:
    """One run of the cell on ``devs``: set-up, window, check; returns the
    result object (the harness's look for a chip is the caller's)."""
    _count_compiles(ctx)
    runner = importlib.import_module("chip." + ctx.mix["kind"])
    out = runner.run(ctx)
    setup_s = ctx.t_setup - t_start
    print(f"window compiles: {ctx.window_compiles['compiles']} "
          f"(traces {ctx.window_compiles['traces']})", flush=True)
    ctx.note(f"setup_s {setup_s!r}")
    correct = bool(out.checks) and all(ok for *_, ok in out.checks) \
        and out.failed == 0
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": out.memory_peak}
    result = {"correct": correct, "attempted": out.attempted,
              "failed": out.failed}
    if ctx.trace:
        metrics, busy, window_s, bd = read_per_layer(ctx, out, devs)
        device.update(busy_s=busy, window_s=window_s)
        result["breakdown"] = bd
    else:
        metrics = {}
        for e in ctx.spec["end_to_end"]:
            if "workloads" in e and ctx.workload not in e["workloads"]:
                continue
            v = setup_s if e["name"] == "setup_s" else out.e2e.get(e["name"])
            if v is None:
                result["correct"] = False
            else:
                metrics[e["name"]] = {"value": float(v), "unit": e["unit"]}
    result.update(metrics=metrics, device=device)
    checks = {n: {"value": v, "limit": lim} for n, v, lim, _ in out.checks}
    checks["failed"] = {"value": out.failed, "limit": 0}
    result["checks"] = checks
    return result
