"""Trace analytics layer: step attribution, overlap bounds, pipeline
bubble accounting, serve latency extraction and SLO burn-rate alerting
(docs/observability.md, "Analysis & SLOs")."""
import jax.numpy as jnp
import pytest

from repro.obs.analyze import (analyze, overlap_efficiency,
                               pipeline_accounting, request_latencies,
                               serve_summary, step_attribution)
from repro.obs.slo import Objective, SLOMonitor, evaluate_trace
from repro.obs.trace import TraceRecorder, strip_wall


# --------------------------------------------------------- attribution
def _train_trace():
    """Two steps with compute/exchange inside, a snapshot between them.
    On the tick basis (strip_wall) every duration is exact integer
    arithmetic."""
    rec = TraceRecorder()
    # step 0: ticks [0, 5]; compute [1, 2]; exchange [3, 4]
    with rec.span("step", pid="train", tid="loop",
                  clock=("train_step", 0)):
        with rec.span("compute", pid="train", tid="loop"):
            pass
        with rec.span("exchange", pid="train", tid="loop"):
            pass
    # a snapshot between steps: ticks [6, 7] on the elastic track
    with rec.span("snapshot", pid="elastic", tid="events"):
        pass
    # step 1: ticks [8, 13]
    with rec.span("step", pid="train", tid="loop",
                  clock=("train_step", 1)):
        with rec.span("compute", pid="train", tid="loop"):
            pass
        with rec.span("exchange", pid="train", tid="loop"):
            pass
    return strip_wall(rec.to_chrome())


def test_step_attribution_windows_and_residual():
    attr = step_attribution(_train_trace())
    assert attr is not None
    assert attr["basis"] == "ticks"          # wall was stripped
    s0, s1 = attr["steps"]
    # step 0 window = its own extent [0, 5]
    assert s0["total"] == 5.0
    assert s0["compute"] == 1.0 and s0["comm"] == 1.0
    assert s0["snapshot"] == 0.0             # happened after step 0 ended
    assert s0["stall"] == 3.0                # residual
    # step 1 window = [prev end 5, end 13]: the between-step snapshot is
    # charged to the step that waited for it
    assert s1["total"] == 8.0
    assert s1["snapshot"] == 1.0
    assert s1["stall"] == 5.0
    for row in (s0, s1):
        assert row["attributed_pct"] == pytest.approx(100.0)
    assert attr["attributed_pct_min"] >= 95.0
    assert attr["attributed_pct_max"] <= 105.0
    # totals/fractions are consistent and cover the taxonomy
    assert attr["totals"]["total"] == 13.0
    assert sum(attr["fractions"].values()) == pytest.approx(1.0)


def test_step_attribution_wall_basis_when_present():
    rec = TraceRecorder()
    with rec.span("step", pid="train", tid="loop",
                  clock=("train_step", 0)):
        with rec.span("compute", pid="train", tid="loop"):
            pass
    attr = step_attribution(rec.to_chrome())
    assert attr["basis"] == "wall"
    assert attr["attributed_pct_min"] == pytest.approx(100.0)


def test_step_attribution_none_without_steps():
    rec = TraceRecorder()
    rec.counter("wire_bytes", {"cumulative": 1.0}, pid="train")
    assert step_attribution(rec.to_chrome()) is None


# ---------------------------------------------------- overlap efficiency
def _exchange_trace(no, tictac, issue):
    rec = TraceRecorder()
    with rec.span("exchange", pid="train", tid="loop",
                  clock=("train_step", 0), n_buckets=3,
                  modeled_no_overlap_us=no,
                  modeled_tictac_overlap_us=tictac,
                  modeled_issue_overlap_us=issue):
        pass
    return rec.to_chrome()


def test_overlap_efficiency_in_bounds():
    ov = overlap_efficiency(_exchange_trace(100.0, 60.0, 70.0))
    assert ov is not None and ov["all_in_bounds"]
    assert ov["exchanges"][0]["efficiency"] == pytest.approx(0.75)


def test_overlap_efficiency_violations_flagged():
    assert not overlap_efficiency(
        _exchange_trace(100.0, 60.0, 120.0))["all_in_bounds"]
    assert not overlap_efficiency(
        _exchange_trace(100.0, 60.0, 40.0))["all_in_bounds"]
    # degenerate plan (single bucket): no == tictac -> efficiency 1.0
    ov = overlap_efficiency(_exchange_trace(50.0, 50.0, 50.0))
    assert ov["all_in_bounds"]
    assert ov["exchanges"][0]["efficiency"] == 1.0


def test_overlap_efficiency_none_without_model_args():
    rec = TraceRecorder()
    with rec.span("exchange", pid="train", tid="loop"):
        pass
    assert overlap_efficiency(rec.to_chrome()) is None


def test_commplan_stamps_modeled_bounds():
    """The real CommPlan exchange span carries the three modeled times
    and its issue order lies between the serial and TicTac bounds."""
    from repro.comm.plan import CommPlan
    from repro.core.compression import Compressor
    params = {"a": jnp.zeros((64, 8)), "b": jnp.zeros((130,))}
    plan = CommPlan.plan(params, axis="w", n=4, topology="ring",
                         compressor=Compressor("onebit"), wire="measured",
                         bucket_mb=1e-4)
    rec = TraceRecorder()
    plan.emit_trace(rec, clock=("train_step", 0))
    ov = overlap_efficiency(rec.to_chrome())
    assert ov is not None and ov["all_in_bounds"]
    ex = ov["exchanges"][0]
    assert ex["tictac_overlap_us"] <= ex["no_overlap_us"]
    assert 0.0 <= ex["efficiency"] <= 1.0


# --------------------------------------------------- pipeline accounting
@pytest.mark.parametrize("stages,micro", [(2, 4), (4, 8), (2, 2), (3, 7)])
def test_pipeline_accounting_matches_analytic(stages, micro):
    from repro.parallel.engine import emit_pipeline_trace
    rec = TraceRecorder()
    emit_pipeline_trace(rec, stages, micro, clock=("train_step", 0))
    pp = pipeline_accounting(rec.to_chrome())
    assert pp is not None and len(pp["pipes"]) == 1
    row = pp["pipes"][0]
    # the schedule model is exact: bubble cells = s * (ticks - m)
    ticks = micro + stages - 1
    assert row["ticks"] == ticks
    assert row["bubble_ticks"] == stages * (ticks - micro)
    assert row["active_ticks"] == stages * micro
    # analytic_bubble is rounded to 6 decimals in the trace args
    assert row["measured_bubble"] == pytest.approx(
        row["analytic_bubble"], abs=1e-5)
    assert pp["rel_err_max"] == pytest.approx(0.0, abs=1e-5)


def test_pipeline_accounting_none_without_pipe():
    rec = TraceRecorder()
    with rec.span("step", pid="train", tid="loop"):
        pass
    assert pipeline_accounting(rec.to_chrome()) is None


def test_emit_pipeline_trace_disabled_is_noop():
    from repro.obs.trace import NullRecorder
    from repro.parallel.engine import emit_pipeline_trace
    emit_pipeline_trace(NullRecorder(), 2, 4)   # must not raise


# -------------------------------------------------------- serve extract
def _serve_trace(n=4, stalls=(1.0, 2.0)):
    """Synthetic lifecycle tracks mirroring serve/engine.py's schema:
    rid i arrives at 0, first token at 2+i, finishes at 8+i having
    generated 4 tokens -> ttft = 2+i, tpot = 2.0."""
    rec = TraceRecorder()
    for i in range(n):
        tid = f"req{i}"
        rec.begin("queued", pid="serve", tid=tid,
                  clock=("serve_iter", 0.0), rid=i, arrival=0.0)
        rec.end(pid="serve", tid=tid)
        rec.begin("prefill", pid="serve", tid=tid,
                  clock=("serve_iter", 1.0 + i), rid=i)
        rec.end(pid="serve", tid=tid)
        rec.begin("decode", pid="serve", tid=tid,
                  clock=("serve_iter", 2.0 + i), rid=i)
        rec.end(pid="serve", tid=tid, generated=4)
        rec.instant("done", pid="serve", tid=tid,
                    clock=("serve_iter", 8.0 + i), rid=i, generated=4)
    for t in stalls:
        rec.instant("admission_stall", pid="serve", tid="engine",
                    clock=("serve_iter", t))
    for t in range(12):
        rec.counter("slots", {"used": 1.0, "free": 3.0}, pid="serve",
                    clock=("serve_iter", float(t)))
    return rec.to_chrome()


def test_request_latencies_and_summary():
    tr = _serve_trace()
    rows = request_latencies(tr)
    assert [r["rid"] for r in rows] == [0, 1, 2, 3]
    assert [r["ttft"] for r in rows] == [2.0, 3.0, 4.0, 5.0]
    assert all(r["tpot"] == pytest.approx(2.0) for r in rows)
    s = serve_summary(tr)
    assert s["requests"] == 4
    assert s["ttft_p99"] == 5.0
    assert s["tpot_p50"] == pytest.approx(2.0)
    assert s["admission_stalls"] == 2
    assert s["slo_burn_alerts"] == 0


def test_analyze_bundles_sections():
    out = analyze(_serve_trace())
    assert out["validation"]["errors"] == []
    assert out["serve"]["requests"] == 4
    assert out["attribution"] is None        # no train spans here
    assert out["pipeline"] is None


# ----------------------------------------------------------------- SLOs
def test_objective_parse():
    o = Objective.parse("ttft_p99<8")
    assert (o.metric, o.threshold) == ("ttft", 8.0)
    assert o.budget == pytest.approx(0.01)
    assert o.bad(8.5) and not o.bad(8.0)
    r = Objective.parse("stall_rate<=0.1")
    assert (r.metric, r.budget, r.threshold) == ("stall", 0.1, 0.0)
    assert r.bad(1.0) and not r.bad(0.0)
    assert Objective.parse("tpot_p50 < 1.5").threshold == 1.5
    for bad in ["ttft<8", "ttft_p0<8", "ttft_p100<8", "x_rate<0",
                "x_rate<1.5", "nonsense", "ttft_p99<"]:
        with pytest.raises(ValueError):
            Objective.parse(bad)


def test_slo_monitor_multiwindow_burn():
    mon = SLOMonitor(["ttft_p99<8"], long_window=10.0, short_window=2.0,
                     factor=2.0)
    # sustained badness: both windows burn -> firing
    for t in range(1, 11):
        mon.observe("ttft", float(t), 20.0)
    assert mon.firing(10.0)
    row = mon.evaluate(10.0)[0]
    assert row["burn_long"] == pytest.approx(100.0)   # 1.0 / 0.01
    # recovery: the short window goes clean first -> alert resets even
    # though the long window still burns
    for t in range(11, 14):
        mon.observe("ttft", float(t), 1.0)
    row = mon.evaluate(13.0)[0]
    assert row["burn_long"] >= 2.0 and row["burn_short"] == 0.0
    assert not row["firing"]
    # no observations in window -> no evidence, no alarm
    assert mon.evaluate(1000.0)[0]["firing"] is False


def test_slo_monitor_requires_objectives():
    with pytest.raises(ValueError):
        SLOMonitor([])


def test_evaluate_trace_fires_on_tight_slo_only():
    tr = _serve_trace()
    hot = evaluate_trace(tr, ["ttft_p99<2"], long_window=16.0,
                         short_window=4.0, factor=1.0)
    assert hot["alerts"], hot
    assert hot["alerts"][0]["objectives"] == ["ttft_p99<2"]
    cold = evaluate_trace(tr, ["ttft_p99<100"], long_window=16.0,
                          short_window=4.0, factor=1.0)
    assert not cold["alerts"]
    # ttft/tpot per request + one stall sample per sampled iteration
    assert hot["observations"] == 2 * 4 + 12


def test_autoscaler_burn_times_force_scale_up():
    from repro.obs.trace import tracing
    from repro.serve.autoscale import AutoscalePolicy, Autoscaler
    pol = AutoscalePolicy(replica_rate=100.0, min_replicas=1,
                          max_replicas=4, interval=5.0,
                          scale_down_patience=2)
    # no arrivals: the rate signal alone never scales up
    quiet = Autoscaler(pol).schedule([], horizon=20.0)
    assert [d.replicas for d in quiet] == [1]
    with tracing() as rec:
        burned = Autoscaler(pol).schedule([], horizon=20.0,
                                          burn_times=[7.0])
    # the burn lands in the (5, 10] decision interval -> forced +1;
    # patience then walks it back down two intervals later
    assert [(d.t, d.replicas) for d in burned] == [
        (0.0, 1), (10.0, 2), (20.0, 1)]
    ups = [ev for ev in rec.events
           if ev["name"] == "autoscale_decision"
           and ev["args"].get("reason") == "slo_burn"]
    assert len(ups) == 1 and ups[0]["args"]["to_replicas"] == 2
