"""The reduction from a profiler trace to per-layer numbers."""
import os

import pytest

from chip import tracing

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def dev(ops, modules=()):
    return {"name": "/device:TPU:0", "ops": [list(o) for o in ops],
            "modules": [list(m) for m in modules]}


def test_union_subtract_and_total():
    assert tracing.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tracing.subtract([(0, 10)], [(2, 3), (5, 7), (6, 8)]) == \
        [(0, 2), (3, 5), (8, 10)]
    assert tracing.total(tracing.clip([(0, 4), (8, 20)], 2, 10)) == 4


def test_busy_is_the_union_of_overlapping_ops_inside_the_window():
    d = dev([("a", 0, 40), ("b", 20, 40), ("c", 90, 30)])
    tr = tracing.Trace([d], [], (10, 100))
    assert tr.busy_s(d) == pytest.approx(60e-9)       # 10-60 and 90-100
    assert tr.idle_gaps(d) == [(60, 90)]


def test_exposed_collective_is_what_no_compute_overlaps():
    d = dev([("fusion.1", 0, 50), ("all-reduce.3", 40, 30),
             ("fusion.2", 60, 30)])
    tr = tracing.Trace([d], [], (0, 100))
    assert tr.exposed_collective_s(d) == pytest.approx(10e-9)   # 50-60


def test_gaps_are_labelled_by_the_host_span_over_them():
    d = dev([("x", 0, 10), ("y", 30, 10), ("z", 60, 40)])
    host = [["serve.iter", 0, 25, {"i": 0}], ["serve.wait", 40, 20, {}]]
    tr = tracing.Trace([d], host, (0, 100))
    assert tracing.gap_labels(tr, d) == {"serve.iter": pytest.approx(20e-9),
                                         "serve.wait": pytest.approx(20e-9)}
    bd = tracing.breakdown(tr)
    assert bd["device_ops"][0] == ["z", pytest.approx(40e-9)]
    assert len(bd["device_ops"]) == 3


def test_modules_started_in_the_window_by_name():
    d = dev([], [("jit_step(3)", 5, 10), ("jit_step(3)", 95, 10),
                 ("jit_scan(7)", 20, 10), ("jit_step(3)", 200, 5)])
    tr = tracing.Trace([d], [], (0, 100))
    assert tr.modules(d, r"^jit_step\b") == [(5, 10), (95, 10)]


def recorded():
    """A quarter second of a serve run's trace on one TPU v5e (four decode
    steps of ``serve.stablelm-1.6b.chat``), in the compact form."""
    return tracing.read(os.path.join(DATA, "serve_trace_v5e.json"))


def test_recorded_trace_busy_and_idle_fill_the_window():
    tr = recorded()
    dev = tr.devices[0]
    busy, window = tr.busy_s(dev), tr.window_s
    idle = tracing.total(tr.idle_gaps(dev)) * 1e-9
    assert 0 < busy < window
    assert busy + idle == pytest.approx(window)
    labelled = tracing.gap_labels(tr, dev)
    assert sum(labelled.values()) == pytest.approx(idle)
    assert set(labelled) <= {"serve.iter", "none"}


def test_recorded_trace_decode_programs_and_kernels():
    from chip.metrics import _serve
    tr = recorded()
    steps = tr.modules(tr.devices[0], _serve.DECODE)
    assert len(steps) >= 3
    # each decode step ran 60-90 ms on the chip when this was recorded
    assert all(60e6 < d < 90e6 for _, d in steps)
    kernels = [n for n, _, _ in tr.devices[0]["ops"]
               if n.endswith(tracing.PALLAS)]
    assert kernels and all(n.startswith("decode") for n in kernels)
    assert tr.op_time_s(tr.devices[0], r"^decode\b.*\[pallas\]$") > 0


def test_recorded_trace_breakdown():
    bd = tracing.breakdown(recorded())
    assert 1 <= len(bd["device_ops"]) <= 10
    assert len(bd["idle_gaps"]) >= 1
    secs = [s for _, s in bd["device_ops"]]
    assert secs == sorted(secs, reverse=True)


def test_op_names_are_cut_to_the_instruction():
    text = ('%decode.4 = bf16[4,1,32,1,64]{4,3,2,1,0} custom-call(%a, %b), '
            'custom_call_target="tpu_custom_call"')
    assert tracing.op_name(text) == "decode.4 [pallas]"
    assert tracing.op_name("%fusion.7 = f32[8] fusion(%x), kind=kLoop") == \
        "fusion.7"
