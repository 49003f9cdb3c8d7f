"""The split of the step's device time by the program's name scopes, and
the readers of the program's spans and scopes."""
import importlib
import os
from types import SimpleNamespace

import pytest

from chip import scopes, tracing

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_self_time_takes_nested_ops_off_their_parent():
    # a layer scan's while (0-100) holds two body ops and a nested loop
    # (60-90) that holds one more; then a sibling op after the scan
    ops = [(0, 100), (10, 20), (40, 10), (60, 30), (65, 20), (120, 5)]
    own, parent = scopes.nesting(ops)
    assert own == [40.0, 20.0, 10.0, 10.0, 20.0, 5.0]
    assert sum(own) == 105                        # the union of the ops
    assert parent == [-1, 0, 0, 0, 3, -1]


@pytest.mark.parametrize("stack, cls, attention", [
    ("jit(sharded_step)/jvp()/while/body/closed_call/attention/dot_general",
     "forward", True),
    ("jit(sharded_step)/transpose(jvp())/while/body/closed_call/attention/"
     "add_any", "backward", True),
    ("jit(sharded_step)/transpose(jvp(norm))/mul", "backward", False),
    ("jit(sharded_step)/jvp()/while/body/dynamic_slice", "forward", False),
    ("jit(sharded_step)/jvp(embed)/gather", "forward", False),
    ("jit(prefill)/attention/dot_general", "forward", True),
    ("jit(sharded_step)/optimizer/sub", "optimizer", False),
    ("jit(sharded_step)/optimizer/exchange/reduce-scatter", "other", False),
    ("jit(sharded_step)/exchange/mul", "other", False),
    ("jit(sharded_step)/broadcast_in_dim", "other", False),
    ("", "other", False),
])
def test_classes_and_attention_by_name_stack(stack, cls, attention):
    assert scopes.classify(stack) == cls
    assert ("attention" in scopes.scope_names(stack)) == attention


def test_per_step_times_by_class_and_scope():
    stacks = ["jit(s)/jvp()/attention/dot", "jit(s)/transpose(jvp())/mlp/dot",
              "jit(s)/optimizer/sub", "jit(s)/exchange/copy"]
    sc = scopes.Scopes((0, 10), stacks, [
        {"name": "/device:TPU:0", "steps": 2,
         "ops": [[0, 4e6], [1, 6e6], [0, 4e6], [2, 2e6], [3, 1e6]]}])
    assert sc.class_ms("forward") == pytest.approx(4.0)
    assert sc.class_ms("backward") == pytest.approx(3.0)
    assert sc.class_ms("optimizer") == pytest.approx(1.0)
    assert sc.class_ms("other") == pytest.approx(0.5)
    assert sc.scope_ms("attention") == pytest.approx(4.0)
    # a scope the program does not have reads nothing, not zero
    assert sc.scope_ms("moe") is None


def _pb(*fields):
    """A protobuf message of (field, int | bytes | str) pairs."""
    def varint(n):
        out = bytearray()
        while True:
            out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
            n >>= 7
            if not n:
                return bytes(out)
    out = b""
    for num, v in fields:
        if isinstance(v, int):
            out += varint(num << 3) + varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += varint(num << 3 | 2) + varint(len(v)) + v
    return out


def _hlo_plane(*modules):
    """A ``/host:metadata`` plane holding HLO modules as a TPU trace does:
    each a stat ``Hlo Proto`` of an event metadata entry.  ``modules``
    are (name, {instruction: op_name})."""
    def inst(name, op_name):
        return _pb((1, name), (2, "add"), (7, _pb((1, "add"), (2, op_name))))

    def event_meta(i, name, ops):
        module = _pb((1, name), (3, _pb((1, "main"), *[
            (2, inst(k, v)) for k, v in ops.items()])))
        return (4, _pb((1, i), (2, _pb((1, i), (2, f"{name}({i})"), (5, _pb(
            (1, 7), (6, _pb((1, module)))))))))
    return _pb((1, 1), (2, "/host:metadata"),
               (5, _pb((1, 7), (2, _pb((1, 7), (2, "Hlo Proto"))))),
               *[event_meta(i + 1, n, ops) for i, (n, ops) in
                 enumerate(modules)])


def test_hlo_op_names_from_the_metadata_plane(tmp_path):
    plane = _hlo_plane(
        ("jit_sharded_step", {"fusion.3": "jit(sharded_step)/optimizer/sub",
                              "while.1": "jit(sharded_step)/jvp()/while"}),
        ("jit_convert", {"fusion.3": "jit(convert)/convert"}))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_pb((1, _pb((1, 0), (2, "/host:CPU"))), (1, plane)))
    assert scopes.hlo_op_names(str(path)) == {
        "fusion.3": "jit(sharded_step)/optimizer/sub",
        "while.1": "jit(sharded_step)/jvp()/while"}


def _xspace(path):
    """An XSpace as a TPU trace lays it out: one step program in the
    window on the ``XLA Modules`` line (a second after it), its ops on
    ``XLA Ops`` named by their HLO text, a layer scan's ``while`` holding
    its body, the program's HLO module on ``/host:metadata`` and the
    benchmark's ``bench.window`` on the host.  Times in ns from 1000."""
    names = ["jit_sharded_step(1)", "%while.1 = (f32[4]) while(%t)",
             "%fusion.2 = bf16[8] fusion(%a), kind=kOutput",
             "%copy-start.3 = (f32[8]) copy-start(%b)",
             "%fusion.9 = f32[8] fusion(%c, %d), kind=kLoop",
             "%fusion.4 = bf16[8] fusion(%e), kind=kLoop", "bench.window"]
    op_names = {"while.1": "jit(sharded_step)/transpose(jvp())/while",
                "fusion.2": "jit(sharded_step)/transpose(jvp())/while/body/"
                            "attention/dot_general",
                "fusion.9": "jit(sharded_step)/optimizer/sub",
                "fusion.4": "jit(sharded_step)/jvp(embed)/gather"}

    def event(i, start_ns, dur_ns):
        return _pb((1, i + 1), (2, start_ns * 1000), (3, dur_ns * 1000))

    def line(name, *events):
        return _pb((2, name), (3, 1000), *[(4, e) for e in events])
    meta = [(4, _pb((1, i + 1), (2, _pb((1, i + 1), (2, n)))))
            for i, n in enumerate(names)]
    device = _pb((2, "/device:TPU:0"), *meta, (3, line(
        "XLA Modules", event(0, 0, 100), event(0, 300, 100))), (3, line(
            "XLA Ops", event(5, 0, 5), event(1, 5, 75), event(2, 10, 30),
            event(3, 50, 10), event(4, 85, 10), event(4, 310, 10))))
    host = _pb((2, "/host:CPU"), *meta, (3, line(
        "python", event(6, 0, 200))))
    path.write_bytes(_pb((1, device), (1, host), (1, _hlo_plane(
        ("jit_sharded_step", op_names)))))
    return str(path)


def test_load_splits_the_step_programs_in_the_window(tmp_path):
    sc = scopes.load(_xspace(tmp_path / "t.xplane.pb"))
    assert sc.window == (1000, 1200)
    assert [d["steps"] for d in sc.devices] == [1]   # the second is outside
    # the copy has no name stack and takes the while's: backward
    assert sc.class_ms("backward") == pytest.approx(75e-6)
    assert sc.class_ms("forward") == pytest.approx(5e-6)
    assert sc.class_ms("optimizer") == pytest.approx(10e-6)
    assert sc.scope_ms("attention") == pytest.approx(30e-6)
    assert sc.class_ms("other") is None


@pytest.mark.parametrize("metric, ms", [("train_fwd_ms", 5e-6),
                                        ("train_bwd_ms", 75e-6),
                                        ("train_opt_ms", 10e-6),
                                        ("train_attn_ms", 30e-6)])
def test_device_reader_reads_the_runs_own_trace(tmp_path, metric, ms):
    path = _xspace(tmp_path / "t.xplane.pb")
    ctx = SimpleNamespace(tracer=SimpleNamespace(path=lambda: path))
    run = tracing.Run(trace=None, ctx=ctx, outcome=None, peak={})
    read = importlib.import_module("chip.metrics." + metric).read
    assert read(run) == pytest.approx(ms)
    # no trace written: nothing to read
    ctx.tracer.path = lambda: None
    assert read(run) is None


def test_host_ms_is_the_step_less_its_wait():
    host = [["train.step", 10, 100, {"step": 0}],
            ["train.step.wait", 60, 40, {}],
            ["train.step", 120, 100, {"step": 1}],
            ["train.step.wait", 150, 60, {}],
            ["train.step", 230, 100, {"step": 2}]]      # not wholly inside
    tr = tracing.Trace([], host, (0, 300))
    run = tracing.Run(trace=tr, ctx=None, outcome=None, peak={})
    from chip.metrics import train_host_ms
    assert train_host_ms.read(run) == pytest.approx(50e-6)
    # a program that writes no step spans reads nothing
    bare = tracing.Run(trace=tracing.Trace([], [], (0, 300)), ctx=None,
                       outcome=None, peak={})
    assert train_host_ms.read(bare) is None


# ---------------------------------------------------- recorded on the chip
def recorded_run():
    """Three steps of ``train.stablelm-1.6b.s2k`` traced on one TPU v5e:
    the host spans (``train_trace_v5e.json``) and the step programs'
    scope split (``train_scopes_v5e.json``)."""
    tr = tracing.read(os.path.join(DATA, "train_trace_v5e.json"))
    path = os.path.join(DATA, "train_scopes_v5e.json")
    ctx = SimpleNamespace(tracer=SimpleNamespace(path=lambda: path))
    return tracing.Run(trace=tr, ctx=ctx, outcome=None, peak={})


@pytest.mark.parametrize("metric", ["train_fwd_ms", "train_bwd_ms",
                                    "train_opt_ms", "train_attn_ms",
                                    "train_host_ms"])
def test_reader_on_the_recorded_trace(metric):
    v = importlib.import_module("chip.metrics." + metric).read(
        recorded_run())
    # a step took about 270 ms on the chip when this was recorded
    assert v is not None and 0 < v < 270


def test_recorded_split_accounts_for_the_step():
    run = recorded_run()
    sc = scopes.of(run)
    split = {c: sc.class_ms(c) or 0.0 for c in scopes.CLASSES}
    total = sum(split.values())
    assert split["forward"] + split["backward"] + split["optimizer"] \
        >= 0.9 * total
    assert sc.scope_ms("attention") < split["forward"] + split["backward"]
    # the step programs' self times fill their device time
    tr = run.trace
    progs = tr.modules(tr.devices[0], r"sharded_step")
    busy = sum(d for _, d in progs) / len(progs) * 1e-6
    assert total == pytest.approx(busy, rel=0.05)
