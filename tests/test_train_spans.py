"""The training path's spans and scopes on the device trace's clock
(docs/observability.md, "On the device clock"): ``train.step`` and its
``feed`` / ``dispatch`` / ``wait`` phases as profiler annotations, the
recorder's own virtual-tick trace unchanged by them, and the model and
engine scopes in the compiled step's op metadata."""
import dataclasses
import json
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.obs.trace import TraceRecorder, set_recorder, span, strip_wall, \
    tracing
from repro.train import Strategy, Trainer

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
KEY = jax.random.PRNGKey(0)
W_TRUE = jax.random.normal(KEY, (8, 1))
P0 = {"W": jnp.zeros((8, 1))}
PHASES = ("train.step.feed", "train.step.dispatch", "train.step.wait")


def batches(t, w):
    X = jax.random.normal(jax.random.fold_in(KEY, t * 100 + w), (16, 8))
    return {"X": X, "y": X @ W_TRUE}


def grad_fn(p, b):
    return jax.value_and_grad(
        lambda q: jnp.mean((b["X"] @ q["W"] - b["y"]) ** 2))(p)


def test_span_records_only_when_asked_and_enabled():
    rec = TraceRecorder()
    prev = set_recorder(rec)
    try:
        with span("train.step", "step", pid="train", tid="loop",
                  clock=("train_step", 4), step=4):
            with span("train.step.wait"):
                pass
    finally:
        set_recorder(prev)
    assert [(e["name"], e["ph"]) for e in rec.events] == [("step", "B"),
                                                          ("step", "E")]
    assert rec.events[0]["args"]["step"] == 4
    assert rec.events[0]["args"]["clock_t"] == 4
    with span("train.step", "step", step=0):     # the no-op recorder
        pass


def _host_events(logdir):
    from jax.profiler import ProfileData
    import glob
    path = sorted(glob.glob(os.path.join(
        str(logdir), "plugins", "profile", "*", "*.xplane.pb")))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            out += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                     dict(e.stats)) for e in line.events
                    if e.name.startswith("train.step")]
    return out


def test_profiler_trace_holds_the_step_phases(tmp_path):
    steps = 3
    trainer = Trainer(Strategy.parse("bsp/allreduce/none@1", lr=0.1))
    trainer.fit(grad_fn, P0, batches, 1)          # compile outside
    with jax.profiler.trace(str(tmp_path)):
        trainer.fit(grad_fn, P0, batches, steps)
    evs = _host_events(tmp_path)
    outer = sorted((e for e in evs if e[0] == "train.step"),
                   key=lambda e: e[1])
    assert [e[3]["step"] for e in outer] == list(range(steps))
    for name in PHASES:
        inner = [e for e in evs if e[0] == name]
        assert len(inner) == steps, name
        for e in inner:
            assert sum(o[1] <= e[1] and e[2] <= o[2] for o in outer) == 1
    for o in outer:      # one of each phase, in order, inside each step
        phases = sorted((e for e in evs if e[0] in PHASES
                         and o[1] <= e[1] and e[2] <= o[2]),
                        key=lambda e: e[1])
        assert [e[0] for e in phases] == list(PHASES)


def _recorded(spec):
    with tracing() as rec:
        if spec == "HybridEngine.run":
            Strategy.parse("bsp/allreduce/none@1:d1.adamw", lr=0.1).build(
                grad_fn).inner.run(P0, batches, 3)
        else:
            kw = {"wire": "measured"} if "dgc" in spec else {}
            Trainer(Strategy.parse(spec, lr=0.1, **kw)).fit(
                grad_fn, P0, batches, 3)
    return json.dumps(strip_wall(rec.to_chrome()), sort_keys=True)


@pytest.mark.parametrize("spec", ["bsp/allreduce/none@1",
                                  "bsp/allreduce/dgc:0.5@1",
                                  "bsp/allreduce/none@1:d1.adamw",
                                  "HybridEngine.run"])
def test_recorder_trace_is_unchanged_by_the_device_spans(spec):
    """The virtual-tick trace of a seeded run, wall time stripped, is
    byte for byte what it was before the profiler spans went in."""
    with open(os.path.join(DATA, "recorder_traces.json")) as f:
        want = json.load(f)[spec]
    assert _recorded(spec) == want


def test_compiled_step_carries_the_scopes():
    from repro.configs import get_config
    from repro.models import build_model
    cfg = dataclasses.replace(get_config("stablelm-1.6b"), d_model=64,
                              num_heads=4, num_kv_heads=4, head_dim=16,
                              d_ff=128, vocab_size=256, num_layers=2)
    model = build_model(cfg)

    def lm_grad_fn(p, b):
        (loss, _), g = jax.value_and_grad(
            lambda q: model.loss_fn(q, b), has_aux=True)(p)
        return loss, g
    params = model.init(KEY)
    tok = jax.random.randint(KEY, (1, 2, 17), 0, cfg.vocab_size)
    batch = {"tokens": tok[..., :-1], "labels": tok[..., 1:]}
    engine = Strategy.parse("bsp/allreduce/none@1", lr=0.1).build(
        lm_grad_fn).inner
    step = engine._build_step(params)
    text = step.lower(params, None, batch, jax.random.split(KEY, 1),
                      jnp.ones((1,))).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    scopes = {s for n in names for s in n.split("/")}
    assert {"attention", "mlp", "norm", "optimizer", "exchange"} <= scopes
    assert any(s.startswith("transpose(jvp(") for s in scopes)
    assert {"jvp(embed)", "transpose(jvp(head))",
            "transpose(jvp(loss))"} <= scopes
    # attention's backward ops carry both the transform and the scope
    assert any(re.search(r"transpose\(jvp\(.*attention", n) for n in names)
