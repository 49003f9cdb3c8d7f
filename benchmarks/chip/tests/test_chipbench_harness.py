"""The harness finds every file ``BENCHMARK.json`` names, and a train
run's feed drives the tracer only inside the measured window."""
import importlib
import json
import os

import numpy as np
import pytest

from chip import harness

SPEC = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda w: w["name"])
def test_every_cell_finds_its_files(cell):
    cfg = {c["name"]: c for c in SPEC["configs"]}[cell["config"]]
    conf = harness.load_json(os.path.join(harness.ROOT, cfg["file"]))
    assert sorted(conf["reduced"]) == sorted(cfg["reduced"])
    for key, (published, run) in conf["reduced"].items():
        assert conf[key] == run != published
    assert os.path.exists(os.path.join(harness.HERE, "traffic",
                                       cell["traffic"] + ".json"))
    assert os.path.exists(os.path.join(harness.HERE, "cells",
                                       cell["name"] + ".json"))
    importlib.import_module("chip.reference." + conf["reference"])
    e2e = {e["name"] for e in SPEC["end_to_end"]
           if cell["name"] in e.get("workloads", [cell["name"]])}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert any(cell["name"] in m.get("workloads", [cell["name"]])
               and m["moves"] in e2e for m in SPEC["per_layer"])


@pytest.mark.parametrize("metric", SPEC["per_layer"], ids=lambda m: m["name"])
def test_every_per_layer_metric_has_a_reader(metric):
    mod = importlib.import_module("chip.metrics." + metric["name"])
    assert callable(mod.read)
    assert metric["moves"] in {e["name"] for e in SPEC["end_to_end"]}


def test_train_feed_polls_the_tracer_only_inside_the_window():
    from chip.train import Feed

    class Polls:
        def __init__(self):
            self.at = []

        def poll(self, now):
            self.at.append(now)

    rows = {"tokens": np.zeros((8, 4), np.int32),
            "targets": np.zeros((8, 4), np.int32)}
    tracer, spans = Polls(), []
    feed = Feed(rows, 1, 2, spans, tracer, [0.0])
    for t in range(3):                      # set-up's steps
        assert feed(t)["tokens"].shape == (2, 4)
    assert tracer.at == []
    feed.in_window = True
    feed(0)
    feed(0, w=1)                            # only worker 0 polls
    assert len(tracer.at) == 1
    assert [s[0] for s in spans] == ["train.batches"] * 5
    assert json.dumps(spans[-1][3]) == '{"t": 0, "w": 1}'
