"""`make comm` tier-1 gate: the unified communication plane, every
topology × codec cell, on 4 virtual devices.

For each topology (ring / tree / butterfly / fully_connected) × codec
(none / onebit / terngrad / qsgd / dgc) the gate runs the device engine
for 2 BSP steps under ``wire="measured"`` — encoded payloads inside the
schedule — and asserts:

  * finite losses and positive wire accounting;
  * the measured-vs-modeled agreement: the engine's shape-static
    per-worker tx bytes equal the critical-path model
    ``per_device_bytes`` divided by the documented ``model_error_factor``
    within 25% (side-info slack; exact for the none codec);
  * ``none`` executes bitwise-identically under modeled and measured
    modes (the legacy schedules ARE the exact path);
  * compressed cells put strictly fewer bytes on the wire than fp32.

  PYTHONPATH=src python tools/comm_smoke.py
"""
import os
import sys

# virtual devices must be configured before jax import
sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src"))

from repro.launch.env import ensure_host_devices  # noqa: E402

ensure_host_devices(4)

import jax                      # noqa: E402
import jax.numpy as jnp         # noqa: E402
import numpy as np              # noqa: E402

from repro.comm.codecs import make_codec                    # noqa: E402
from repro.comm.transport import (model_error_factor,       # noqa: E402
                                  pad_for_schedule, per_device_bytes)
from repro.train import Strategy                            # noqa: E402

KEY = jax.random.PRNGKey(0)
W_TRUE = jax.random.normal(KEY, (64, 1))
WORKERS = 4
STEPS = 2
TOPOLOGIES = ("ring", "tree", "butterfly", "fully_connected")
CODECS = ("none", "onebit", "terngrad", "qsgd", "dgc")


def make_batch(t, w):
    k = jax.random.fold_in(KEY, t * 100 + w)
    X = jax.random.normal(k, (16, 64))
    return {"X": X, "y": X @ W_TRUE}


def grad_fn(params, batch):
    def loss(p):
        return jnp.mean((batch["X"] @ p["W"] - batch["y"]) ** 2)
    return jax.value_and_grad(loss)(params)


P0 = {"W": jnp.zeros((64, 1)), "b": jnp.zeros((4096,))}


def check_measured_vs_model(engine, topology, method) -> None:
    """The engine's static per-worker tx bytes must match the topology's
    critical-path model through the documented error factor."""
    plan = engine.inner.comm_plan
    codec = (make_codec("none") if method in ("none",)
             else plan.codec)
    expect = 0.0
    for b in range(len(plan.buckets)):
        P = pad_for_schedule(plan.bucket_len(b), WORKERS)
        model = per_device_bytes(topology, WORKERS,
                                 codec.static_tx_bytes(P))
        expect += model / model_error_factor(topology, WORKERS,
                                             exact=(method == "none"))
    got = engine.metrics()["measured_step_tx_bytes"]
    rel = abs(got - expect) / max(expect, 1.0)
    tol = 1e-6 if method == "none" else 0.25
    assert rel <= tol, (topology, method, got, expect, rel)


def main() -> int:
    failures = []
    for topology in TOPOLOGIES:
        fp32_wire = None
        for method in CODECS:
            spec = f"bsp/{topology}/{method}@{WORKERS}"
            if method == "dgc":
                spec = f"bsp/{topology}/dgc:0.1@{WORKERS}"
            try:
                eng = Strategy.parse(spec, lr=0.05, backend="device",
                                     wire="measured").build(grad_fn)
                _, hist, wire = eng.run(P0, make_batch, STEPS)
                assert hist and all(np.isfinite(h["loss"]) for h in hist)
                assert wire > 0
                check_measured_vs_model(eng, topology, method)
                if method == "none":
                    fp32_wire = wire
                    # bitwise: modeled and measured run the same program
                    pm, hm, _ = Strategy.parse(
                        spec, lr=0.05, backend="device",
                        wire="modeled").build(grad_fn).run(
                            P0, make_batch, STEPS)
                    assert [h["loss"] for h in hm] == \
                           [h["loss"] for h in hist], "none not bitwise"
                else:
                    assert wire < fp32_wire, (wire, fp32_wire)
                print(f"ok   {spec:34s} wire {wire:>9d} B "
                      f"(fp32 {fp32_wire} B)")
            except Exception as e:  # noqa: BLE001
                failures.append((spec, e))
                print(f"FAIL {spec}: {e!r}")
    if failures:
        print(f"FAIL: {len(failures)} comm cells failing")
        return 1
    print(f"comm: all {len(TOPOLOGIES) * len(CODECS)} topology x codec "
          f"cells executed on {WORKERS} virtual devices (wire=measured)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
