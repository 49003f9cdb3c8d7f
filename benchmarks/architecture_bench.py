"""Survey §3.3.1: centralized (parameter server) vs decentralized
(allreduce) architecture, in their TPU-native forms (DESIGN.md §2.2):

  PS          = reduce-scatter grads -> update my 1/n shard -> all-gather
  decentral   = all-reduce grads -> every worker updates the full model

Measured on 8 host devices via subprocess: wall time per step and the
derived update-FLOPs ratio (PS does 1/n of the optimizer work — the ZeRO
observation).
"""
from __future__ import annotations

import os
import subprocess
import sys

from benchmarks.common import emit

_CHILD = r"""
import time, jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from repro.core.parameter_server import make_ps_step
N = 1_000_000
mesh = Mesh(np.array(jax.devices()).reshape(8), ("w",))
params = {"w": jax.random.normal(jax.random.PRNGKey(0), (N,))}
grads = {"w": jnp.stack([jnp.full((N,), float(i)) for i in range(8)])}

def update(p, g, o):
    return jax.tree.map(lambda a, b: a - 0.1 * b, p, g), o
ps = make_ps_step(update, "w")
f_ps = jax.jit(jax.shard_map(
    lambda p, g: ps(p, jax.tree.map(lambda a: a[0], g), None)[0],
    mesh=mesh, in_specs=(P(), P("w")), out_specs=P(), check_vma=False))

def dec(p, g):
    gsum = jax.lax.psum(jax.tree.map(lambda a: a[0], g)["w"], "w")
    return {"w": p["w"] - 0.1 * gsum}
f_dec = jax.jit(jax.shard_map(dec, mesh=mesh, in_specs=(P(), P("w")),
                out_specs=P(), check_vma=False))
for name, f in [("ps", f_ps), ("decentralized", f_dec)]:
    jax.block_until_ready(f(params, grads))
    t0 = time.perf_counter()
    for _ in range(10):
        jax.block_until_ready(f(params, grads))
    print(f"TIME {name} {(time.perf_counter()-t0)/10*1e6:.0f}")
"""


def main():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.path.join(repo, "src")
    res = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                         capture_output=True, text=True, timeout=600)
    times = {}
    for line in res.stdout.splitlines():
        if line.startswith("TIME "):
            _, name, us = line.split()
            times[name] = float(us)
    rows = [("architecture.variant", "us_per_step_8dev",
             "update_flops_share")]
    rows.append(("architecture.ps_rs_ag", times.get("ps", -1), "1/8"))
    rows.append(("architecture.decentralized_ar",
                 times.get("decentralized", -1), "8/8"))
    emit(rows)


if __name__ == "__main__":
    main()
