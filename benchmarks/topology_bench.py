"""Survey §3.3.1(2): decentralized allreduce topologies.

Numeric correctness is covered by tests on 8 devices; here we (a) measure
the 8-device wall time of each schedule via subprocess, and (b) report the
analytic per-device traffic at production scale (n=256), which is what the
survey's topology discussion is about (ring's 2(n-1)/n vs fully-connected's
(n-1)).
"""
from __future__ import annotations

import os
import subprocess
import sys

from repro.core.allreduce import per_device_bytes

from benchmarks.common import emit

SIZE_MB = 8   # an 8 MB gradient bucket

_CHILD = r"""
import time, jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from repro.core.allreduce import TOPOLOGIES
mesh = Mesh(np.array(jax.devices()).reshape(8), ("w",))
x = jax.random.normal(jax.random.PRNGKey(0), (8, %d))
for name, fn in TOPOLOGIES.items():
    f = jax.jit(jax.shard_map(lambda a, _fn=fn: _fn(a[0], "w")[None],
                mesh=mesh, in_specs=P("w", None), out_specs=P("w", None),
                check_vma=False))
    f(x).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(5):
        f(x).block_until_ready()
    print(f"TIME {name} {(time.perf_counter()-t0)/5*1e6:.0f}")
""" % (SIZE_MB * 1024 * 1024 // 4 // 8)


def main():
    rows = [("topology.name", "us_per_call_8dev",
             "per_device_MB_at_n256")]
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
    for name in ("ring", "butterfly", "tree", "fully_connected", "psum"):
        analytic = per_device_bytes(name, 256, SIZE_MB * 1e6) / 1e6
        rows.append((f"topology.{name}", round(times.get(name, -1), 0),
                     round(analytic, 1)))
    emit(rows)


if __name__ == "__main__":
    main()
