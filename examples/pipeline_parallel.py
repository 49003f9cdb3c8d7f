"""Pipeline parallelism (GPipe, survey §3.2.3) on 4 virtual devices: a
4-stage pipeline over micro-batches, showing the bubble fraction shrink as
micro-batch count grows.  Re-execs itself with virtual devices.

  PYTHONPATH=src python examples/pipeline_parallel.py
"""
import os
import sys

if "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import jax                                        # noqa: E402
import jax.numpy as jnp                           # noqa: E402
import numpy as np                                # noqa: E402
from jax.sharding import Mesh, PartitionSpec as P  # noqa: E402

from repro.core.pipeline import bubble_fraction, gpipe_forward  # noqa: E402
from repro.launch.env import use_compile_cache  # noqa: E402


def main():
    use_compile_cache()
    n_stages = 4
    d = 64
    mesh = Mesh(np.array(jax.devices()[:n_stages]), ("stage",))
    key = jax.random.PRNGKey(0)
    stage_w = jax.random.normal(key, (n_stages, d, d)) / jnp.sqrt(d)

    def stage_fn(w, x):
        return jnp.tanh(x @ w)

    for n_micro in (1, 4, 16):
        xm = jax.random.normal(key, (n_micro, 8, d))
        f = jax.shard_map(
            lambda w, x: gpipe_forward(stage_fn, w[0], x, "stage")[None],
            mesh=mesh, in_specs=(P("stage"), P(None)), out_specs=P("stage"),
            check_vma=False)
        out = f(stage_w, xm).sum(0)
        # sequential reference
        seq = xm
        for i in range(n_stages):
            seq = jnp.tanh(seq @ stage_w[i])
        err = float(jnp.max(jnp.abs(out - seq)))
        print(f"micro-batches={n_micro:3d}  bubble="
              f"{bubble_fraction(n_stages, n_micro):.2f}  max_err={err:.2e}")
    print("\npipeline == sequential; bubble -> 0 as micro-batches grow "
          "(GPipe Fig. 2).")


if __name__ == "__main__":
    main()
