"""The hybrid training mesh.  A function (never a module-level constant)
so importing this module never touches jax device state."""
from __future__ import annotations


def make_hybrid_mesh(devices, data: int, tensor: int, stage: int,
                     axes=("data", "tensor", "stage")):
    """The hybrid-parallel training mesh (repro.parallel): ``data`` x
    ``tensor`` x ``stage`` over an explicit device list — virtual host
    devices in tests, real chips in production.  Device order is
    data-major so a data-axis resize keeps (tensor, stage) blocks
    contiguous."""
    import numpy as np
    n = data * tensor * stage
    if len(devices) < n:
        raise ValueError(f"mesh {data}x{tensor}x{stage} needs {n} devices, "
                         f"have {len(devices)}")
    from jax.sharding import Mesh
    return Mesh(np.array(devices[:n]).reshape(data, tensor, stage), axes)


# TPU v5e hardware constants for the roofline analysis (per chip)
PEAK_FLOPS_BF16 = 197e12       # FLOP/s
HBM_BW = 819e9                 # B/s
ICI_BW_PER_LINK = 50e9         # B/s per link
