"""The flash-attention forward kernel's least time (``work.flash_fwd``:
the larger of causal FLOPs over the peak and q/k/v/out bytes over HBM
bandwidth) over its device time in the traced window, as a percentage."""
import jax.numpy as jnp

from chip import work
from chip.metrics import _train
from chip.peaks import roofline_s

# the Pallas forward kernel of kernels/flash_attention, called through
# ``attention_grad`` (its custom call is named after that wrapper)
KERNEL = r"^attention_grad\b.*\[pallas\]$"


def read(run):
    tr = run.trace
    t = tr.op_time_s(tr.devices[0], KERNEL)
    n = _train.steps_in_window(run)
    if not t or not n:
        return None
    c, cp = run.ctx.config, run.ctx.cell_params
    flops, nbytes = work.flash_fwd(c, cp["rows_per_worker"],
                                   run.ctx.mix["seq_len"],
                                   jnp.dtype(c["compute_dtype"]).itemsize)
    return 100.0 * n * roofline_s(flops, nbytes, run.peak)[0] / t
