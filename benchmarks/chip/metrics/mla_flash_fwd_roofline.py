"""The flash-attention forward kernel's least time at latent attention's
shapes (``work_mla_moe.flash_fwd``: QK over the 192 q/k dims and PV over
the 128 v dims on causal pairs, the larger of FLOPs over the peak and
bytes over HBM bandwidth) over its device time in the traced window, as a
percentage."""
import jax.numpy as jnp

from chip import work_mla_moe
from chip.metrics import _train
from chip.metrics.flash_fwd_roofline import KERNEL
from chip.peaks import roofline_s


def read(run):
    tr = run.trace
    t = tr.op_time_s(tr.devices[0], KERNEL)
    n = _train.steps_in_window(run)
    if not t or not n:
        return None
    c, cp = run.ctx.config, run.ctx.cell_params
    flops, nbytes = work_mla_moe.flash_fwd(
        c, cp["rows_per_worker"], run.ctx.mix["seq_len"],
        jnp.dtype(c["compute_dtype"]).itemsize)
    return 100.0 * n * roofline_s(flops, nbytes, run.peak)[0] / t
