"""The held experts' least time (``work_mla_moe.experts_train``: their
SwiGLU forward and backward at the balanced load, the larger of FLOPs over
the peak and bytes over HBM bandwidth) over the device self time of the
ops under ``moe/experts`` in the step programs of the traced window, as a
percentage."""
import jax.numpy as jnp

from chip import scopes, work_mla_moe
from chip.peaks import roofline_s


def read(run):
    sc = scopes.of(run)
    ms = sc.per_step_ms(lambda s: {"moe", "experts"}.issubset(
        scopes.scope_names(s))) if sc else None
    if not ms:
        return None
    c = run.ctx.config
    flops, nbytes = work_mla_moe.experts_train(
        c, run.outcome.extra["tokens_per_step"],
        jnp.dtype(c["compute_dtype"]).itemsize)
    return 100.0 * roofline_s(flops, nbytes, run.peak)[0] / (1e-3 * ms)
