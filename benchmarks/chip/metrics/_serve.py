"""Shared reading of a serve cell's trace: the decode-step programs, the
other (prefill and cache-write) programs, and the engine iterations the
benchmark recorded inside the traced window."""
import re

import jax.numpy as jnp

from chip import work
from chip.peaks import roofline_s

# the engine's jitted decode step (``ServeEngine._build_step``: ``step``)
DECODE = r"^jit_step\b"


def decode_times(run):
    tr = run.trace
    return [d * 1e-9 for dev in tr.devices[:1]
            for _, d in tr.modules(dev, DECODE)]


def other_program_s(run):
    """Device time of every program but the decode step that started in
    the window: the eager prefill, its cache conversion and page writes."""
    tr = run.trace
    dev = tr.devices[0]
    rx = re.compile(DECODE)
    return sum(d for n, s, d in dev["modules"]
               if not rx.search(n) and tr.window[0] <= s < tr.window[1]) \
        * 1e-9


def traced_iters(run):
    iters = run.outcome.extra["iters"]
    return [iters[int(h[3]["i"])] for h in run.trace.spans("serve.iter")
            if "i" in h[3] and int(h[3]["i"]) < len(iters)]


def itemsize(run):
    return jnp.dtype(run.ctx.config["dtype"]).itemsize


def decode_least_s(run, it):
    c = run.ctx.config
    return roofline_s(work.decode_flops(c, it.n_active, it.live),
                      work.decode_least_bytes(c, itemsize(run), it.n_active,
                                              it.live), run.peak)[0]
