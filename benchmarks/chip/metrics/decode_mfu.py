"""Model FLOPs of the decode steps of the traced window (active slots
only) over their device time times the bf16 peak, as a percentage."""
from chip import work
from chip.metrics import _serve


def read(run):
    ts = _serve.decode_times(run)
    its = [it for it in _serve.traced_iters(run) if it.n_active]
    if not ts or not its:
        return None
    c = run.ctx.config
    flops = sum(work.decode_flops(c, it.n_active, it.live)
                for it in its) / len(its)
    return 100.0 * flops / (sum(ts) / len(ts)
                            * run.peak["bf16_flops_per_s"])
