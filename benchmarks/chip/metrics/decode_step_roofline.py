"""The least time a decode step needs (the larger of its FLOPs over the
peak and its least bytes over HBM bandwidth; see ``work.py``) over the
device time of the decode-step program, as a percentage, both averaged
over the steps of the traced window."""
from chip.metrics import _serve


def read(run):
    ts = _serve.decode_times(run)
    its = [it for it in _serve.traced_iters(run) if it.n_active]
    if not ts or not its:
        return None
    least = sum(_serve.decode_least_s(run, it) for it in its) / len(its)
    return 100.0 * least / (sum(ts) / len(ts))
