"""Device time of one decode-step program, mean over the traced window."""
from chip.metrics import _serve


def read(run):
    ts = _serve.decode_times(run)
    return 1e3 * sum(ts) / len(ts) if ts else None
