"""Device idle share of the traced window, counted only while at least
one request is in flight: the window less the benchmark's ``serve.wait``
spans (waiting for the next arrival with nothing to do)."""
from chip import tracing


def read(run):
    tr = run.trace
    waits = [(s, s + d) for _, s, d, _ in tr.spans("serve.wait")]
    inflight = tracing.subtract([tr.window], waits)
    span = tracing.total(inflight)
    if span <= 0:
        return None
    shares = []
    for dev in tr.devices:
        busy = tracing.union(tr.op_intervals(dev))
        outside = tracing.total(tracing.subtract(busy, inflight))
        shares.append(1.0 - (tracing.total(busy) - outside) / span)
    return 100.0 * sum(shares) / len(shares)
