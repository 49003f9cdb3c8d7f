"""Host time per step inside the benchmark's ``train.batches`` span, the
``batches`` callable ``fit`` calls before each step's dispatch."""


def read(run):
    tr = run.trace
    spans = tr.spans("train.batches")
    steps = sum(1 for h in spans if int(h[3].get("w", 0)) == 0)
    if not steps:
        return None
    return 1e3 * tr.span_time_s("train.batches") / steps
