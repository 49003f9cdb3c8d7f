"""Host time per step that the loop does not spend waiting on the chip:
over the program's ``train.step`` spans wholly inside the traced window,
each span's time less its ``train.step.wait`` (the blocking read of the
step's loss), averaged.  The program writes these spans; a program
without them reads nothing here."""
from chip import tracing


def read(run):
    tr = run.trace
    lo, hi = tr.window
    steps = [(s, s + d) for _, s, d, _ in tr.spans("train.step")
             if lo <= s and s + d <= hi]
    if not steps:
        return None
    waits = [(s, s + d) for _, s, d, _ in tr.spans("train.step.wait")]
    host = sum((b - a) - tracing.total(tracing.clip(waits, a, b))
               for a, b in steps)
    return 1e-6 * host / len(steps)
