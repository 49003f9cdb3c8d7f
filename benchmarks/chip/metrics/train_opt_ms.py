"""Device self time per step of the step program's optimizer ops
(``scopes.classify``), averaged over the chips used."""
from chip import scopes


def read(run):
    sc = scopes.of(run)
    return sc.class_ms("optimizer") if sc else None
