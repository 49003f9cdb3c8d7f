"""Device self time per step of the step program's ops under the model's
``attention`` scope, forward and backward (the q/k/v/o projections,
rotary, the flash kernel and its VJP), averaged over the chips used."""
from chip import scopes


def read(run):
    sc = scopes.of(run)
    return sc.scope_ms("attention") if sc else None
