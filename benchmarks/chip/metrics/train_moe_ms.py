"""Device self time per step of the step program's ops under the model's
``moe`` scope, forward and backward (routing, dispatch, the held experts'
grouped matmuls, combine, shared experts), averaged over the chips used."""
from chip import scopes


def read(run):
    sc = scopes.of(run)
    return sc.scope_ms("moe") if sc else None
