"""Device time of the optimizer (scope ``optimizer``: the master copy to
the working layout and its gradient, clipping and AdamW), ms a step
averaged over the chips."""
from chipbench.metrics import _scopes


def reduce(run):
    return _scopes.scope_ms(run, {"optimizer"})
