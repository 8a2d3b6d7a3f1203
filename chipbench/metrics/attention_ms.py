"""Device time of the attention sub-blocks (scope ``attention``: norm,
attention and residual), forward and backward, ms a step averaged over the
chips."""
from chipbench.metrics import _scopes


def reduce(run):
    return _scopes.scope_ms(run, {"attention"})
