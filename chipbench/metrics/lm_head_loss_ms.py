"""Device time of the final norm, LM head and cross entropy (scope
``lm_head_loss``), forward and backward, ms a step averaged over the
chips."""
from chipbench.metrics import _scopes


def reduce(run):
    return _scopes.scope_ms(run, {"lm_head_loss"})
