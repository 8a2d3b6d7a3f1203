"""Device time spent moving token rows into and out of the experts' flat
buffer, ms a step averaged over the chips: the non-collective events of
scopes ``moe.dispatch`` and ``moe.combine``, forward and backward."""
from chipbench.metrics import _scopes


def reduce(run):
    return _scopes.scope_ms(run, {"moe.dispatch", "moe.combine"},
                            keep=lambda ev: not ev[3])
