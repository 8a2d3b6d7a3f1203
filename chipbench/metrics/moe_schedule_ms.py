"""Device time of the MoE layer's schedule, ms a step averaged over the
chips: the expert counts and their all-gather, the LP solve, rounding and
Alg. 1 routing (scopes ``moe.counts``, ``moe.lp_solve``, ``moe.alg1``)."""
from chipbench.metrics import _scopes


def reduce(run):
    return _scopes.scope_ms(run, {"moe.counts", "moe.lp_solve", "moe.alg1"})
