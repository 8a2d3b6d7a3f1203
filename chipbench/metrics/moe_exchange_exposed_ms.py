"""The MoE layer's own collectives (the counts all-gather and the dispatch
and combine all-to-alls, scopes ``moe.*``) during which no other operation
runs on that chip, ms a step averaged over the chips."""
from chipbench.metrics import _scopes


def reduce(run):
    return _scopes.exposed_ms(run, "moe.")
