"""Device time of collective operations during which no other operation
runs on that device, per step, averaged over the chips."""
from chipbench import tracing


def reduce(run):
    t = run.trace
    if t is None or run.chips < 2 or not t.devices or not run.steps:
        return None
    lo, hi = t.window
    exposed, seen = 0.0, False
    for ops in t.devices:
        ops = tracing.in_window(t, ops)
        coll = [(o.start, o.end) for o in ops if tracing.is_collective(o)]
        seen = seen or bool(coll)
        comp = [(o.start, o.end) for o in ops if not tracing.is_collective(o)]
        coll = tracing.clip(tracing.union(coll), lo, hi)
        exposed += tracing.length(tracing.subtract(coll,
                                                   tracing.union(comp)))
    if not seen:
        return None
    return exposed / len(t.devices) / run.steps * 1e-6
