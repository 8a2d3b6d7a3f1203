"""The share of the traced window in which no operation ran on the
device, averaged over the chips."""
from chipbench import tracing


def reduce(run):
    if run.trace is None or not run.trace.devices:
        return None
    return 100.0 * (1.0 - tracing.busy_seconds(run.trace)
                    / tracing.window_seconds(run.trace))
