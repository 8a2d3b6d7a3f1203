"""Shared by the kernel roofline reducers: which trace events belong to the
grouped expert FFN, and the share of its roofline.

The forward is the Pallas kernel, whose HLO instruction is named for it
(``grouped_ffn``).  The backward is the custom VJP's ``lax.ragged_dot``s
(instructions named ``ragged-dot...``; the program has no other ragged
dot): the trace carries no name scopes, so the ``grouped_ffn_bwd`` scope
cannot be read there.  The backward recomputes the forward's two input
projections; that time is counted, their operations are not.
"""
from chipbench import flops, tracing

KERNEL = "grouped_ffn"
BWD = "ragged-dot"


def is_forward(op) -> bool:
    return op.name.split(".")[0] == KERNEL


def is_backward(op) -> bool:
    return op.name.startswith(BWD)


def roofline_share(run, pick, count):
    t = run.trace
    if t is None or not t.devices or not run.steps:
        return None
    busy = sum(o.end - o.start for ops in t.devices
               for o in tracing.in_window(t, ops) if pick(o)) * 1e-9
    if busy <= 0.0:
        return None
    k = run.kernel
    ops, byts = count(run.conf["model"], k["rows_per_call"],
                      k["slot_weights"], k["etp"])
    least, _ = flops.least_time(ops, byts, run.peaks)
    calls = run.steps * k["calls_per_step"] * len(t.devices)
    return 100.0 * least * calls / busy
