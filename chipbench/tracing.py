"""From a profiler trace to device intervals, host spans and a breakdown.

The traced window runs under ``jax.profiler`` (``capture``); the
``.xplane.pb`` it writes is read with ``jax.profiler.ProfileData`` into a
:class:`Trace`: for every device, the operations it ran (from the plane's
"XLA Ops" line), and the benchmark's own host spans (``TraceAnnotation``s
whose names start with ``chipbench.``).  All times are nanoseconds on the
trace's clock.  The per-layer metrics are reductions of a ``Trace``.

On a TPU an operation's event is named by its HLO instruction text
(``%grouped_ffn.14 = bf16[...] custom-call(...)``); an :class:`Op` keeps the
instruction's name and its opcode.  Control flow (``while``, ``call``,
``conditional``) spans the operations of its body and is left out, so that
no time is counted twice.
"""
from __future__ import annotations

import contextlib
import glob
import os
import re
import shutil
import tempfile
from typing import NamedTuple

SPAN = "chipbench."
COLLECTIVES = ("all-to-all", "all-gather", "all-reduce", "reduce-scatter",
               "collective-permute", "send", "recv")
CONTROL_FLOW = ("while", "call", "conditional")


class Op(NamedTuple):
    name: str        # HLO instruction name, e.g. 'grouped_ffn.14'
    kind: str        # HLO opcode, e.g. 'custom-call'; '' for host spans
    start: float     # ns
    end: float       # ns


def hlo_name_and_opcode(text: str) -> tuple:
    """('grouped_ffn.14', 'custom-call') from an instruction's text; a text
    that is no instruction gives (text, '')."""
    m = re.match(r"%?(\S+) = ", text)
    if not m:
        return text, ""
    rest = text[m.end():]
    if rest.startswith("("):                     # a tuple shape
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        rest = rest[i + 1:]
    else:
        rest = rest.split(" ", 1)[1] if " " in rest else ""
    op = re.match(r"\s*([\w\-]+)\(", rest)
    return m.group(1), op.group(1) if op else ""


class Trace(NamedTuple):
    devices: list    # [device][Op], sorted by start
    spans: list      # [Op] host spans of the benchmark (kind = '')
    window: tuple    # (start, end) of the 'chipbench.window' span


def is_collective(op: Op) -> bool:
    return any(op.kind.startswith(c) for c in COLLECTIVES)


def union(intervals) -> list:
    """Sorted, merged [(start, end)]."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a, b) -> list:
    """Parts of merged intervals ``a`` not covered by merged ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def in_window(trace: Trace, ops) -> list:
    lo, hi = trace.window
    return [o for o in ops if o.end > lo and o.start < hi]


def busy(trace: Trace, dev: int) -> list:
    lo, hi = trace.window
    return clip(union((o.start, o.end) for o in trace.devices[dev]), lo, hi)


# --------------------------------------------------------------------------
# capture and parse
# --------------------------------------------------------------------------


@contextlib.contextmanager
def capture(out: list):
    """Trace the body; on exit parse the trace into ``out[0]``.  The trace
    files go to a temporary directory that is removed afterwards."""
    import jax
    d = tempfile.mkdtemp(prefix="chipbench-trace-")
    try:
        jax.profiler.start_trace(d)
        try:
            yield
        finally:
            jax.profiler.stop_trace()
        files = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                          recursive=True)
        if not files:
            raise RuntimeError("the profiler wrote no .xplane.pb")
        out.append(parse(files[0]))
    finally:
        shutil.rmtree(d, ignore_errors=True)


def parse(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, spans = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name \
                and "TPU_CORE" not in plane.name.upper():
            lines = {ln.name: ln for ln in plane.lines}
            line = lines.get("XLA Ops")
            if line is None:
                continue
            ops = []
            for ev in line.events:
                name, kind = hlo_name_and_opcode(ev.name)
                if kind in CONTROL_FLOW:
                    continue
                ops.append(Op(name, kind, float(ev.start_ns),
                              float(ev.start_ns + ev.duration_ns)))
            ops.sort(key=lambda o: o.start)
            devices.append((plane.name, ops))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name.startswith(SPAN):
                        spans.append(Op(ev.name, "", float(ev.start_ns),
                                        float(ev.start_ns + ev.duration_ns)))
    devices.sort(key=lambda t: _device_index(t[0]))
    win = [s for s in spans if s.name == SPAN + "window"]
    if not win:
        raise RuntimeError("the trace holds no chipbench.window span")
    spans.sort(key=lambda s: s.start)
    return Trace(devices=[ops for _, ops in devices], spans=spans,
                 window=(win[0].start, win[0].end))


def _device_index(name: str) -> int:
    digits = "".join(ch for ch in name.rsplit(":", 1)[-1] if ch.isdigit())
    return int(digits) if digits else 0


# --------------------------------------------------------------------------
# summaries
# --------------------------------------------------------------------------


def busy_seconds(trace: Trace) -> float:
    """Seconds in which an operation ran, averaged over the devices."""
    if not trace.devices:
        return 0.0
    return sum(length(busy(trace, d)) for d in range(len(trace.devices))) \
        / len(trace.devices) * 1e-9


def window_seconds(trace: Trace) -> float:
    return (trace.window[1] - trace.window[0]) * 1e-9


def breakdown(trace: Trace, top: int = 10) -> dict:
    """The device operations that took most time (summed over devices and
    steps, by instruction) and the longest idle gaps on device 0, each
    named by the host span that overlaps it most."""
    total: dict = {}
    for ops in trace.devices:
        for o in in_window(trace, ops):
            key = f"{o.name} {o.kind}"
            total[key] = total.get(key, 0.0) + (o.end - o.start) * 1e-9
    device_ops = sorted(total.items(), key=lambda kv: -kv[1])[:top]
    gaps = []
    if trace.devices:
        lo, hi = trace.window
        idle = subtract([(lo, hi)], busy(trace, 0))
        for s, e in sorted(idle, key=lambda g: g[0] - g[1])[:top]:
            best, name = 0.0, "no host span"
            for sp in trace.spans:
                if sp.name == SPAN + "window":
                    continue
                ov = min(e, sp.end) - max(s, sp.start)
                if ov > best:
                    best, name = ov, sp.name[len(SPAN):]
            gaps.append([name, (e - s) * 1e-9])
    return {"device_ops": [[k, v] for k, v in device_ops],
            "idle_gaps": gaps}
