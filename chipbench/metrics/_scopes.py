"""Shared by the per-layer metrics that read the program's layer scopes.

The program runs each layer of its train step under ``jax.named_scope``
with a name from ``repro.scopes`` (``attention``, ``moe.lp_solve``, ...).
XLA keeps the scope path in every instruction's ``op_name`` metadata:
``jit(train_step)/while/body/jvp(attention)/dot_general`` forward, and
``transpose(jvp(attention))`` or a transposed enclosing loop backward.  A
device trace names its events by instruction (``tracing.Op.name``), so an
event's layer is the innermost program scope on its instruction's path,
with ``/bwd`` added when the path runs through a ``transpose(``.

The instructions' metadata is read from the step's compiled module: the
reducer builds the program's step for the cell as the run built it and
compiles it again, which JAX's persistent compilation cache answers with
the executable the window ran.  The join is checked: a traced event's
instruction has to carry the event's opcode in that module, else the
event is unscoped, and over ``MAX_MISMATCHED`` of such events means
another module, and the metric reads nothing.  The profiler's
``.xplane.pb`` embeds the same modules (``hlo_modules_from_xplane``), for
a reader that keeps the file.

A scope's device time on a chip is the union of its events' intervals,
plus every idle gap whose neighbouring events on both sides carry that
same scope (the overhead of a loop such as the LP solver's sweeps).  A
metric reads nothing when the program sets no scopes, or when fewer than
``MIN_SCOPED`` of the busy time carries one.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import re

from chipbench import tracing

MIN_SCOPED = 0.95
MAX_MISMATCHED = 0.01
BWD = "/bwd"

_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT\s+)?%?(?P<name>[^\s=]+)\s*=\s*(?P<rest>.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_WRAPPED = re.compile(r"^[\w\-]+\((.*)\)$")
_OPERAND = re.compile(r"%([\w.\-]+)")
_CALLED = re.compile(r"\b(body|condition|calls|to_apply|true_computation|"
                     r"false_computation|branch_computations)="
                     r"(\{[^}]*\}|%?[\w.\-]+)")


def scope_of(op_name: str, names) -> str:
    """The innermost of ``names`` on an ``op_name`` path, with ``/bwd``
    when a ``transpose(`` encloses it; '' when none is on the path."""
    found, bwd = "", False
    for seg in op_name.split("/"):
        bwd = bwd or "transpose(" in seg
        core = seg
        while True:
            m = _WRAPPED.match(core)
            if not m:
                break
            core = m.group(1)
        if core in names:
            found = core + (BWD if bwd else "")
    return found


def module_scopes(hlo_text: str, names) -> dict:
    """{instruction name: (opcode, scope)} of the instructions an HLO
    module's text runs as events: its entry computation's and those of the
    loops, calls and branches it runs (names are unique in a module).  XLA
    adds copies, loop bookkeeping and some kernels (the ragged dots) with
    no metadata; such an instruction takes, if it is a fusion, the scope of
    its fused computation's root (else of the last instruction there that
    has one); else, unless it only moves data (a copy, an async start or
    done), that of its first operand that has one; else that of its first
    user that has one; else that of the loop, call or branch that runs its
    computation."""
    comps, entry, cur = {}, None, None
    for line in hlo_text.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            head = line.split("(", 1)[0].split()
            if head and head[0] == "ENTRY":
                head = head[1:]
                entry = head[0].lstrip("%") if head else None
            cur = comps.setdefault(head[0].lstrip("%"), []) if head else None
            continue
        if line.strip() == "}":
            cur = None
        m = _INSTRUCTION.match(line) if cur is not None else None
        if not m:
            continue
        rest = m.group("rest")
        name, kind = tracing.hlo_name_and_opcode(
            f"%{m.group('name')} = {rest}")
        if not kind:
            continue
        meta = _OP_NAME.search(rest)
        cur.append(_Instruction(
            name, kind, scope_of(meta.group(1) if meta else "", names),
            [(attr, c.strip().lstrip("%"))
             for attr, cs in _CALLED.findall(rest)
             for c in cs.strip("{}").split(",")],
            _OPERAND.findall(rest.split("), ", 1)[0]),
            line.lstrip().startswith("ROOT")))

    def fused(comp):
        ins = comps.get(comp, [])
        root = [i.scope for i in ins if i.root and i.scope]
        some = [i.scope for i in ins if i.scope]
        return (root or some or [""])[-1]

    context, todo, out = {entry: ""}, [entry], {}
    while todo:
        comp = todo.pop()
        ins = comps.get(comp, [])
        by_name = {i.name: i for i in ins}
        users = {}
        for i in ins:
            if not i.scope and i.kind == "fusion" and i.called:
                i.scope = fused(i.called[0][1])
            for op in i.operands:
                users.setdefault(op, []).append(i)
        for i in ins:                       # work goes with its inputs
            if not i.scope and not _moves(i.kind):
                i.scope = next((by_name[o].scope for o in i.operands
                                if o in by_name and by_name[o].scope), "")
        for i in reversed(ins):             # a copy with what it feeds
            if not i.scope:
                i.scope = next((u.scope for u in users.get(i.name, [])
                                if u.scope), "")
        for i in ins:
            out[i.name] = (i.kind, i.scope or context[comp])
            if i.kind == "fusion":
                continue
            # a loop, branch or call runs its computations as events; a
            # reduction's to_apply does not
            for attr, callee in i.called:
                if (attr != "to_apply" or i.kind == "call") \
                        and callee not in context:
                    context[callee] = out[i.name][1]
                    todo.append(callee)
    return out


@dataclasses.dataclass
class _Instruction:
    name: str
    kind: str                # opcode
    scope: str               # from its own op_name, then filled in
    called: list             # [(attribute, computation)]
    operands: list           # instruction names
    root: bool


def _moves(kind: str) -> bool:
    return kind == "copy" or kind.endswith(("-start", "-done"))


@functools.lru_cache(maxsize=2)
def _step_scopes(conf_json: str, chips: int, seq_len: int):
    """``module_scopes`` of a cell's train step, compiled again (None for a
    program that sets no scopes)."""
    if importlib.util.find_spec("repro.scopes") is None:
        return None
    import jax

    from chipbench import program
    from repro import scopes
    pc = program.build(json.loads(conf_json), chips, seq_len,
                       jax.devices()[:chips])
    state = jax.eval_shape(pc.init_state, jax.random.PRNGKey(0))
    lowered = pc.step.lower(state, pc.batch_specs)
    try:
        text = lowered.compile().as_text()
    except NotImplementedError:
        # an executable loaded from the cache may keep no HLO text; the
        # compiler gives the same module again
        cached = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            text = lowered.compile().as_text()
        finally:
            jax.config.update("jax_enable_compilation_cache", cached)
    return module_scopes(text, frozenset(scopes.ALL))


def _same_op(event_kind: str, kind: str) -> bool:
    """An event names an async op by its own start/done (``slice-start``);
    the module's text by the wrapper (``async-start``)."""
    if not event_kind or event_kind == kind:
        return True
    for part in ("-start", "-done", "-update"):
        if kind == "async" + part and event_kind.endswith(part):
            return True
    return False


def labelled(run):
    """Per chip, the window's events as (start, end, scope, collective),
    sorted by start and clipped to the window.  None when the program sets
    no scopes, over MAX_MISMATCHED of the events' opcodes are not their
    instructions' (the module is not the one traced), or under MIN_SCOPED
    of the busy time is scoped."""
    t = run.trace
    if t is None or not t.devices or not run.steps:
        return None
    table = _step_scopes(json.dumps(run.conf, sort_keys=True), run.chips,
                         int(run.traffic["seq_len"]))
    if table is None:
        return None
    lo, hi = t.window
    out, busy, scoped, seen, mismatched = [], 0.0, 0.0, 0, 0
    for ops in t.devices:
        evs = []
        for o in tracing.in_window(t, ops):
            kind, sc = table.get(o.name, (o.kind, ""))
            if not _same_op(o.kind, kind):
                mismatched, sc = mismatched + 1, ""
            seen += 1
            evs.append((max(o.start, lo), min(o.end, hi), sc,
                        tracing.is_collective(o)))
        evs.sort()
        busy += tracing.length(tracing.union((s, e) for s, e, _, _ in evs))
        scoped += tracing.length(tracing.union(
            (s, e) for s, e, sc, _ in evs if sc))
        out.append(evs)
    if mismatched > MAX_MISMATCHED * seen or busy <= 0.0 \
            or scoped < MIN_SCOPED * busy:
        return None
    return out


def _gaps(evs):
    """Idle gaps of one chip as (start, end, event before, event after)."""
    out, last = [], None
    for ev in evs:
        if last is not None and ev[0] > last[1]:
            out.append((last[1], ev[0], last, ev))
        if last is None or ev[1] >= last[1]:
            last = ev
    return out


def scope_ms(run, want, keep=lambda ev: True):
    """Device ms a step, averaged over the chips, of the events whose scope
    (``/bwd`` stripped) is in ``want`` and that ``keep`` accepts, plus the
    idle gaps between two such events of one scope."""
    chips = labelled(run)
    if chips is None:
        return None

    def mine(ev):
        sc = ev[2][:-len(BWD)] if ev[2].endswith(BWD) else ev[2]
        return sc in want and keep(ev)

    total = 0.0
    for evs in chips:
        total += tracing.length(tracing.union(
            (s, e) for s, e, _, _ in filter(mine, evs)))
        total += sum(e - s for s, e, left, right in _gaps(evs)
                     if left[2] == right[2] and mine(left) and mine(right))
    return total / len(chips) / run.steps * 1e-6


def exposed_ms(run, want_prefix):
    """Device ms a step, averaged over the chips, of collective events whose
    scope starts with ``want_prefix`` during which no non-collective event
    runs on that chip."""
    chips = labelled(run) if run.chips > 1 else None
    if chips is None:
        return None
    total = 0.0
    for evs in chips:
        coll = tracing.union((s, e) for s, e, sc, c in evs
                             if c and sc.startswith(want_prefix))
        comp = tracing.union((s, e) for s, e, _, c in evs if not c)
        total += tracing.length(tracing.subtract(coll, comp))
    return total / len(chips) / run.steps * 1e-6


def hlo_modules_from_xplane(path: str) -> dict:
    """{module name: HLO text} of the modules a ``jax.profiler`` trace
    embeds: the ``Hlo Proto`` stats of its ``/host:metadata`` plane."""
    from jax._src.lib import xla_client

    with open(path, "rb") as f:
        data = f.read()
    out = {}
    for field, plane in _fields(data):
        if field != 1:                                  # XSpace.planes
            continue
        plane = list(_fields(plane))
        if _one(plane, 2) != b"/host:metadata":
            continue
        stat_names = {}
        for num, entry in plane:                        # stat_metadata
            if num == 5:
                meta = dict(_fields(dict(_fields(entry))[2]))
                stat_names[meta.get(1, 0)] = meta.get(2, b"")
        for num, entry in plane:                        # event_metadata
            if num != 4:
                continue
            meta = list(_fields(dict(_fields(entry))[2]))
            for stat in (v for num, v in meta if num == 5):  # stats
                stat = dict(_fields(stat))
                if stat_names.get(stat.get(1)) == b"Hlo Proto":
                    module = dict(_fields(stat[6]))[1]  # HloProto.hlo_module
                    hlo = xla_client._xla.HloModule \
                        .from_serialized_hlo_module_proto(module)
                    out[_one(meta, 2).decode()] = hlo.to_string()
    return out


def _one(fields, number):
    return next((v for f, v in fields if f == number), b"")


def _varint(b, i):
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return out, i


def _fields(b):
    """(field number, value) of a protobuf message's wire form: ints for
    varints, bytes for the rest."""
    i = 0
    while i < len(b):
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 1:
            v, i = b[i:i + 8], i + 8
        elif wire == 2:
            n, i = _varint(b, i)
            v, i = b[i:i + n], i + n
        elif wire == 5:
            v, i = b[i:i + 4], i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} is not read here")
        yield key >> 3, v
