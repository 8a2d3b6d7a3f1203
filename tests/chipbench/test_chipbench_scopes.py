"""The program's layer scopes, read back: from a profiler trace's embedded
HLO, from the compiled step, and reduced to per-layer times."""
import glob
import json
import re

import jax
import jax.numpy as jnp
import pytest

import tinycell
from chipbench import bench, program, tracing
from chipbench.metrics import _scopes
from chipbench.tasks.train import RunContext

SCOPE_METRICS = ("moe_schedule_ms", "moe_row_movement_ms",
                 "moe_exchange_exposed_ms", "attention_ms",
                 "lm_head_loss_ms", "optimizer_ms")
PLUMBING = {"parameter", "constant", "get-tuple-element", "tuple", "bitcast"}


def test_decoder_reads_scopes_from_a_cpu_trace(tmp_path):
    def f(x, w):
        with jax.named_scope("myscope"):
            return jnp.sum(jnp.tanh(x @ w) ** 2)

    g = jax.jit(jax.grad(f, argnums=1))
    x, w = jnp.ones((8, 8)), jnp.full((8, 8), 0.1)
    g(x, w).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        g(x, w).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    mods = _scopes.hlo_modules_from_xplane(path)
    text, = [v for k, v in mods.items() if k.startswith("jit_f(")]
    table = _scopes.module_scopes(text, {"myscope"})
    got = {sc for kind, sc in table.values() if kind not in PLUMBING}
    assert got == {"myscope", "myscope/bwd"}


@pytest.mark.parametrize("op_name,want", [
    ("jit(f)/jvp(attention)/dot_general", "attention"),
    ("jit(f)/transpose(jvp(attention))/dot_general", "attention/bwd"),
    ("jit(f)/while/body/moe/moe.lp_solve/while/body/min", "moe.lp_solve"),
    ("jit(f)/transpose(jvp())/while/body/closed_call/moe/moe.dispatch/"
     "gather", "moe.dispatch/bwd"),
    ("jit(f)/optimizer/transpose(jvp(optimizer))/add", "optimizer/bwd"),
    ("jit(f)/while/body/add", ""),
    ("", ""),
])
def test_innermost_scope(op_name, want):
    names = {"attention", "moe", "moe.lp_solve", "moe.dispatch",
             "optimizer"}
    assert _scopes.scope_of(op_name, names) == want


HLO = """HloModule jit_step, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%fused_add (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %add.1 = f32[8]{0} add(%p, %p), metadata={op_name="jit(s)/attention/add"}
}

%body (t: (s32[], f32[8])) -> (s32[], f32[8]) {
  %t = (s32[], f32[8]{0}) parameter(0)
  %gte.0 = f32[8]{0} get-tuple-element(%t), index=1
  %copy.9 = f32[8]{0} copy(%gte.0)
  %gte.1 = s32[] get-tuple-element(%t), index=0
  ROOT %tuple.2 = (s32[], f32[8]{0}) tuple(%gte.1, %copy.9)
}

%cond (t: (s32[], f32[8])) -> pred[] {
  %t = (s32[], f32[8]{0}) parameter(0)
  ROOT %constant.5 = pred[] constant(false)
}

ENTRY %main (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  %fusion.3 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_add
  %copy.4 = f32[8]{0} copy(%x)
  %cc.5 = f32[8]{0} custom-call(%fusion.3), custom_call_target="kernel"
  %mul.6 = f32[8]{0} multiply(%copy.4, %cc.5), metadata={op_name="jit(s)/transpose(jvp(lm_head_loss))/mul"}
  %zero = s32[] constant(0)
  %tuple.7 = (s32[], f32[8]{0}) tuple(%zero, %mul.6)
  %while.8 = (s32[], f32[8]{0}) while(%tuple.7), condition=%cond, body=%body, metadata={op_name="jit(s)/grad_accum/while"}
  ROOT %gte.9 = f32[8]{0} get-tuple-element(%while.8), index=1
}
"""


def test_module_scopes_fill_what_xla_left_unnamed():
    table = _scopes.module_scopes(
        HLO, {"attention", "lm_head_loss", "grad_accum"})
    assert table["fusion.3"] == ("fusion", "attention")      # fused root
    assert table["copy.4"] == ("copy", "lm_head_loss/bwd")   # its user
    assert table["cc.5"] == ("custom-call", "attention")     # its operand
    assert table["mul.6"] == ("multiply", "lm_head_loss/bwd")
    assert table["while.8"] == ("while", "grad_accum")
    assert table["copy.9"] == ("copy", "grad_accum")         # its loop
    assert "add.1" not in table and "p" not in table         # fused body


def op(name, start, end, kind="fusion"):
    return tracing.Op(name, kind, float(start), float(end))


# one step, window 0..100 ns; every event's scope is in TABLE
EVENTS = [op("f.1", 0, 10), op("f.2", 10, 20), op("f.3", 22, 30),
          op("f.4", 34, 40), op("f.5", 40, 45),
          op("a2a.6", 45, 55, "all-to-all"), op("f.7", 50, 60),
          op("f.8", 60, 70), op("f.9", 70, 80), op("f.10", 80, 90),
          op("c.11", 90, 92, "copy")]
TABLE = {"f.1": ("fusion", "attention"), "f.2": ("fusion", "moe.counts"),
         "f.3": ("fusion", "moe.lp_solve"),
         "f.4": ("fusion", "moe.lp_solve"), "f.5": ("fusion", "moe.alg1"),
         "a2a.6": ("all-to-all", "moe.dispatch"),
         "f.7": ("fusion", "moe.dispatch"),
         "f.8": ("fusion", "moe.combine/bwd"),
         "f.9": ("fusion", "lm_head_loss"), "f.10": ("fusion", "optimizer"),
         "c.11": ("copy", ""), "ar.12": ("all-reduce", "optimizer")}


def _ctx(devices, chips):
    trace = tracing.Trace(devices=devices,
                          spans=[op(tracing.SPAN + "window", 0, 100, "")],
                          window=(0.0, 100.0))
    return RunContext(conf={"model": dict(tinycell.MODEL)},
                      traffic={"seq_len": 32}, chips=chips, peaks={},
                      steps=1, tokens_per_step=8, window_s=100e-9,
                      trace=trace, balance=[1.0], kernel={})


def _read(name, ctx):
    return bench.metric_reducer(tinycell.REPO, name).reduce(ctx)


@pytest.fixture
def table(monkeypatch):
    tab = dict(TABLE)
    monkeypatch.setattr(_scopes, "_step_scopes", lambda *a: tab)
    return tab


@pytest.mark.parametrize("name,want_ns", [
    # counts 10 + LP solve 8 + 6 + the 4 ns gap inside it + Alg. 1 5;
    # the 2 ns gap between counts and the solve is no one scope's
    ("moe_schedule_ms", 33),
    # non-collective dispatch 10 + combine (backward) 10
    ("moe_row_movement_ms", 20),
    ("attention_ms", 10),
    ("lm_head_loss_ms", 10),
    ("optimizer_ms", 10),
])
def test_scope_times(table, name, want_ns):
    assert _read(name, _ctx([EVENTS], 1)) == pytest.approx(want_ns * 1e-6)


def test_async_ops_and_a_stray_opcode(table):
    """An event names an async op by its start/done, the module by its
    wrapper; one stray opcode in a hundred events leaves that event out."""
    table["s.20"] = ("async-start", "attention")
    events = EVENTS + [op("s.20", 0, 4, "slice-start")]
    assert _read("attention_ms", _ctx([events], 1)) == pytest.approx(1e-5)
    table["f.99"] = ("copy", "optimizer")
    many = events + [op("f.99", 92, 93)] + [
        op(f"f.{100 + i}", 93, 93, "copy") for i in range(100)]
    assert _read("optimizer_ms", _ctx([many], 1)) == pytest.approx(1e-5)
    assert _read("optimizer_ms", _ctx([many[:20]], 1)) is None


def test_moe_exchange_exposed(table):
    # 45..50 of the all-to-all runs alone on each chip; the optimizer's
    # all-reduce on chip 1 is exposed too, but is not the MoE layer's
    chip1 = EVENTS + [op("ar.12", 92, 96, "all-reduce")]
    assert _read("moe_exchange_exposed_ms", _ctx([EVENTS, chip1], 2)) == \
        pytest.approx(5e-6)
    assert _read("moe_exchange_exposed_ms", _ctx([EVENTS], 1)) is None


@pytest.mark.parametrize("case", ["no scopes", "under 95%",
                                  "another module", "empty trace"])
def test_nothing_to_read(monkeypatch, table, case):
    devices = [EVENTS]
    if case == "no scopes":
        monkeypatch.setattr(_scopes, "_step_scopes", lambda *a: None)
    elif case == "under 95%":
        devices = [EVENTS + [op("c.13", 92, 100, "copy")]]
    elif case == "another module":
        for name in TABLE:
            table[name] = ("copy", table[name][1])
    else:
        devices = []
    for name in SCOPE_METRICS:
        assert _read(name, _ctx(devices, 2)) is None, name


def _tiny_conf():
    """tinycell's one-device configuration."""
    conf = json.loads((tinycell.REPO / "chipbench" / "configs"
                       / "paper-mixtral-16x2b.json").read_text())
    conf["model"].update(tinycell.MODEL)
    conf["meshes"] = {"1": {"data": 1, "model": 1, "placement": "latin",
                            "global_batch": 4, "n_micro": 2}}
    return conf


def test_the_step_carries_every_scope():
    """The tiny cell's train step, compiled on the CPU: every scope the
    program names is in the module, every one a metric reads is the
    program's, and the instructions its events would come from carry one."""
    from repro import scopes
    conf = _tiny_conf()
    pc = program.build(conf, 1, 32, jax.devices(), impl="interpret")
    state = jax.eval_shape(pc.init_state, jax.random.PRNGKey(0))
    text = pc.step.lower(state, pc.batch_specs).compile().as_text()
    assert [n for n in scopes.TRAIN_STEP if n not in text] == []
    table = _scopes.module_scopes(text, frozenset(scopes.ALL))
    work = [sc for kind, sc in table.values()
            if kind not in PLUMBING | set(tracing.CONTROL_FLOW)]
    assert sum(map(bool, work)) >= 0.95 * len(work)
    for name in SCOPE_METRICS:
        src = (tinycell.REPO / "chipbench" / "metrics"
               / f"{name}.py").read_text()
        read = re.findall(r'"([a-z_.]+)"', src.split('"""', 2)[2])
        assert read and all(r in scopes.ALL or r == "moe." for r in read)


def test_step_scopes_without_text_from_the_cache(monkeypatch):
    """Where an executable has no HLO text (as one loaded from the
    persistent cache may), the step is compiled again without the cache."""
    from jax import stages

    text = stages.Compiled.as_text

    def as_text(self):
        if jax.config.jax_enable_compilation_cache:
            raise NotImplementedError("no text")
        return text(self)

    monkeypatch.setattr(stages.Compiled, "as_text", as_text)
    conf = _tiny_conf()
    table = _scopes._step_scopes.__wrapped__(json.dumps(conf), 1, 32)
    assert jax.config.jax_enable_compilation_cache
    assert "attention" in {sc for _, sc in table.values()}
