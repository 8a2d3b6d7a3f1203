"""The reduction from a trace to per-layer metrics, on a hand-made trace
whose answers are known."""
import pytest

import tinycell
from chipbench import bench, tracing
from chipbench.tasks.train import RunContext

S = tracing.SPAN


def op(name, start, end, kind="fusion"):
    return tracing.Op(name, kind, float(start), float(end))


def test_interval_algebra():
    u = tracing.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert u == [(0, 3), (5, 8)]
    assert tracing.length(u) == 6
    assert tracing.clip(u, 1, 6) == [(1, 3), (5, 6)]
    assert tracing.subtract([(0, 10)], [(2, 3), (5, 7)]) == \
        [(0, 2), (3, 5), (7, 10)]
    assert tracing.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]


@pytest.fixture
def trace():
    # window 0..100 ns; device 0: kernel 10..30, collective 30..50 of
    # which 40..50 overlaps a fusion, backward ops 60..80; device 1: the
    # kernel 10..40 and an exposed collective 40..60; the host fetches
    # while device 0 idles 80..100
    dev0 = [op("grouped_ffn.1", 10, 30, "custom-call"),
            op("all-to-all.1", 30, 50, "all-to-all"),
            op("fusion.2", 40, 50),
            op("ragged-dot-none.3", 60, 70, "custom-call"),
            op("ragged-dot-none.4", 70, 80, "custom-call")]
    dev1 = [op("grouped_ffn.1", 10, 40, "custom-call"),
            op("all-gather-start.5", 40, 60, "all-gather-start")]
    spans = [op(S + "window", 0, 100, ""), op(S + "dispatch", 0, 8, ""),
             op(S + "fetch", 80, 100, "")]
    return tracing.Trace(devices=[dev0, dev1], spans=spans, window=(0, 100))


def _ctx(trace, chips=2):
    m = dict(tinycell.MODEL)
    return RunContext(
        conf={"model": m}, traffic={"seq_len": 32}, chips=chips,
        peaks={"bf16_flops": 1e3, "hbm_bytes_per_s": 1e12}, steps=1,
        tokens_per_step=8, window_s=100e-9, trace=trace, balance=[1.2, 1.4],
        kernel={"calls_per_step": 1, "rows_per_call": 2.0,
                "slot_weights": 10.0, "etp": 1})


def _metric(name, ctx):
    return bench.metric_reducer(tinycell.REPO, name).reduce(ctx)


def test_busy_and_idle(trace):
    # device 0 busy 10..50 and 60..80 = 60 ns, device 1 10..60 = 50 ns
    assert tracing.busy_seconds(trace) == pytest.approx(55e-9)
    assert tracing.window_seconds(trace) == pytest.approx(100e-9)
    assert _metric("device_idle_share", _ctx(trace)) == pytest.approx(45.0)


def test_collective_exposed(trace):
    # device 0: 30..40 exposed (10 ns); device 1: 40..60 (20 ns); mean 15
    # ns over 1 step = 1.5e-5 ms
    assert _metric("collective_exposed_ms", _ctx(trace)) == \
        pytest.approx(1.5e-5)
    assert _metric("collective_exposed_ms", _ctx(trace, chips=1)) is None


def test_kernel_rooflines(trace):
    m = tinycell.MODEL
    h, f = m["hidden_size"], m["moe_intermediate_size"]
    # compute-bound at 1e3 op/s: forward 6*2*h*f ops per call, 2 calls
    # (one per device) over 20 + 30 ns of kernel time
    least = 6 * 2 * h * f / 1e3
    assert _metric("grouped_ffn_fwd_roofline", _ctx(trace)) == \
        pytest.approx(100 * 2 * least / 50e-9)
    least = 12 * 2 * h * f / 1e3
    assert _metric("grouped_ffn_bwd_roofline", _ctx(trace)) == \
        pytest.approx(100 * 2 * least / 20e-9)


def test_balance_and_mfu(trace):
    assert _metric("moe_device_balance", _ctx(trace)) == pytest.approx(1.3)
    assert _metric("moe_device_balance", _ctx(trace, chips=1)) is None
    ctx = _ctx(trace)
    from chipbench import flops
    want = 100 * flops.train_flops_per_token(ctx.conf["model"], 32) \
        * 8 / 100e-9 / (2 * 1e3)
    assert _metric("train_mfu", ctx) == pytest.approx(want)


def test_nothing_to_read_gives_nothing():
    empty = tracing.Trace(devices=[], spans=[], window=(0, 1))
    for name in ("device_idle_share", "collective_exposed_ms",
                 "grouped_ffn_fwd_roofline", "grouped_ffn_bwd_roofline"):
        assert _metric(name, _ctx(empty)) is None


def test_breakdown_names_gaps_by_host_span(trace):
    b = tracing.breakdown(trace)
    names = [n for n, _ in b["device_ops"]]
    assert names[0] == "grouped_ffn.1 custom-call"   # 20 + 30 ns
    assert b["device_ops"][0][1] == pytest.approx(50e-9)
    # device 0 idles 0..10 (dispatch), 50..60 (no span), 80..100 (fetch)
    gaps = {n: s for n, s in b["idle_gaps"]}
    assert gaps["fetch"] == pytest.approx(20e-9)
    assert gaps["dispatch"] == pytest.approx(10e-9)
    assert b["idle_gaps"][0][0] == "fetch"


@pytest.mark.parametrize("text,want", [
    ("%grouped_ffn.14 = bf16[106496,2048]{1,0:T(8,128)(2,1)} custom-call("
     "s32[896]{0:T(1024)S(1)} %copy-done.207)", ("grouped_ffn.14",
                                                 "custom-call")),
    ("%while.611 = (s32[]{:T(128)}, f32[1,1,64,1]{2,3,1,0:T(1,128)}) "
     "while((s32[]{:T(128)}) %tuple), body=%body", ("while.611", "while")),
    ("%copy-start.209 = (s32[1024]{0:T(1024)}, u32[]{:S(2)}) copy-start("
     "s32[1024]{0:T(1024)} %x)", ("copy-start.209", "copy-start")),
    ("%iota.255 = s32[64]{0:T(128)S(1)} iota(), iota_dimension=0",
     ("iota.255", "iota")),
    ("chipbench.window", ("chipbench.window", "")),
])
def test_hlo_instruction_text(text, want):
    assert tracing.hlo_name_and_opcode(text) == want


def _fixture():
    """One step of olmoe-train-zipf's traced window on a TPU v5 lite."""
    import gzip
    import json
    path = tinycell.REPO / "tests" / "chipbench" / "fixtures" / \
        "olmoe-train-zipf.step.json.gz"
    d = json.loads(gzip.decompress(path.read_bytes()))
    devices = [[tracing.Op(n, k, float(a), float(b)) for n, k, a, b in ops]
               for ops in d["devices"]]
    spans = [tracing.Op(n, "", float(a), float(b)) for n, a, b in d["spans"]]
    win = [s for s in spans if s.name == S + "window"][0]
    return tracing.Trace(devices=devices, spans=spans,
                         window=(win.start, win.end))


def test_recorded_step():
    """The reduction on a step recorded on the chip: the kernel events are
    found, and no share passes 100%."""
    import json
    trace = _fixture()
    conf = json.loads((tinycell.REPO / "chipbench" / "configs"
                       / "olmoe-1b-7b.json").read_text())
    ctx = RunContext(
        conf=conf, traffic={"seq_len": 2048}, chips=1,
        peaks=bench.peaks(tinycell.REPO, "TPU v5 lite"), steps=1,
        tokens_per_step=8192, window_s=tracing.window_seconds(trace),
        trace=trace, balance=[1.0],
        kernel={"calls_per_step": 2, "rows_per_call": 32768.0,
                "slot_weights": 3.0 * 64 * 2048 * 1024, "etp": 1})
    idle = _metric("device_idle_share", ctx)
    assert 0.0 <= idle < 5.0
    fwd = _metric("grouped_ffn_fwd_roofline", ctx)
    bwd = _metric("grouped_ffn_bwd_roofline", ctx)
    assert 5.0 < fwd < 100.0 and 5.0 < bwd < 100.0
    assert _metric("collective_exposed_ms", ctx) is None
    b = tracing.breakdown(trace)
    assert b["device_ops"][0][0] == "grouped_ffn.14 custom-call"
    assert len(b["device_ops"]) == 10
