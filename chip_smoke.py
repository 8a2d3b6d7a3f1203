#!/usr/bin/env python3
"""On-chip smoke check: MicroEP training and serving at OLMoE-1B-7B width.

    python chip_smoke.py             # one chip: kernel, train, serve phases
    python chip_smoke.py --chips 4   # four chips: MicroEP vs vanilla train

Every phase runs in this one process (a chip belongs to one process), through
the launchers a user calls (``repro.launch.train`` / ``repro.launch.serve``),
at the published widths of ``olmoe-1b-7b`` (d_model 2048, 16x128 heads,
64 experts top-8, expert d_ff 1024, vocab 50304); depth is the only cut.
Weights are random, from a fixed seed.

  kernel  the Pallas grouped FFN, forward and gradient, against the f32
          oracle of kernels/ref.py at H=2048, F=1024, 64 groups;
  train   1 layer on a 1x1 mesh in bf16, a few steps: finite losses, zero
          MoE overflow, and the compiled step must hold the Pallas kernel
          (``tpu_custom_call``) — the default kernel on a TPU;
  serve   2 layers on a 1x1 mesh: 4 requests x 16 generated tokens, each
          served with the tokens it asked for;
  four    (--chips 4 only) the train step on a (data=1, model=4) mesh, 16
          experts per chip, MicroEP placement against vanilla EP on the same
          batch: losses agree, MicroEP's max device load is at or below
          vanilla's, overflow is 0.

Timings printed here are smoke timings of one run, not benchmark results.
The last line of stdout is the JSON verdict; it is printed only when every
phase passed.  The script exits non-zero without it when JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
ARCH = "olmoe-1b-7b"


def _peak_gib(dev) -> str:
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak / 2 ** 30:.3f} GiB"


def _rel_err(got, want) -> float:
    """max |got - want| over max |want|."""
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


def phase_kernel(dev) -> None:
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref

    s, c, h, f = 64, 256, 2048, 1024       # C: a bm=128-aligned group slot
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    counts = jax.random.randint(ks[0], (s,), 0, 201).astype(jnp.int32)
    start = jnp.arange(s, dtype=jnp.int32) * c
    bf16 = jnp.bfloat16
    x = (jax.random.normal(ks[1], (s * c, h)) * 0.5).astype(bf16)
    wg = (jax.random.normal(ks[2], (s, h, f)) * h ** -0.5).astype(bf16)
    wu = (jax.random.normal(ks[3], (s, h, f)) * h ** -0.5).astype(bf16)
    wd = (jax.random.normal(ks[4], (s, f, h)) * f ** -0.5).astype(bf16)
    probe = jax.random.normal(ks[5], (s * c, h))
    valid = (jnp.arange(c)[None, :] < counts[:, None]).reshape(-1)
    print(f"kernel: S={s} H={h} F={f} rows={s * c} "
          f"valid={int(counts.sum())}")

    def kernel_loss(x_, wg_, wu_, wd_, counts_, probe_):
        out = ops.grouped_ffn_flat(x_, start, start + counts_, wg_, wu_,
                                   wd_, impl="pallas")
        return jnp.sum(out.astype(jnp.float32) * probe_), out

    def ref_loss(x_, wg_, wu_, wd_, counts_, probe_):
        # the slot-grouped oracle on the same groups, all math in f32
        out = ref.grouped_ffn_ref(
            x_.astype(jnp.float32).reshape(s, c, h), counts_,
            wg_.astype(jnp.float32), wu_.astype(jnp.float32),
            wd_.astype(jnp.float32)).reshape(s * c, h)
        return jnp.sum(out * probe_), out

    args = (x, wg, wu, wd, counts, probe)
    t0 = time.perf_counter()
    grad_k = jax.jit(jax.grad(kernel_loss, argnums=(0, 1, 2, 3),
                              has_aux=True))
    gk, out_k = jax.block_until_ready(grad_k(*args))
    print(f"smoke timing: kernel compile+first call "
          f"{time.perf_counter() - t0:.3f} s")
    with jax.default_matmul_precision("highest"):
        gr, out_r = jax.jit(jax.grad(ref_loss, argnums=(0, 1, 2, 3),
                                     has_aux=True))(*args)

    # Rows outside [start, end) are junk slots and must come out as zeros.
    junk = float(jnp.abs(jnp.where(valid[:, None], 0.0,
                                   out_k.astype(jnp.float32))).max())
    assert junk == 0.0, f"kernel wrote {junk} into rows outside the groups"
    # Tolerance: operands are bf16 (8 significant bits, u = 2^-8 ≈ 3.9e-3);
    # the kernel rounds h to bf16 before the down-projection and rounds its
    # output to bf16, the oracle does neither.  Two roundings of ~u each,
    # relative to the largest output, stay well under 2e-2.
    err = _rel_err(out_k, out_r)
    print(f"kernel: forward max-abs err / max-abs ref = {err:.3e} "
          f"(limit 2e-2)")
    assert err < 2e-2, f"grouped FFN forward off the f32 oracle: {err}"
    # Gradients come back in the operands' dtype (bf16) after an f32
    # accumulation in the ragged-dot backward: the same two roundings plus
    # the output rounding of each gradient, so the same 2e-2 bound.
    for name, a, b in zip(("dx", "dw_gate", "dw_up", "dw_down"), gk, gr):
        err = _rel_err(a, b)
        print(f"kernel: {name} max-abs err / max-abs ref = {err:.3e} "
              f"(limit 2e-2)")
        assert err < 2e-2, f"grouped FFN {name} off the f32 oracle: {err}"
    print(f"kernel: PASS peak_bytes_in_use={_peak_gib(dev)}")


def _train(extra) -> dict:
    from repro.launch import train
    # batch x seq = 4 x 2048: one OLMoE layer's master, Adam moments, f32
    # grads and bf16 working copy take ~9.4 GB of the chip's 16 GB; the
    # activations of a 2 x 2048 micro-batch fit in what is left
    return train.run(["--arch", ARCH, "--layers", "1", "--dtype", "bfloat16",
                      "--batch", "4", "--seq", "2048", "--n-micro", "2",
                      "--steps", "3", "--seed", "0"] + extra)


def _check_train(res: dict, label: str) -> None:
    losses = [row["loss"] for row in res["history"]]
    overflow = [row["overflow"] for row in res["history"]]
    print(f"{label}: layers={res['layers']} losses={losses} "
          f"overflow={overflow} balance="
          f"{[row['balance'] for row in res['history']]}")
    print(f"smoke timing: {label} compile {res['compile_s']:.3f} s, "
          f"steps {[round(t, 4) for t in res['step_s']]} s "
          f"(steady {min(res['step_s'][1:] or res['step_s']):.4f} s)")
    assert res["layers"] == 1
    assert all(math.isfinite(v) for v in losses), f"non-finite loss {losses}"
    assert all(v == 0 for v in overflow), f"MoE overflow {overflow}"
    assert res["has_pallas_kernel"], \
        "compiled train step holds no Pallas kernel (tpu_custom_call)"


def phase_train(dev) -> None:
    res = _train(["--data-axis", "1", "--model-axis", "1"])
    _check_train(res, "train")
    print(f"train: PASS peak_bytes_in_use={_peak_gib(dev)}")


def phase_serve(dev) -> None:
    from repro.launch import serve
    t0 = time.perf_counter()
    report, requests = serve.run([
        "--arch", ARCH, "--layers", "2", "--dtype", "bfloat16",
        "--data-axis", "1", "--model-axis", "1", "--traffic", "replay",
        "--requests", "4", "--rate", "1", "--prompt-len", "16",
        "--gen", "16", "--max-batch", "4", "--seed", "0"])
    total = time.perf_counter() - t0
    asked = {r.req_id: r.max_new for r in requests}
    got = {rec.req_id: rec.n_generated for rec in report.records}
    print(f"serve: asked={asked} served={got} rejected={report.rejected} "
          f"overflow={report.overflow}")
    print(f"smoke timing: serve set-up incl. compile "
          f"{total - report.wall_s:.3f} s, {report.steps} steps in "
          f"{report.wall_s:.3f} s "
          f"({report.wall_s / max(report.steps, 1):.4f} s/step)")
    assert len(asked) == 4 and all(n == 16 for n in asked.values())
    assert report.rejected == 0, f"{report.rejected} requests rejected"
    assert got == asked, f"served {got}, asked for {asked}"
    assert report.overflow == 0, f"MoE overflow {report.overflow}"
    print(f"serve: PASS peak_bytes_in_use={_peak_gib(dev)}")


def phase_four_chips(dev) -> None:
    mesh = ["--data-axis", "1", "--model-axis", "4"]
    micro = _train(mesh)                                # latin + LP schedule
    _check_train(micro, "four/microep")
    vanilla = _train(mesh + ["--placement", "vanilla", "--mode", "vanilla"])
    _check_train(vanilla, "four/vanilla")
    lm = [row["loss"] for row in micro["history"]]
    lv = [row["loss"] for row in vanilla["history"]]
    # Tolerance: both runs start from the same master and see the same
    # batches; placement changes only which chip computes an expert and the
    # order of reductions, so the f32 losses differ by bf16 rounding noise
    # (u ≈ 3.9e-3 per value, averaged over 8192 tokens) — far under 1e-2.
    rel = max(abs(a - b) / abs(b) for a, b in zip(lm, lv))
    print(f"four: loss rel diff {rel:.3e} (limit 1e-2)")
    assert rel < 1e-2, f"MicroEP and vanilla losses disagree: {lm} vs {lv}"
    # balance = max device load / mean device load; the mean is the same
    # for both (same routed rows over 4 chips), so this compares max loads
    bm = [row["balance"] for row in micro["history"]]
    bv = [row["balance"] for row in vanilla["history"]]
    print(f"four: max/mean device load microep={bm} vanilla={bv}")
    assert bm[0] <= bv[0] + 1e-6, "MicroEP max device load above vanilla's"
    assert sum(bm) <= sum(bv) + 1e-6, "MicroEP mean max load above vanilla's"
    print(f"four: PASS peak_bytes_in_use={_peak_gib(dev)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the four-chip MicroEP-vs-vanilla phase")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: FAIL: the repro package is not next to this "
              f"script ({ROOT / 'src' / 'repro'} missing)", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))

    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"chip_smoke: FAIL: JAX found no devices: {e}", file=sys.stderr)
        return 1
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: FAIL: no TPU found (JAX platform "
              f"'{dev.platform}'); this check runs only on the chip",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: FAIL: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    from repro.launch.mesh import enable_compile_cache
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)} compile cache={enable_compile_cache()}")

    phases = ([phase_four_chips] if args.chips == 4
              else [phase_kernel, phase_train, phase_serve])
    for phase in phases:
        t0 = time.perf_counter()
        phase(dev)
        print(f"smoke timing: phase {phase.__name__} "
              f"{time.perf_counter() - t0:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
