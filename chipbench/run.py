#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Run from the root of a checkout (BENCHMARK.json beside ``chipbench/``, the
program under ``src/``).  The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device``, with ``--trace 1`` a ``breakdown``, and last ``checks``: each
number compared, beside its limit.  The same numbers are the last lines on
standard error.  With no accelerator, fewer chips than the cell asks for,
or no program beside it, the run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def _finite(v):
    return v if isinstance(v, (int, float)) and math.isfinite(v) else None


def result_line(cell, res: dict, traced: bool, devices, peaks) -> dict:
    """The result object, with ``checks`` last."""
    from chipbench import bench, tracing
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": res["memory_peak_bytes"]}
    metrics = {}
    if traced:
        trace = res["trace"]
        device["busy_s"] = tracing.busy_seconds(trace)
        device["window_s"] = tracing.window_seconds(trace)
        ctx = res["context"]._replace(peaks=peaks)
        for m in cell.per_layer:
            v = bench.metric_reducer(cell.root, m["name"]).reduce(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": res["e2e"][m["name"]],
                                  "unit": m["unit"]}
    out = {"correct": res["correct"], "attempted": res["attempted"],
           "failed": res["failed"], "metrics": metrics, "device": device}
    if traced:
        out["breakdown"] = tracing.breakdown(res["trace"])
    out["checks"] = {k: {"value": _finite(c["value"]), "limit": c["limit"]}
                     for k, c in res["checks"].items()}
    return out


def execute(args, devices, *, root=ROOT, fault=None, t0=T0):
    """Everything after the look for a chip: the run and its result."""
    from chipbench import bench
    cell = bench.find_cell(root, args.workload)
    if len(devices) < cell.chips:
        raise SystemExit(f"chipbench: {args.workload} needs {cell.chips} "
                         f"chips, JAX sees {len(devices)}")
    peaks = bench.peaks(root, devices[0].device_kind) if args.trace else None
    res = bench.task(cell.conf).run(cell, args.seed, args.seconds,
                                    bool(args.trace), devices=devices,
                                    t0=t0, fault=fault)
    return result_line(cell, res, bool(args.trace),
                       list(devices)[:cell.chips], peaks), res


def main(argv=None) -> int:
    args = parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chipbench: the program is not beside the benchmark "
              f"({ROOT / 'src' / 'repro'} missing)", file=sys.stderr)
        return 2
    here = Path(__file__).resolve().parent
    sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != here]
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"chipbench: JAX found no devices: {e}", file=sys.stderr)
        return 3
    if devices[0].platform != "tpu":
        print(f"chipbench: no accelerator (JAX platform "
              f"{devices[0].platform!r}); the benchmark runs only on the "
              f"chip", file=sys.stderr)
        return 3
    line, res = execute(args, devices)
    print("chipbench: " + json.dumps(res["info"]), file=sys.stderr)
    for k, c in line["checks"].items():
        print(f"check {k}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
