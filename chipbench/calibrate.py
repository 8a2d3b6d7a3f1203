#!/usr/bin/env python3
"""Readings that set the limits of the comparison deciding ``correct``.

    python3 chipbench/calibrate.py --workload <name> --seeds 1,2,3 \
        --what program|control|unchanged|half_batch|no_exchange [--out F]

One process, one build of the cell, then for every seed the cell's three
checked steps and the reference's, compared as a run compares them:

  program      the program as the configuration states: sound runs, whose
               largest reading is a limit's lower end;
  control      the reference computed in float8 (e4m3, per-tensor scale)
               in the program's place: the precision below the configured
               bfloat16, which a limit has to fail;
  unchanged, half_batch, no_exchange
               the program with the fault planted underneath (a state
               returned unchanged; the second half of each batch left out of
               the loss; the all-to-all exchange between chips left out).

Prints one JSON line per seed, and appends them to ``--out`` if given.  Not
part of a benchmark run.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--what", required=True,
                    choices=("program", "control", "unchanged", "half_batch",
                             "no_exchange"))
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    here = Path(__file__).resolve().parent
    sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != here]
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax
    if args.what == "no_exchange":
        # the dispatch and combine all-to-all return what they were given
        jax.lax.all_to_all = lambda x, *a, **k: x
    for line in readings(ROOT, args.workload,
                         [int(s) for s in args.seeds.split(",")], args.what,
                         jax.devices()):
        print(json.dumps(line), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(line) + "\n")
    return 0


def readings(root, workload, seeds, what, devices):
    """Yields {seed, what, loss_gap, grad_gap, change_gap, ...} per seed."""
    import gc

    import jax

    from chipbench import bench, compare, program
    from chipbench.tasks import train
    cell = bench.find_cell(root, workload)
    train._enable_cache()
    devices = list(devices)[:cell.chips]
    pc = program.build(cell.conf, cell.chips, int(cell.traffic["seq_len"]),
                       devices)
    names = program.leaf_names(pc.runtime.master_sds())
    reference = train.make_reference(cell, pc, devices)
    control = (train.make_reference(cell, pc, devices, quant="fp8")
               if what == "control" else None)
    for seed in seeds:
        t = time.perf_counter()
        kw, kt = jax.random.split(bench.seed_key(seed))
        pool = train.make_pool(cell, pc, kt)
        batches = jax.device_get(pool[:train.CHECK_STEPS])
        del pool
        if what == "control":
            got = control(seed, batches)
        else:
            ts, pool, _, got, _ = train.program_side(
                cell, pc, seed, fault=None if what == "program" else what)
            del ts, pool
        gc.collect()
        want = reference(seed, batches)
        read = compare.readings(got, want, names)
        read.update(seed=seed, what=what, workload=workload, names=names,
                    loss=[float(v) for v in got["losses"]],
                    reference_loss=[float(v) for v in want["losses"]],
                    seconds=time.perf_counter() - t)
        yield read


if __name__ == "__main__":
    sys.exit(main())
