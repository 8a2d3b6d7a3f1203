#!/usr/bin/env python3
"""Compile a cell's train step for a described TPU, with no chip attached.

    JAX_PLATFORMS=cpu python3 chipbench/rehearse.py --workload <name>

Builds the cell's step on the devices of a described ``v5e:2x2`` (the first
one, or all four), compiles it at the cell's sizes, and prints the
compiler's memory analysis per device and whether the Pallas kernel
(``tpu_custom_call``) is in the program.  Gives bytes, never a time.
"""
from __future__ import annotations

import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
TOPOLOGY = "v5e:2x2"


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    here = Path(__file__).resolve().parent
    sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != here]
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax
    from jax.experimental import topologies

    from chipbench import bench, program
    jax.config.update("jax_enable_compilation_cache", False)
    cell = bench.find_cell(ROOT, args.workload)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=TOPOLOGY)
    # the CPU backend would resolve the kernel to its oracle; name the
    # one the chip runs
    pc = program.build(cell.conf, cell.chips, int(cell.traffic["seq_len"]),
                       topo.devices, impl="pallas")
    state = jax.eval_shape(pc.init_state, jax.random.PRNGKey(0))
    shard = pc.runtime.train_state_shardings()
    state = jax.tree_util.tree_map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        state, shard)
    compiled = pc.step.lower(state, pc.batch_specs).compile()
    mem = compiled.memory_analysis()
    gib = 2.0 ** 30
    out = {"workload": args.workload, "chips": cell.chips,
           "argument_gib": mem.argument_size_in_bytes / gib,
           "output_gib": mem.output_size_in_bytes / gib,
           "alias_gib": mem.alias_size_in_bytes / gib,
           "temp_gib": mem.temp_size_in_bytes / gib,
           "generated_code_gib": mem.generated_code_size_in_bytes / gib,
           "tpu_custom_call": "tpu_custom_call" in compiled.as_text()}
    out["total_gib"] = (out["argument_gib"] + out["output_gib"]
                        - out["alias_gib"] + out["temp_gib"])
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
