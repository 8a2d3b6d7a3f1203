"""A tiny training cell in a temporary checkout, for the benchmark's CPU
tests: the repository's ``chipbench`` files plus a small configuration
(the program's paper-mixtral-16x2b architecture at toy widths, two expert
tensor-parallel halves), a short Zipf mix, and BENCHMARK.json entries for a
one-device and a four-device cell."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
for p in (str(REPO / "src"), str(REPO)):
    if p not in sys.path:
        sys.path.insert(0, p)

MODEL = {"hidden_size": 64, "num_attention_heads": 4,
         "num_key_value_heads": 2, "head_dim": 16, "num_experts": 8,
         "num_experts_per_tok": 2, "moe_intermediate_size": 64,
         "vocab_size": 512, "num_hidden_layers": 2}
# Set from readings of this tiny cell on the CPU (seeds 1-6, 11, 2**32+17
# and 2**33+5 on one device; 1-4 and 23 on four): the bfloat16 program read
# at most loss 3.2e-3, grad 3.9e-2, change 6.6e-3 and median-leaf gradient
# difference 0.204; the float8 control at least 0.319 on the last, half a
# batch at least 0.48 on the loss, the exchange left out at least 0.678 on
# the last, a state left unchanged 1 on grad and change.  At these toy
# widths the two sides lie closer than at the cells' own (PERF.md).
LIMITS = {"loss_gap": 0.02, "grad_gap": 0.3, "change_gap": 0.03,
          "grad_diff_median": 0.28}


def make_root(root: Path) -> Path:
    """Write the tiny checkout under ``root`` and return it."""
    cb = root / "chipbench"
    for sub in ("traffic", "metrics"):
        shutil.copytree(REPO / "chipbench" / sub, cb / sub,
                        dirs_exist_ok=True,
                        ignore=shutil.ignore_patterns("__pycache__"))
    (cb / "configs").mkdir(parents=True, exist_ok=True)
    (cb / "limits").mkdir(parents=True, exist_ok=True)
    peaks = json.loads((REPO / "chipbench" / "peaks.json").read_text())
    peaks["devices"]["cpu"] = peaks["devices"]["TPU v5 lite"]
    (cb / "peaks.json").write_text(json.dumps(peaks))
    conf = json.loads((REPO / "chipbench" / "configs"
                       / "paper-mixtral-16x2b.json").read_text())
    conf["name"] = "tiny"
    conf["model"].update(MODEL)
    conf["meshes"] = {
        "1": {"data": 1, "model": 1, "placement": "latin",
              "global_batch": 4, "n_micro": 2},
        "4": {"data": 2, "model": 2, "placement": "latin",
              "global_batch": 8, "n_micro": 2}}
    (cb / "configs" / "tiny.json").write_text(json.dumps(conf))
    (cb / "traffic" / "tiny-zipf.json").write_text(json.dumps(
        {"kind": "zipf", "exponent": 1.0, "seq_len": 32, "pool": 4}))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "tiny", "source": "toy widths",
                         "file": "chipbench/configs/tiny.json",
                         "reduced": [], "why": "CPU tests"}]
    bench["workloads"] = [
        {"name": f"tiny-{n}", "config": "tiny", "traffic": "tiny-zipf",
         "chips": n, "why": "CPU tests"} for n in (1, 4)]
    for m in bench["per_layer"]:
        m.pop("workloads", None)
    for w in bench["workloads"]:
        (cb / "limits" / f"{w['name']}.json").write_text(json.dumps(LIMITS))
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root
