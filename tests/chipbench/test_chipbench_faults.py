"""The comparison that decides ``correct`` fails the faults a training cell
can have: the whole run past the look for a chip, with the timed path
broken underneath, on a tiny cell on the CPU."""
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import jax
import pytest

import tinycell
from chipbench import run

HERE = Path(__file__).resolve().parent


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tinycell.make_root(tmp_path_factory.mktemp("faults"))


def _run(root, workload, fault, seed=11):
    args = run.parse_args(["--workload", workload, "--seed", str(seed),
                           "--seconds", "0.3", "--trace", "0"])
    line, _ = run.execute(args, jax.devices(), root=root, fault=fault,
                          t0=time.perf_counter())
    return line


@pytest.mark.parametrize("fault,number", [
    ("unchanged", "change_gap"),     # a step that returns its state
    ("half_batch", "loss_gap"),      # half the batch out, mean over the rest
])
def test_fault_is_not_correct(root, fault, number):
    line = _run(root, "tiny-1", fault)
    assert line["correct"] is False
    c = line["checks"][number]
    assert c["value"] > c["limit"]


FOUR = r"""
import json, sys, time
sys.path.insert(0, sys.argv[1])
import tinycell
from pathlib import Path
root = tinycell.make_root(Path(sys.argv[2]))
import jax
from chipbench import run
out = {}
for fault in (None, "no_exchange"):
    if fault == "no_exchange":
        # the dispatch and combine exchange between chips left out
        jax.lax.all_to_all = lambda x, *a, **k: x
    args = run.parse_args(["--workload", "tiny-4", "--seed", "23",
                           "--seconds", "0.3", "--trace", "0"])
    line, _ = run.execute(args, jax.devices(), root=root, fault=fault,
                          t0=time.perf_counter())
    out[str(fault)] = line
print(json.dumps(out))
"""


def test_exchange_left_out_is_not_correct(tmp_path):
    """Four host devices on a 2x2 mesh: the sound run is correct, the run
    whose all-to-all returns its input is not."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", FOUR, str(HERE),
                        str(tmp_path)], env=env, capture_output=True,
                       text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["None"]["correct"] is True, out["None"]["checks"]
    assert out["None"]["device"]["count"] == 4
    assert out["no_exchange"]["correct"] is False
