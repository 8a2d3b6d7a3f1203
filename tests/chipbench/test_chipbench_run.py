"""A run of the benchmark: its last line, and its refusals."""
import json
import os
import shutil
import subprocess
import time

import jax
import pytest

import tinycell
from chipbench import bench, program, run

KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def _cpu_env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return env


def _bench_cmd(cwd):
    b = json.loads((tinycell.REPO / "BENCHMARK.json").read_text())
    w = b["workloads"][0]["name"]
    return b["command"] + ["--workload", w, "--seed", "5", "--seconds", "1",
                           "--trace", "0"]


def test_no_accelerator_exits_nonzero_without_a_result():
    p = subprocess.run(_bench_cmd(tinycell.REPO), cwd=tinycell.REPO,
                       env=_cpu_env(), capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no accelerator" in p.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's paths."""
    b = json.loads((tinycell.REPO / "BENCHMARK.json").read_text())
    shutil.copy(tinycell.REPO / "BENCHMARK.json", tmp_path)
    for p in b["paths"]:
        shutil.copytree(tinycell.REPO / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(_bench_cmd(tmp_path), cwd=tmp_path, env=_cpu_env(),
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tinycell.make_root(tmp_path_factory.mktemp("tinyroot"))


@pytest.mark.parametrize("traced", [0, 1])
def test_last_line(root, traced):
    """The whole run past the look for a chip, on the CPU: the result's
    keys, ``checks`` last, and a sound run is correct."""
    args = run.parse_args(["--workload", "tiny-1", "--seed",
                           str(2 ** 32 + 17), "--seconds", "0.5",
                           "--trace", str(traced)])
    line, res = run.execute(args, jax.devices(), root=root,
                            t0=time.perf_counter())
    assert list(line)[:5] == KEYS
    assert list(line)[-1] == "checks"
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["checks"]) == set(tinycell.LIMITS)
    for c in line["checks"].values():
        assert c["value"] <= c["limit"]
    dev = line["device"]
    assert dev["count"] == 1 and dev["platform"] == "cpu"
    if traced:
        assert {"busy_s", "window_s"} <= set(dev)
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
        # the CPU has no device plane: only the host-clock metric reads
        assert set(line["metrics"]) == {"train_mfu"}
    else:
        assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
        assert all(m["value"] > 0 for m in line["metrics"].values())
    json.loads(json.dumps(line))


def test_untied_head_is_correct(tmp_path):
    """A configuration with an LM head of its own, as OLMoE-1B-7B has: the
    program holds a ``head`` leaf, the reference follows it, and a sound
    run is correct."""
    root = tinycell.make_root(tmp_path)
    cb = root / "chipbench"
    conf = json.loads((cb / "configs" / "tiny.json").read_text())
    conf["model"]["tie_word_embeddings"] = False
    (cb / "configs" / "tiny-untied.json").write_text(json.dumps(conf))
    shutil.copy(cb / "limits" / "tiny-1.json", cb / "limits"
                / "tiny-untied-1.json")
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "tiny-untied", "source": "toy widths",
                         "file": "chipbench/configs/tiny-untied.json",
                         "reduced": [], "why": "CPU tests"})
    b["workloads"].append({"name": "tiny-untied-1", "config": "tiny-untied",
                           "traffic": "tiny-zipf", "chips": 1,
                           "why": "CPU tests"})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    args = run.parse_args(["--workload", "tiny-untied-1", "--seed", "7",
                           "--seconds", "0.5", "--trace", "0"])
    line, _ = run.execute(args, jax.devices(), root=root,
                          t0=time.perf_counter())
    assert line["correct"] is True, line["checks"]
    cell = bench.find_cell(root, "tiny-untied-1")
    pc = program.build(cell.conf, 1, 32, jax.devices())
    assert "head" in program.leaf_names(pc.runtime.master_sds())
