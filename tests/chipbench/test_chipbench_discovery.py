"""Everything a cell needs is found by name: new configurations, mixes and
metrics are files and entries, with no code edited."""
import json

import pytest

import tinycell
from chipbench import bench


@pytest.fixture
def root(tmp_path):
    return tinycell.make_root(tmp_path)


def test_a_new_config_mix_and_metric_are_found(root):
    cb = root / "chipbench"
    conf = json.loads((cb / "configs" / "tiny.json").read_text())
    conf["model"]["num_hidden_layers"] = 3
    (cb / "configs" / "tiny3.json").write_text(json.dumps(conf))
    (cb / "traffic" / "flat.json").write_text(json.dumps(
        {"kind": "zipf", "exponent": 0.0, "seq_len": 16, "pool": 2}))
    (cb / "metrics" / "steps_seen.py").write_text(
        "def reduce(run):\n    return float(run.steps)\n")
    (cb / "limits" / "tiny3-flat.json").write_text(json.dumps(
        tinycell.LIMITS))
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "tiny3", "source": "toy",
                         "file": "chipbench/configs/tiny3.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": "tiny3-flat", "config": "tiny3",
                           "traffic": "flat", "chips": 1, "why": "test"})
    b["per_layer"].append({"name": "steps_seen", "unit": "steps",
                           "better": "higher", "source": "host_clock",
                           "layer": "test", "moves": "train_tokens_per_s",
                           "workloads": ["tiny3-flat"]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))

    cell = bench.find_cell(root, "tiny3-flat")
    assert cell.conf["model"]["num_hidden_layers"] == 3
    assert cell.traffic["exponent"] == 0.0
    assert cell.limits == tinycell.LIMITS
    assert "steps_seen" in [m["name"] for m in cell.per_layer]
    assert bench.traffic_generator(cell).make
    reducer = bench.metric_reducer(root, "steps_seen")
    assert reducer.reduce(type("R", (), {"steps": 7})) == 7.0
    # a metric listed for other cells only is not this cell's
    other = bench.find_cell(root, "tiny-1")
    assert "steps_seen" not in [m["name"] for m in other.per_layer]


def test_unknown_workload_is_an_error(root):
    with pytest.raises(KeyError, match="unknown workload"):
        bench.find_cell(root, "nope")


def test_peaks_by_device_kind():
    assert bench.peaks(tinycell.REPO, "TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError, match="no peaks for device kind"):
        bench.peaks(tinycell.REPO, "TPU v9 imaginary")


def test_seeds_beyond_32_bits():
    import jax
    big = 2 ** 33 + 5
    a = jax.random.key_data(bench.seed_key(big))
    b = jax.random.key_data(bench.seed_key(big))
    c = jax.random.key_data(bench.seed_key(5))
    assert (a == b).all() and not (a == c).all()
    with pytest.raises(ValueError):
        bench.seed_key(-1)


def test_the_committed_benchmark_is_complete():
    """Every name BENCHMARK.json gives has its file."""
    b = bench.load_benchmark(tinycell.REPO)
    cb = tinycell.REPO / "chipbench"
    for c in b["configs"]:
        conf = json.loads((tinycell.REPO / c["file"]).read_text())
        assert conf["name"] == c["name"]
        assert (cb / "tasks" / f"{conf['task']}.py").is_file()
    for w in b["workloads"]:
        cell = bench.find_cell(tinycell.REPO, w["name"])
        assert str(cell.chips) in cell.conf["meshes"]
        assert (cb / "traffic" / f"{cell.traffic['kind']}.py").is_file()
    for m in b["per_layer"]:
        assert (cb / "metrics" / f"{m['name']}.py").is_file()
