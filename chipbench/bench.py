"""Discovery by name: everything a cell needs is found from BENCHMARK.json.

- a cell (``workloads`` entry) names a configuration and a traffic mix;
- ``configs[].file`` is the configuration, whose ``task`` names the runner
  ``chipbench/tasks/<task>.py``;
- ``chipbench/traffic/<mix>.json`` holds the mix's parameters, whose
  ``kind`` names the generator ``chipbench/traffic/<kind>.py``;
- ``chipbench/metrics/<metric>.py`` reduces one per-layer metric;
- ``chipbench/limits/<cell>.json`` holds the limits of the comparison that
  decides ``correct``;
- ``chipbench/peaks.json`` holds the chip's peaks by ``device_kind``.

Adding a cell, a mix or a metric adds files and entries; no code here
changes.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
from pathlib import Path
from typing import NamedTuple


class Cell(NamedTuple):
    root: Path           # the checkout: BENCHMARK.json and chipbench/
    name: str
    chips: int
    conf: dict           # the configuration file
    traffic: dict        # the traffic mix's parameters
    end_to_end: list     # BENCHMARK.json metric entries this cell reports
    per_layer: list
    limits: dict


def load_benchmark(root: Path) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(root: Path, name: str, bench: dict | None = None) -> Cell:
    bench = bench or load_benchmark(root)
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"unknown workload {name!r}; BENCHMARK.json has "
                       f"{', '.join(sorted(by_name))}")
    w = by_name[name]
    confs = {c["name"]: c for c in bench["configs"]}
    conf = json.loads((root / confs[w["config"]]["file"]).read_text())
    traffic = json.loads((root / "chipbench" / "traffic"
                          / f"{w['traffic']}.json").read_text())
    limits = json.loads((root / "chipbench" / "limits" / f"{name}.json")
                        .read_text())
    return Cell(root=root, name=name, chips=int(w["chips"]), conf=conf,
                traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _applies(m, name)],
                limits=limits)


def _from_file(root: Path, kind: str, name: str):
    path = root / "chipbench" / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} module {path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name.replace('-', '_').replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def task(conf: dict):
    """The cell runner ``chipbench/tasks/<task>.py``."""
    return importlib.import_module(f"chipbench.tasks.{conf['task']}")


def traffic_generator(cell: Cell):
    """The generator ``chipbench/traffic/<kind>.py`` of the cell's mix."""
    return _from_file(cell.root, "traffic", cell.traffic["kind"])


def metric_reducer(root: Path, name: str):
    """The reducer ``chipbench/metrics/<name>.py``."""
    return _from_file(root, "metrics", name)


def peaks(root: Path, device_kind: str) -> dict:
    """The chip's peaks; a device not in the table is an error."""
    table = json.loads((root / "chipbench" / "peaks.json")
                       .read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"chipbench/peaks.json (known: {', '.join(table)})")
    return table[device_kind]


def seed_key(seed: int):
    """A PRNG key for any non-negative whole number, beyond 32 bits too."""
    import jax
    if seed < 0:
        raise ValueError(f"--seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32) if seed >> 32 else key
