"""Hot-path microbenchmarks — the pipelined MoE critical path (PR 4).

Three comparisons, each emitted as BENCH lines and collected into
``BENCH_hotpath.json`` (the repo's perf-trajectory baseline; CI runs
``--smoke``):

  * **solver**: scan (Gauss-Seidel `lax.scan` over experts) vs batched
    (damped-Jacobi, all experts per sweep) in-graph LPP-1 solves, cold and
    layer-batched; the batched variant must measure faster at equal
    quality band (the acceptance gate of ISSUE 4);
  * **dispatch**: dense-scatter vs packed-gather buffer movement through
    `dispatch`/`combine` at serving-scale token counts;
  * **pipeline**: monolithic vs destination-chunked `moe_ffn` on a real
    shard_map mesh (subprocess — the XLA host-device count is
    per-process).  CPU wall-clock cannot show collective/compute overlap
    (CPU collectives are memcpys), so these rows *track* the chunking
    overhead rather than assert a win; the overlap itself is scheduled by
    XLA on real interconnects (DESIGN.md §2).

Usage::

  PYTHONPATH=src python -m benchmarks.bench_hotpath [--smoke] [--out PATH]
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.lp import solve_lpp1
from repro.core.solver_jax import (device_loads, solve_replica_loads,
                                   solve_replica_loads_batched)
from repro.engine import MicroEPEngine
from repro.moe import dispatch as D
from repro.moe.router import top_k_gating

from .common import (emit, make_engine, make_main, register_bench,
                     time_it, zipf_input)

SOLVER_CONFIGS = [(8, 32), (16, 64), (32, 128), (64, 256)]
SOLVER_CONFIGS_SMOKE = [(8, 32), (16, 64)]


def bench_solver(rows_out, smoke: bool, seed: int = 0):
    """scan vs batched solver wall-clock, cold and warm-started.

    The warm row is the one the training/serving loops live in: the solver
    state threads across micro-batches, so each solve starts from the
    previous micro-batch's solution under ±10% load jitter (the paper's
    warm-start regime).  The acceptance gate uses warm speedups."""
    rng = np.random.default_rng(seed)
    iters = 5 if smoke else 20
    reductions = []
    for g, e in (SOLVER_CONFIGS_SMOKE if smoke else SOLVER_CONFIGS):
        eng = make_engine(2, g // 2, e)
        dev = jnp.asarray(eng.statics.dev, jnp.int32)
        loads0 = jnp.asarray(
            zipf_input(rng, e, g, 2048, 1.0).sum(axis=1), jnp.float32)
        jitter = jnp.asarray(
            rng.uniform(0.9, 1.1, size=e).astype(np.float32))
        loads = loads0 * jitter             # "next micro-batch" loads

        scan_cold = jax.jit(lambda l: solve_replica_loads(
            l, dev, g, sweeps=6).x)
        batched_cold = jax.jit(lambda l: solve_replica_loads_batched(
            l, dev, g, sweeps=12).x)
        scan_warm = jax.jit(lambda l, x0: solve_replica_loads(
            l, dev, g, x_init=x0, sweeps=6).x)
        batched_warm = jax.jit(lambda l, x0: solve_replica_loads_batched(
            l, dev, g, x_init=x0, sweeps=12).x)
        # steady-state warm inputs: a converged solve of the previous loads
        w_scan = solve_replica_loads(loads0, dev, g, sweeps=30).x
        w_batched = solve_replica_loads_batched(loads0, dev, g,
                                                sweeps=60).x
        oracle = solve_lpp1(np.asarray(loads, np.float64),
                            eng.statics.dev, g).max_load
        row = {"bench": "solver", "devices": g, "experts": e,
               "lp_max_load": round(float(oracle), 2)}
        runs = (("scan", "cold", lambda: scan_cold(loads)),
                ("batched", "cold", lambda: batched_cold(loads)),
                ("scan", "warm", lambda: scan_warm(loads, w_scan)),
                ("batched", "warm", lambda: batched_warm(loads, w_batched)))
        for name, phase, fn in runs:
            t = time_it(lambda: jax.block_until_ready(fn()), iters=iters)
            mx = float(device_loads(fn(), dev, g).max())
            row[f"{name}_{phase}_us"] = round(t * 1e6, 1)
            row[f"{name}_{phase}_max_load"] = round(mx, 2)
            emit("hotpath_solver", solver=name, phase=phase, devices=g,
                 experts=e, us=round(t * 1e6, 1), max_load=round(mx, 2),
                 lp_max_load=round(float(oracle), 2))
        row["warm_speedup"] = round(
            row["scan_warm_us"] / row["batched_warm_us"], 3)
        reductions.append(row["warm_speedup"])
        rows_out.append(row)

    # layer-batched solve: all MoE layers of a decoder sweep in one call
    g, e = (16, 64) if smoke else (32, 128)
    layers = 4 if smoke else 12
    eng = make_engine(2, g // 2, e)
    dev = jnp.asarray(eng.statics.dev, jnp.int32)
    loads_l = jnp.asarray(
        np.stack([zipf_input(rng, e, g, 2048, 1.0).sum(axis=1)
                  for _ in range(layers)]), jnp.float32)
    per_layer = jax.jit(lambda ls: jnp.stack(
        [solve_replica_loads_batched(ls[i], dev, g, sweeps=12).x
         for i in range(layers)]))
    all_at_once = jax.jit(lambda ls: solve_replica_loads_batched(
        ls, dev, g, sweeps=12).x)
    t_seq = time_it(lambda: jax.block_until_ready(per_layer(loads_l)),
                    iters=iters)
    t_vmap = time_it(lambda: jax.block_until_ready(all_at_once(loads_l)),
                     iters=iters)
    emit("hotpath_solver_layers", layers=layers, devices=g, experts=e,
         per_layer_us=round(t_seq * 1e6, 1),
         vmapped_us=round(t_vmap * 1e6, 1))
    rows_out.append({"bench": "solver_layers", "layers": layers,
                     "devices": g, "experts": e,
                     "per_layer_us": round(t_seq * 1e6, 1),
                     "vmapped_us": round(t_vmap * 1e6, 1)})
    return reductions


def bench_dispatch(rows_out, smoke: bool, seed: int = 1):
    """dense-scatter vs packed-gather through dispatch + combine (G=1
    degenerate group isolates the buffer movement from collectives)."""
    rng = np.random.default_rng(seed)
    e, top_k = 16, 2
    t, h = (512, 64) if smoke else (4096, 256)
    iters = 5 if smoke else 20
    eng = MicroEPEngine.build(e, (1, 1), placement="vanilla")
    spec = eng.moe_spec(t, top_k, group_axes=(), capacity_factor=2.0,
                        bm=128, kernel_impl="ref")
    st = spec.statics
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (t, h), jnp.float32)
    w_router = jax.random.normal(jax.random.fold_in(key, 1), (h, e)) * 0.1
    r = top_k_gating(x, w_router, top_k)
    ex = r.expert_ids.reshape(-1)
    rows = jnp.repeat(x, top_k, axis=0)
    cnt = jnp.zeros(e + 1, jnp.int32).at[ex].add(1)[:e]
    sched = spec.scheduler(cnt[:, None])
    plan = D.make_plan(st, ex, sched.flow, jnp.zeros((), jnp.int32))

    row = {"bench": "dispatch", "tokens": t, "hidden": h, "experts": e}
    for mode in ("scatter", "packed"):
        fn = jax.jit(lambda rws, mode=mode: D.combine(
            st, plan, D.dispatch(st, plan, rws, (), mode=mode), (),
            mode=mode))
        tm = time_it(lambda: jax.block_until_ready(fn(rows)), iters=iters)
        row[f"{mode}_us"] = round(tm * 1e6, 1)
        emit("hotpath_dispatch", mode=mode, tokens=t, hidden=h,
             us=round(tm * 1e6, 1))
    row["speedup"] = round(row["scatter_us"] / row["packed_us"], 3)
    rows_out.append(row)


_PIPELINE_SCRIPT = r"""
import json, sys
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.engine import MicroEPEngine
from repro.launch.mesh import make_local_mesh
from repro.moe.experts import init_canonical_experts, ExpertParams
from repro.moe.layer import moe_ffn
from benchmarks.common import time_it

smoke = sys.argv[1] == "1"
rows_, cols_ = (1, 2) if smoke else (2, 4)
E, TOP_K = (8, 2)
T_LOC, H, F = (64, 32, 48) if smoke else (256, 128, 256)
iters = 3 if smoke else 10
g = rows_ * cols_
mesh = make_local_mesh(rows_, cols_)
eng = MicroEPEngine.build(E, (rows_, cols_), placement="latin")
key = jax.random.PRNGKey(0)
ks = jax.random.split(key, 3)
x = jax.random.normal(ks[0], (g * T_LOC, H), jnp.float32) * 0.5
w_router = jax.random.normal(ks[1], (H, E)) * 0.1
canon = init_canonical_experts(ks[2], E, H, F)
table = eng.placement.table
work = ExpertParams(w_gate=canon.w_gate[table], w_up=canon.w_up[table],
                    w_down=canon.w_down[table])

out_rows = []
stage_list = sorted({1, 2, g})
for stages in stage_list:
    spec = eng.moe_spec(T_LOC, TOP_K, activation="swiglu",
                        group_axes=("data", "model"), capacity_factor=4.0,
                        bm=8, kernel_impl="ref", pipeline_stages=stages)

    def inner(wr, exp, x_loc):
        exp_loc = jax.tree_util.tree_map(lambda w: w[0, 0], exp)
        out, _, _ = moe_ffn(spec, x_loc, wr, exp_loc)
        return out

    fn = jax.jit(shard_map(
        inner, mesh=mesh,
        in_specs=(P(), P("data", "model"), P(("data", "model"))),
        out_specs=P(("data", "model")), check_vma=False))
    t = time_it(lambda: jax.block_until_ready(fn(w_router, work, x)),
                iters=iters, warmup=2)
    out_rows.append({"bench": "pipeline", "devices": g,
                     "tokens_per_device": T_LOC, "hidden": H,
                     "pipeline_stages": stages, "us": round(t * 1e6, 1)})
print("JSON:" + json.dumps(out_rows))
"""


def bench_pipeline_path(rows_out, smoke: bool):
    # the child runs a fake-device CPU mesh by design: pin it to the CPU
    # so it never reaches for an accelerator this process may hold
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    env.setdefault("PYTHONPATH", "src")
    r = subprocess.run(
        [sys.executable, "-c", _PIPELINE_SCRIPT, "1" if smoke else "0"],
        env=env, capture_output=True, text=True, timeout=1200)
    if r.returncode != 0:
        raise RuntimeError(
            f"pipeline bench subprocess failed:\n{r.stdout}\n{r.stderr}")
    payload = [ln for ln in r.stdout.splitlines() if ln.startswith("JSON:")]
    rows = json.loads(payload[0][len("JSON:"):])
    for row in rows:
        emit("hotpath_pipeline", devices=row["devices"],
             stages=row["pipeline_stages"], us=row["us"],
             tokens_per_device=row["tokens_per_device"])
    rows_out.extend(rows)


def run(smoke: bool = False, out: str = "BENCH_hotpath.json",
        seed: int = 0):
    rows: list = []
    reductions = bench_solver(rows, smoke, seed)
    bench_dispatch(rows, smoke, seed + 1)
    bench_pipeline_path(rows, smoke)
    result = {
        "bench": "hotpath",
        "smoke": smoke,
        "rows": rows,
        "solver_speedups": reductions,
    }
    if out:
        with open(out, "w") as f:
            json.dump(result, f, indent=1, sort_keys=True)
        print(f"wrote {out}")
    # the acceptance gate: the batched solver must measure faster than the
    # scan solver (geometric mean across configs, robust to one noisy row).
    # Smoke mode only records — 2 tiny configs x 5 iters on a shared CI
    # runner is too noisy to gate on.
    gmean = float(np.exp(np.mean(np.log(reductions))))
    emit("hotpath_summary", solver_speedup_gmean=round(gmean, 3))
    if not smoke:
        assert gmean > 1.0, \
            f"batched solver should beat the scan solver, gmean {gmean:.3f}x"
    return result


main = make_main(register_bench("hotpath", run))

if __name__ == "__main__":
    raise SystemExit(main())
