"""Heterogeneity-aware scheduling (DESIGN.md §11).

Four families:

  * weighted in-graph solvers (Gauss-Seidel scan + damped Jacobi) match
    the weighted HiGHS oracle, and the weighted Eq. 3 density identity
    holds;
  * budget-respecting placements never exceed per-device slot budgets,
    and the budget-feasibility reduction (weighted LP <= 1) is exact;
  * `DeviceProfile` config surface: parsing, round-trips, validation,
    canonicalization of uniform profiles;
  * uniform-profile runs are bit-identical to no-profile runs across the
    PR-4 pipeline matrix (pipeline_stages × dispatch_mode × solver_mode)
    on a shard_map CPU mesh, and weighted/budgeted engines run the same
    matrix end-to-end (subprocess — device count is per-process).
"""
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.lp import budget_feasible, replica_devices, solve_lpp1
from repro.core.placement import (asymmetric_placement, latin_placement,
                                  max_induced_density, random_placement)
from repro.core.replacement import ReplacementConfig, ReplacementManager
from repro.core.rounding import round_replica_loads
from repro.core.solver_jax import (device_loads, solve_replica_loads,
                                   solve_replica_loads_batched, water_fill)
from repro.engine import (ConfigError, DeviceProfile, MicroEPEngine,
                          PlacementSpec, RuntimeConfig, SchedulePolicy,
                          profile_slot_budgets, profile_weights)
from repro.telemetry.planner import ReplacementPlanner, lp_balance_ratio

ENV = dict(os.environ,
           XLA_FLAGS="--xla_force_host_platform_device_count=4",
           PYTHONPATH=os.path.join(os.path.dirname(__file__), "..", "src"))


def _weights(rng, g):
    w = rng.choice([1.0, 2.0, 4.0], size=g)
    if np.all(w == w[0]):
        w[0] *= 2.0
    return w / w.mean()


# --------------------------------------------------- weighted solvers


@pytest.mark.parametrize("rows,cols,k,seed", [
    (2, 4, 2, 0), (4, 4, 2, 1), (2, 8, 4, 2), (4, 2, 8, 4),
])
def test_weighted_solvers_match_weighted_oracle(rows, cols, k, seed):
    rng = np.random.default_rng(seed)
    e = cols * k
    p = random_placement(rows, cols, e, seed=seed)
    g = p.num_devices
    dev = replica_devices(p)
    devj = jnp.asarray(dev, jnp.int32)
    loads = rng.integers(0, 200, size=e).astype(np.float64)
    w = _weights(rng, g)
    wj = jnp.asarray(w, jnp.float32)

    oracle = solve_lpp1(loads, dev, g, weights=w)
    gs = solve_replica_loads(jnp.asarray(loads, jnp.float32), devj, g,
                             sweeps=30, weights=wj)
    jb = solve_replica_loads_batched(jnp.asarray(loads, jnp.float32), devj,
                                     g, sweeps=80, weights=wj)
    for name, sol in (("scan", gs), ("batched", jb)):
        x = np.asarray(sol.x)
        # feasibility: conservation, positivity, padding
        np.testing.assert_allclose(x.sum(-1), loads, rtol=1e-5, atol=1e-2,
                                   err_msg=name)
        assert x.min() >= -1e-5
        assert np.all(x[dev < 0] == 0)
        # weighted makespan within 2% + 1 token of the weighted optimum
        dl = np.asarray(device_loads(sol.x, devj, g))
        mk = (dl / w).max()
        assert mk <= oracle.objective * 1.02 + 1.0, (name, mk, oracle)
        # integer rounding keeps exact conservation
        x_int = round_replica_loads(sol.x, jnp.asarray(loads, jnp.int32),
                                    devj >= 0)
        np.testing.assert_array_equal(np.asarray(x_int).sum(-1),
                                      loads.astype(np.int64))


def test_weighted_water_fill_kkt():
    """Weighted water-fill: active replicas equalize (b+x)/w, inactive sit
    above the water level; budget conserved."""
    rng = np.random.default_rng(3)
    for _ in range(20):
        r = int(rng.integers(2, 8))
        levels = jnp.asarray(rng.uniform(0, 100, r), jnp.float32)
        w = jnp.asarray(rng.uniform(0.5, 4.0, r), jnp.float32)
        valid = jnp.asarray(rng.uniform(size=r) < 0.8)
        if not bool(valid.any()):
            valid = valid.at[0].set(True)
        budget = float(rng.uniform(1, 500))
        alloc = water_fill(levels, jnp.float32(budget), valid, weights=w)
        a = np.asarray(alloc)
        assert a.min() >= -1e-4
        np.testing.assert_allclose(a.sum(), budget, rtol=1e-5, atol=1e-2)
        assert np.all(a[~np.asarray(valid)] == 0)
        t = (np.asarray(levels) + a) / np.asarray(w)
        active = (a > 1e-3) & np.asarray(valid)
        if active.any():
            top = t[active]
            assert top.max() - top.min() < 1e-2 * max(top.max(), 1.0)
            idle = (~active) & np.asarray(valid)
            if idle.any():
                t0 = np.asarray(levels) / np.asarray(w)
                assert t0[idle].min() >= top.max() - 1e-2 * max(top.max(), 1)


def test_weighted_density_equals_weighted_lp():
    """Weighted Eq. 3: LP optimum == max_S load(S) / w(S) (DESIGN.md §11)."""
    rng = np.random.default_rng(7)
    for seed in range(3):
        p = random_placement(2, 4, 16, seed=seed)
        dev = replica_devices(p)
        loads = rng.integers(0, 200, size=16).astype(np.float64)
        w = _weights(rng, p.num_devices)
        res = solve_lpp1(loads, dev, p.num_devices, weights=w)
        m = max_induced_density(p, loads, weights=w)
        np.testing.assert_allclose(res.objective, m, rtol=1e-6, atol=1e-6)


def test_uniform_weights_bit_identical_to_unweighted():
    """weights=ones through the solvers == the historic unweighted path
    (the scheduler canonicalizes uniform profiles to None, but explicit
    ones must agree too — same optimum, same feasibility)."""
    rng = np.random.default_rng(11)
    p = latin_placement(2, 4, 16)
    dev = jnp.asarray(replica_devices(p), jnp.int32)
    loads = jnp.asarray(rng.integers(0, 100, size=16), jnp.float32)
    base = solve_replica_loads(loads, dev, 8, sweeps=10)
    ones = solve_replica_loads(loads, dev, 8, sweeps=10,
                               weights=jnp.ones((8,), jnp.float32))
    np.testing.assert_allclose(np.asarray(base.x), np.asarray(ones.x),
                               rtol=1e-6, atol=1e-4)


def test_weighted_makespan_beats_uniform_on_skewed_mesh():
    """The acceptance property behind bench_hetero: on a 2:1 compute skew
    the weighted schedule has strictly lower weighted makespan."""
    rng = np.random.default_rng(5)
    e, g = 16, 8
    eng_u = MicroEPEngine.build(e, (2, 4), placement="latin")
    eng_w = MicroEPEngine.build(e, (2, 4), placement="latin",
                                device_profiles="2,2,2,2,1,1,1,1")
    w = np.asarray(eng_w.weights)
    dev = jnp.asarray(eng_w.statics.dev, jnp.int32)
    input_eg = jnp.asarray(rng.integers(0, 50, size=(e, g)), jnp.int32)
    s_u = eng_u.schedule(input_eg)
    s_w = eng_w.schedule(input_eg)
    dl_u = np.asarray(device_loads(s_u.x_int.astype(jnp.float32), dev, g))
    dl_w = np.asarray(device_loads(s_w.x_int.astype(jnp.float32), dev, g))
    assert (dl_w / w).max() < (dl_u / w).max()
    # both conserve every expert's tokens
    np.testing.assert_array_equal(np.asarray(s_w.flow).sum(axis=2),
                                  np.asarray(input_eg))
    # the oracle through the engine solves the weighted LP
    x_opt = eng_w.schedule_host(np.asarray(input_eg))
    dl_opt = np.asarray(device_loads(jnp.asarray(x_opt, jnp.float32),
                                     dev, g))
    assert (dl_w / w).max() <= (dl_opt / w).max() * 1.02 + float(
        eng_w.placement.slots) + 1.0


# ------------------------------------------------------ budgets


def test_budgeted_placement_respects_slots():
    rng = np.random.default_rng(0)
    loads = rng.zipf(1.4, size=16).astype(np.float64)
    budgets = np.asarray([6, 2, 4, 4, 2, 2, 6, 6])
    p = asymmetric_placement(2, 4, 16, loads, seed=0, num_samples=16,
                             slot_budgets=budgets)
    assert (p.slots_per_device() == budgets).all()
    assert (p.replica_count() >= 1).all()
    assert p.slots == budgets.max()
    # empty slots exist and are inert: replica_devices skips them
    assert (p.table == -1).any()
    dev = replica_devices(p)
    assert dev.max() < p.num_devices
    counts = p.replica_count()
    assert (np.sort(dev, axis=1) >= -1).all()
    assert ((dev >= 0).sum(axis=1) == counts).all()


def test_budget_feasibility_reduction():
    rng = np.random.default_rng(1)
    p = latin_placement(2, 4, 16)
    dev = replica_devices(p)
    loads = rng.integers(1, 100, size=16).astype(np.float64)
    total = loads.sum()
    ok, util = budget_feasible(loads, dev, 8, np.full(8, total / 4))
    assert ok and util <= 1.0 + 1e-6
    # exactly at the ideal: still feasible (latin placement schedules
    # perfectly only if the LP optimum equals the mean — use a margin)
    bad, util_bad = budget_feasible(loads, dev, 8, np.full(8, total / 64))
    assert not bad and util_bad > 1.0
    # skewed budgets: tight on half the fleet
    b = np.asarray([total] * 4 + [total / 64] * 4)
    ok_s, util_s = budget_feasible(loads, dev, 8, b)
    assert util_s > 0


def test_engine_validates_budgets_and_length():
    with pytest.raises(ConfigError, match="entries"):
        MicroEPEngine.build(16, (2, 4), placement="latin",
                            device_profiles="2,1")
    # latin needs k=4 slots everywhere; a budget of 1 cannot hold it
    with pytest.raises(ConfigError, match="budget"):
        MicroEPEngine.build(16, (2, 4), placement="latin",
                            device_profiles="1@1,1,1,1,1,1,1,1")


def test_replacement_manager_regenerates_under_budgets():
    rng = np.random.default_rng(2)
    budgets = np.asarray([6, 2, 4, 4, 2, 2, 6, 6])
    w = _weights(rng, 8)
    loads0 = rng.zipf(1.4, size=16).astype(np.float64)
    p0 = asymmetric_placement(2, 4, 16, loads0, seed=1, num_samples=16,
                              slot_budgets=budgets, weights=w)
    mgr = ReplacementManager(
        p0, ReplacementConfig(check_every=4, threshold=1.05, seed=3),
        weights=w, slot_budgets=budgets)
    fired = False
    for step in range(32):
        skew = np.zeros(16)
        skew[(step // 8) % 16] = 1000.0      # hard regime shifts
        skew += rng.uniform(0, 5, size=16)
        fired |= mgr.observe(skew)
    assert fired, "expected at least one regeneration"
    assert (mgr.placement.slots_per_device() <= budgets).all()
    assert (mgr.placement.replica_count() >= 1).all()


def test_planner_weighted_scoring_and_budgets():
    rng = np.random.default_rng(4)
    budgets = np.asarray([6, 2, 4, 4, 2, 2, 6, 6])
    w = _weights(rng, 8)
    loads0 = rng.zipf(1.4, size=16).astype(np.float64)
    p0 = asymmetric_placement(2, 4, 16, loads0, seed=1, num_samples=16,
                              slot_budgets=budgets, weights=w)
    pl = ReplacementPlanner(p0, predictor="last", check_every=4,
                            threshold=1.02, min_history=1, mc_samples=16,
                            weights=w, slot_budgets=budgets, seed=5)
    for step in range(24):
        skew = np.zeros(16)
        skew[(step // 6) % 16] = 1000.0
        skew += rng.uniform(0, 5, size=16)
        pl.observe(skew)
    assert pl.decisions, "planner never checked"
    assert (pl.placement.slots_per_device() <= budgets).all()
    # weighted warm start solves the weighted LP
    x = pl.warm_start_x(loads0)
    dev = replica_devices(pl.placement)
    dl = np.zeros(8)
    np.add.at(dl, dev[dev >= 0], x[dev >= 0])
    opt = solve_lpp1(loads0, dev, 8, weights=w).objective
    assert (dl / w).max() <= opt * 1.01 + 1e-6
    # the jacobi prewarm stays in the same band
    xj = pl.warm_start_x(loads0, solver="jacobi")
    dlj = np.zeros(8)
    np.add.at(dlj, dev[dev >= 0], xj[dev >= 0])
    assert (dlj / w).max() <= opt * 1.05 + 1.0
    # weighted balance ratio >= 1 and reduces to uniform when w is None
    assert lp_balance_ratio(pl.placement, loads0, weights=w) >= 1.0 - 1e-9


# ------------------------------------------------- config surface


def test_device_profile_parsing_and_round_trips():
    assert DeviceProfile.parse("2") == DeviceProfile(2.0, None)
    assert DeviceProfile.parse("1.5@4") == DeviceProfile(1.5, 4)
    assert DeviceProfile.parse_list("2@4, 1@2") == (
        DeviceProfile(2.0, 4), DeviceProfile(1.0, 2))
    with pytest.raises(ConfigError, match="weight"):
        DeviceProfile.parse("fast")
    with pytest.raises(ConfigError, match="slots"):
        DeviceProfile.parse("2@many")
    with pytest.raises(ConfigError, match="weight"):
        DeviceProfile(weight=0)
    with pytest.raises(ConfigError, match="slots"):
        DeviceProfile(slots=0)

    cfg = RuntimeConfig(device_profiles="2@4,1@2,1@2,1@2")
    assert cfg.device_profiles == (
        DeviceProfile(2.0, 4), DeviceProfile(1.0, 2),
        DeviceProfile(1.0, 2), DeviceProfile(1.0, 2))
    assert RuntimeConfig.from_dict(cfg.to_dict()) == cfg
    import argparse
    ap = argparse.ArgumentParser()
    RuntimeConfig.add_cli_args(ap)
    assert RuntimeConfig.from_cli_args(
        ap.parse_args(cfg.to_cli_args())) == cfg
    # legacy kwargs shim + numeric sequences
    assert RuntimeConfig.from_kwargs(
        device_profiles=[2, 1]).device_profiles == (
        DeviceProfile(2.0), DeviceProfile(1.0))
    # default stays None and round-trips
    assert RuntimeConfig().device_profiles is None
    assert RuntimeConfig.from_dict(
        RuntimeConfig().to_dict()).device_profiles is None


def test_profile_canonicalization():
    uniform = DeviceProfile.parse_list("3,3,3,3")
    assert profile_weights(uniform) is None
    assert profile_slot_budgets(uniform) is None
    skew = DeviceProfile.parse_list("2,1,1,2")
    w = profile_weights(skew)
    np.testing.assert_allclose(w.mean(), 1.0)
    assert profile_slot_budgets(skew) is None
    budg = DeviceProfile.parse_list("1@4,1@2,1,1")
    b = profile_slot_budgets(budg, default_slots=3)
    np.testing.assert_array_equal(b, [4, 2, 3, 3])
    # engine canonicalizes uniform profiles away entirely
    eng = MicroEPEngine.build(16, (2, 4), placement="latin",
                              device_profiles="1,1,1,1,1,1,1,1")
    assert eng.weights is None and eng.slot_budgets is None
    assert eng.statics.weights is None


# ----------------------- uniform bit-identity on the pipeline matrix


_MESH_SCRIPT = r"""
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.engine import MicroEPEngine, PlacementSpec, SchedulePolicy
from repro.launch.mesh import make_local_mesh
from repro.moe.experts import init_canonical_experts, ExpertParams
from repro.moe.layer import moe_ffn

E, TOP_K, T_LOC, H, F = 8, 2, 32, 16, 24
rows, cols = 2, 2
g = rows * cols
mesh = make_local_mesh(rows, cols)
key = jax.random.PRNGKey(0)
ks = jax.random.split(key, 3)
x = jax.random.normal(ks[0], (g * T_LOC, H), jnp.float32) * 0.5
w_router = jax.random.normal(ks[1], (H, E)) * 0.1
canon = init_canonical_experts(ks[2], E, H, F)


def run(eng, stages, comm="ppermute", mode="packed"):
    table = np.maximum(eng.placement.table, 0)
    work = ExpertParams(w_gate=canon.w_gate[table],
                        w_up=canon.w_up[table],
                        w_down=canon.w_down[table])
    spec = eng.moe_spec(T_LOC, TOP_K, activation="swiglu",
                        group_axes=("data", "model"), capacity_factor=4.0,
                        bm=8, kernel_impl="ref", pipeline_stages=stages,
                        dispatch_mode=mode, chunk_comm=comm)

    def inner(wr, exp, x_loc):
        exp_loc = jax.tree_util.tree_map(lambda w: w[0, 0], exp)
        out, metrics, _ = moe_ffn(spec, x_loc, wr, exp_loc)
        return out, metrics.overflow[None], metrics.balance[None]

    out, ovf, bal = shard_map(
        inner, mesh=mesh,
        in_specs=(P(), P("data", "model"), P(("data", "model"))),
        out_specs=(P(("data", "model")),) * 3,
        check_vma=False)(w_router, work, x)
    return np.asarray(out), np.asarray(ovf), np.asarray(bal)


# --- uniform profiles: bit-identical to no profiles across the matrix ---
# (pipeline_stages x dispatch_mode under solver_mode=scan; solver_mode=
# batched covered on a pipelined combo — each extra combo is a shard_map
# compile, so the matrix is spanned rather than exhausted)
MATRIX = {"scan": [(1, "ppermute", "packed"), (1, "ppermute", "scatter"),
                   (2, "ppermute", "packed"), (4, "a2a", "packed")],
          "batched": [(2, "ppermute", "packed")]}
for solver_mode, combos in MATRIX.items():
    pol = SchedulePolicy(mode="microep", sweeps=8, solver_mode=solver_mode)
    eng0 = MicroEPEngine.build(E, (rows, cols), placement="latin",
                               policy=pol)
    engU = MicroEPEngine.build(E, (rows, cols), placement="latin",
                               policy=pol,
                               device_profiles="1,1,1,1")
    for stages, comm, mode in combos:
        o0, v0, b0 = run(eng0, stages, comm, mode)
        oU, vU, bU = run(engU, stages, comm, mode)
        assert (v0 == 0).all() and (vU == 0).all()
        np.testing.assert_array_equal(
            oU, o0, err_msg=f"uniform != none: {solver_mode} {stages} "
                            f"{comm} {mode}")
        np.testing.assert_array_equal(bU, b0)
    print(f"uniform bit-identity ok: solver_mode={solver_mode}")

# --- weighted 2:1 profiles: pipelined == monolithic, no overflow ---------
pol = SchedulePolicy(mode="microep", sweeps=8)
engW = MicroEPEngine.build(E, (rows, cols), placement="latin",
                           policy=pol, device_profiles="2,1,2,1")
base, v, balW = run(engW, 1)
assert (v == 0).all()
assert np.isfinite(base).all() and np.abs(base).sum() > 0
out, v2, _ = run(engW, 2)
assert (v2 == 0).all()
np.testing.assert_array_equal(out, base, err_msg="weighted pipeline")
print("weighted matrix ok")

# --- budgeted placement with empty slots through the full layer ----------
loads = np.random.default_rng(0).zipf(1.4, size=E).astype(np.float64)
engB = MicroEPEngine.build(
    E, (rows, cols),
    placement=PlacementSpec("asymmetric", loads=tuple(loads)),
    device_profiles="2@4,1@2,2@4,1@2")
assert (engB.placement.slots_per_device() <= engB.slot_budgets).all()
assert (engB.placement.table == -1).any()
base, v, _ = run(engB, 1)
assert (v == 0).all()
out, v2, _ = run(engB, 2)
assert (v2 == 0).all()
np.testing.assert_array_equal(out, base)
print("budgeted placement ok")
print("OK")
"""


def test_hetero_pipeline_matrix_on_mesh():
    """Uniform profiles bit-identical to none, weighted and budgeted
    engines bit-stable across pipeline stages, on a 4-device CPU mesh."""
    r = subprocess.run([sys.executable, "-c", _MESH_SCRIPT], env=ENV,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "OK" in r.stdout
