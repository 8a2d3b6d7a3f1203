"""Pipelined MoE hot path (DESIGN.md §2).

Three equivalence families, all hard gates for perf-path refactors:

  * batched-Jacobi LP solver == Gauss-Seidel scan solver (same max device
    load within tolerance, exact feasibility after integer rounding);
  * packed-gather dispatch/combine == legacy dense-scatter buffers
    (bit-identical flat buffer and round-trip);
  * destination-chunked pipelined moe_ffn == monolithic moe_ffn,
    bit-identical, across pipeline_stages in {1, 2, G}, G in {1, 2, 4}
    on a shard_map CPU mesh (subprocess — device count is per-process),
    for both chunk collectives (ppermute and a2a).
"""
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.lp import replica_devices, solve_lpp1
from repro.core.placement import latin_placement, random_placement
from repro.core.rounding import round_replica_loads
from repro.core.solver_jax import (device_loads, solve_replica_loads,
                                   solve_replica_loads_batched)
from repro.engine import MicroEPEngine, SchedulePolicy
from repro.moe import dispatch as D
from repro.moe.experts import init_canonical_experts
from repro.moe.layer import moe_ffn
from repro.moe.router import top_k_gating

ENV = dict(os.environ,
           XLA_FLAGS="--xla_force_host_platform_device_count=4",
           PYTHONPATH=os.path.join(os.path.dirname(__file__), "..", "src"))


# ------------------------------------------------------ solver equivalence

@pytest.mark.parametrize("rows,cols,k,seed", [
    (2, 4, 2, 0), (4, 4, 2, 1), (2, 8, 4, 2), (8, 8, 1, 3), (4, 2, 8, 4),
])
def test_batched_jacobi_matches_gauss_seidel(rows, cols, k, seed):
    rng = np.random.default_rng(seed)
    e = cols * k
    p = random_placement(rows, cols, e, seed=seed)
    dev = replica_devices(p)
    devj = jnp.asarray(dev, jnp.int32)
    loads = rng.integers(0, 200, size=e).astype(np.float64)
    loads_j = jnp.asarray(loads, jnp.float32)

    gs = solve_replica_loads(loads_j, devj, p.num_devices, sweeps=30)
    jb = solve_replica_loads_batched(loads_j, devj, p.num_devices, sweeps=30)

    gs_max = float(device_loads(gs.x, devj, p.num_devices).max())
    jb_max = float(device_loads(jb.x, devj, p.num_devices).max())
    oracle = solve_lpp1(loads, dev, p.num_devices).max_load
    # same quality band: both within 2% + 1 token of the LP optimum, and
    # of each other
    assert jb_max <= oracle * 1.02 + 1.0
    assert abs(jb_max - gs_max) <= 0.02 * max(gs_max, 1.0) + 1.0
    # fractional feasibility (float-tight)
    np.testing.assert_allclose(np.asarray(jb.x.sum(-1)), loads,
                               rtol=1e-5, atol=1e-3)
    assert float(jb.x.min()) >= -1e-5
    # padding replicas carry nothing
    assert np.all(np.asarray(jb.x)[dev < 0] == 0)
    # integer rounding restores exact conservation, as the scheduler uses it
    x_int = round_replica_loads(jb.x, jnp.asarray(loads, jnp.int32),
                                devj >= 0)
    np.testing.assert_array_equal(np.asarray(x_int).sum(-1),
                                  loads.astype(np.int64))


def test_batched_solver_leading_batch_dims():
    """[L, E] loads (all decoder MoE layers at once) == L separate solves."""
    rng = np.random.default_rng(7)
    p = latin_placement(2, 4, 16)
    dev = jnp.asarray(replica_devices(p), jnp.int32)
    loads = jnp.asarray(rng.integers(0, 100, size=(5, 16)), jnp.float32)
    batched = solve_replica_loads_batched(loads, dev, p.num_devices,
                                          sweeps=12)
    assert batched.x.shape == (5, 16, dev.shape[1])
    for i in range(5):
        single = solve_replica_loads_batched(loads[i], dev, p.num_devices,
                                             sweeps=12)
        np.testing.assert_allclose(np.asarray(batched.x[i]),
                                   np.asarray(single.x), rtol=1e-6,
                                   atol=1e-5)


def test_batched_solver_warm_start_feasible():
    rng = np.random.default_rng(8)
    p = random_placement(4, 4, 8, seed=8)
    dev = jnp.asarray(replica_devices(p), jnp.int32)
    loads = jnp.asarray(rng.integers(1, 100, size=8), jnp.float32)
    base = solve_replica_loads_batched(loads, dev, p.num_devices, sweeps=20)
    loads2 = loads * 1.1
    warm = solve_replica_loads_batched(loads2, dev, p.num_devices,
                                       x_init=base.x, sweeps=2)
    np.testing.assert_allclose(np.asarray(warm.x.sum(-1)),
                               np.asarray(loads2), rtol=1e-5, atol=1e-3)


def test_scheduler_solver_mode_batched_schedules():
    """solver_mode='batched' through the engine: token conservation holds
    and the schedule's balance stays in the scan solver's band."""
    rng = np.random.default_rng(9)
    out = {}
    for mode in ("scan", "batched"):
        eng = MicroEPEngine.build(
            16, (2, 4), placement="latin",
            policy=SchedulePolicy(mode="microep", sweeps=8,
                                  solver_mode=mode))
        input_eg = jnp.asarray(rng.integers(0, 40, size=(16, 8)), jnp.int32)
        s = eng.schedule(input_eg)
        np.testing.assert_array_equal(
            np.asarray(s.flow).sum(axis=2), np.asarray(input_eg))
        out[mode] = float(s.balance)
        rng = np.random.default_rng(9)   # same draw for both modes
    assert out["batched"] <= out["scan"] * 1.05 + 0.05


def test_solver_mode_validated():
    with pytest.raises(Exception, match="solver_mode"):
        SchedulePolicy(solver_mode="nope")


def test_planner_jacobi_warm_start():
    """ReplacementPlanner.warm_start_x(solver='jacobi'): in-graph batched
    prewarm — same quality band as the HiGHS oracle, and a [L, E] batch
    solves all layers in one pass."""
    from repro.telemetry.planner import ReplacementPlanner
    p = latin_placement(2, 4, 16)
    pl = ReplacementPlanner(p)
    rng = np.random.default_rng(11)
    loads = rng.integers(1, 100, size=16).astype(np.float64)
    x_lp = pl.warm_start_x(loads)
    x_j = pl.warm_start_x(loads, solver="jacobi")
    assert x_j.shape == x_lp.shape
    np.testing.assert_allclose(x_j.sum(-1), loads, rtol=1e-5, atol=1e-3)
    dev = jnp.asarray(replica_devices(p), jnp.int32)
    mx_lp = float(device_loads(jnp.asarray(x_lp), dev, p.num_devices).max())
    mx_j = float(device_loads(jnp.asarray(x_j), dev, p.num_devices).max())
    assert mx_j <= mx_lp * 1.02 + 1.0
    loads_le = rng.integers(1, 100, size=(3, 16)).astype(np.float64)
    x_le = pl.warm_start_x(loads_le, solver="jacobi")
    assert x_le.shape == (3,) + x_lp.shape
    # the lp path accepts the same batch (one exact solve per row)
    x_le_lp = pl.warm_start_x(loads_le, solver="lp")
    assert x_le_lp.shape == x_le.shape
    np.testing.assert_allclose(x_le_lp.sum(-1), loads_le, rtol=1e-5,
                               atol=1e-3)
    with pytest.raises(ValueError, match="solver"):
        pl.warm_start_x(loads, solver="nope")


# ------------------------------------------- packed vs scatter (G=1 group)

def _local_setup(key, e=4, top_k=2, t=48, h=16, f=24):
    eng = MicroEPEngine.build(e, (1, 1), placement="vanilla")
    spec = eng.moe_spec(t, top_k, activation="swiglu", group_axes=(),
                        capacity_factor=2.0, bm=8, kernel_impl="ref")
    ks = jax.random.split(key, 3)
    x = jax.random.normal(ks[0], (t, h), jnp.float32) * 0.5
    w_router = jax.random.normal(ks[1], (h, e)) * 0.1
    experts = init_canonical_experts(ks[2], e, h, f)
    return eng, spec, x, w_router, experts


def test_packed_dispatch_bitwise_matches_scatter():
    key = jax.random.PRNGKey(3)
    e, top_k = 4, 2
    eng, spec, x, w_router, experts = _local_setup(key, e=e, top_k=top_k)
    st = spec.statics
    r = top_k_gating(x, w_router, top_k)
    ex = r.expert_ids.reshape(-1)
    rows = jnp.repeat(x, top_k, axis=0)
    cnt = jnp.zeros(e + 1, jnp.int32).at[ex].add(1)[:e]
    sched = spec.scheduler(cnt[:, None])
    plan = D.make_plan(st, ex, sched.flow, jnp.zeros((), jnp.int32))

    flat_scatter = D.dispatch(st, plan, rows, (), mode="scatter")
    flat_packed = D.dispatch(st, plan, rows, (), mode="packed")
    np.testing.assert_array_equal(np.asarray(flat_packed),
                                  np.asarray(flat_scatter))

    back_scatter = D.combine(st, plan, flat_scatter, (), mode="scatter")
    back_packed = D.combine(st, plan, flat_packed, (), mode="packed")
    np.testing.assert_array_equal(np.asarray(back_packed),
                                  np.asarray(back_scatter))
    # round trip still the identity on dispatched rows
    np.testing.assert_allclose(np.asarray(back_packed), np.asarray(rows),
                               rtol=1e-6, atol=1e-6)


def test_moe_ffn_dispatch_modes_agree():
    key = jax.random.PRNGKey(4)
    _, spec, x, w_router, experts = _local_setup(key)
    out_p, _, _ = moe_ffn(spec, x, w_router, experts)
    out_s, _, _ = moe_ffn(spec._replace(dispatch_mode="scatter"),
                          x, w_router, experts)
    np.testing.assert_array_equal(np.asarray(out_p), np.asarray(out_s))


def test_moe_ffn_packed_differentiable():
    key = jax.random.PRNGKey(5)
    _, spec, x, w_router, experts = _local_setup(key, t=32)

    def loss(x, experts):
        out, _, _ = moe_ffn(spec, x, w_router, experts)
        return jnp.sum(out ** 2)

    gx, ge = jax.grad(loss, argnums=(0, 1))(x, experts)
    assert jnp.isfinite(gx).all()
    assert all(jnp.isfinite(g).all() for g in jax.tree_util.tree_leaves(ge))
    assert float(jnp.abs(gx).sum()) > 0


def test_effective_stages_divisor_fallback():
    assert D.effective_stages(1, 8) == 1
    assert D.effective_stages(2, 8) == 2
    assert D.effective_stages(3, 8) == 2    # largest divisor below
    assert D.effective_stages(8, 8) == 8
    assert D.effective_stages(16, 8) == 8   # clamped to the group
    assert D.effective_stages(2, 1) == 1    # single device: no pipeline
    assert D.effective_stages(5, 6) == 3


def test_chunk_caps_accounting():
    """Pipelined buffer = monolithic + (n-1)*S*bm alignment slack, before
    per-chunk rounding (DESIGN.md §2 buffer accounting)."""
    eng = MicroEPEngine.build(8, (2, 2), placement="latin")
    st = eng.dispatch_statics(64, 2, 4.0, 8)
    mono = D.flat_buffer_size(st)
    for n in (1, 2, 4):
        caps = D.chunk_caps(st, n)
        assert len(caps) == n
        assert all(c % st.bm == 0 for c in caps)
        total = sum(caps)
        # within one bm round-up per chunk of the monolithic size + slack
        assert total <= mono + (n - 1) * st.num_slots * st.bm + n * st.bm
        assert total >= (st.group_size - 1) * st.cap + st.c_in


# ------------------------- flat buffer sized to the rows a device receives

_GRIDS = {1: (1, 1), 2: (1, 2), 4: (1, 4)}


@pytest.mark.parametrize("g", [1, 2, 4])
def test_flat_buffer_size_is_receive_bound(g):
    """(G-1)·cap remote rows + C_in own rows + S·bm slack, rounded to bm;
    the monolithic buffer is the one-stage pipelined layout."""
    eng = MicroEPEngine.build(8, _GRIDS[g], placement="latin")
    st = eng.dispatch_statics(48, 2, 2.0, 8)
    raw = (g - 1) * st.cap + st.c_in + st.num_slots * st.bm
    assert D.flat_buffer_size(st) == -(-raw // st.bm) * st.bm
    assert D.chunk_caps(st, 1) == (D.flat_buffer_size(st),)


def _vanilla_statics(g):
    """One replica per expert on a (1, G) grid, C_in = 32, cap = 32/G."""
    eng = MicroEPEngine.build(2 * g, (1, g), placement="vanilla")
    return eng.dispatch_statics(16, 2, 1.0, 8)


def _vanilla_exchange(st, sent_to_0):
    """G devices simulated by ``vmap`` over a named axis: device 0 keeps
    its C_in rows local, every other device sends ``sent_to_0`` rows to
    device 0 (one replica per expert, so the flow is the counts) and keeps
    the rest.  Returns plans, flat buffers, the round trip and the round
    trip of the two-stage pipelined path."""
    g = st.group_size
    hosted = st.exp_of_dev_slot                       # [G, S]
    ex = np.stack([np.resize(hosted[0], st.c_in)] + [
        np.concatenate([np.resize(hosted[0], sent_to_0),
                        np.resize(hosted[d], st.c_in - sent_to_0)])
        for d in range(1, g)]).astype(np.int32)       # [G, C_in]
    flow = np.zeros((st.num_experts, g, 1), np.int32)
    for d in range(g):
        np.add.at(flow[:, d, 0], ex[d], 1)
    rows = (jnp.arange(g * st.c_in, dtype=jnp.float32) + 1.0).reshape(
        g, st.c_in, 1) * jnp.ones((1, 1, 4))

    def device(ex_d, rows_d):
        me = jax.lax.axis_index("g").astype(jnp.int32)
        plan = D.make_plan(st, ex_d, jnp.asarray(flow), me)
        flat = D.dispatch(st, plan, rows_d, ("g",))
        cplan = D.make_chunked_plan(st, ex_d, jnp.asarray(flow), me, 2)
        chunks = D.dispatch_pipelined(st, cplan, rows_d, ("g",), me)
        return (plan, flat, D.combine(st, plan, flat, ("g",)),
                D.combine_pipelined(st, cplan, chunks, ("g",), me))

    plan, flat, back, back2 = jax.vmap(device, axis_name="g")(
        jnp.asarray(ex), rows)
    return (plan, np.asarray(flat), np.asarray(back), np.asarray(back2),
            np.asarray(rows))


@pytest.mark.parametrize("g,over", [(2, 0), (2, 5), (4, 0), (4, 3),
                                    (4, None)])
def test_full_receive_fits_flat_buffer(g, over):
    """Device 0 receives a full ``cap`` from every other source (``over``
    more rows sent beyond it; None = every row of every source) plus all
    C_in of its own.  Overflow comes only from sender clipping, every
    dispatched row lands in the flat buffer, none in the trash slot, and
    the round trip, monolithic and pipelined, returns each of them."""
    st = _vanilla_statics(g)
    cap, n_flat = st.cap, D.flat_buffer_size(st)
    sent_to_0 = cap + over if over is not None else st.c_in
    plan, flat, back, back2, rows = _vanilla_exchange(st, sent_to_0)
    assert np.asarray(plan.overflow).sum() == (g - 1) * (sent_to_0 - cap)
    assert int(plan.overflow[0]) == 0
    assert int(jnp.sum(plan.local_pos[0] < n_flat)) == st.c_in
    assert int(jnp.sum(plan.flat_pos[0] < n_flat)) == (g - 1) * cap
    assert int(plan.group_end[0].max()) <= n_flat
    valid = np.asarray(plan.valid)
    np.testing.assert_array_equal(back, np.where(valid[..., None], rows, 0))
    np.testing.assert_array_equal(back2, back)
    # device 0's buffer holds exactly the dispatched rows sent to it
    to_0 = np.zeros_like(valid)
    to_0[0] = True
    to_0[1:, :sent_to_0] = True
    want = np.sort(rows[valid & to_0][:, 0])
    got = np.sort(flat[0][flat[0, :, 0] != 0][:, 0])
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("g", [1, 2, 4])
def test_flat_fill_counter(g):
    """``flat_fill`` (routed rows over flat rows) lies in (0, 1]; on a
    one-device group without overflow it is C_in / N_flat."""
    e, t, k, h = 8, 24, 2, 16
    eng = MicroEPEngine.build(e, _GRIDS[g], placement="latin")
    spec = eng.moe_spec(t, k, activation="swiglu", group_axes=("g",),
                        capacity_factor=2.0, bm=8, kernel_impl="ref")
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    x = jax.random.normal(ks[0], (g, t, h), jnp.float32) * 0.5
    w_router = jax.random.normal(ks[1], (h, e)) * 0.1
    canon = init_canonical_experts(ks[2], e, h, 24)
    table = jnp.asarray(eng.placement.flat())         # [G, S]
    work = jax.tree_util.tree_map(lambda w: w[table], canon)

    def device(x_d, exp_d):
        _, m, _ = moe_ffn(spec, x_d, w_router, exp_d)
        return m

    m = jax.vmap(device, axis_name="g")(x, work)
    fill = np.asarray(m.flat_fill)
    assert ((fill > 0) & (fill <= 1)).all(), fill
    if g == 1:
        assert int(m.overflow[0]) == 0
        assert float(fill[0]) == np.float32(
            spec.statics.c_in / D.flat_buffer_size(spec.statics))


def test_one_device_group_has_no_exchange():
    """A 1x1 mesh with group_axes=("data", "model") computes what the
    group_axes=() path computes, bit for bit, forward and backward, and
    its step holds no all-to-all and no H-wide send or receive buffer
    (G·cap rows)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_local_mesh

    _, spec0, x, w_router, experts = _local_setup(jax.random.PRNGKey(11))
    spec1 = spec0._replace(group_axes=("data", "model"))
    mesh = make_local_mesh(1, 1)

    def loss_local(x, experts):
        out, _, _ = moe_ffn(spec0, x, w_router, experts)
        return jnp.sum(out ** 2), out

    def loss_mesh(x, experts):
        def inner(x_loc, exp_loc):
            out, _, _ = moe_ffn(spec1, x_loc, w_router, exp_loc)
            return out
        out = shard_map(inner, mesh=mesh,
                        in_specs=(P(("data", "model")), P()),
                        out_specs=P(("data", "model")),
                        check_vma=False)(x, experts)
        return jnp.sum(out ** 2), out

    grad = lambda f: jax.jit(jax.grad(f, argnums=(0, 1), has_aux=True))
    (gx0, ge0), out0 = grad(loss_local)(x, experts)
    (gx1, ge1), out1 = grad(loss_mesh)(x, experts)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out0))
    np.testing.assert_array_equal(np.asarray(gx1), np.asarray(gx0))
    for a, b in zip(jax.tree_util.tree_leaves(ge1),
                    jax.tree_util.tree_leaves(ge0)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    lowered = grad(loss_mesh).lower(x, experts)
    compiled = lowered.compile().as_text()
    assert "all_to_all" not in lowered.as_text()
    assert "all-to-all" not in compiled
    st = spec1.statics
    assert not re.search(rf"f32\[(1,)?{st.cap},{x.shape[1]}\]", compiled)


# -------------------------------- pipelined == monolithic on shard_map mesh

_MESH_SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.engine import MicroEPEngine
from repro.launch.mesh import make_local_mesh
from repro.moe.experts import init_canonical_experts, ExpertParams
from repro.moe.layer import moe_ffn

E, TOP_K, T_LOC, H, F = 8, 2, 32, 16, 24
key = jax.random.PRNGKey(0)

for rows, cols in [(1, 1), (1, 2), (2, 2)]:
    g = rows * cols
    mesh = make_local_mesh(rows, cols)
    eng = MicroEPEngine.build(E, (rows, cols), placement="latin")
    ks = jax.random.split(jax.random.fold_in(key, g), 3)
    x = jax.random.normal(ks[0], (g * T_LOC, H), jnp.float32) * 0.5
    w_router = jax.random.normal(ks[1], (H, E)) * 0.1
    canon = init_canonical_experts(ks[2], E, H, F)
    table = eng.placement.table                      # [rows, cols, S]
    work = ExpertParams(w_gate=canon.w_gate[table], w_up=canon.w_up[table],
                        w_down=canon.w_down[table])

    def run(stages, comm="ppermute", mode="packed"):
        spec = eng.moe_spec(T_LOC, TOP_K, activation="swiglu",
                            group_axes=("data", "model"),
                            capacity_factor=4.0, bm=8, kernel_impl="ref",
                            pipeline_stages=stages, dispatch_mode=mode,
                            chunk_comm=comm)

        def inner(wr, exp, x_loc):
            exp_loc = jax.tree_util.tree_map(lambda w: w[0, 0], exp)
            out, metrics, _ = moe_ffn(spec, x_loc, wr, exp_loc)
            return out, metrics.overflow[None]

        out, ovf = shard_map(
            inner, mesh=mesh,
            in_specs=(P(), P("data", "model"), P(("data", "model"))),
            out_specs=(P(("data", "model")), P(("data", "model"))),
            check_vma=False)(w_router, work, x)
        return np.asarray(out), np.asarray(ovf)

    base, ovf = run(1, mode="scatter")
    assert (ovf == 0).all(), ("overflow in base", g, ovf)
    packed, _ = run(1, mode="packed")
    np.testing.assert_array_equal(packed, base)
    stage_set = sorted({1, 2, g} & set(range(1, g + 1)) | {2})
    for stages in stage_set:
        for comm in ("ppermute", "a2a"):
            out, ovf2 = run(stages, comm=comm)
            assert (ovf2 == 0).all(), ("overflow", g, stages, comm)
            np.testing.assert_array_equal(
                out, base, err_msg=f"G={g} stages={stages} comm={comm}")
    print(f"G={g} ok: stages {stage_set} x (ppermute, a2a) bit-identical")
print("OK")
"""


def test_pipelined_bit_identical_on_mesh():
    """pipeline_stages in {1, 2, G} x chunk_comm in {ppermute, a2a} on
    G in {1, 2, 4} CPU meshes — all bit-identical to the monolithic path,
    and packed == scatter under the real all_to_all."""
    r = subprocess.run([sys.executable, "-c", _MESH_SCRIPT], env=ENV,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "OK" in r.stdout
