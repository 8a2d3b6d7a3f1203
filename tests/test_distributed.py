"""Multi-device tests via subprocess (the main pytest process stays at one
CPU device; --xla_force_host_platform_device_count is per-process).

Each check is a standalone script executed with 8 fake devices on a
(data=2, model=4) mesh:
  * distributed train step == single-device reference (loss, grads)
  * MicroEP dispatch conservation under real all_to_all
  * EDP gradient sync (sync.py ppermute path) == table scatter-add
  * distributed flash-decode (seq-sharded KV) == single-device attention
"""
import os
import subprocess
import sys

import pytest

ENV = dict(os.environ,
           XLA_FLAGS="--xla_force_host_platform_device_count=8",
           PYTHONPATH=os.path.join(os.path.dirname(__file__), "..", "src"))


def run(script: str):
    r = subprocess.run([sys.executable, "-c", script], env=ENV,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


def test_distributed_step_matches_local():
    run("""
import jax, jax.numpy as jnp
from repro.configs import get_config
from repro.launch.mesh import make_local_mesh
from repro.launch import runtime as R
from repro.train.loop import TrainState, make_train_step
from repro.optim.adamw import adamw_init
from repro.data.synthetic import SyntheticLM
from repro.models import decoder as dec

assert len(jax.devices()) == 8
cfg = get_config("paper-gpt-32x1.3b").smoke()
mesh = make_local_mesh(2, 4)
# capacity_factor 4: at toy scale (16 tokens/device) the per-(src,dst)
# chunk is 8 rows at cf=2 and integer spikes overflow; production scales
# (thousands of tokens/device) keep cf=2 overflow-free (dry-run configs)
dr = R.build_runtime(cfg, mesh, dtype=jnp.float32, impl="ref", remat=False,
                     capacity_factor=4.0)
key = jax.random.PRNGKey(0)
master = dec.init_params(key, cfg, jnp.float32)
ts = TrainState(master=master, opt=adamw_init(master), solver=dr.init_solver(),
                step=jnp.zeros((), jnp.int32))
step = jax.jit(R.make_train_fn(dr, n_micro=2))
b = SyntheticLM(vocab=cfg.vocab, seq_len=32, batch=8, seed=1).batch_at(0)
ts2, m = step(ts, b)

ts_ref = TrainState(master=master, opt=adamw_init(master),
                    solver=dec.init_solver_states(cfg, 1),
                    step=jnp.zeros((), jnp.int32))
step_ref = jax.jit(make_train_step(cfg, n_micro=2))
ts_ref2, m_ref = step_ref(ts_ref, b)
dl = abs(float(m["loss"]) - float(m_ref["loss"]))
assert dl < 2e-4, (float(m["loss"]), float(m_ref["loss"]))
assert float(m["overflow"]) == 0.0, m
# optimizer moments match closely (pre-Adam-rescaling comparison)
import jax.tree_util as jtu
for a, b_ in zip(jtu.tree_leaves(ts2.opt.mu), jtu.tree_leaves(ts_ref2.opt.mu)):
    import numpy as np
    np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=2e-2, atol=2e-4)
print("OK")
""")


def test_vanilla_ep_baseline_runs_and_balances_worse():
    run("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.launch.mesh import make_local_mesh
from repro.launch import runtime as R
from repro.models import decoder as dec
from repro.moe.router import zipf_gating

cfg = get_config("paper-gpt-32x1.3b").smoke()
# 8 experts over 4 cols -> k=2 slots (intersecting EDP groups)
import dataclasses
cfg = dataclasses.replace(cfg, num_experts=8)
mesh = make_local_mesh(2, 4)
key = jax.random.PRNGKey(0)
bal = {}
for mode in ("microep", "vanilla"):
    strat = "latin" if mode == "microep" else "vanilla"
    dr = R.build_runtime(cfg, mesh, dtype=jnp.float32, impl="ref",
                         remat=False, mode=mode, placement_strategy=strat,
                         capacity_factor=4.0)
    master = dec.init_params(key, cfg, jnp.float32)
    params = dr.hooks.to_working(master)
    n = 512
    x = jax.random.normal(key, (n, cfg.d_model)) * 0.5
    # skewed synthetic routing (Zipf s=1.0)
    r = zipf_gating(jax.random.fold_in(key, 1), n, cfg.num_experts,
                    cfg.top_k, s=1.0)

    def apply(p_moe, x):
        # use the island directly with the synthetic router via monkeypatch
        out, metrics, _ = dr.rt.moe_apply(p_moe, x, None)
        return metrics

    # patch gating inside by binding router output: route via moe_apply's
    # own gate on a crafted input is hard - instead measure schedule balance
    # through the metrics of a real call (router at init is ~uniform), then
    # through the scheduler directly for the skewed load:
    sched = dr.engine.scheduler
    loads = np.asarray(jax.random.categorical(
        jax.random.fold_in(key, 2),
        jnp.log(jnp.arange(1, cfg.num_experts + 1.) ** -1.0)[None].repeat(n, 0)))
    cnt = np.zeros((cfg.num_experts, 8), np.int32)
    for i, e in enumerate(loads):
        cnt[e, i % 8] += 1
    out = sched(jnp.asarray(cnt))
    bal[mode] = float(out.balance)
print(bal)
assert bal["microep"] <= bal["vanilla"] + 1e-6
# 8 devices x 8 experts (k=2 slots) at Zipf s=1.0: MicroEP stays well
# below vanilla's ~2.28x.  The HiGHS LP optimum for this exact load draw
# is 1.539x (engine.schedule_host) — the in-graph solver + rounding land
# on 1.547x — so assert a band just above the true optimum.
assert bal["microep"] < 1.6
print("OK")
""")


def test_edp_grad_sync_ppermute_matches_scatter():
    """sync.py's explicit ppermute grad sync == scatter-add through the
    placement table (the GSPMD path used by the training loop)."""
    run("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.core.placement import latin_placement
from repro.moe.sync import (build_sync_plan, working_grads_to_canonical,
                            canonical_to_working)
from repro.launch.mesh import make_local_mesh

mesh = make_local_mesh(2, 4)
p = latin_placement(2, 4, 8)     # 8 experts over 2x4 devices, k=2 slots
plan = build_sync_plan(p)
k_c = plan.k_canonical
rng = np.random.default_rng(0)
g_work = rng.normal(size=(2, 4, p.slots, 3, 5)).astype(np.float32)

canon_ref = np.zeros((8, 3, 5), np.float32)
for d in range(2):
    for m in range(4):
        for s in range(p.slots):
            canon_ref[p.table[d, m, s]] += g_work[d, m, s]

send = jnp.asarray(plan.send_slot)[:, :, None]   # [n_match, G, 1]
recv = jnp.asarray(plan.recv_slot)[:, :, None]
own = jnp.asarray(plan.self_slot)[:, None, :]    # [G, 1, k]

def per_device(gw, send_slot, recv_slot, self_slot):
    canon = working_grads_to_canonical(
        plan, gw[0, 0], send_slot[:, 0, 0], recv_slot[:, 0, 0],
        self_slot[0, 0], ("data", "model"))
    canon = jax.lax.psum(canon, "data")          # finish the EDP reduce
    work = canonical_to_working(
        plan, canon, send_slot[:, 0, 0], recv_slot[:, 0, 0],
        self_slot[0, 0], ("data", "model"))
    return canon[None, None], work[None, None]

canon_out, work_out = shard_map(per_device, mesh=mesh,
    in_specs=(P("data", "model"), P(None, ("data", "model"), None),
              P(None, ("data", "model"), None),
              P(("data", "model"), None, None)),
    out_specs=(P("data", "model"), P("data", "model")),
    check_vma=False)(jnp.asarray(g_work), send, recv, own)

canon_out = np.asarray(canon_out)   # [D, M, k, 3, 5]
for d in range(2):
    for c in range(4):
        for j in range(k_c):
            np.testing.assert_allclose(canon_out[d, c, j],
                                       canon_ref[c * k_c + j],
                                       rtol=1e-5, atol=1e-5)
# redistribute (canonical -> working) lands each slot's expert params
work_out = np.asarray(work_out)
for d in range(2):
    for m in range(4):
        for s in range(p.slots):
            np.testing.assert_allclose(work_out[d, m, s],
                                       canon_ref[p.table[d, m, s]],
                                       rtol=1e-5, atol=1e-5)
print("OK")
""")


def test_seq_sharded_flash_decode_matches_local():
    run("""
import jax, jax.numpy as jnp, numpy as np
from functools import partial
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.models.layers.attention import (AttnConfig, init_attention,
                                           decode_attention, init_kv_cache,
                                           attention)
from repro.launch.mesh import make_local_mesh

mesh = make_local_mesh(8, 1)
cfg = AttnConfig(d_model=32, num_heads=2, num_kv_heads=2, head_dim=16)
key = jax.random.PRNGKey(0)
p = init_attention(key, cfg)
t = 64
x = jax.random.normal(jax.random.fold_in(key, 1), (1, t, 32)) * 0.5
pos = jnp.arange(t)[None]
ref = attention(p, cfg, x, pos)

# decode against a cache sharded over 'data' on the sequence axis
cache = init_kv_cache(cfg, 1, t, seq_shards=8)  # local view builder
# build global cache then let shard_map split it
k_all = jnp.zeros((1, 2, t, 16)); v_all = jnp.zeros((1, 2, t, 16))

def step(p, x_t, k_all, v_all, length):
    def inner(p, x_t, k_loc, v_loc, length):
        from repro.models.layers.attention import KVCache
        cache = KVCache(k=k_loc, v=v_loc, length=length)
        o, c = decode_attention(p, cfg, x_t, cache, seq_axis="data")
        return o, c.k, c.v
    return shard_map(inner, mesh=mesh,
        in_specs=(P(), P(), P(None, None, "data", None),
                  P(None, None, "data", None), P()),
        out_specs=(P(), P(None, None, "data", None),
                   P(None, None, "data", None)), check_vma=False)(
        p, x_t, k_all, v_all, length)

outs = []
for i in range(t):
    o, k_all, v_all = step(p, x[:, i:i+1], k_all, v_all, jnp.asarray(i))
    outs.append(o[:, 0])
got = jnp.stack(outs, axis=1)
np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-4, atol=2e-4)
print("OK")
""")
