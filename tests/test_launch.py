"""Launch-layer units that don't need multi-device compiles: HLO collective
parsing, roofline math, model-FLOPs accounting, layout equivalence,
sharding-policy rules."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import SHAPES, get_config
from repro.data.synthetic import make_batch
from repro.launch import analysis as A
from repro.models import decoder as dec

HLO_SAMPLE = """
HloModule test
ENTRY main {
  %p0 = bf16[128,256]{1,0} parameter(0)
  %all-gather = bf16[2048,256]{1,0} all-gather(%p0), replica_groups={{0,1,2,3,4,5,6,7,8,9,10,11,12,13,14,15}}, dimensions={0}
  %all-reduce = f32[64,64]{1,0} all-reduce(%c), replica_groups={{0,1,2,3}}, to_apply=%add
  %rs = f32[16,64]{1,0} reduce-scatter(%big), replica_groups={{0,1,2,3}}, dimensions={0}
  %a2a = bf16[8,32,64]{2,1,0} all-to-all(%x), replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}
  %cp = f32[4,4]{1,0} collective-permute(%y), source_target_pairs={{0,1},{1,0}}
  ROOT %t = tuple()
}
"""


def test_parse_collectives_operand_semantics():
    cs = A.parse_collectives(HLO_SAMPLE)
    # all-gather: operand = result / group  (2048*256*2 / 16)
    assert cs.bytes_by_kind["all-gather"] == 2048 * 256 * 2 // 16
    # all-reduce: operand = result
    assert cs.bytes_by_kind["all-reduce"] == 64 * 64 * 4
    # reduce-scatter: operand = result * group
    assert cs.bytes_by_kind["reduce-scatter"] == 16 * 64 * 4 * 4
    assert cs.bytes_by_kind["all-to-all"] == 8 * 32 * 64 * 2
    assert cs.bytes_by_kind["collective-permute"] == 4 * 4 * 4
    assert cs.count_by_kind["all-gather"] == 1
    assert cs.total_bytes == sum(cs.bytes_by_kind.values())


def test_roofline_terms_and_bottleneck():
    costs = {"flops": 197e12 * 0.010, "bytes": 819e9 * 0.002,
             "coll_all-reduce": 50e9 * 0.005}
    rep = A.roofline_from_raw("a", "s", "m", costs, chips=256,
                              model_flops_total=197e12 * 0.010 * 256 * 0.5)
    assert rep.compute_s == pytest.approx(0.010)
    assert rep.memory_s == pytest.approx(0.002)
    assert rep.collective_s == pytest.approx(0.005)
    assert rep.bottleneck == "compute"
    assert rep.useful_ratio == pytest.approx(0.5)


def test_combine_costs_linear():
    a = {"flops": 10.0, "bytes": 4.0}
    b = {"flops": 16.0, "bytes": 6.0, "coll_all-to-all": 2.0}
    out = A.combine_costs((-1.0, a), (2.0, b))
    assert out["flops"] == 22.0 and out["bytes"] == 8.0
    assert out["coll_all-to-all"] == 4.0


def test_count_params_moe_active():
    cfg = get_config("olmoe-1b-7b")
    n = A.count_params(cfg)
    assert n["total"] > n["active"] > n["dense"] > 0
    # 64 experts top-8: active expert share = 8/64 of expert params
    assert n["active"] - n["dense"] == pytest.approx(
        n["expert"] * cfg.top_k / cfg.num_experts, rel=1e-6)
    dense_cfg = get_config("gemma-2b")
    nd = A.count_params(dense_cfg)
    assert nd["active"] == nd["total"]


def test_model_flops_kinds():
    cfg = get_config("qwen1.5-0.5b")
    tr = A.model_flops(cfg, SHAPES["train_4k"], "train")
    pf = A.model_flops(cfg, SHAPES["prefill_32k"], "prefill")
    dc = A.model_flops(cfg, SHAPES["decode_32k"], "decode")
    assert tr == pytest.approx(3 * pf * (4096 * 256) / (32768 * 32))
    assert dc < pf / 1000


def test_list_layout_equivalent_to_scan():
    """Same weights, both layouts -> identical logits (the dry-run cost
    pass relies on this)."""
    cfg = get_config("recurrentgemma-9b").smoke()
    key = jax.random.PRNGKey(0)
    p_scan = dec.init_params(key, cfg, layout="scan")
    P_ = len(cfg.pattern)
    reps, rem = cfg.num_layers // P_, cfg.num_layers % P_
    layers = []
    for r in range(reps):
        for i in range(P_):
            layers.append(jax.tree_util.tree_map(
                lambda a: a[r], p_scan["layers_scan"][i]))
    for i in range(rem):
        layers.append(p_scan["layers_rem"][i])
    p_list = {k: v for k, v in p_scan.items()
              if not k.startswith("layers")}
    p_list["layers_list"] = tuple(layers)
    b = make_batch(key, cfg.vocab, 2, 12)
    l1, _, _ = dec.forward(p_scan, cfg, b)
    l2, _, _ = dec.forward(p_list, cfg, b)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                               rtol=2e-5, atol=2e-5)


def test_sharding_policy_rules():
    from repro import sharding as sh

    class FakeMI:
        model = 16
        data = 16
        pods = 1

    mi = FakeMI()
    # attention q: heads*hd divisible -> model-sharded on outputs
    spec = sh.param_pspec("layers_scan/0/attn/wq", (8, 1024, 2048), mi,
                          None, scanned=True)
    assert spec == P(None, None, "model")
    # kv columns divisible -> model-sharded; non-divisible -> replicated
    spec = sh.param_pspec("layers_rem/1/attn/wk", (1024, 256), mi, None,
                          scanned=False)
    assert spec == P(None, "model")
    spec = sh.param_pspec("layers_rem/1/attn/wk", (1024, 40), mi, None,
                          scanned=False)
    assert spec == P(None, None)
    # experts working layout
    spec = sh.param_pspec("layers_list/3/moe/experts/w_gate",
                          (16, 16, 4, 2048, 1024), mi, None, scanned=False)
    assert spec == P("data", "model", None, None, None)
    # experts canonical master
    spec = sh.param_pspec("layers_scan/0/moe/experts/w_up",
                          (10, 64, 2048, 1024), mi, None, scanned=True)
    assert spec == P(None, "model", "data", None)
    # embedding vocab-sharded
    spec = sh.param_pspec("embed", (262144, 5376), mi, None, scanned=False)
    assert spec == P("model", None)


def test_train_layers_cuts_depth_only():
    """``--layers`` overrides num_layers and nothing else; ``run`` returns
    the per-step record the chip smoke checks."""
    from repro.launch import train
    res = train.run(["--arch", "olmoe-1b-7b", "--smoke", "--layers", "1",
                     "--steps", "2", "--batch", "2", "--seq", "16",
                     "--impl", "ref"])
    assert res["layers"] == 1 and len(res["history"]) == 2
    assert len(res["step_s"]) == 2 and res["compile_s"] > 0
    assert all(np.isfinite(r["loss"]) and r["overflow"] == 0
               for r in res["history"])
    assert not res["has_pallas_kernel"]       # impl=ref: no Mosaic call


_CACHE_PROBE = """
import jax, jax.numpy as jnp
from repro.launch.mesh import COMPILE_CACHE_DIR, enable_compile_cache
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
used = enable_compile_cache()
assert jax.config.jax_compilation_cache_dir == used, used
print("USED", used, COMPILE_CACHE_DIR)
if used != str(COMPILE_CACHE_DIR):
    jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()
"""


def test_compile_cache_placement(tmp_path):
    """The env var, where set, decides the cache directory and the cache is
    written there; otherwise it is the fixed in-checkout path."""
    import os
    import subprocess
    import sys
    from repro.launch.mesh import COMPILE_CACHE_DIR
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = os.pathsep.join(
        [str(COMPILE_CACHE_DIR.parent / "src"), env.get("PYTHONPATH", "")])
    r = subprocess.run([sys.executable, "-c", _CACHE_PROBE], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr
    assert f"USED {COMPILE_CACHE_DIR} " in r.stdout
    checkout = COMPILE_CACHE_DIR.parent          # fixed, and gitignored
    assert (checkout / "src" / "repro").is_dir()
    assert ".jax_cache/" in (checkout / ".gitignore").read_text().split()
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    r = subprocess.run([sys.executable, "-c", _CACHE_PROBE], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr
    assert f"USED {tmp_path} " in r.stdout
    assert any(tmp_path.iterdir()), "nothing cached in JAX_COMPILATION_CACHE_DIR"
