"""A training cell: the program's jitted train step, fed from a pool of
batches, timed over a window, and checked against the float32 reference.

Set-up (``setup_s``, from process start to the first timed step):
  the step is built and compiled (or loaded from the compile cache); the
  weights are made on the device from the seed; the traffic's pool of
  batches is made on the device from the seed; then the step runs three
  times on batches 0, 1, 2 of the pool.  Those three steps are the warm-up
  and the steps the reference follows: after the first, the gradient is
  read from Adam's first moment and copied to the host (the comparison's
  work, not the set-up's: its seconds are left out of ``setup_s``); after
  the third, the leaf norms of the change from the seeded weights.
Window: the same state goes on through the same step on batches 3, 4, ...
  of the pool, cycled, until ``seconds`` have passed; it ends when the last
  step is done.  ``train_tokens_per_s`` is every token of those steps over
  the window's wall time.
After the window the device memory peak is read, the program's state is
freed, and the reference takes its three steps (``reference.py``).
"""
from __future__ import annotations

import gc
import time
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from .. import bench, compare, program, reference as ref, tracing

CHECK_STEPS = 3


class RunContext(NamedTuple):
    """What a per-layer metric's reducer may read."""
    conf: dict
    traffic: dict
    chips: int
    peaks: dict                # chipbench/peaks.json entry of this device
    steps: int                 # steps in the window
    tokens_per_step: int
    window_s: float            # host clock
    trace: Any                 # tracing.Trace
    balance: list              # per step: max / mean device load
    kernel: dict               # rows and weights the grouped FFN sees


def _span(name):
    return TraceAnnotation(tracing.SPAN + name)


def _enable_cache():
    from repro.launch.mesh import enable_compile_cache
    enable_compile_cache()
    # every program this run compiles is kept, small ones too
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def make_pool(cell: bench.Cell, pc: program.Cell, key):
    """The traffic's pool of batches, made on the device in one call and
    placed as the step reads them."""
    gen = bench.traffic_generator(cell)
    specs = pc.batch_specs
    b, t = specs["tokens"].shape
    n = int(cell.traffic["pool"])
    vocab = cell.conf["model"]["vocab_size"]

    def make(k):
        pool = gen.make(cell.traffic, vocab=vocab, batch=b, seq_len=t,
                        pool=n, key=k)
        return tuple({"tokens": pool["tokens"][i], "labels": pool["labels"][i]}
                     for i in range(n))

    sh = {k: v.sharding for k, v in specs.items()}
    return jax.jit(make, out_shardings=tuple(sh for _ in range(n)))(key)


def _faulty(step, fault, batch_rows):
    """The timed path broken underneath (tests and the chip's readings of
    the faults): 'unchanged' returns the state it was given; 'half_batch'
    leaves the second half of every batch out of the loss."""
    if fault is None or fault == "no_exchange":
        return step
    if fault == "unchanged":
        copy = jax.jit(lambda ts: jax.tree_util.tree_map(jnp.copy, ts))
        return lambda ts, b: (ts, step(copy(ts), b)[1])
    if fault == "half_batch":
        cut = jax.jit(lambda lab: lab.at[batch_rows // 2:].set(-1))
        return lambda ts, b: step(ts, dict(b, labels=cut(b["labels"])))
    raise ValueError(f"unknown fault {fault!r}")


def program_side(cell, pc, seed, fault=None):
    """The pool, the seeded state and the three checked steps.  Returns the
    state, the pool, the step as the window calls it, the program's
    readings {"losses", "grad", "change_norms"}, and the seconds of each
    phase: "state" (weights and pool), "steps" (the checked steps, the
    first one's compile or cache load with them) and "grad_copy" (the
    first gradient copied to the host for the comparison)."""
    opt = cell.conf["optimizer"]
    d = ref.dims(cell.conf["model"])
    t = time.perf_counter()
    kw, kt = jax.random.split(bench.seed_key(seed))
    pool = make_pool(cell, pc, kt)
    ts = pc.init_state(kw)
    jax.block_until_ready((pool, ts))
    phases = {"state": time.perf_counter() - t, "grad_copy": 0.0}
    t = time.perf_counter()
    step = _faulty(pc.step, fault, pc.batch_specs["tokens"].shape[0])
    change = jax.jit(lambda t, k: program.leaf_norms(jax.tree_util.tree_map(
        jnp.subtract, t.master, pc.to_program(ref.init_params(k, d)))))
    losses, g = [], None
    for i in range(CHECK_STEPS):
        ts, m = step(ts, pool[i])
        losses.append(m["loss"])
        if i == 0:      # Adam's first moment is (1 - b1) * gradient
            jax.block_until_ready(ts.opt.mu)
            tc = time.perf_counter()
            g = [x / (1.0 - opt["b1"]) for x in _host_leaves(ts.opt.mu)]
            phases["grad_copy"] = time.perf_counter() - tc
    c = change(ts, kw)
    out = _readings(losses, g, c)
    phases["steps"] = time.perf_counter() - t - phases["grad_copy"]
    return ts, pool, step, out, phases


def _host_leaves(tree) -> list:
    """The leaves, copied to the host one at a time."""
    return [np.asarray(jax.device_get(x), np.float32)
            for x in jax.tree_util.tree_leaves(tree)]


def _readings(losses, g, c) -> dict:
    """{"losses", "grad" (host leaves of the first gradient as the
    optimizer gets it), "change_norms"}."""
    out = jax.device_get({"losses": losses, "change_norms": c})
    out["grad"] = g
    return out


def window(ts, pool, step, seconds):
    """Steps until ``seconds`` have passed, one step in flight ahead of the
    host; ends when the last step is done."""
    n, i, steps, ms = len(pool), CHECK_STEPS, 0, []
    prev = None
    with _span("window"):
        start = time.perf_counter()
        while True:
            with _span("feed"):
                b = pool[i % n]
            with _span("dispatch"):
                ts, m = step(ts, b)
            ms.append(m)
            steps, i = steps + 1, i + 1
            if prev is not None:
                with _span("fetch"):
                    prev["loss"].block_until_ready()
            prev = m
            if time.perf_counter() - start >= seconds:
                break
        with _span("fetch"):
            jax.block_until_ready((ts, m))
        end = time.perf_counter()
    return ts, steps, end - start, ms


def make_reference(cell, pc, devices, quant=None):
    """The reference's three steps, compiled once: a function of (seed,
    the three batches) -> {"losses", "grad_norms", "change_norms"} in the
    program's leaf order, from the seed's weights.  Expert weights are
    split over the chips by columns of the wide FFN; the rest is
    replicated."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    conf = cell.conf
    d = ref.dims(conf["model"])
    n_micro = conf["meshes"][str(cell.chips)]["n_micro"]
    opt = ref.AdamW(**conf["optimizer"])
    mesh = Mesh(np.asarray(devices[:cell.chips]), ("x",))
    split = {"w_gate": P(None, "x"), "w_up": P(None, "x"),
             "w_down": P("x", None)}

    def spec(path, _):
        name = program._path(path).rsplit("/", 1)[-1]
        return NamedSharding(mesh, split.get(name, P()))

    def fresh(k):
        return ref.init_state(ref.init_params(k, d))

    st_sh = jax.tree_util.tree_map_with_path(
        spec, jax.eval_shape(fresh, jax.random.PRNGKey(0)))
    rep = NamedSharding(mesh, P())

    def one(st, batch):
        loss, g = ref.grads(st.params, batch, d, n_micro, groups=cell.chips,
                            quant=quant)
        st, g = ref.adamw(st, g, opt)
        return jax.lax.with_sharding_constraint(st, st_sh), loss, \
            pc.to_program(g)

    def change(p, k):
        return program.leaf_norms(pc.to_program(jax.tree_util.tree_map(
            jnp.subtract, p, ref.init_params(k, d))))

    init = jax.jit(fresh, out_shardings=st_sh)
    step = jax.jit(one, donate_argnums=0)
    change = jax.jit(change, out_shardings=rep)

    def run(seed, batches):
        kw, _ = jax.random.split(bench.seed_key(seed))
        with jax.default_matmul_precision("highest"):
            st = init(kw)
            losses, g = [], None
            for i in range(CHECK_STEPS):
                st, loss, gi = step(st, jax.device_put(batches[i], rep))
                losses.append(loss)
                if i == 0:
                    g = _host_leaves(gi)
                del gi
            return _readings(losses, g, change(st.params, kw))

    return run


def memory_peak(devices) -> int:
    """The peak on the fullest chip.  A TPU holds each loaded program's
    scratch (the step's temporaries) as a reservation outside the
    allocator's ``bytes_in_use``, so the peak is both peaks together."""
    peaks = []
    for dv in devices:
        st = dv.memory_stats() or {}
        peaks.append(st.get("peak_bytes_in_use", 0)
                     + st.get("peak_bytes_reserved", 0))
    return int(max(peaks))


def run(cell: bench.Cell, seed: int, seconds: float, traced: bool, *,
        devices, t0: float, fault=None) -> dict:
    """One run of a training cell; the result line's fields."""
    t_start = time.perf_counter()
    _enable_cache()
    devices = list(devices)[:cell.chips]
    pc = program.build(cell.conf, cell.chips, int(cell.traffic["seq_len"]),
                       devices)
    t_built = time.perf_counter()
    ts, pool, step, prog, phases = program_side(cell, pc, seed, fault)
    # the first gradient's copy to the host serves the comparison alone
    setup_s = time.perf_counter() - t0 - phases["grad_copy"]
    phases.update(start=t_start - t0, build=t_built - t_start)

    trace_out: list = []
    if traced:
        with tracing.capture(trace_out):
            ts, steps, window_s, ms = window(ts, pool, step, seconds)
    else:
        ts, steps, window_s, ms = window(ts, pool, step, seconds)
    mem = memory_peak(devices)
    per_step = jax.device_get([{k: m[k] for k in ("overflow", "balance",
                                                   "expert_load")}
                               for m in ms])
    batches = jax.device_get(pool[:CHECK_STEPS])
    del ts, pool, ms, step
    gc.collect()

    ref_read = make_reference(cell, pc, devices)(seed, batches)
    names = program.leaf_names(pc.runtime.master_sds())
    read = compare.readings(prog, ref_read, names)
    correct, checks = compare.verdict(read, cell.limits)

    m = cell.conf["model"]
    mc = cell.conf["meshes"][str(cell.chips)]
    tokens_per_step = mc["global_batch"] * int(cell.traffic["seq_len"])
    etp = cell.conf["program"]["etp"]
    rows_per_step = tokens_per_step * m["num_experts_per_tok"] * etp
    dropped = sum(float(r["overflow"]) for r in per_step) * cell.chips
    eload = np.sum([r["expert_load"] for r in per_step], axis=0)
    eload = eload.reshape(m["num_experts"], etp).sum(1)
    info = {"loss": [float(v) for v in prog["losses"]],
            "reference_loss": [float(v) for v in ref_read["losses"]],
            "loss1_gap": read["loss1_gap"],
            "worst_grad_leaf": read["worst_grad_leaf"],
            "worst_change_leaf": read["worst_change_leaf"],
            "left_out_of_change": read["left_out_of_change"],
            "steps": steps, "window_s": window_s,
            "device_balance_mean": float(np.mean([r["balance"]
                                                  for r in per_step])),
            "expert_load_max_over_mean": float(eload.max() / eload.mean()),
            "setup_phases_s": phases}

    e2e = {"setup_s": setup_s,
           "train_tokens_per_s": steps * tokens_per_step / window_s}
    result = {"correct": correct, "attempted": steps * rows_per_step,
              "failed": int(round(dropped)), "e2e": e2e, "checks": checks,
              "info": info, "memory_peak_bytes": mem}
    if traced:
        trace = trace_out[0]
        slots = int(pc.runtime.engine.placement.table.shape[-1])
        h = m["hidden_size"]
        f_local = m["moe_intermediate_size"] // etp
        result["trace"] = trace
        result["context"] = RunContext(
            conf=cell.conf, traffic=cell.traffic, chips=cell.chips,
            peaks=None, steps=steps, tokens_per_step=tokens_per_step,
            window_s=window_s, trace=trace,
            balance=[float(r["balance"]) for r in per_step],
            kernel={"calls_per_step": mc["n_micro"] * m["num_hidden_layers"],
                    "rows_per_call": rows_per_step / mc["n_micro"]
                    / cell.chips,
                    "slot_weights": 3.0 * slots * h * f_local, "etp": etp})
    return result
