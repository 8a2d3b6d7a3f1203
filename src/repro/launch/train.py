"""Training driver.

Runs a real training loop for any ``--arch`` on the host devices (use
XLA_FLAGS=--xla_force_host_platform_device_count=N for a local mesh) or, on
a real TPU slice, on the production mesh.  The CPU-scale path is what the
end-to-end examples use: reduced config, synthetic learnable data, real
MicroEP scheduling per micro-batch.

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  PYTHONPATH=src python -m repro.launch.train --arch paper-gpt-32x1.3b \
      --smoke --steps 100 --batch 16 --seq 64 --data-axis 2 --model-axis 4

Engine flags (--placement, --mode, --sweeps, --dtype, --capacity-factor,
--remat/--no-remat, ...) are the shared RuntimeConfig surface (ENGINE.md).
Multi-host flags (--coordinator, --num-hosts, --host-id) call
``jax.distributed.initialize`` before any device work; the single-host
default is a no-op.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from ..checkpoint import save_checkpoint
from ..configs import get_config
from ..data.synthetic import SyntheticLM
from ..engine import ReplicationConfig, RuntimeConfig, TelemetryConfig
from ..models import decoder as dec
from ..optim.adamw import AdamWConfig, adamw_init
from ..optim.schedule import warmup_cosine
from ..replication import TopologyController
from ..telemetry import (LoadTraceRecorder, ReplacementPlanner,
                         predictor_from_config, prewarm_solver_states)
from ..train.loop import TrainState, make_train_step
from ..train.metrics import MetricLogger
from . import runtime as R
from .mesh import (add_distributed_cli_args, enable_compile_cache,
                   make_local_mesh, make_production_mesh,
                   maybe_initialize_distributed)


def run(argv=None) -> dict:
    """Train and return the run's record: per-step metric rows
    (``history``), compile seconds, per-step seconds, and whether the
    compiled step holds a Pallas kernel (``tpu_custom_call``)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config")
    ap.add_argument("--layers", type=int, default=None,
                    help="override num_layers only; every width stays")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--n-micro", type=int, default=2)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--data-axis", type=int, default=0,
                    help="0 = single device (no mesh)")
    ap.add_argument("--model-axis", type=int, default=1)
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--csv", default=None)
    ap.add_argument("--seed", type=int, default=0)
    # shared engine flag surface (same parser as serve/bench): CPU-scale
    # training defaults to float32 master math without remat
    RuntimeConfig.add_cli_args(
        ap, defaults=RuntimeConfig(dtype="float32", remat=False))
    TelemetryConfig.add_cli_args(ap)
    ReplicationConfig.add_cli_args(ap)
    add_distributed_cli_args(ap)
    args = ap.parse_args(argv)
    run_cfg = RuntimeConfig.from_cli_args(args)
    telemetry = TelemetryConfig.from_cli_args(args)
    replication = ReplicationConfig.from_cli_args(args)
    try:
        # multi-host init must precede any other jax API (no-op on one host)
        maybe_initialize_distributed(args)
    except ValueError as e:
        ap.error(str(e))

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    if args.layers is not None:
        cfg = dataclasses.replace(cfg, num_layers=args.layers)
    print(f"train arch={cfg.name} layers={cfg.num_layers} "
          f"impl={run_cfg.impl or 'default'} dtype={run_cfg.dtype}")
    # telemetry needs the per-step expert-load vector out of the compiled
    # step (TELEMETRY.md); dense configs have nothing to record
    want_load = cfg.moe and (telemetry.record or telemetry.prewarm
                             or telemetry.trace_path is not None
                             or replication.enabled)

    opt_cfg = AdamWConfig(lr=args.lr)
    lr_fn = lambda s: warmup_cosine(s, args.lr, warmup=20, total=args.steps)
    key = jax.random.PRNGKey(args.seed)

    if args.production_mesh or args.data_axis > 0:
        mesh = (make_production_mesh() if args.production_mesh
                else make_local_mesh(args.data_axis, args.model_axis))
        dr = R.build_runtime(cfg, mesh, run_cfg)
        ts, ts_sh = dr.init_train_state(key), dr.train_state_shardings()

        def jit_step(dr):
            return jax.jit(R.make_train_fn(dr, n_micro=args.n_micro,
                                           opt_cfg=opt_cfg,
                                           with_expert_load=want_load),
                           out_shardings=(ts_sh, None), donate_argnums=0)
        step = jit_step(dr)
        placement = dr.engine.placement if cfg.moe else None
    else:
        dr = None
        master = dec.init_params(key, cfg, jnp.float32)
        ts = TrainState(master=master, opt=adamw_init(master),
                        solver=dec.init_solver_states(cfg, 1),
                        step=jnp.zeros((), jnp.int32))
        step = jax.jit(make_train_step(cfg, dec.Runtime(impl=run_cfg.impl),
                                       opt_cfg=opt_cfg,
                                       n_micro=args.n_micro, lr_fn=lr_fn,
                                       with_expert_load=want_load),
                       donate_argnums=0)
        placement = None
        if cfg.moe:
            from ..core.placement import vanilla_placement
            placement = vanilla_placement(
                1, 1, cfg.num_experts * max(cfg.etp, 1))

    recorder = None
    if want_load:
        recorder = LoadTraceRecorder(
            source="train", meta={"arch": cfg.name, "seed": int(args.seed)})
    # forecast-driven solver pre-warm (TELEMETRY.md): the LPP-1 oracle on
    # the *predicted* next-step loads seeds the in-graph warm start
    planner = None
    if want_load and telemetry.prewarm:
        # heterogeneous groups: the LP prewarm must solve the same
        # weighted LP the in-graph scheduler descends (DESIGN.md §11)
        eng = dr.engine if dr is not None else None
        planner = ReplacementPlanner(
            placement, predictor=predictor_from_config(telemetry),
            check_every=10 ** 9,        # plan never; forecast every step
            horizon=telemetry.horizon, seed=args.seed,
            weights=None if eng is None else eng.weights,
            slot_budgets=None if eng is None else eng.slot_budgets)
    # dynamic replica-topology planning (DESIGN.md §12): re-plan where
    # replicas live from forecast loads, migrate through the same
    # runtime-rebuild path a serving migration uses; without a mesh the
    # controller runs in shadow mode (planned, counted, nothing to move)
    controller = None
    if want_load and replication.enabled:
        eng = dr.engine if dr is not None else None
        bpe = 3 * cfg.d_model * max(cfg.moe_d_ff, 1) * \
            jnp.dtype(dr.dtype if dr is not None else jnp.float32).itemsize
        controller = TopologyController(
            placement, bpe,
            migration_gate=replication.migration_gate,
            predictor=predictor_from_config(telemetry),
            check_every=replication.check_every,
            threshold=replication.threshold,
            improve_margin=replication.improve_margin,
            mc_samples=replication.mc_samples,
            horizon=telemetry.horizon, seed=args.seed,
            weights=None if eng is None else eng.weights,
            slot_budgets=None if eng is None else eng.slot_budgets)

    data = SyntheticLM(vocab=cfg.vocab, seq_len=args.seq, batch=args.batch,
                       noise=0.05, n_maps=4, seed=args.seed + 1)
    logger = MetricLogger(csv_path=args.csv, print_every=10)
    compiled, compile_s, step_s, has_kernel = None, 0.0, [], False
    # host spans on the profiler's clock (TELEMETRY.md): a trace of this
    # loop names each step and what the host did between the steps
    batches = iter(data)
    for i in range(args.steps):
        with TraceAnnotation("repro.feed"):
            batch = next(batches)
        with StepTraceAnnotation("train", step_num=i):
            if compiled is None:
                t0 = time.perf_counter()
                compiled = step.lower(ts, batch).compile()
                compile_s += time.perf_counter() - t0
                has_kernel = has_kernel or \
                    "tpu_custom_call" in compiled.as_text()
            t0 = time.perf_counter()
            ts, m = compiled(ts, batch)
        with TraceAnnotation("repro.fetch"):
            jax.block_until_ready(m)
        step_s.append(time.perf_counter() - t0)
        if want_load:
            with TraceAnnotation("repro.telemetry"):
                eload = np.asarray(m.pop("expert_load"), np.float64)
                if recorder is not None:
                    recorder.record(i, eload)
                if controller is not None:
                    new_table = controller.observe(eload)
                    if new_table is not None and dr is not None:
                        # topology migration: rebuild the runtime around
                        # the new table (the same path as a serving
                        # migration; the re-jit suspension is the cost)
                        dr = R.build_runtime(cfg, mesh, run_cfg,
                                             placement_table=new_table)
                        step, compiled = jit_step(dr), None
                        ts = ts._replace(solver=jax.device_put(
                            dr.init_solver(), ts_sh.solver))
                        placement = dr.engine.placement
                        if planner is not None:
                            planner.placement = placement
                if planner is not None:
                    planner.observe(eload)
                    if planner.history_size >= planner.min_history:
                        # in-graph batched solver: no per-step host LP
                        ts = ts._replace(solver=prewarm_solver_states(
                            ts.solver,
                            planner.warm_start_x(solver="jacobi")))
        logger.log(i, m)
    logger.close()
    if controller is not None and controller.replacements:
        print(f"replication: {controller.replacements} topology migrations, "
              f"{controller.moved_slots} slots moved "
              f"({controller.migrated_bytes} B)")
    if recorder is not None and telemetry.trace_path:
        recorder.save(telemetry.trace_path)
        print(f"recorded {len(recorder)}-step load trace -> "
              f"{telemetry.trace_path}")

    if args.ckpt_dir:
        path = save_checkpoint(args.ckpt_dir, args.steps, ts.master,
                               {"arch": cfg.name})
        print("saved", path)
    first = logger.history[0]["loss"]
    last = logger.history[-1]["loss"]
    print(f"loss {first:.4f} -> {last:.4f} "
          f"({'improved' if last < first else 'NO IMPROVEMENT'})")
    return {"arch": cfg.name, "layers": cfg.num_layers,
            "history": logger.history, "compile_s": compile_s,
            "step_s": step_s, "has_pallas_kernel": has_kernel}


def main(argv=None) -> int:
    run(argv)
    return 0


if __name__ == "__main__":
    enable_compile_cache()
    raise SystemExit(main())
