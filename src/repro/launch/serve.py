"""Serving driver: continuous batching over an open-loop request stream.

Thin CLI over :mod:`repro.serve` (SERVING.md): synthetic Poisson or replay
traffic feeds the slot/KV-budget batch manager; one compiled per-slot
decode step interleaves prefill and decode, re-running the MicroEP
scheduler every step on the live batch's expert loads; per-request latency,
throughput and balance stats are printed (add ``--json`` for the full
report).

  PYTHONPATH=src python -m repro.launch.serve --arch qwen1_5-0.5b --smoke \
      --traffic poisson
  PYTHONPATH=src python -m repro.launch.serve --arch paper-gpt-32x1.3b \
      --smoke --traffic poisson --requests 16 --rate 0.5 --replacement
  PYTHONPATH=src python -m repro.launch.serve --arch olmoe-1b-7b --smoke \
      --traffic replay --trace trace.json
  PYTHONPATH=src python -m repro.launch.serve --arch paper-gpt-32x1.3b \
      --smoke --traffic replay --disagg --prefill-slots 4 --decode-slots 2

Disaggregation flags (``--disagg``, ``--prefill-slots``,
``--decode-slots``, ``--handoff-depth``, ``--prefill-profiles``,
``--decode-profiles`` — DESIGN.md §13) split the session into a prefill
fleet and a decode fleet joined by a bounded KV-handoff buffer.

Elastic fleet flags (``--fleet``, ``--scaling-policy``, ``--min-groups`` /
``--max-groups``, ``--scale-check-every``, ``--drain-grace-steps`` —
FLEET.md, DESIGN.md §14) let the session admit and drain device groups at
runtime; resize events surface in the report (``--json``).  Resilience
flags (``--resilience``, ``--crash-at-steps``, ``--straggler-at-steps``,
``--transfer-fail-at-steps``, ``--max-retries`` — RESILIENCE.md,
DESIGN.md §15) arm fault injection + recovery on the same step clock:
crashes and stragglers need ``--fleet``, transfer failures need
``--disagg``.  Multi-host
flags (``--coordinator``, ``--num-hosts``, ``--host-id``) initialize the
JAX distributed runtime before any device work; the default is a no-op.

Engine flags (``--placement``, ``--mode``, ``--sweeps``, ``--dtype``,
``--capacity-factor``, ...), serving flags (``--max-batch``, ``--max-seq``,
``--kv-budget``, ``--replacement``, ...) and telemetry flags
(``--telemetry-record``, ``--trace-out``, ``--forecast-replacement``,
``--predictor``, ... — TELEMETRY.md) share the typed config surface of
``repro.engine`` (ENGINE.md).  ``--data-axis N`` (with
``XLA_FLAGS=--xla_force_host_platform_device_count=...``) serves on a
local mesh through the distributed runtime.
"""
from __future__ import annotations

import argparse
import dataclasses
import json

from ..configs import get_config
from ..engine import (DisaggConfig, FleetConfig, ReplicationConfig,
                      ResilienceConfig, RuntimeConfig, ServeConfig,
                      TelemetryConfig)
from ..serve import (ServingSession, load_trace, poisson_trace, replay_trace,
                     trace_requests)
from .mesh import (add_distributed_cli_args, enable_compile_cache,
                   make_local_mesh, maybe_initialize_distributed)


def run(argv=None):
    """Serve the trace and return ``(ServeReport, requests)``."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--layers", type=int, default=None,
                    help="override num_layers only; every width stays")
    ap.add_argument("--traffic", default="poisson",
                    choices=["poisson", "replay", "trace"],
                    help="'trace' shapes non-stationary arrivals from a "
                         "recorded expert-load trace (TELEMETRY.md)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--rate", type=float, default=0.25,
                    help="poisson arrival rate (requests per decode step)")
    ap.add_argument("--prompt-len", type=int, default=12,
                    help="max prompt length (sampled uniform in [len/2, len])")
    ap.add_argument("--gen", type=int, default=16,
                    help="max generation length (sampled like --prompt-len)")
    ap.add_argument("--trace", default=None,
                    help="JSON request trace for --traffic replay, or a "
                         "recorded load trace (npz/jsonl) for "
                         "--traffic trace")
    ap.add_argument("--data-axis", type=int, default=0,
                    help="0 = single device (no mesh)")
    ap.add_argument("--model-axis", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", action="store_true",
                    help="print the full ServeReport as JSON")
    # shared engine + serving flag surfaces (same parser family as train)
    RuntimeConfig.add_cli_args(
        ap, defaults=RuntimeConfig(dtype="float32", remat=False))
    ServeConfig.add_cli_args(ap)
    TelemetryConfig.add_cli_args(ap)
    ReplicationConfig.add_cli_args(ap)
    DisaggConfig.add_cli_args(ap)
    FleetConfig.add_cli_args(ap)
    ResilienceConfig.add_cli_args(ap)
    add_distributed_cli_args(ap)
    args = ap.parse_args(argv)
    run_cfg = RuntimeConfig.from_cli_args(args)
    serve_cfg = ServeConfig.from_cli_args(args)
    telemetry = TelemetryConfig.from_cli_args(args)
    replication = ReplicationConfig.from_cli_args(args)
    disagg = DisaggConfig.from_cli_args(args)
    fleet = FleetConfig.from_cli_args(args)
    resilience = ResilienceConfig.from_cli_args(args)
    if telemetry.forecast_replacement and not serve_cfg.replacement:
        ap.error("--forecast-replacement selects the trigger policy of the "
                 "replacement hook; enable the hook with --replacement")
    if fleet.enabled and disagg.enabled:
        ap.error("--fleet and --disagg cannot be combined")
    if resilience.enabled and not (fleet.enabled or disagg.enabled):
        ap.error("--resilience needs --fleet (group crashes/stragglers) "
                 "or --disagg (transfer failures)")
    if resilience.enabled and resilience.has_group_faults \
            and not fleet.enabled:
        ap.error("crash/straggler faults need --fleet")
    if resilience.enabled and resilience.has_transfer_faults \
            and not disagg.enabled:
        ap.error("transfer faults need --disagg")
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
    print(f"serve arch={cfg.name} layers={cfg.num_layers} "
          f"impl={run_cfg.impl or 'default'} dtype={run_cfg.dtype}")
    # convenience: grow the default cache to fit the requested lengths, but
    # never override explicit --max-seq / --kv-budget (oversize requests
    # are then rejected and reported instead)
    if (serve_cfg.max_seq == ServeConfig().max_seq
            and serve_cfg.kv_budget is None
            and serve_cfg.max_seq < args.prompt_len + args.gen):
        serve_cfg = ServeConfig.from_dict(
            {**serve_cfg.to_dict(), "max_seq": args.prompt_len + args.gen})
        print(f"note: default --max-seq grown to {serve_cfg.max_seq} to fit "
              f"--prompt-len {args.prompt_len} + --gen {args.gen}")

    if args.traffic == "trace":
        if not args.trace:
            ap.error("--traffic trace needs --trace LOADTRACE.npz")
        requests = trace_requests(args.trace, cfg.vocab, rate=args.rate,
                                  prompt_len=args.prompt_len,
                                  gen_len=args.gen, seed=args.seed + 1)
    elif args.traffic == "replay" and args.trace:
        requests = load_trace(args.trace, cfg.vocab, seed=args.seed + 1)
    elif args.traffic == "replay":
        every = max(int(round(1.0 / args.rate)), 1)
        requests = replay_trace(
            [(i * every, args.prompt_len, args.gen)
             for i in range(args.requests)], cfg.vocab, seed=args.seed + 1)
    else:
        requests = poisson_trace(
            args.requests, args.rate, cfg.vocab,
            prompt_len=args.prompt_len, gen_len=args.gen,
            seed=args.seed + 1)

    mesh = (make_local_mesh(args.data_axis, args.model_axis)
            if args.data_axis > 0 else None)
    sess = ServingSession(cfg, serve_cfg, run_cfg=run_cfg, mesh=mesh,
                          seed=args.seed,
                          telemetry=telemetry if telemetry.enabled else None,
                          replication=(replication if replication.enabled
                                       else None),
                          disagg=disagg if disagg.enabled else None,
                          fleet=fleet if fleet.enabled else None,
                          resilience=(resilience if resilience.enabled
                                      else None))
    report = sess.run(requests)
    if disagg.enabled:
        print(f"arch={cfg.name} disagg: prefill={disagg.prefill_slots} "
              f"decode={disagg.decode_slots} "
              f"handoff_depth={disagg.handoff_depth} "
              f"max_seq={serve_cfg.max_seq} traffic={args.traffic}")
    elif fleet.enabled:
        print(f"arch={cfg.name} fleet: groups in "
              f"[{fleet.min_groups}, {fleet.max_groups}] x "
              f"{fleet.slots_per_group} slots, "
              f"policy={fleet.scaling_policy} "
              f"max_seq={serve_cfg.max_seq} traffic={args.traffic}")
    else:
        print(f"arch={cfg.name} slots={serve_cfg.max_batch} "
              f"max_seq={serve_cfg.max_seq} "
              f"kv_budget={serve_cfg.budget_tokens} traffic={args.traffic}")
    print(report.summary())
    if sess.recorder is not None and telemetry.trace_path:
        print(f"recorded {len(sess.recorder)}-step load trace -> "
              f"{telemetry.trace_path}")
    if args.json:
        print(json.dumps(report.to_dict(), indent=1))
    return report, requests


def main(argv=None) -> int:
    run(argv)
    return 0


if __name__ == "__main__":
    enable_compile_cache()
    raise SystemExit(main())
