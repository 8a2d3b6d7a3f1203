"""The continuous-batching serving loop (SERVING.md).

One :class:`ServingSession` owns the model params, the per-slot decode
state, one compiled ``decode_step``, a :class:`BatchManager` and (optional)
the adaptive replacement hook, and drives an open-loop request trace:

  per decode step:
    1. admit arrived requests into free slots against the KV budget
       (slot caches are reset via ``decoder.reset_decode_slots``);
    2. feed one token per active slot (prompt token while prefilling, else
       the slot's last sampled token — prefill/decode interleaving);
    3. run the compiled step.  Inside it the MicroEP scheduler re-solves
       on the live batch's expert loads, warm-started from the previous
       step (the per-micro-batch LP of paper §5 applied to serving);
    4. harvest sampled tokens, retire finished sequences, free their
       slots/budget;
    5. feed measured expert loads to the replacement hook; on trigger,
       migrate: rebuild the runtime around the regenerated placement and
       re-materialize working params from the canonical master (paper
       §6.4 — re-jit by design, the suspension cost is measured).

The step clock (one tick per compiled step) is the virtual time base for
arrivals, so a (trace seed, model seed) pair reproduces token-identical
runs; wall-clock timestamps are recorded alongside for latency stats.

Disaggregated serving (``DisaggConfig.enabled``, DESIGN.md §13) splits the
session into a *prefill fleet* and a *decode fleet* on the same shared
step clock: arrivals admit only into prefill slots, a completed prefill's
per-slot KV caches are extracted into a bounded :class:`HandoffBuffer`
(``models.decoder.extract_decode_slot`` — the staged transfer), and decode
slots admit only staged sequences (``insert_decode_slot`` on the receive
side).  Each fleet gets its own ``DeviceProfile`` mix, runtime/placement,
per-step LP re-solve, and replacement hook (decision records tagged with
the fleet that fired).  Disabled or absent, the co-located path below is
bit-identical to the pre-disaggregation loop (golden-pinned in
tests/test_serve.py).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ArchConfig
from ..engine import (DisaggConfig, FleetConfig, ReplicationConfig,
                      ResilienceConfig, RuntimeConfig, ServeConfig,
                      TelemetryConfig)
from ..models import decoder as dec
from ..telemetry import LoadTraceRecorder
from .batching import BatchManager, HandoffBuffer, HandoffItem
from .replacement import ServeReplacement
from .request import Request, RequestRecord, percentile

__all__ = ["ServingSession", "ServeReport"]


@dataclasses.dataclass
class ServeReport:
    """Aggregate + per-request serving statistics (JSON schema: SERVING.md)."""

    records: List[RequestRecord]
    steps: int
    wall_s: float
    gen_tokens: int
    processed_tokens: int
    mean_balance: Optional[float]      # None for dense (no MoE layers)
    overflow: float
    migrations: int
    migrated_bytes: int
    rejected: int
    # decision records of fired migrations: step, observed/predicted loads,
    # score, threshold (SERVING.md / TELEMETRY.md — *why* each one fired);
    # disaggregated runs tag each with the fleet that fired it
    migration_events: List[dict] = dataclasses.field(default_factory=list)
    # disaggregated runs only (DESIGN.md §13): fleet widths, handoff
    # transfer/occupancy/bytes stats, per-fleet balance.  None co-located —
    # the co-located to_dict() stays bit-identical to pre-disaggregation.
    disagg: Optional[dict] = None
    # elastic-fleet runs only (FLEET.md, DESIGN.md §14): group counts,
    # admit/drain events, moved slots + migration bytes, device-step cost.
    # None on fixed-fleet runs — to_dict() stays bit-identical without it.
    fleet: Optional[dict] = None
    # resilience-armed runs only (RESILIENCE.md, DESIGN.md §15): injected
    # crashes/stragglers/transfer failures and every recovery action
    # (victims, requeues, terminal failures, weight deflations).  None
    # when ResilienceConfig is absent or disabled — to_dict() stays
    # bit-identical without it (golden fixture pin).
    resilience: Optional[dict] = None

    def _ms(self, attr: str, q: float) -> Optional[float]:
        vals = [getattr(r, attr) * 1e3 for r in self.records]
        return percentile(vals, q)

    def to_dict(self) -> dict:
        rd = lambda v, n=3: None if v is None else round(v, n)
        w = max(self.wall_s, 1e-9)
        lat_mean = (float(np.mean([r.latency_s * 1e3 for r in self.records]))
                    if self.records else None)
        out = {
            "requests": len(self.records),
            "rejected": self.rejected,
            "steps": self.steps,
            "wall_s": round(self.wall_s, 4),
            "latency_ms": {"p50": rd(self._ms("latency_s", 50)),
                           "p99": rd(self._ms("latency_s", 99)),
                           "mean": rd(lat_mean)},
            "ttft_ms": {"p50": rd(self._ms("ttft_s", 50)),
                        "p99": rd(self._ms("ttft_s", 99))},
            "gen_tokens": self.gen_tokens,
            "processed_tokens": self.processed_tokens,
            "gen_tokens_per_s": round(self.gen_tokens / w, 2),
            "tokens_per_s": round(self.processed_tokens / w, 2),
            "mean_balance": rd(self.mean_balance, 4),
            "overflow": self.overflow,
            "migrations": self.migrations,
            "migrated_bytes": self.migrated_bytes,
            "migration_events": self.migration_events,
            "per_request": [r.to_dict() for r in self.records],
        }
        if self.disagg is not None:
            out["disagg"] = self.disagg
        if self.fleet is not None:
            out["fleet"] = self.fleet
        if self.resilience is not None:
            out["resilience"] = self.resilience
        return out

    def summary(self) -> str:
        d = self.to_dict()
        bal = ("1.000 (dense: no MoE layers)" if self.mean_balance is None
               else f"{self.mean_balance:.3f}")
        fmt = lambda v: "n/a" if v is None else f"{v:.1f}"
        why = ""
        if self.migration_events:
            e = self.migration_events[-1]
            why = (f"\nlast migration: step {e['step']} score "
                   f"{e['score']:.3f} > threshold {e['threshold']:.3f}")
        return (
            f"served {d['requests']} requests "
            f"({d['rejected']} rejected) in {d['steps']} steps, "
            f"{d['wall_s']:.2f}s wall\n"
            f"latency ms: p50={fmt(d['latency_ms']['p50'])} "
            f"p99={fmt(d['latency_ms']['p99'])}   "
            f"ttft ms: p50={fmt(d['ttft_ms']['p50'])} "
            f"p99={fmt(d['ttft_ms']['p99'])}\n"
            f"throughput: {d['gen_tokens_per_s']:.1f} generated tokens/s "
            f"({d['tokens_per_s']:.1f} processed tokens/s)\n"
            f"mean balance ratio: {bal}   migrations: {self.migrations} "
            f"({self.migrated_bytes} B)" + why + (
                f"\ndisagg: prefill {self.disagg['prefill_slots']} + decode "
                f"{self.disagg['decode_slots']} slots, "
                f"{self.disagg['transferred']} handoffs "
                f"(buffer peak {self.disagg['handoff_peak']}/"
                f"{self.disagg['handoff_depth']}, "
                f"{self.disagg['handoff_bytes']} B staged, "
                f"{self.disagg['prefill_stall_seq_steps']} stall seq-steps)"
                if self.disagg is not None else "") + (
                f"\nfleet: {self.fleet['active_groups']}/"
                f"{self.fleet['max_groups']} groups active "
                f"(peak {self.fleet['peak_groups']}), "
                f"{self.fleet['admits']} admits / {self.fleet['drains']} "
                f"drains, {self.fleet['migration_bytes']} B moved, "
                f"{self.fleet['device_steps']} device-steps"
                if self.fleet is not None else "") + (
                f"\nresilience: {self.resilience['crashes']} crash(es), "
                f"{self.resilience['requeues']} requeue(s), "
                f"{len(self.resilience['failed_requests'])} failed, "
                f"{self.resilience['straggler_deflations']} straggler "
                f"deflation(s), {self.resilience['transfer_failures']} "
                f"transfer failure(s)"
                if self.resilience is not None else ""))


@dataclasses.dataclass
class _Fleet:
    """One side of the disaggregated boundary (DESIGN.md §13): its own
    slots/KV budget, runtime (profile mix), compiled step, replacement
    hook, decode state, and balance accumulators.  The batch manager and
    state are (re)built per run; the runtime persists across runs like the
    co-located session's."""

    name: str                              # "prefill" | "decode"
    serve_cfg: ServeConfig
    run_cfg: RuntimeConfig
    dr: Any                                # DistRuntime, or None (shadow)
    params: Any
    rt: Any
    dtype: Any
    step_fn: Any
    replacement: Optional[ServeReplacement]
    bm: Optional[BatchManager] = None
    state: Optional[dict] = None
    bal_sum: float = 0.0
    bal_steps: int = 0
    overflow: float = 0.0

    @property
    def balance(self) -> Optional[float]:
        return self.bal_sum / self.bal_steps if self.bal_steps else None


class ServingSession:
    """Continuous-batching server for one (arch config, optional mesh).

    Without a mesh this is the CPU smoke path: the MoE dispatch runs the
    full MicroEP machinery on the degenerate single-device group and the
    replacement hook (if enabled) runs in shadow mode.  With a mesh the
    decode step runs under the distributed runtime (``DistRuntime``) and
    replacement migrations rebuild it around the regenerated placement.
    """

    def __init__(self, cfg: ArchConfig, serve_cfg: ServeConfig,
                 run_cfg: Optional[RuntimeConfig] = None,
                 mesh=None, seed: int = 0,
                 telemetry: Optional[TelemetryConfig] = None,
                 replication: Optional[ReplicationConfig] = None,
                 disagg: Optional[DisaggConfig] = None,
                 fleet: Optional[FleetConfig] = None,
                 resilience: Optional[ResilienceConfig] = None):
        self.cfg = cfg
        self.serve_cfg = serve_cfg
        self.telemetry = telemetry
        self.replication = replication
        self.seed = int(seed)
        # a DisaggConfig with enabled=False is the co-located loop, same
        # as passing no DisaggConfig at all (golden-pinned bit-identity)
        self.disagg = disagg if (disagg is not None
                                 and disagg.enabled) else None
        # elastic fleet (FLEET.md): same enabled=False convention.  The
        # compiled batch width is pinned at the fleet's *maximum* capacity
        # (max_groups x slots_per_group) and admission is masked down to
        # the live capacity (BatchManager.slot_limit) — resizes never
        # recompile the step.
        self.fleet_cfg = fleet if (fleet is not None
                                   and fleet.enabled) else None
        if self.fleet_cfg is not None and self.disagg is not None:
            raise ValueError(
                "elastic fleet serving (--fleet) and disaggregated serving "
                "(--disagg) cannot be combined in one session")
        # fault injection + recovery (RESILIENCE.md): same enabled=False
        # convention — disabled, the loop below is bit-identical to the
        # pre-resilience path (golden-pinned)
        self.resilience = resilience if (resilience is not None
                                         and resilience.enabled) else None
        if self.resilience is not None:
            if self.fleet_cfg is None and self.disagg is None:
                raise ValueError(
                    "resilience fault injection needs a fleet to fault: "
                    "combine --resilience with --fleet (group crashes / "
                    "stragglers) or --disagg (transfer failures)")
            if self.resilience.has_group_faults and self.fleet_cfg is None:
                raise ValueError(
                    "crash/straggler faults need elastic fleet serving "
                    "(--fleet): there is no device group to fail")
            if self.resilience.has_transfer_faults and self.disagg is None:
                raise ValueError(
                    "handoff-transfer faults need disaggregated serving "
                    "(--disagg): there is no transfer boundary to fail")
        if self.fleet_cfg is not None:
            width = (self.fleet_cfg.max_groups
                     * self.fleet_cfg.slots_per_group)
            self.serve_cfg = serve_cfg = dataclasses.replace(
                serve_cfg, max_batch=width)
        self.run_cfg = run_cfg if run_cfg is not None else RuntimeConfig(
            dtype="float32", remat=False)
        self.mesh = mesh
        self.n_moe = dec.n_moe_layers(cfg)
        key = jax.random.PRNGKey(seed)

        if mesh is not None:
            from ..launch import runtime as R     # avoid cycle at import
            self._R = R
            self.master = R.init_master(cfg, mesh, key)
            if self.disagg is None:
                self.dr = R.build_runtime(cfg, mesh, self.run_cfg)
                self.params = self.dr.hooks.to_working(self.master)
                self.rt = self.dr.rt
                self.dtype = self.dr.dtype
            else:
                # disaggregated: each fleet builds its own runtime around
                # its own profile mix (_build_fleet); the session keeps
                # only the canonical master both fleets materialize from
                self.dr = None
                self.params = None
                self.rt = None
                self.dtype = jnp.float32
        else:
            self._R = None
            self.dr = None
            self.master = None
            self.params = dec.init_params(key, cfg, jnp.float32)
            self.rt = dec.Runtime(impl=self.run_cfg.impl)
            self.dtype = jnp.float32

        # disaggregated runs get one hook per fleet instead (_build_fleet)
        self.replacement: Optional[ServeReplacement] = None
        if self.disagg is None:
            self.replacement = self._make_replacement_hook(self.dr,
                                                           self.dtype)

        # expert-load trace capture on the step clock (TELEMETRY.md)
        self.recorder: Optional[LoadTraceRecorder] = None
        if telemetry is not None and cfg.moe and \
                (telemetry.record or telemetry.trace_path is not None):
            self.recorder = LoadTraceRecorder(
                source="serve", meta={"arch": cfg.name, "seed": int(seed)})

        self._step = self._make_step() if self.rt is not None else None
        self._reset = jax.jit(dec.reset_decode_slots)

        self.fleets: Optional[Dict[str, _Fleet]] = None
        if self.disagg is not None:
            dg = self.disagg
            # decorrelated per-fleet candidate RNG streams: seed, seed + 1
            self.fleets = {
                "prefill": self._build_fleet("prefill", dg.prefill_slots,
                                             dg.prefill_profiles, seed),
                "decode": self._build_fleet("decode", dg.decode_slots,
                                            dg.decode_profiles, seed + 1),
            }

    # ----------------------------------------------------- replacement
    def _make_replacement_hook(self, dr, dtype, fleet: Optional[str] = None,
                               seed: Optional[int] = None
                               ) -> Optional[ServeReplacement]:
        """The adaptive replacement hook for one runtime (paper §6.4) —
        the co-located session has one, a disaggregated session one per
        fleet (decision records tagged with ``fleet``)."""
        want = self.serve_cfg.replacement or (
            self.replication is not None and self.replication.enabled)
        if not (want and self.cfg.moe):
            return None
        placement = (dr.engine.placement if dr is not None else None)
        if placement is None:
            # shadow mode: degenerate one-device placement
            from ..core.placement import vanilla_placement
            placement = vanilla_placement(
                1, 1, self.cfg.num_experts * max(self.cfg.etp, 1))
        bpe = 3 * self.cfg.d_model * max(self.cfg.moe_d_ff, 1) \
            * jnp.dtype(dtype).itemsize
        # heterogeneous groups: the regenerated placements must respect
        # the same weights/budgets the runtime schedules under
        weights = budgets = None
        if dr is not None and dr.engine is not None:
            weights = dr.engine.weights
            budgets = dr.engine.slot_budgets
        return ServeReplacement(placement, self.serve_cfg, bpe,
                                seed=self.seed if seed is None else seed,
                                telemetry=self.telemetry,
                                weights=weights,
                                slot_budgets=budgets,
                                replication=self.replication,
                                fleet=fleet)

    # --------------------------------------------------- elastic fleet
    def _make_fleet_controller(self):
        """One :class:`repro.fleet.FleetController` per run (FLEET.md):
        group state and device-step accounting restart with the clock.
        On an in-process mesh the regenerated placements run shadow (the
        mesh cannot physically shrink), the same convention as shadow
        replacement — migration pricing is still exact."""
        from ..fleet import FleetController
        n_exp = (self.cfg.num_experts * max(self.cfg.etp, 1)
                 if self.cfg.moe else 1)
        bpe = (3 * self.cfg.d_model * max(self.cfg.moe_d_ff, 1)
               * jnp.dtype(self.dtype).itemsize) if self.cfg.moe else 0
        return FleetController(self.fleet_cfg, n_exp,
                               bytes_per_expert=bpe, seed=self.seed)

    # ------------------------------------------------------------ fleets
    def _fleet_serve_cfg(self, slots: int) -> ServeConfig:
        """Per-fleet ServeConfig: the fleet's slot count, with an explicit
        KV budget split proportionally (clamped so one request can always
        fit).  None stays None — slot-limited per fleet."""
        sc = self.serve_cfg
        kv = sc.kv_budget
        if kv is not None:
            total = self.disagg.prefill_slots + self.disagg.decode_slots
            kv = max(sc.max_seq, (kv * slots) // total)
        return dataclasses.replace(sc, max_batch=slots, kv_budget=kv)

    def _build_fleet(self, name: str, slots: int, profiles,
                     hook_seed: int) -> "_Fleet":
        sc = self._fleet_serve_cfg(slots)
        run_cfg = self.run_cfg
        if profiles is not None:
            run_cfg = dataclasses.replace(run_cfg, device_profiles=profiles)
        if self.mesh is not None:
            dr = self._R.build_runtime(self.cfg, self.mesh, run_cfg)
            params = dr.hooks.to_working(self.master)
            rt = dr.rt
            dtype = dr.dtype
            step_fn = self._make_step(rt)
        else:
            # shadow path: fleets share the single-device params/step —
            # the fleet split is purely a scheduling boundary here
            dr = None
            params = self.params
            rt = self.rt
            dtype = self.dtype
            step_fn = self._step
        return _Fleet(name=name, serve_cfg=sc, run_cfg=run_cfg, dr=dr,
                      params=params, rt=rt, dtype=dtype, step_fn=step_fn,
                      replacement=self._make_replacement_hook(
                          dr, dtype, fleet=name, seed=hook_seed))

    def _init_fleet_state(self, fleet: "_Fleet") -> dict:
        sc = fleet.serve_cfg
        state = dec.init_decode_state(self.cfg, sc.max_batch, sc.max_seq,
                                      fleet.dtype, fleet.rt, per_slot=True)
        if self.cfg.moe:
            state["solver"] = (fleet.dr.init_solver()
                               if fleet.dr is not None
                               else dec.init_solver_states(self.cfg, 1))
        return state

    def _warmup_fleet(self, fleet: "_Fleet") -> None:
        b = fleet.serve_cfg.max_batch
        toks = jnp.zeros((b, 1), jnp.int32)
        act = jnp.ones((b,), bool)
        out = fleet.step_fn(fleet.params, fleet.state, toks, act)
        jax.block_until_ready(out[0])
        jax.block_until_ready(
            self._reset(fleet.state, jnp.zeros((b,), bool))["pos"])

    def _migrate_fleet(self, fleet: "_Fleet", table) -> None:
        """Per-fleet replacement migration: rebuild that fleet's runtime
        only — the other fleet keeps serving through it."""
        if fleet.dr is None:
            return                             # shadow mode: no-op
        fleet.dr = self._R.build_runtime(self.cfg, self.mesh,
                                         fleet.run_cfg,
                                         placement_table=table)
        fleet.params = fleet.dr.hooks.to_working(self.master)
        fleet.rt = fleet.dr.rt
        fleet.step_fn = self._make_step(fleet.rt)
        fleet.state = dict(fleet.state)
        fleet.state["solver"] = fleet.dr.init_solver()

    # ---------------------------------------------------------- compiled
    def _make_step(self, rt=None):
        cfg = self.cfg
        rt = self.rt if rt is None else rt

        def step(params, state, toks, active):
            logits, new_state, m = dec.decode_step(
                params, cfg, state, {"tokens": toks, "active": active},
                rt, with_metrics=True)
            nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
            return nxt, new_state, (m.balance, m.expert_load, m.overflow)

        return jax.jit(step)

    def _warmup(self, state: dict) -> None:
        """Compile the step + reset programs before the clock starts, so
        latency stats measure serving, not XLA.  (A replacement migration's
        mid-run re-jit stays in the stats by design — that suspension is
        the measured migration cost.)"""
        b = self.serve_cfg.max_batch
        toks = jnp.zeros((b, 1), jnp.int32)
        act = jnp.ones((b,), bool)
        out = self._step(self.params, state, toks, act)
        jax.block_until_ready(out[0])          # discard: state is immutable
        jax.block_until_ready(
            self._reset(state, jnp.zeros((b,), bool))["pos"])

    def _init_state(self) -> dict:
        sc = self.serve_cfg
        state = dec.init_decode_state(self.cfg, sc.max_batch, sc.max_seq,
                                      self.dtype, self.rt, per_slot=True)
        if self.cfg.moe:
            state["solver"] = (self.dr.init_solver() if self.dr is not None
                               else dec.init_solver_states(self.cfg, 1))
        return state

    def _migrate(self, table, state: dict) -> dict:
        """Swap in a regenerated placement (paper §6.4): rebuild the
        runtime, redistribute canonical master params into the new working
        layout, re-jit the step.  Shadow mode (no mesh) is a no-op."""
        if self.dr is None:
            return state
        self.dr = self._R.build_runtime(self.cfg, self.mesh, self.run_cfg,
                                        placement_table=table)
        self.params = self.dr.hooks.to_working(self.master)
        self.rt = self.dr.rt
        self._step = self._make_step()
        # replica geometry follows the new table; restart the warm start
        state = dict(state)
        state["solver"] = self.dr.init_solver()
        return state

    # -------------------------------------------------------------- run
    def run(self, requests: List[Request],
            max_steps: Optional[int] = None,
            warmup: bool = True) -> ServeReport:
        if self.disagg is not None:
            return self._run_disagg(requests, max_steps, warmup)
        bm = BatchManager(self.serve_cfg)
        fleet_ctl = None
        if self.fleet_cfg is not None:
            from ..fleet import FleetSignals      # lazy: co-located runs
            fleet_ctl = self._make_fleet_controller()
            bm.set_slot_limit(fleet_ctl.capacity)
        # fault injection + recovery (RESILIENCE.md): injector and retry
        # accounting restart with the step clock, like the controller
        injector = tracker = mitigator = None
        res_events: List[dict] = []
        requeues = deflations = 0
        prev_mult: Dict[int, float] = {}
        if self.resilience is not None and fleet_ctl is not None:
            from ..resilience import (FaultInjector, FaultPlan,
                                      RetryTracker, StragglerMitigator,
                                      recover_from_crash)
            injector = FaultInjector(FaultPlan.from_config(self.resilience))
            tracker = RetryTracker(self.resilience.max_retries)
            mitigator = StragglerMitigator(
                self.resilience.straggler_threshold)
        for r in sorted(requests, key=lambda r: (r.arrival_step, r.req_id)):
            bm.submit(r)
        if self.recorder is not None and len(self.recorder):
            # one run = one trace: a second run() starts a fresh recording
            self.recorder = LoadTraceRecorder(source="serve",
                                              meta=dict(self.recorder.meta))
        # replacement state (placement, history) persists across runs, but
        # the report counts only this run's migrations/events
        mig0 = self.replacement.migrations if self.replacement else 0
        bytes0 = self.replacement.migrated_bytes if self.replacement else 0
        ev0 = len(self.replacement.events) if self.replacement else 0
        state = self._init_state()
        if warmup:
            self._warmup(state)
        records: List[RequestRecord] = []
        arrival_wall: dict = {}
        step = 0
        bal_sum = 0.0
        bal_steps = 0
        overflow = 0.0
        processed = 0
        lat_ema = 0.0                        # per-step wall EMA (fleet SLO)
        t0 = time.perf_counter()

        while bm.has_work() and (max_steps is None or step < max_steps):
            if bm.n_active == 0:
                nxt_arr = bm.next_arrival_step()
                if nxt_arr is not None and nxt_arr > step:
                    step = nxt_arr           # idle fast-forward (step clock)
            step_faults = None
            if injector is not None:
                step_faults = injector.tick(
                    step, [g.gid for g in fleet_ctl.groups])
                for _ in range(step_faults.crashes):
                    # unplanned loss of the newest group: evict its
                    # in-flight sequences (KV gone), emergency re-pack on
                    # the survivors, re-enqueue victims at the FIFO head
                    # (FleetInfeasibleError propagates at the floor)
                    rec = recover_from_crash(bm, fleet_ctl, tracker, step)
                    requeues += len(rec.requeued)
                    res_events.append(rec.to_event())
            now = time.perf_counter() - t0
            tick_wall = now
            for req in bm.queue:             # stamp wall arrival lazily
                if req.arrival_step <= step and req.req_id not in arrival_wall:
                    arrival_wall[req.req_id] = now
            mask = bm.admit_ready(step)
            if mask.any():
                state = self._reset(state, jnp.asarray(mask))
            toks, active = bm.next_tokens()
            nxt, state, (bal, eload, ovf) = self._step(
                self.params, state, jnp.asarray(toks), jnp.asarray(active))
            nxt = np.asarray(nxt)            # block on the step
            now = time.perf_counter() - t0
            processed += int(active.sum())
            for s in bm.observe(nxt, step, now):
                records.append(RequestRecord(
                    req_id=s.request.req_id,
                    prompt_len=s.request.prompt_len,
                    arrival_step=s.request.arrival_step,
                    admit_step=s.admit_step,
                    first_token_step=s.first_token_step,
                    finish_step=step,
                    arrival_wall=arrival_wall.get(s.request.req_id, now),
                    first_token_wall=s.first_token_wall,
                    finish_wall=now,
                    tokens=list(s.tokens)))
            if self.n_moe:
                bal_sum += float(bal) / self.n_moe
                bal_steps += 1
                overflow += float(ovf)
                if self.recorder is not None:
                    self.recorder.record(step, np.asarray(eload, np.float64))
                if self.replacement is not None:
                    new_table = self.replacement.observe(np.asarray(eload),
                                                         step=step)
                    if new_table is not None:
                        state = self._migrate(new_table, state)
            if fleet_ctl is not None:
                step_ms = max(now - tick_wall, 0.0) * 1e3
                lat_ema = (step_ms if lat_ema == 0.0
                           else 0.8 * lat_ema + 0.2 * step_ms)
                cap = fleet_ctl.capacity
                if fleet_ctl.observe(FleetSignals(
                        step=step,
                        utilization=bm.n_active / max(cap, 1),
                        queue_depth=sum(1 for r in bm.queue
                                        if r.arrival_step <= step),
                        step_latency_ms=lat_ema,
                        active_slots=bm.n_active,
                        capacity=cap,
                        busy_above_capacity=bm.n_active_above(cap),
                        expert_load=(np.asarray(eload, np.float64)
                                     if self.n_moe else None)), step):
                    # a resize fired: admission follows the new capacity
                    # immediately; in-flight slots above it finish in place
                    bm.set_slot_limit(fleet_ctl.capacity)
                if mitigator is not None:
                    # per-group step latency: the shared measured step,
                    # inflated for groups inside an injected straggler
                    # window; EWMA -> weight deflation -> weighted LP
                    base = max(step_ms, 1e-3)
                    factors = (step_faults.straggler_factors
                               if step_faults is not None else {})
                    mult = mitigator.observe(
                        {g.gid: base * factors.get(g.gid, 1.0)
                         for g in fleet_ctl.groups})
                    for gid, m in mult.items():
                        was = prev_mult.get(gid, 1.0)
                        fleet_ctl.set_weight_override(gid, m)
                        if m < 1.0 and was >= 1.0:
                            deflations += 1
                            res_events.append(
                                {"step": step, "kind": "straggler_deflate",
                                 "group": gid, "multiplier": round(m, 4)})
                        elif m >= 1.0 > was:
                            res_events.append(
                                {"step": step, "kind": "straggler_restore",
                                 "group": gid})
                    prev_mult = mult
            step += 1

        wall = time.perf_counter() - t0
        if self.recorder is not None and self.telemetry is not None \
                and self.telemetry.trace_path:
            self.recorder.save(self.telemetry.trace_path)
        return ServeReport(
            records=sorted(records, key=lambda r: r.req_id),
            steps=step,
            wall_s=wall,
            gen_tokens=sum(r.n_generated for r in records),
            processed_tokens=processed,
            mean_balance=(bal_sum / bal_steps if bal_steps else None),
            overflow=overflow,
            migrations=(self.replacement.migrations - mig0
                        if self.replacement else 0),
            migrated_bytes=(self.replacement.migrated_bytes - bytes0
                            if self.replacement else 0),
            rejected=len(bm.rejected),
            migration_events=([e for e in self.replacement.events[ev0:]
                               if e.get("fired")]
                              if self.replacement else []),
            fleet=(fleet_ctl.summary() if fleet_ctl is not None else None),
            resilience=(None if injector is None else {
                "enabled": True,
                "crashes": fleet_ctl.crashes,
                "requeues": requeues,
                "failed_requests": sorted(r.req_id
                                          for r in tracker.failed),
                "straggler_deflations": deflations,
                "transfer_failures": 0,
                "transfer_retries": 0,
                "injected": list(injector.events_log),
                "events": res_events,
            }))

    # ------------------------------------------------ disaggregated run
    def _run_disagg(self, requests: List[Request],
                    max_steps: Optional[int],
                    warmup: bool) -> ServeReport:
        """The two-fleet loop (DESIGN.md §13) on one shared step clock.

        Per tick: drain staged transfers from the handoff buffer into free
        decode slots (``insert_decode_slot`` — the receive side), admit
        arrivals into prefill slots, step each fleet that has live work,
        then stage completed prefills' per-slot KV
        (``extract_decode_slot``) into the bounded buffer; a completed
        prefill the full buffer cannot take stalls in its slot
        (back-pressure, never loss — tests/test_disagg.py)."""
        dg = self.disagg
        pf, dc = self.fleets["prefill"], self.fleets["decode"]
        buf = HandoffBuffer(dg.handoff_depth)
        # transfer-fault injection (RESILIENCE.md): failed handoffs stay
        # staged and retry with capped exponential backoff, never drop
        injector = None
        res_events: List[dict] = []
        transfer_failures = 0
        if self.resilience is not None:
            from ..resilience import (FaultInjector, FaultPlan,
                                      transfer_backoff)
            injector = FaultInjector(FaultPlan.from_config(self.resilience))
        for f in (pf, dc):
            f.bm = BatchManager(f.serve_cfg, role=f.name)
            f.state = self._init_fleet_state(f)
            f.bal_sum = 0.0
            f.bal_steps = 0
            f.overflow = 0.0
        for r in sorted(requests, key=lambda r: (r.arrival_step, r.req_id)):
            pf.bm.submit(r)
        if self.recorder is not None and len(self.recorder):
            # one run = one trace: a second run() starts a fresh recording
            self.recorder = LoadTraceRecorder(source="serve",
                                              meta=dict(self.recorder.meta))
        mig0 = {f.name: (f.replacement.migrations if f.replacement else 0)
                for f in (pf, dc)}
        bytes0 = {f.name: (f.replacement.migrated_bytes
                           if f.replacement else 0) for f in (pf, dc)}
        ev0 = {f.name: (len(f.replacement.events) if f.replacement else 0)
               for f in (pf, dc)}
        if warmup:
            self._warmup_fleet(pf)
            self._warmup_fleet(dc)
        # what one staged transfer costs: the per-slot share of the
        # prefill fleet's KV caches (models.decoder.decode_slot_bytes)
        slot_bytes = dec.decode_slot_bytes(pf.state)
        records: List[RequestRecord] = []
        arrival_wall: dict = {}
        step = 0
        processed = 0
        stalls = 0                 # seq-steps spent parked on a full buffer
        t0 = time.perf_counter()

        while (pf.bm.has_work() or dc.bm.has_work() or len(buf)) \
                and (max_steps is None or step < max_steps):
            if pf.bm.n_active == 0 and dc.bm.n_active == 0 \
                    and not len(buf):
                nxt_arr = pf.bm.next_arrival_step()
                if nxt_arr is not None and nxt_arr > step:
                    step = nxt_arr          # idle fast-forward (step clock)
            now = time.perf_counter() - t0
            for req in pf.bm.queue:         # stamp wall arrival lazily
                if req.arrival_step <= step \
                        and req.req_id not in arrival_wall:
                    arrival_wall[req.req_id] = now
            # receive side: drain staged transfers, eldest first, while a
            # decode slot is free and the KV reservation fits
            while True:
                item = buf.peek()
                if item is None:
                    break
                if item.next_attempt_step > step:
                    break           # backing off after a failed transfer:
                                    # head-of-line blocks (back-pressure)
                if injector is not None:
                    if not dc.bm.can_admit_transfer(item.seq):
                        break       # no attempt occurs: no fault verdict
                    if injector.transfer_fails(step):
                        # failed in flight: the staged KV is intact, retry
                        # after capped exponential backoff — never dropped
                        item.retries += 1
                        transfer_failures += 1
                        item.next_attempt_step = step + transfer_backoff(
                            item.retries,
                            self.resilience.retry_backoff_steps,
                            self.resilience.max_transfer_retries)
                        res_events.append(
                            {"step": step, "kind": "transfer_fail",
                             "req": item.seq.request.req_id,
                             "retries": item.retries,
                             "next_attempt_step": item.next_attempt_step})
                        break
                slot = dc.bm.admit_transfer(item.seq, step)
                if slot is None:
                    break                   # decode fleet full: stay staged
                buf.pop()
                if item.payload is not None:
                    dc.state = dec.insert_decode_slot(dc.state,
                                                      item.payload, slot)
            # arrivals admit only into prefill slots
            mask = pf.bm.admit_ready(step)
            if mask.any():
                pf.state = self._reset(pf.state, jnp.asarray(mask))
            # step both fleets on the shared clock (prefill first: its
            # tick-t completions stage this tick, transfer next tick)
            tick_load = None
            for f in (pf, dc):
                toks, active = f.bm.next_tokens()
                if not active.any():
                    continue                # fleet idle/stalled this tick
                nxt, f.state, (bal, eload, ovf) = f.step_fn(
                    f.params, f.state, jnp.asarray(toks),
                    jnp.asarray(active))
                nxt = np.asarray(nxt)       # block on the fleet's step
                now = time.perf_counter() - t0
                processed += int(active.sum())
                for s in f.bm.observe(nxt, step, now):
                    records.append(RequestRecord(
                        req_id=s.request.req_id,
                        prompt_len=s.request.prompt_len,
                        arrival_step=s.request.arrival_step,
                        admit_step=s.admit_step,
                        first_token_step=s.first_token_step,
                        finish_step=step,
                        arrival_wall=arrival_wall.get(s.request.req_id,
                                                      now),
                        first_token_wall=s.first_token_wall,
                        finish_wall=now,
                        tokens=list(s.tokens)))
                if self.n_moe:
                    f.bal_sum += float(bal) / self.n_moe
                    f.bal_steps += 1
                    f.overflow += float(ovf)
                    load = np.asarray(eload, np.float64)
                    tick_load = (load if tick_load is None
                                 else tick_load + load)
                    if f.replacement is not None:
                        new_table = f.replacement.observe(load, step=step)
                        if new_table is not None:
                            self._migrate_fleet(f, new_table)
            if self.recorder is not None and tick_load is not None:
                self.recorder.record(step, tick_load)
            # send side: stage completed prefills while the buffer has
            # space, then free their prefill slots
            for s in pf.bm.take_handoff_ready():
                if buf.full:
                    break
                payload = dec.extract_decode_slot(pf.state, s.slot)
                staged = buf.push(HandoffItem(seq=s, payload=payload,
                                              kv_bytes=slot_bytes,
                                              push_step=step))
                assert staged
                pf.bm.release(s)
            stalls += len(pf.bm.take_handoff_ready())
            step += 1

        wall = time.perf_counter() - t0
        if self.recorder is not None and self.telemetry is not None \
                and self.telemetry.trace_path:
            self.recorder.save(self.telemetry.trace_path)
        migrations = migrated = 0
        events: List[dict] = []
        for f in (pf, dc):
            if f.replacement is None:
                continue
            migrations += f.replacement.migrations - mig0[f.name]
            migrated += f.replacement.migrated_bytes - bytes0[f.name]
            events.extend(e for e in f.replacement.events[ev0[f.name]:]
                          if e.get("fired"))
        events.sort(key=lambda e: e.get("step", 0))
        bal_steps = pf.bal_steps + dc.bal_steps
        return ServeReport(
            records=sorted(records, key=lambda r: r.req_id),
            steps=step,
            wall_s=wall,
            gen_tokens=sum(r.n_generated for r in records),
            processed_tokens=processed,
            mean_balance=((pf.bal_sum + dc.bal_sum) / bal_steps
                          if bal_steps else None),
            overflow=pf.overflow + dc.overflow,
            migrations=migrations,
            migrated_bytes=migrated,
            rejected=len(pf.bm.rejected),
            migration_events=events,
            disagg={
                "prefill_slots": dg.prefill_slots,
                "decode_slots": dg.decode_slots,
                "handoff_depth": dg.handoff_depth,
                "transferred": buf.transferred,
                "handoff_peak": buf.peak,
                "handoff_bytes": buf.bytes_total,
                "prefill_stall_seq_steps": stalls,
                "prefill_balance": (None if pf.balance is None
                                    else round(pf.balance, 4)),
                "decode_balance": (None if dc.balance is None
                                   else round(dc.balance, 4)),
            },
            resilience=(None if injector is None else {
                "enabled": True,
                "crashes": 0,
                "requeues": 0,
                "failed_requests": [],
                "straggler_deflations": 0,
                "transfer_failures": transfer_failures,
                "transfer_retries": sum(1 for e in res_events
                                        if e["kind"] == "transfer_fail"
                                        and e["retries"] > 1),
                "injected": list(injector.events_log),
                "events": res_events,
            }))
