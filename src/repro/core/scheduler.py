"""MicroEP scheduler: per-micro-batch token scheduling (paper §5).

Pipeline per micro-batch (identical, deterministic, on every device — the
paper's *distributed scheduling*, §5.3):

    counts all-gather -> LPP solve (warm-started) -> integer rounding ->
    locality-aware routing (Alg. 1) -> flow tensor F[E, G, R]

The flow tensor plus the placement table is everything the dispatcher needs
to compute send offsets (on the source device) and receive layouts (on the
destination device) with pure cumsums — both sides derive them from the same
F, which is why no extra coordination round-trip is needed.

Construction note: ``ScheduleStatics`` and ``MicroEPScheduler`` are engine
internals.  Code outside ``repro.core``/``repro.engine`` should build them
through the :class:`repro.engine.MicroEPEngine` facade.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import scopes
from . import lp as lp_host
from .placement import Placement
from .rounding import round_replica_loads
from .routing import RoutingResult, route_tokens
from .solver_jax import (SolverState, device_loads, solve_replica_loads,
                         solve_replica_loads_batched)

__all__ = ["ScheduleStatics", "Schedule", "MicroEPScheduler"]

# Eq. 3 enumerates the 2^G - 1 device subsets; larger groups get no bound
LP_BOUND_MAX_DEVICES = 12


@dataclasses.dataclass(frozen=True)
class ScheduleStatics:
    """Static (trace-time) description of one MicroEP group's placement.

    ``weights`` (f64[G], mean-normalized, or None) are the per-device
    compute weights of a heterogeneous group (DESIGN.md §11).  None means
    homogeneous — the canonical form for uniform profiles, so the uniform
    path stays bit-identical to the pre-profile scheduler.

    ``mem_caps`` (f64[G], or None) are per-device activation-memory token
    caps from the MemFine planner (core.memory, DESIGN.md §16): the
    in-graph solvers project onto them, the host oracle adds them as LP
    rows.  None (canonical for disabled/infinite budgets) keeps every
    schedule bit-identical to the memory-oblivious path."""

    placement: Placement
    dev: np.ndarray          # int[E, R] replica -> flat device, -1 pad
    slot: np.ndarray         # int[E, R] replica -> local slot id on its device
    num_devices: int
    weights: Optional[np.ndarray] = None   # f64[G] device compute weights
    mem_caps: Optional[np.ndarray] = None  # f64[G] memory token caps

    @classmethod
    def from_placement(cls, p: Placement,
                       weights: Optional[np.ndarray] = None,
                       mem_caps: Optional[np.ndarray] = None
                       ) -> "ScheduleStatics":
        dev = lp_host.replica_devices(p)
        flat = p.flat()
        slot = np.full_like(dev, -1)
        for e in range(p.num_experts):
            for r in range(dev.shape[1]):
                g = dev[e, r]
                if g >= 0:
                    slot[e, r] = int(np.nonzero(flat[g] == e)[0][0])
        if weights is not None:
            weights = np.asarray(weights, np.float64).ravel()
            if weights.shape != (p.num_devices,):
                raise ValueError(
                    f"weights must have one entry per device "
                    f"({p.num_devices}), got shape {weights.shape}")
            if not (weights > 0).all():
                raise ValueError("device weights must all be > 0")
            if np.all(weights == weights[0]):
                weights = None          # canonical: uniform == no weights
            else:
                weights = weights / weights.mean()
        if mem_caps is not None:
            mem_caps = np.asarray(mem_caps, np.float64).ravel()
            if mem_caps.shape != (p.num_devices,):
                raise ValueError(
                    f"mem_caps must have one entry per device "
                    f"({p.num_devices}), got shape {mem_caps.shape}")
            if (mem_caps < 0).any():
                raise ValueError("mem_caps must all be >= 0")
            if not np.isfinite(mem_caps).all():
                mem_caps = None      # canonical: infinite budget == no caps
        return cls(placement=p, dev=dev, slot=slot,
                   num_devices=p.num_devices, weights=weights,
                   mem_caps=mem_caps)

    @property
    def num_experts(self) -> int:
        return self.placement.num_experts

    @property
    def max_replicas(self) -> int:
        return self.dev.shape[1]


class Schedule(NamedTuple):
    """Per-micro-batch scheduling decision (identical on every device)."""

    flow: jax.Array          # int32[E, G, R] routed token counts
    x_int: jax.Array         # int32[E, R] integer replica loads
    solver_state: SolverState  # warm-start carry for the next micro-batch
    max_load: jax.Array      # f32[] resulting max device load (diagnostic)
    balance: jax.Array       # f32[] max/mean device load (diagnostic)
    lp_bound: jax.Array      # f32[] the LP optimum (Eq. 3): the least
                             # max_g L_g / w_g any split reaches (w mean-
                             # normalized; tokens on a uniform group); NaN
                             # above LP_BOUND_MAX_DEVICES devices
    balance_gap: jax.Array   # f32[] achieved max_g L_g / w_g over lp_bound
                             # (>= 1; 0 when nothing is routed)


def _subset_density(statics: ScheduleStatics) -> Optional[np.ndarray]:
    """f32[2^G - 1, E]: row S holds 1 / w(S) at each expert whose replicas
    all lie in device subset S (bit g of S+1 set = device g in S), else 0.
    Its product with the expert loads, maximized over S, is Eq. 3's LP
    optimum, as ``core.placement.max_induced_density`` enumerates it
    (MemFine caps are not part of it).  None above LP_BOUND_MAX_DEVICES."""
    g = statics.num_devices
    if g > LP_BOUND_MAX_DEVICES:
        return None
    dev = np.asarray(statics.dev)
    bits = np.bitwise_or.reduce(
        np.where(dev >= 0, 1 << np.maximum(dev, 0), 0), axis=1)
    subsets = np.arange(1, 1 << g, dtype=np.int64)
    inside = (bits[None, :] & ~subsets[:, None]) == 0
    member = (subsets[:, None] >> np.arange(g)) & 1
    w = np.ones(g) if statics.weights is None else statics.weights
    return (inside / (member @ w)[:, None]).astype(np.float32)


class MicroEPScheduler:
    """Schedules tokens within one MicroEP group (paper §5.1-5.2).

    Modes:
      * microep: solve LPP 1 in-graph (water-filling GS) and route (Alg. 1).
      * vanilla: no scheduling freedom — each token goes to the replica in
        its own EP group (row); reproduces Megatron EP for baselines.

    ``solver_mode`` picks the in-graph LP solver sweep order:
      * scan    — Gauss-Seidel, one `lax.scan` step per expert per sweep
                  (best per-sweep progress, E×sweeps sequential steps);
      * batched — damped Jacobi, all experts water-fill per sweep in one
                  vectorized step (`solve_replica_loads_batched`; sweeps
                  are internally doubled to match Gauss-Seidel progress).
    """

    def __init__(
        self,
        statics: ScheduleStatics,
        sweeps: int = 6,
        locality: bool = True,
        mode: str = "microep",
        sequencing: str = "proportional",
        solver_mode: str = "scan",
    ):
        if mode not in ("microep", "vanilla"):
            raise ValueError(
                f"MicroEPScheduler mode={mode!r} is not a registered option; "
                f"choose one of: microep, vanilla")
        if sequencing not in ("proportional", "greedy"):
            raise ValueError(
                f"MicroEPScheduler sequencing={sequencing!r} is not a "
                f"registered option; choose one of: proportional, greedy")
        if solver_mode not in ("scan", "batched"):
            raise ValueError(
                f"MicroEPScheduler solver_mode={solver_mode!r} is not a "
                f"registered option; choose one of: scan, batched")
        self.statics = statics
        self.sweeps = sweeps
        self.locality = locality
        self.mode = mode
        self.sequencing = sequencing
        self.solver_mode = solver_mode
        # keep host numpy here: converting at call time keeps this object
        # safe to cache/reuse across different jit traces
        self._dev = np.asarray(statics.dev, np.int32)
        # heterogeneous groups (DESIGN.md §11): None = uniform fast path
        self._weights = (None if statics.weights is None
                         else np.asarray(statics.weights, np.float32))
        # MemFine token caps (DESIGN.md §16): None = memory-oblivious path
        self._mem_caps = (None if statics.mem_caps is None
                          else np.asarray(statics.mem_caps, np.float32))
        self._density = _subset_density(statics)

    def init_state(self) -> SolverState:
        e, r = self.statics.dev.shape
        return SolverState(x=jnp.zeros((e, r), jnp.float32))

    @jax.named_scope(scopes.ALG1)
    def __call__(
        self, input_eg: jax.Array, state: Optional[SolverState] = None,
        mem_caps: Optional[jax.Array] = None,
    ) -> Schedule:
        """input_eg: int32[E, G] per-(expert, source-device) token counts.

        ``mem_caps`` (f32[G] per-device token caps, MemFine DESIGN.md §16)
        overrides the statics-level caps for this call — the per-geometry
        plan the engine's ``moe_spec`` threads through the MoE layer.
        None falls back to ``statics.mem_caps`` (None = memory-oblivious,
        bit-identical to the pre-MemFine scheduler)."""
        st = self.statics
        dev = jnp.asarray(self._dev, jnp.int32)
        valid = dev >= 0
        loads = input_eg.sum(axis=1).astype(jnp.int32)           # [E]
        weights = (None if self._weights is None
                   else jnp.asarray(self._weights, jnp.float32))
        if mem_caps is None and self._mem_caps is not None:
            mem_caps = self._mem_caps
        caps = (None if mem_caps is None
                else jnp.asarray(mem_caps, jnp.float32))

        if self.mode == "vanilla":
            # Each source row dispatches within its own EP group: replica on
            # the token's own row.  flow[e, g, r] = input if dev[e,r] is in
            # g's row else 0.  With one replica per row (symmetric placement)
            # this is exactly Megatron EP.
            cols = st.placement.cols
            src_row = jnp.arange(st.num_devices, dtype=jnp.int32) // cols
            rep_row = jnp.where(valid, dev // cols, -1)          # [E, R]
            same_row = rep_row[:, None, :] == src_row[None, :, None]
            flow = jnp.where(same_row, input_eg[:, :, None], 0).astype(jnp.int32)
            x_int = flow.sum(axis=1)
            dl = device_loads(x_int.astype(jnp.float32), dev, st.num_devices)
            state_out = state if state is not None else self.init_state()
        else:
            with jax.named_scope(scopes.LP_SOLVE):
                if self.solver_mode == "batched":
                    # a damped-Jacobi sweep makes roughly half the progress
                    # of a Gauss-Seidel sweep but costs one vectorized step,
                    # so 2x the sweeps still cuts the sequential-depth
                    # bottleneck
                    sol = solve_replica_loads_batched(
                        loads.astype(jnp.float32),
                        dev,
                        st.num_devices,
                        x_init=None if state is None else state.x,
                        sweeps=2 * self.sweeps,
                        weights=weights,
                        mem_caps=caps,
                    )
                else:
                    sol = solve_replica_loads(
                        loads.astype(jnp.float32),
                        dev,
                        st.num_devices,
                        x_init=None if state is None else state.x,
                        sweeps=self.sweeps,
                        weights=weights,
                        mem_caps=caps,
                    )
            x_int = round_replica_loads(sol.x, loads, valid)
            routed = route_tokens(input_eg, x_int, dev,
                                  locality=self.locality,
                                  sequencing=self.sequencing)
            flow = routed.flow
            dl = device_loads(x_int.astype(jnp.float32), dev, st.num_devices)
            state_out = sol

        # balance: max over the mean device load — against *weighted* loads
        # on a heterogeneous group (weights are mean-normalized, so the
        # ideal per-unit-weight load is still the plain mean)
        mean = jnp.maximum(dl.mean(), 1e-9)
        dl_norm = dl if weights is None else dl / weights
        if self._density is None:
            lp_bound = jnp.full((), jnp.nan, jnp.float32)
        else:
            lp_bound = jnp.max(jnp.sum(jnp.asarray(self._density)
                                       * loads.astype(jnp.float32), axis=1))
        return Schedule(
            flow=flow,
            x_int=x_int,
            solver_state=state_out,
            max_load=dl.max(),
            balance=dl_norm.max() / mean,
            lp_bound=lp_bound,
            balance_gap=dl_norm.max() / jnp.maximum(lp_bound, 1e-9),
        )

    # ---------------- host-side oracle (paper's HiGHS path) ----------------
    def schedule_host(self, input_eg: np.ndarray,
                      mem_budgets: Optional[np.ndarray] = None) -> np.ndarray:
        """Solve with HiGHS on the host (paper §5.1 exact path).  Returns the
        optimal fractional x[E, R].  Used by tests/benches as the oracle.
        On a heterogeneous group this is the weighted LP (DESIGN.md §11);
        with memory token caps present (``mem_budgets`` argument, falling
        back to ``statics.mem_caps``) the caps enter as the MemFine
        feasibility rows of DESIGN.md §16."""
        loads = np.asarray(input_eg).sum(axis=1)
        if mem_budgets is None:
            mem_budgets = self.statics.mem_caps
        res = lp_host.solve_lpp1(loads, self.statics.dev,
                                 self.statics.num_devices,
                                 weights=self.statics.weights,
                                 mem_budgets=mem_budgets)
        return res.x
