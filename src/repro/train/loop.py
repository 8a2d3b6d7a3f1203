"""Gradient-accumulated training loop with master/working parameter split.

The step follows DESIGN.md §2's TPU adaptation of the paper's training flow:

  master params (f32, MoE experts in *canonical* [E, ...] layout)
    --to_working-->  working params (model dtype, experts in *placement*
                     layout — the gather through the placement table)
    --scan over micro-batches-->  per-micro-batch loss/grad with MicroEP
                     scheduling per micro-batch, solver warm-start threaded
                     through the scan (paper §5.1 warm start)
    --vjp(to_working)-->  master grads.  The vjp of the placement gather is
                     exactly the EDP replica-sum (paper §B.3 gradient sync):
                     every replica slot's gradient scatter-adds into its
                     canonical expert.  GSPMD lowers it to the collectives
                     measured in moe/sync.py's explicit shard_map variant.
    --AdamW--> new master.

``LayoutHooks.to_working`` is identity-cast by default (CPU smoke path,
canonical == placement for the 1-device group); the launcher installs the
placement gather for distributed runs.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from .. import scopes
from ..configs.base import ArchConfig
from ..models import decoder as dec
from ..optim.adamw import AdamWConfig, AdamWState, adamw_init, adamw_update

__all__ = ["TrainState", "LayoutHooks", "make_train_step",
           "init_train_state"]


class TrainState(NamedTuple):
    master: Any          # f32 parameter tree (experts canonical)
    opt: AdamWState
    solver: Any          # MoE solver warm-start states (or None)
    step: jax.Array


@dataclasses.dataclass(frozen=True)
class LayoutHooks:
    """Layout/dtype transforms between optimizer and model parameter views."""

    to_working: Callable[[Any], Any]

    @classmethod
    def cast_only(cls, dtype=jnp.float32) -> "LayoutHooks":
        def to_working(master):
            return jax.tree_util.tree_map(
                lambda x: x.astype(dtype)
                if jnp.issubdtype(x.dtype, jnp.floating) else x, master)
        return cls(to_working=to_working)


def init_train_state(key, cfg: ArchConfig, opt_cfg: AdamWConfig = AdamWConfig(),
                     num_replicas: int = 1,
                     master_init: Optional[Callable] = None) -> TrainState:
    master = (master_init(key) if master_init is not None
              else dec.init_params(key, cfg, jnp.float32))
    return TrainState(
        master=master,
        opt=adamw_init(master),
        solver=dec.init_solver_states(cfg, num_replicas),
        step=jnp.zeros((), jnp.int32),
    )


def _split_micro(batch: dict, n_micro: int) -> dict:
    def split(x):
        b = x.shape[0]
        assert b % n_micro == 0, (b, n_micro)
        return x.reshape((n_micro, b // n_micro) + x.shape[1:])
    return jax.tree_util.tree_map(split, batch)


def make_train_step(
    cfg: ArchConfig,
    rt: dec.Runtime = dec.Runtime(),
    opt_cfg: AdamWConfig = AdamWConfig(),
    hooks: Optional[LayoutHooks] = None,
    n_micro: int = 1,
    lr_fn: Optional[Callable] = None,
    aux_coeff: float = 1e-4,
    z_coeff: float = 1e-4,
    master_grad_constraint: Optional[Callable] = None,
    with_expert_load: bool = False,
):
    """Build ``train_step(state, batch) -> (state, metrics_dict)``.

    ``batch`` leaves are [B, ...]; B is split into ``n_micro`` micro-batches
    scanned sequentially (per-micro-batch MicroEP scheduling — paper R2).

    ``with_expert_load=True`` (MoE configs only) adds an ``"expert_load"``
    f32[E_virt] vector — routed tokens per expert, summed over layers and
    micro-batches — to the metrics dict, feeding the telemetry recorder
    (TELEMETRY.md).  Scalar-only consumers must pop it before logging.
    """
    hooks = hooks or LayoutHooks.cast_only()
    if with_expert_load and not cfg.moe:
        raise ValueError("with_expert_load=True needs an MoE config")

    def train_step(ts: TrainState, batch: dict):
        with jax.named_scope(scopes.OPTIMIZER):
            params, vjp_fn = jax.vjp(hooks.to_working, ts.master)
        micro = _split_micro(batch, n_micro)

        def micro_fn(carry, mb):
            solver, gsum, msum, esum = carry
            (loss, aux), grads = jax.value_and_grad(
                dec.loss_fn, has_aux=True)(
                    params, cfg, mb, rt, solver,
                    aux_coeff=aux_coeff, z_coeff=z_coeff,
                    with_expert_load=with_expert_load)
            if with_expert_load:
                metrics, new_solver, eload = aux
                esum = esum + eload
            else:
                metrics, new_solver = aux
            gsum = jax.tree_util.tree_map(jnp.add, gsum, grads)
            msum = jax.tree_util.tree_map(
                lambda a, b: a + b.astype(jnp.float32), msum, metrics)
            return (new_solver, gsum, msum, esum), None

        # the loop's own work (carries, sums) is the scope's; the model's
        # layers inside it carry their own, innermost scopes
        with jax.named_scope(scopes.GRAD_ACCUM):
            zero_g = jax.tree_util.tree_map(jnp.zeros_like, params)
            zero_m = dec.Metrics(*(jnp.zeros(())
                                   for _ in dec.Metrics._fields))
            zero_e = jnp.zeros((cfg.num_experts * max(cfg.etp, 1),)
                               if with_expert_load else ())
            (solver, gsum, msum, esum), _ = jax.lax.scan(
                micro_fn, (ts.solver, zero_g, zero_m, zero_e), micro)
            gavg = jax.tree_util.tree_map(lambda g: g / n_micro, gsum)
            mavg = jax.tree_util.tree_map(lambda x: x / n_micro, msum)
        with jax.named_scope(scopes.OPTIMIZER):
            (master_grads,) = vjp_fn(gavg)
            if master_grad_constraint is not None:
                # pin grads to the (ZeRO-1-sharded) master layout so GSPMD
                # lowers the data-parallel reduction as reduce-scatter
                # rather than all-reduce + slice (§Perf lever)
                master_grads = master_grad_constraint(master_grads)
            lr = lr_fn(ts.opt.step) if lr_fn is not None else None
            new_master, new_opt, gnorm = adamw_update(
                master_grads, ts.opt, ts.master, opt_cfg, lr=lr)
            new_ts = TrainState(master=new_master, opt=new_opt,
                                solver=solver, step=ts.step + 1)

        out = {"loss": mavg.loss, "ce_loss": mavg.ce_loss,
               "aux_loss": mavg.aux_loss, "z_loss": mavg.z_loss,
               "balance": mavg.balance, "balance_gap": mavg.balance_gap,
               "flat_fill": mavg.flat_fill,
               "overflow": msum.overflow, "grad_norm": gnorm,
               "lr": jnp.asarray(lr if lr is not None else opt_cfg.lr)}
        if with_expert_load:
            out["expert_load"] = esum
        return new_ts, out

    return train_step
