"""Production mesh definitions (DESIGN.md §3) and multi-host launch
scaffolding.

Kept as FUNCTIONS so importing this module never touches jax device state —
the dry-run must set XLA_FLAGS before the first jax initialization.

Multi-host: every launcher (train/serve) takes ``--coordinator``,
``--num-hosts`` and ``--host-id`` (:func:`add_distributed_cli_args`); with
``--num-hosts`` above 1, :func:`maybe_initialize_distributed` calls
``jax.distributed.initialize`` before any other jax API so each process
sees the global device set.  The single-host default is a strict no-op —
nothing about the existing entry points changes.

Mesh axes are ``Auto``: GSPMD propagates shardings from the
``with_sharding_constraint`` hints the runtime places (DESIGN.md §3).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_local_mesh",
           "add_distributed_cli_args", "maybe_initialize_distributed",
           "enable_compile_cache", "COMPILE_CACHE_DIR"]

# Fixed, so a later run of this checkout finds what an earlier one cached
# (the directory is part of the cache key).
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; call before the first
    compile.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX's own reading
    of it stands and nothing is set here; otherwise the cache goes to
    ``<checkout>/.jax_cache``.  Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    return str(COMPILE_CACHE_DIR)


def add_distributed_cli_args(ap) -> None:
    """Multi-host launch flags, shared by the train and serve drivers."""
    g = ap.add_argument_group("multi-host")
    g.add_argument("--coordinator", default=None, metavar="HOST:PORT",
                   help="coordinator address for jax.distributed.initialize "
                        "(required when --num-hosts > 1)")
    g.add_argument("--num-hosts", type=int, default=1,
                   help="total processes in the multi-host job (default 1: "
                        "single-host, no distributed init)")
    g.add_argument("--host-id", type=int, default=0,
                   help="this process's index in [0, --num-hosts)")


def maybe_initialize_distributed(args) -> bool:
    """Validate the multi-host flags and initialize the JAX distributed
    runtime when a real multi-host job is requested.

    Returns True when ``jax.distributed.initialize`` was called.  With the
    default ``--num-hosts 1`` this validates and returns False without
    touching jax state (the flags are inert scaffolding on one host).
    Raises ValueError on inconsistent flags — the launchers surface it as
    a CLI error before any device work starts.
    """
    num_hosts = getattr(args, "num_hosts", None)
    num_hosts = 1 if num_hosts is None else int(num_hosts)
    host_id = getattr(args, "host_id", None)
    host_id = 0 if host_id is None else int(host_id)
    coordinator = getattr(args, "coordinator", None)
    if num_hosts < 1:
        raise ValueError(f"--num-hosts must be >= 1, got {num_hosts}")
    if not 0 <= host_id < num_hosts:
        raise ValueError(f"--host-id {host_id} outside "
                         f"[0, --num-hosts {num_hosts})")
    if num_hosts == 1:
        if coordinator is not None:
            raise ValueError("--coordinator is only meaningful with "
                             "--num-hosts > 1")
        return False
    if not coordinator:
        raise ValueError("--num-hosts > 1 needs --coordinator HOST:PORT")
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=num_hosts,
                               process_id=host_id)
    return True


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 256 chips as (data=16, model=16).  Multi-pod: 2 pods,
    (pod=2, data=16, model=16); the 'pod' axis carries only data-parallel
    gradient reduction (DCN-class links), MicroEP groups stay inside a pod
    (ICI-class links) — the paper's PP-per-node analogue under slow
    inter-node links."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over host platform devices (tests / examples).  Requires
    the caller to have set --xla_force_host_platform_device_count."""
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
