"""Multi-host bootstrap: the raft-dask ``Comms`` analog.

Reference: python/raft-dask/raft_dask/common/comms.py:93-245 — pick an
NCCL root, broadcast the uniqueId, run per-worker init that injects a
ready communicator into each worker's handle (§3.5 call stack).

TPU design: `jax.distributed.initialize` plays the bootstrap role
(coordinator address ≈ the NCCL uniqueId broadcast; process_id ≈ rank);
after it, every process sees the global device set and a `Mesh` over
those devices is the communicator clique. This module is the ONE entry
point for that init — :func:`init_distributed` — so every launcher
(the fleet dryrun, a pod job, a test worker) bootstraps identically:

* **env autodetect**: each field falls back to
  ``RAFT_TPU_COORDINATOR`` / ``RAFT_TPU_NUM_PROCESSES`` /
  ``RAFT_TPU_PROCESS_ID`` (then the ``JAX_*`` equivalents), so a
  launcher can export three variables and every worker just calls
  ``init_comms()`` with no arguments;
* **all-or-nothing**: a partial specification (coordinator set but no
  process id, etc.) is a configuration bug that would otherwise surface
  as a hang at first collective — it raises immediately, naming what is
  set and what is missing;
* **idempotent**: re-init with the same (coordinator, n, rank) triple
  is a no-op (serving code paths may all call it defensively); re-init
  with a DIFFERENT triple raises — one process is one rank for life.

`init_comms` wires the result into a `Resources` so algorithms reach it
via `get_comms()`, exactly the reference's injection pattern
(comms/std_comms.hpp:69).
"""
from __future__ import annotations

import os
from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

from ..core.errors import expects
from .comms import AxisComms

__all__ = ["init_comms", "init_distributed", "local_mesh"]

# per-field env fallbacks, first hit wins (RAFT_TPU_* preferred so a
# launcher can scope the fleet without touching jax's own variables)
_ENV_VARS = {
    "coordinator_address": ("RAFT_TPU_COORDINATOR", "JAX_COORDINATOR_ADDRESS"),
    "num_processes": ("RAFT_TPU_NUM_PROCESSES", "JAX_NUM_PROCESSES"),
    "process_id": ("RAFT_TPU_PROCESS_ID", "JAX_PROCESS_ID"),
}

# the (coordinator, num_processes, process_id) triple this process was
# initialized with — the idempotence guard's memory
_initialized: Optional[Tuple[str, int, int]] = None


def _resolve_env(coordinator_address=None, num_processes=None,
                 process_id=None, environ=None) -> dict:
    """Merge explicit args over the env fallbacks into one validated
    config: ``{"distributed": False}`` when nothing is specified, else
    the full coerced triple. Raises on a PARTIAL specification — the
    alternative is a silent hang at the first collective. ``environ``
    is injectable for tests."""
    env = os.environ if environ is None else environ
    vals = {"coordinator_address": coordinator_address,
            "num_processes": num_processes, "process_id": process_id}
    source = {}
    for field, names in _ENV_VARS.items():
        if vals[field] is not None:
            source[field] = "argument"
            continue
        for name in names:
            raw = env.get(name)
            if raw is not None and str(raw) != "":
                vals[field] = raw
                source[field] = f"env {name}"
                break
    given = {f for f, v in vals.items() if v is not None}
    if not given:
        return {"distributed": False}
    missing = sorted(set(_ENV_VARS) - given)
    expects(not missing,
            "partial jax.distributed config: %s but missing %s — set all "
            "three (args to init_distributed, or env %s)",
            ", ".join(f"{f}={vals[f]!r} ({source[f]})" for f in sorted(given)),
            ", ".join(f"{f} ({'/'.join(_ENV_VARS[f])})" for f in missing),
            "/".join(v for vs in _ENV_VARS.values() for v in vs[:1]))
    try:
        num = int(vals["num_processes"])
        pid = int(vals["process_id"])
    except (TypeError, ValueError):
        expects(False, "non-integer num_processes=%r / process_id=%r",
                vals["num_processes"], vals["process_id"])
    expects(num >= 1, "num_processes must be >= 1, got %d", num)
    expects(0 <= pid < num, "process_id %d out of range [0, %d)", pid, num)
    return {"distributed": True,
            "coordinator_address": str(vals["coordinator_address"]),
            "num_processes": num, "process_id": pid}


def init_distributed(coordinator_address: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None) -> dict:
    """THE ``jax.distributed`` entry point (module docstring): resolve
    args+env, initialize once, and return the resolved config —
    ``{"distributed": False}`` (single-process), or the full triple plus
    ``"already": True`` when this process was already initialized with
    the same triple. Call BEFORE any jax operation that touches the
    backend; every process in the fleet must resolve the same
    coordinator and num_processes."""
    global _initialized
    cfg = _resolve_env(coordinator_address, num_processes, process_id)
    if not cfg["distributed"]:
        return cfg
    triple = (cfg["coordinator_address"], cfg["num_processes"],
              cfg["process_id"])
    if _initialized is not None:
        expects(_initialized == triple,
                "jax.distributed already initialized as %s; refusing "
                "re-init as %s (one process is one rank for life)",
                _initialized, triple)
        return {**cfg, "already": True}
    try:
        jax.distributed.initialize(coordinator_address=triple[0],
                                   num_processes=triple[1],
                                   process_id=triple[2])
    except RuntimeError as e:
        # initialized outside this module (e.g. a launcher calling jax
        # directly) — adopt it; anything else is a real bootstrap error
        if "already" not in str(e).lower():
            raise
    _initialized = triple
    return cfg


def local_mesh(n_devices: Optional[int] = None, axis: str = "shard",
               platform: Optional[str] = None) -> Mesh:
    """1-D mesh over local devices (the LocalCUDACluster-style test path).
    Raises when ``platform`` (default: JAX's default) has fewer than
    ``n_devices`` devices — it never swaps in another platform's."""
    devices = jax.devices(platform) if platform else jax.devices()
    if n_devices is not None:
        expects(len(devices) >= n_devices, "need %d devices, have %d",
                n_devices, len(devices))
        devices = devices[:n_devices]
    return Mesh(np.array(devices), (axis,))


def init_comms(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    n_devices: Optional[int] = None,
    axis: str = "shard",
    resources=None,
) -> Tuple[Mesh, AxisComms]:
    """Bootstrap a communicator clique → (mesh, comms).

    Single-process (nothing specified by arg OR env): a mesh over local
    devices — the raft-dask LocalCluster path. Multi-process: runs
    :func:`init_distributed` first (DCN bootstrap, env-autodetected:
    a worker under a launcher that exported ``RAFT_TPU_COORDINATOR``/
    ``_NUM_PROCESSES``/``_PROCESS_ID`` calls ``init_comms()`` bare),
    then builds the mesh over the *global* device set.

    When ``resources`` is given, the comms object is injected via
    ``set_comms`` (the build_comms_nccl_only analog).
    """
    init_distributed(coordinator_address, num_processes, process_id)
    mesh = local_mesh(n_devices, axis)
    comms = AxisComms(axis, size=mesh.shape[axis])
    if resources is not None:
        resources.set_comms(comms)
    return mesh, comms
