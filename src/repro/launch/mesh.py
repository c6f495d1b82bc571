"""Production mesh builders + the multi-host init lane.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state; the dry-run sets
``--xla_force_host_platform_device_count`` before calling it.

Multi-host: ``init_distributed()`` is the single entry point for
``jax.distributed.initialize`` — guarded so single-process runs (tests,
the CPU container) never touch the distributed client — and
``is_main_process()`` / ``process_count()`` are the per-host guards the
launcher and checkpoint layer route through.  ``make_train_mesh`` is the
launcher's one mesh constructor: flags land here instead of ad-hoc
``jax.make_mesh`` calls, so the pod axis and the single-device
degenerate case are handled in exactly one place.

Every mesh in the repo is built by ``make_mesh``, which marks all axes
``Auto``.  ``jax.make_mesh`` defaults to ``Explicit`` axes, on which
``with_sharding_constraint`` (``Runtime.constrain``, the gradient and
flat-buffer constraints of the train step) is refused: the model and
engine code leave the partitioning of everything they do not pin to
the SPMD partitioner, which is what ``Auto`` means.
"""
from __future__ import annotations

import os
from typing import Optional

import jax
from jax.sharding import AxisType


def make_mesh(shape, axis_names) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis ``Auto`` (see the module doc)."""
    return jax.make_mesh(tuple(shape), tuple(axis_names),
                         axis_types=(AxisType.Auto,) * len(axis_names))


def init_distributed(*, coordinator_address: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None,
                     local_device_ids=None) -> bool:
    """Initialize the multi-process JAX runtime when one is configured.

    Guarded no-op returning False when nothing asks for it: no explicit
    arguments AND no coordinator in the environment
    (``JAX_COORDINATOR_ADDRESS`` / ``COORDINATOR_ADDRESS`` — the names
    jax's cluster autodetect and TPU pod launchers export).  Calling it
    a second time in an already-initialized process is safe."""
    env = os.environ
    configured = (coordinator_address is not None
                  or bool(num_processes)
                  or bool(env.get("JAX_COORDINATOR_ADDRESS"))
                  or bool(env.get("COORDINATOR_ADDRESS")))
    if not configured:
        return False
    kw = {}
    if coordinator_address is not None:
        kw["coordinator_address"] = coordinator_address
    if num_processes:
        kw["num_processes"] = num_processes
    if process_id is not None:
        kw["process_id"] = process_id
    if local_device_ids is not None:
        kw["local_device_ids"] = local_device_ids
    try:
        jax.distributed.initialize(**kw)
    except RuntimeError as e:  # double init: keep the existing client
        if "already" not in str(e).lower():
            raise
    return True


def process_count() -> int:
    return jax.process_count()


def is_main_process() -> bool:
    """Per-host guard: logging, metrics files, and meta writes happen on
    process 0 only (every process still writes its own checkpoint
    shard)."""
    return jax.process_index() == 0


def make_train_mesh(data: int = 0, model: int = 1,
                    pod: int = 1) -> Optional[jax.sharding.Mesh]:
    """The launcher's mesh: ``(pod?, data, model)`` axes over the global
    device set, with the size-1 pod axis dropped.  ``data=0`` means "all
    remaining devices".  Returns None for the degenerate 1x1x1 case so
    single-device runs skip sharding machinery entirely."""
    n_dev = len(jax.devices())
    n_data = data or max(1, n_dev // (model * pod))
    if pod > 1:
        return make_mesh((pod, n_data, model), ("pod", "data", "model"))
    if n_data * model > 1:
        return make_mesh((n_data, model), ("data", "model"))
    return None


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """Single pod: (data=16, model=16) = 256 chips (TPU v5e pod).
    Multi-pod:  (pod=2, data=16, model=16) = 512 chips; "pod" is pure DP."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def data_axes_of(mesh: jax.sharding.Mesh):
    return tuple(a for a in ("pod", "data") if a in mesh.shape)
