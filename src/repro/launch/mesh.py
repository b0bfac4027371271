"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — required for the dry-run's host-device
override to land before first jax initialization.

Every mesh is built by :func:`make_mesh` with ``Auto`` axis types: the
sharding rules place arrays with ``NamedSharding`` and let the compiler
propagate, which is what ``Auto`` means (``jax.make_mesh`` itself now
defaults to ``Explicit``).
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """A mesh of ``shape`` named ``axes`` (Auto axis types); ``devices``
    selects the devices explicitly (default: the first of ``jax.devices()``)."""
    shape, axes = tuple(shape), tuple(axes)
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips when ``multi_pod``."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Single-device mesh with the production axis names, for CPU smoke runs."""
    n = len(jax.devices())
    shape = (1, n) if n == 1 else (n, 1)
    return make_mesh(shape, ("data", "model"))


def serve_tp_mesh(tp: int, devices=None):
    """A 1-D ``("model",)`` mesh of ``tp`` devices for the tensor-parallel
    paged decode (:mod:`repro.serve.paged`). ``devices`` selects the
    slice explicitly (replica pinning); default = the first ``tp`` of
    ``jax.devices()``."""
    devices = list(devices) if devices is not None else jax.devices()[:tp]
    if len(devices) < tp:
        raise ValueError(f"need {tp} devices for tp={tp}, have "
                         f"{len(devices)} (set "
                         "--xla_force_host_platform_device_count or run on "
                         "a larger host)")
    return make_mesh((tp,), ("model",), devices=np.asarray(devices[:tp]))


def replica_meshes(replicas: int, tp: int, devices=None):
    """Disjoint ``("model",)`` meshes for data-parallel replica serving:
    ``replicas`` slices of ``tp`` devices each, carved consecutively from
    ``devices`` (default ``jax.devices()``). Slice ``i`` gets devices
    ``[i*tp, (i+1)*tp)`` — disjoint by construction, which is what lets
    :meth:`~repro.serve.cluster.ServeCluster.add_replica_group` pin each
    replica's arena and params to its own devices."""
    devices = list(devices) if devices is not None else jax.devices()
    need = replicas * tp
    if len(devices) < need:
        raise ValueError(f"need {need} devices for {replicas} replicas at "
                         f"tp={tp}, have {len(devices)}")
    return [serve_tp_mesh(tp, devices[i * tp:(i + 1) * tp])
            for i in range(replicas)]
