"""Production meshes.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state).  Axes:

  pod   — failure/locality domain (the paper's "edge site"); crosses the
          slow (DCN/WAN-class) links where gradient compression applies
  data  — FSDP / batch parallelism (fast ICI)
  model — tensor/expert parallelism (fast ICI)
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

from repro.distributed.sharding import data_shards  # noqa: F401 (re-export)


def _mesh(shape, axes):
    """Mesh with Auto axis types: the program places arrays through
    ``with_sharding_constraint`` and jit in/out shardings, which act on
    Auto axes only (``jax.make_mesh`` defaults to Explicit axes)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def elastic_mesh(model_parallel: int = 16, pods: int = 1):
    """Build the largest (pod, data, model) mesh the live devices support —
    restore-time elasticity: a checkpoint re-shards onto whatever is alive."""
    n = len(jax.devices())
    model_parallel = min(model_parallel, n)
    while n % model_parallel:
        model_parallel //= 2
    rest = n // model_parallel
    pods = min(pods, rest)
    while rest % pods:
        pods -= 1
    data = rest // pods
    if pods > 1:
        return _mesh((pods, data, model_parallel), ("pod", "data", "model"))
    return _mesh((data, model_parallel), ("data", "model"))


def serving_mesh(model_parallel: int = 1):
    """(data, model) mesh over the live devices for the serving runtime.

    ``model_parallel`` is clamped down to the nearest divisor of the device
    count; the remaining devices become the 'data' axis (decode slots /
    request batch).  With one device this is the degenerate (1, 1) mesh —
    the sharded engine code path with single-device placement, which the
    parity tests use to keep the sharded runtime exercised in 1-CPU CI.
    """
    n = len(jax.devices())
    model_parallel = max(1, min(model_parallel, n))
    while n % model_parallel:
        model_parallel -= 1
    return _mesh((n // model_parallel, model_parallel), ("data", "model"))

