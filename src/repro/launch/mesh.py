"""Mesh construction (functions, not module constants — importing this
module never touches jax device state)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` with ``Auto`` axes.  Recent jax builds ``Explicit``
    axes by default, under which ``with_sharding_constraint`` asserts a
    layout instead of requesting one; the repo's ``logical()`` rules
    request layouts and leave the rest to GSPMD."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips).

    Axes:
      pod   — inter-pod data parallelism (gradient reduction only; the only
              traffic crossing the slow inter-pod links)
      data  — intra-pod DP/FSDP
      model — tensor/expert/sequence parallelism
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(ndev: int = 8):
    """(data, model) mesh over ``ndev`` devices: serving's ``--shard`` mesh
    and the CI-scale dry-run mesh.  Degrades to a thinner "model" axis
    when fewer devices are available (ndev=1 -> 1x1) instead of building
    an impossible (0, 4) mesh."""
    model = next(m for m in (4, 2, 1) if ndev % m == 0)
    return make_mesh((ndev // model, model), ("data", "model"))
