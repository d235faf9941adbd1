"""Production meshes. Functions, not module constants — importing this
module never touches jax device state.

``make_mesh`` is the constructor every caller uses: every axis is
``AxisType.Auto``, so GSPMD places whatever the sharding rules leave
open.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax


def make_mesh(shape: Sequence[int], axes: Sequence[str], *,
              devices: Optional[Sequence] = None):
    """``jax.make_mesh`` with every axis ``Auto``."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod ("data","model"); multi_pod adds a 2-pod axis.

    The axis roles follow the paper's case-studies: intra-operator (tensor)
    parallelism on the fast innermost "model" axis, data parallelism on
    "data", and pods connected by DCN carry only data parallelism
    (PaLM §5.3: 2x data parallel across pods, no inter-layer parallelism).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(*, model: int = 1, data: int = 0):
    """Small mesh over the locally available devices (tests/examples)."""
    n = len(jax.devices())
    if data == 0:
        data = n // model
    return make_mesh((data, model), ("data", "model"))


def make_pipeline_mesh(*, data: int, pipe: int, model: int,
                       devices: Optional[Sequence] = None):
    """Mesh with an explicit inter-operator ("pipe") axis for
    core/pipeline.py — the survey's hybrid dp x pp x tp layout (Table 2)."""
    return make_mesh((data, pipe, model), ("data", "pipe", "model"),
                     devices=devices)
