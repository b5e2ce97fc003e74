"""Production mesh builders (functions only — importing this module never
touches jax device state)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes):
    """`jax.make_mesh` with every axis Auto: the sharding rules here
    annotate with `with_sharding_constraint` and leave the propagation
    (gathers included) to GSPMD, which Explicit axes would refuse."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod.

    On the multi-pod mesh the "pod" axis serves double duty: MPC party
    axis for the selection workload (DESIGN.md §3), extra DP dim for
    plain training/serving.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_host_mesh(model_parallel: int = 1):
    """Mesh over whatever devices actually exist (smoke runs, examples)."""
    n = len(jax.devices())
    assert n % model_parallel == 0
    return auto_mesh((n // model_parallel, model_parallel),
                     ("data", "model"))


def data_shards(mesh) -> int:
    """Number of data-parallel shards (routing groups for MoE)."""
    n = mesh.shape.get("data", 1)
    if "pod" in mesh.axis_names:
        n *= mesh.shape["pod"]
    return n
