"""Production mesh construction.

Functions (not module constants) so importing never touches jax device
state. TPU v5e targets: 256 chips/pod (16x16), 2 pods = 512 chips.

Every mesh in the repository is built through :func:`make_mesh`, which
pins each axis to ``AxisType.Auto``: since JAX 0.9 ``jax.make_mesh``
defaults to Explicit axes, under which ``with_sharding_constraint`` and
the ``shard_map`` programs of ``core.spmd_exec`` refuse the specs they
are given.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

from repro.models.sharding import ShardingPlan


def make_mesh(shape, axes, *, devices=None) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis ``AxisType.Auto``; ``devices``
    (default: ``jax.devices()``) may be described, not attached,
    devices — e.g. those of ``topologies.get_topology_desc``."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_plan(mesh, shard_seq: bool = True) -> ShardingPlan:
    axes = mesh.axis_names
    data_axes = ("pod", "data") if "pod" in axes else ("data",)
    return ShardingPlan(mesh=mesh, data_axes=data_axes, model_axis="model",
                        shard_seq=shard_seq)


def make_io_mesh(n_nodes: int, lagg: int, lmem: int, *, devices=None):
    """3-D collective-I/O mesh view (node, lagg, lmem) — see core.tam."""
    return make_mesh((n_nodes, lagg, lmem), ("node", "lagg", "lmem"),
                     devices=devices)


# Hardware constants (TPU v5e) used by the roofline analysis.
PEAK_FLOPS_BF16 = 197e12          # per chip
HBM_BW = 819e9                    # bytes/s per chip
ICI_BW = 50e9                     # bytes/s per link
