"""Production meshes.

Single pod: 256 TPU v5e chips as (data=16, model=16).
Multi-pod:  2 pods = 512 chips as (pod=2, data=16, model=16); the ``pod``
axis carries data parallelism (or pipeline stages — Takeaway #1 puts PP on
the slowest links, which is exactly the pod boundary).

These are FUNCTIONS so importing this module never touches jax device
state; callers (dryrun.py) must set XLA_FLAGS before first jax init.
"""
from __future__ import annotations

import jax


def _mk(shape, axes):
    """Mesh with Auto axes: GSPMD propagates shardings and
    ``with_sharding_constraint`` takes bare ``PartitionSpec``s."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_pipeline_mesh(n_stages: int = 2, n_data: int = 4):
    """PP x DP mesh for the shard_map pipeline runtime (tests/examples)."""
    return _mk((n_stages, n_data), ("pipe", "data"))


def make_ring_mesh(n_seq: int = 0, n_data: int = 1):
    """DP x SP mesh for ring-attention sequence parallelism.

    The ``seq`` axis carries the searched ``plan.sp_degree``: K/V panels
    rotate around it (runtime/sequence.py) and batch token dims shard
    over it (runtime/sharding.py).  ``n_seq=0`` takes every device left
    after the ``data`` axis.
    """
    n = len(jax.devices())
    n_seq = n_seq or n // n_data
    return _mk((n_data, n_seq), ("data", "seq"))


def make_expert_mesh(n_ep: int = 0, n_data: int = 1):
    """DP x EP mesh for expert parallelism.

    The ``expert`` axis carries the searched ``plan.ep_degree`` (format
    v5): expert weights shard over it (runtime/sharding.py), the batch
    dim co-shards over data x expert, and MoE dispatch runs the
    all-to-all path (models/moe.py::_moe_ep).  ``n_ep=0`` takes every
    device left after the ``data`` axis.
    """
    n = len(jax.devices())
    n_ep = n_ep or n // n_data
    return _mk((n_data, n_ep), ("data", "expert"))


def make_local_mesh(model: int = 1):
    """Whatever this host offers (examples, smoke tests)."""
    n = len(jax.devices())
    model = min(model, n)
    return _mk((n // model, model), ("data", "model"))
