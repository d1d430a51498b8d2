"""Global lowering flags.

``force_unroll`` makes every ``lax.scan`` in the model fully unroll.  XLA's
``cost_analysis`` counts a while-loop body ONCE regardless of trip count,
so the dry-run's shallow roofline probes compile with unrolled scans to get
true per-device FLOP/byte/collective counts; production lowering keeps the
rolled scans (small HLO, fast compiles).
"""
from __future__ import annotations

import contextlib

_UNROLL = False


def unroll_scans() -> bool:
    return _UNROLL


def scan_unroll(length: int) -> int:
    """`unroll=` argument for lax.scan."""
    return length if _UNROLL else 1


@contextlib.contextmanager
def force_unroll(on: bool = True):
    global _UNROLL
    old = _UNROLL
    _UNROLL = on
    try:
        yield
    finally:
        _UNROLL = old


# ---------------------------------------------------------------------------
# activation batch-sharding anchor
# ---------------------------------------------------------------------------
_BATCH_AXES = None
_SEQ_AXIS = None          # (axis_name, axis_size) for sequence parallelism
_MESH = None              # ambient mesh for shard_map-based layers


def current_mesh():
    return _MESH


def current_batch_axes():
    return _BATCH_AXES


@contextlib.contextmanager
def batch_sharding(axes, seq_axis=None, seq_axis_size=1, mesh=None):
    """While tracing under this context, ``constrain_batch`` pins the leading
    (batch) dim of activations to the given mesh axes — anchors GSPMD so the
    batch dimension never silently degrades to replicated.

    ``seq_axis`` additionally shards dim 1 (the sequence) of rank>=3
    activations — Megatron-style sequence parallelism for the residual
    stream, our beyond-paper memory optimization (EXPERIMENTS.md §Perf)."""
    global _BATCH_AXES, _SEQ_AXIS, _MESH
    old, olds, oldm = _BATCH_AXES, _SEQ_AXIS, _MESH
    _BATCH_AXES = tuple(axes) if axes else None
    _SEQ_AXIS = (seq_axis, seq_axis_size) if seq_axis else None
    _MESH = mesh
    try:
        yield
    finally:
        _BATCH_AXES, _SEQ_AXIS, _MESH = old, olds, oldm


def _constrain(x, spec_axes):
    """Pin ``x`` to the ambient mesh.  Outside ``batch_sharding`` (no axes)
    or without a mesh there is nothing to pin to; with both, a layout the
    mesh cannot take is an error, not a silent no-op."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(_MESH, P(*spec_axes)))


def constrain_batch(x):
    if _BATCH_AXES is None or _MESH is None:
        return x
    rest = [None] * (x.ndim - 1)
    if (_SEQ_AXIS is not None and x.ndim >= 3
            and x.shape[1] % max(1, _SEQ_AXIS[1]) == 0):
        rest[0] = _SEQ_AXIS[0]
    return _constrain(x, (_BATCH_AXES, *rest))


def constrain_batch_only(x):
    """Pin ONLY the leading dim to the batch axes (no sequence sharding) —
    used for tensors whose dim-1 must stay unsharded (MoE dispatch buffers)."""
    if _BATCH_AXES is None or _MESH is None:
        return x
    return _constrain(x, (_BATCH_AXES, *([None] * (x.ndim - 1))))


def seq_sharding_active() -> bool:
    return _SEQ_AXIS is not None
