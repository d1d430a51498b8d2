"""The comparisons that decide ``correct``.

Norms are compared leaf by leaf, by the gap between the program's norm
and the reference's (not the norm of their difference), measured against
the reference's norm of that leaf or of the median leaf, whichever is
larger, since some leaves are all but zero.
"""
from __future__ import annotations

import statistics
from typing import Dict, Iterable, Optional


def leaf_gaps(prog: Dict[str, float], ref: Dict[str, float],
              leaves: Optional[Iterable[str]] = None) -> Dict[str, float]:
    keys = list(ref if leaves is None else leaves)
    med = statistics.median(ref[k] for k in keys)
    return {k: abs(prog[k] - ref[k]) / max(ref[k], med) for k in keys}


def worst_leaf(prog, ref, leaves=None) -> float:
    return max(leaf_gaps(prog, ref, leaves).values())


def moving_leaves(ref_grad: Dict[str, float], floor: float = 1e-3):
    """Leaves whose first gradient in the reference is above ``floor``
    times the median leaf's; the others move under Adam by round-off."""
    med = statistics.median(ref_grad.values())
    return [k for k, v in ref_grad.items() if v > floor * med]


def _norm(x) -> float:
    import numpy as np
    return float(np.linalg.norm(np.ravel(x)))


def train_gaps(prog: Dict, ref: Dict) -> Dict[str, float]:
    """The numbers a training cell can compare: each checked step's loss
    (relative gap); the first gradient by the worst leaf, as the gap of
    its norms and as the norm of its difference over the leaf's norm
    (moving leaves only); each leaf's change after the checked steps by
    the worst leaf.  The cell compares those its traffic file gives a
    limit; PERF.md says why."""
    import numpy as np
    ref_norm = {k: _norm(v) for k, v in ref["grads"].items()}
    prog_norm = {k: _norm(v) for k, v in prog["grads"].items()}
    moving = moving_leaves(ref_norm)
    diff = [_norm(np.ravel(prog["grads"][k]) - np.ravel(ref["grads"][k]))
            / ref_norm[k] for k in moving]
    changes = leaf_gaps(prog["change_norms"], ref["change_norms"], moving)
    gaps = {f"loss{i}_rel_gap": abs(p - r) / abs(r) for i, (p, r) in
            enumerate(zip(prog["losses"], ref["losses"]), 1)}
    gaps.update(grad_leaf_gap=worst_leaf(prog_norm, ref_norm),
                grad_diff_leaf_gap=max(diff),
                change_leaf_gap=max(changes.values()))
    return gaps


def widest_logit_gap(logits, target) -> float:
    """Widest gap by which a served token's reference logit lies below the
    reference's best at that position.  logits (M, V), target (M,)."""
    import jax.numpy as jnp
    t = jnp.asarray(target)
    got = jnp.take_along_axis(logits, t[:, None], axis=-1)[:, 0]
    return float(jnp.max(jnp.max(logits, axis=-1) - got))
