"""The numbers that decide ``correct``: the program's first rounds set
against the reference's, each number beside its limit.

Losses compare as relative gaps.  Norms compare leaf by leaf: the gap
between the program's norm and the reference's, over the reference's
norm of that leaf or of the median leaf, whichever is larger; the
worst leaf gives the number.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

Check = Tuple[str, float, float]            # (name, value, limit)


def _leaves(tree) -> Dict[str, object]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(p): x for p, x in flat}


def _norm(x) -> float:
    """Norm of one leaf: on the host in float64, on the device in the
    leaf's own float32 (with float32 accumulation)."""
    if isinstance(x, np.ndarray):
        return float(np.linalg.norm(x.astype(np.float64).ravel()))
    import jax.numpy as jnp
    return float(jnp.sqrt(jnp.sum(jnp.square(x))))


def norms(tree) -> Dict[str, float]:
    """Per-leaf norms, each leaf taken whole over the node axis."""
    return {k: _norm(x) for k, x in _leaves(tree).items()}


def delta_norms(before, after) -> Dict[str, float]:
    """Per-leaf norms of ``after - before``."""
    a, b = _leaves(before), _leaves(after)
    return {k: _norm(b[k] - a[k]) for k in a}


def worst_leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
                   keep: Optional[Sequence[str]] = None) -> float:
    keys = list(ref) if keep is None else list(keep)
    if set(prog) != set(ref):
        raise ValueError(f"leaf sets differ: {sorted(set(prog) ^ set(ref))}")
    if not keys:
        return 0.0
    floor = float(np.median([ref[k] for k in keys]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], floor, 1e-30)
               for k in keys)


def moved_leaves(ref_grad: Dict[str, float], share: float = 1e-3
                 ) -> List[str]:
    """Leaves whose reference gradient is not nought to rounding: at
    least ``share`` of the median leaf's."""
    if not ref_grad:
        return []
    med = float(np.median(list(ref_grad.values())))
    return [k for k, v in ref_grad.items() if v >= share * med]


def rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-30)


def _passes(value: float, limit: float) -> bool:
    return bool(np.isfinite(value)) and value <= limit


def judge(checks: List[Check]) -> bool:
    return all(_passes(v, lim) for _, v, lim in checks)


def format_checks(checks: List[Check]) -> List[str]:
    return [f"{name} {value!r} limit {limit!r} "
            f"{'ok' if _passes(value, limit) else 'FAIL'}"
            for name, value, limit in checks]
