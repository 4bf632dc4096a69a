"""jnp reference for the fused low-rank apply sweep.

The merge every engine runs after an adapter-wire exchange is, per
matrix leaf,

    out[i] = w[i] + Σ_j coeffs[i, j] · (B[j] @ A[j])         (naive)
    out[i] = w[i] + Σ_j coeffs[i, j] · (B[j] @ Ã[i, j])      (RegMean)

with a *sequential* per-sender accumulation of the delta and ONE final
add onto ``w`` — the contract the Pallas sweep reproduces tile by tile
(the reduction over the rank axis lives entirely inside each ``B @ A``
dot, so tiling the output never splits it).  Accumulating the delta
separately (rather than onto ``w``) lets the plane sweep apply it
straight to the packed buffer span: ``flat(w) + flat(delta)`` runs the
same elementwise adds as ``flat(w + delta)``, so the buffer-native add
is bit-identical to materializing the leaf.  This file is the
executable definition: the materialized per-sender ``[d, k]`` products
the fused plane sweep exists to avoid.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro import fp32


def lowrank_delta_ref(coeffs: jnp.ndarray, b: jnp.ndarray,
                      a: jnp.ndarray) -> jnp.ndarray:
    """The merged delta ``Σ_j coeffs[:, j]·(B_j @ A_j)`` alone:
    ``coeffs`` [N, S]; ``b`` [S, *lead, d, r]; ``a`` [S, *lead, r, k]
    (shared) or [N, S, *lead, r, k] (per-receiver RegMean factors)
    -> [N, *lead, d, k].

    Senders accumulate in index order j = 0..S-1; a zero coefficient
    contributes an exact ``+ 0.0`` (so dense gossip rows with
    non-neighbors zeroed reproduce the neighbor-only loop)."""
    n_send = b.shape[0]
    per_recv = a.ndim == b.ndim + 1
    delta = None
    for j in range(n_send):
        if per_recv:
            pj = fp32.matmul(b[j][None].astype(jnp.float32),
                             a[:, j].astype(jnp.float32),
                             preferred_element_type=jnp.float32)
        else:
            pj = fp32.matmul(b[j].astype(jnp.float32),
                             a[j].astype(jnp.float32),
                             preferred_element_type=jnp.float32)[None]
        cshape = (coeffs.shape[0],) + (1,) * (pj.ndim - 1)
        term = coeffs[:, j].reshape(cshape) * pj
        delta = term if delta is None else delta + term
    return delta


def lowrank_apply_ref(w: jnp.ndarray, coeffs: jnp.ndarray,
                      b: jnp.ndarray, a: jnp.ndarray) -> jnp.ndarray:
    """``w`` [N, *lead, d, k] + :func:`lowrank_delta_ref` of the factor
    bank -> merged [N, *lead, d, k].  ``lead`` is empty for plain
    matrix leaves; a scanned-stack leaf carries its layer axis there
    and every product broadcasts over it."""
    w = jnp.asarray(w, jnp.float32)
    if b.shape[0] == 0:
        return w
    return w + lowrank_delta_ref(coeffs, b, a)
