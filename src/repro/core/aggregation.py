"""Decentralized model aggregation (gossip round).

Each node averages the (de-quantized) student parameters it received from
its neighbours together with its own, weighted by local dataset sizes —
FedAvg-style weights, evaluated per node over its neighbourhood (no
central server).

With the adapter-rank wire (``core/adapters.py``) matrix leaves stop
averaging parameters and instead *merge deltas*: each receiver applies
``W += Σ_j c_ij·(B_j @ Ã_j)`` through ``kernels/lowrank_apply``.
:func:`regmean_adjust` computes the RegMean variant of ``Ã`` — the
gram-weighted least-squares merge ``(Σ_j c_j Δ_j G_j)(Σ_j c_j G_j)⁻¹``
restricted to the low-rank factors, so the merge weighs each sender's
delta by the geometry its gram statistic reports instead of by dataset
size alone.  Grams off falls back to the naive weighted factor sum
(``Ã = A``, coefficients used as-is).
"""
from __future__ import annotations

from typing import Any, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import fp32


def weighted_tree_mean(trees: Sequence[Any], weights: Sequence[float]):
    w = np.asarray(weights, np.float64)
    w = w / w.sum()

    def combine(*leaves):
        out = sum(wi * leaf.astype(jnp.float32)
                  for wi, leaf in zip(w, leaves))
        return out.astype(leaves[0].dtype)

    return jax.tree_util.tree_map(combine, *trees)


def neighborhood_aggregate(node: int, own_tree, received: List[Any],
                           own_size: float, received_sizes: List[float]):
    """Aggregate own + neighbour models, dataset-size weighted."""
    return weighted_tree_mean([own_tree] + received,
                              [own_size] + list(received_sizes))


# Ridge strength of the RegMean solve, relative to tr(Gsum)/k.  The
# wire gram is a rank-r proxy (AᵀA of rank-r factors), so Gsum is
# heavily rank-deficient and the solve's conditioning is set BY the
# ridge: at 1e-5 a one-ulp (FMA-rounding) difference in Gsum was
# amplified ~1e7x into O(10%) disagreement between exchange modes.
# 1e-3 caps the amplification at ~1e3 (modes agree to ~1e-4 relative)
# while the equal-gram normalization property still holds to ~0.1%.
REGMEAN_EPS = 1e-3


def regmean_adjust(a: jnp.ndarray, grams: jnp.ndarray,
                   coeffs: jnp.ndarray, *,
                   per_recv: Optional[bool] = None,
                   eps: float = REGMEAN_EPS) -> jnp.ndarray:
    """RegMean-adjusted per-receiver wire factors for one matrix leaf.

    ``a`` [S, *lead, r, k] per-sender factors; ``grams``
    [S, *lead, k, k] per-sender gram statistics; ``coeffs`` [N, S]
    merge coefficients (zero for non-neighbors).  ``lead`` is empty
    for plain matrix leaves; a scanned stack's layer axis broadcasts
    through every product and solve.  Per receiver ``i``::

        Gsum_i  = Σ_j coeffs[i, j]·G_j  (+ scaled ridge)
        Ã[i, j] = A_j G_j Gsum_i⁻¹

    so ``Σ_j coeffs[i, j]·B_j Ã[i, j] = (Σ_j c_j Δ̂_j G_j)(Σ_j c_j
    G_j)⁻¹`` — the RegMean closed form over the rank-r deltas.  The
    normalization is built in: with equal grams this reduces to
    ``A_j / Σ_j c_ij`` (the *normalized* weighted factor average).
    The ridge is trace-scaled (``eps·tr(Gsum)/k + 1e-6``) so isolated
    receivers (all-zero coefficient rows) stay finite — their zero
    coefficients then zero the merge exactly.

    ``per_recv=True`` (the mesh ppermute exchange, where each receiver
    holds its own dequantized view of the wire): ``a``
    [N, S, *lead, r, k] with ``grams`` [N, S, *lead, k, k] run the
    same closed form per receiver row.  The default infers the legacy
    no-lead convention (``grams.ndim == 4``); callers with lead axes
    must pass the flag."""
    k = grams.shape[-1]
    a32 = a.astype(jnp.float32)
    g32 = grams.astype(jnp.float32)
    c32 = coeffs.astype(jnp.float32)
    if per_recv is None:
        per_recv = grams.ndim == 4
    gsum = fp32.einsum("ns,ns...kl->n...kl" if per_recv
                       else "ns,s...kl->n...kl", c32, g32)
    tr = jnp.trace(gsum, axis1=-2, axis2=-1) / k
    gsum = gsum + (eps * tr + 1e-6)[..., None, None] * \
        jnp.eye(k, dtype=jnp.float32)
    ag = fp32.matmul(a32, g32)              # [(N,) S, *lead, r, k]
    # Gsum is symmetric: solve(Gsum_i, agᵀ)ᵀ == ag @ Gsum_i⁻¹
    if per_recv:
        x = jax.vmap(lambda g, m: jnp.linalg.solve(
            g, jnp.swapaxes(m, -1, -2)))(gsum, ag)     # [N, S, *lead, k, r]
    else:
        x = jax.vmap(lambda g: jnp.linalg.solve(
            g, jnp.swapaxes(ag, -1, -2)))(gsum)        # [N, S, *lead, k, r]
    return jnp.swapaxes(x, -1, -2)                     # [N, S, *lead, r, k]


def weighted_plane_mean(planes: Sequence[Any], weights: Sequence[float]):
    """:func:`weighted_tree_mean` over plane-backed models, applied to
    the ``[R, 512]`` buffers directly — no leaf views, no
    ``plane_from_tree`` rebuild at the round boundary.

    Bit-identical to mixing the leaf views and repacking: the plane
    layout is a placement-only rearrangement of the leaves, the mix is
    linear, and the buffers run the *same* normalized weights in the
    *same* summation order per element, so
    ``pack(Σ wᵢ·leafᵢ) == Σ wᵢ·pack(leafᵢ)`` bitwise.  Padding lanes
    are zero in every input (the plane invariant), so the mix keeps
    them zero."""
    from repro.optim.plane import Plane
    w = np.asarray(weights, np.float64)
    w = w / w.sum()
    out = sum(wi * p.buf.astype(jnp.float32)
              for wi, p in zip(w, planes))
    first = planes[0]
    return Plane(out.astype(first.buf.dtype), first.raw, first.meta)
