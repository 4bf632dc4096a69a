"""Adapter-rank wire: low-rank delta factors for the gossip payload.

ProFe's wire ships full student parameters every round — O(d·k) per
matrix leaf even at int4.  With ``FederationConfig.adapter_rank = r >
0`` each *matrix* leaf of the student instead gossips the low-rank
factors of its per-round delta,

    Δ = W − W_ref,    B = Q(QR(Δ Ω)) ∈ [d, r],    A = Bᵀ Δ ∈ [r, k],

so the wire carries O(r·(d+k)) per matrix (the "adapters" payload
group) plus the dense non-matrix rest (the "student" group).  Ω is a
*fixed* per-leaf Gaussian basis (a deterministic function of the leaf
name alone), so every engine and every node projects identically — the
randomized-QB sketch needs no SVD, batches over the node axis, and
satisfies ``B @ A = Q Qᵀ Δ`` with orthonormal ``B``.

``W_ref`` is the receiver-side value the previous round's merge
produced (the round-start student), carried per node as
``NodeState.adapter_state = {"ref": {leaf: W}, ["grams": {leaf: G}]}``.
Aggregation is merge-based (see :mod:`repro.core.aggregation`):
receivers reconstruct ``W ← W_ref + Σ_j c_ij · B_j @ Ã_j`` — RegMean
gram-weighted least squares when gram statistics ride the wire
(``adapter_grams``), naive weighted factor averaging otherwise — via
the fused ``kernels/lowrank_apply`` sweep, so the dense per-node delta
never materializes.

The gram statistic is a *row-space proxy*: RegMean proper weights each
layer by the gram of its input activations (XᵀX, which needs forward
hooks); here ``G ← GRAM_EMA·G_prev + AᵀA`` accumulates the row-space
gram of the transmitted deltas (``AᵀA = ΔᵀQQᵀΔ`` — exactly the gram of
the wire-visible update).  Activation-sourced grams are scoped in the
ROADMAP.  Grams ride as their own ``"grams"`` payload group ([k, k]
per matrix — wire-expensive, off by default).

Leaf selection: a leaf rides the adapter group iff it is a float
2-D matrix with ``min(d, k) > r`` — anything else (biases, conv
kernels, small heads where factors would not compress) stays dense.
One shared :func:`adapter_layout` drives the engines, the payload
template, and the byte accounting, so predictions stay byte-exact.
"""
from __future__ import annotations

import zlib
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import fp32

# decay on the carried gram statistic: G <- GRAM_EMA * G_prev + A^T A.
# 0.5 halves a stale round's influence per round — enough memory to
# smooth per-round sketch noise without freezing early-round geometry.
GRAM_EMA = 0.5

_OMEGA_SEED = 0xADA


class AdapterLayout(NamedTuple):
    """Static partition of one student tree: which flatten-order leaves
    ride the adapter wire.  ``names`` are ``jax.tree_util.keystr``
    paths (the stable wire-dict keys); ``shapes`` are the logical
    (node-axis-free) leaf shapes."""
    treedef: Any
    names: Tuple[str, ...]
    is_mat: Tuple[bool, ...]
    shapes: Tuple[Tuple[int, ...], ...]
    rank: int

    @property
    def mat_names(self) -> Tuple[str, ...]:
        return tuple(n for n, m in zip(self.names, self.is_mat) if m)

    @property
    def n_mats(self) -> int:
        return sum(self.is_mat)


def is_adapter_shape(shape, rank: int) -> bool:
    """A leaf is factored iff its trailing two dims are both > r
    (factors of an [r-or-smaller] matrix would not compress).  Leading
    axes are batch: a scanned transformer stack's ``[L, d, k]`` kernels
    factor per layer slice — every factorize/merge op broadcasts the
    lead axes, and the wire ships ``L·r·(d+k)`` instead of ``L·d·k``."""
    return len(shape) >= 2 and min(shape[-2:]) > rank


def adapter_layout(tree, rank: int, *, node_axis: bool = False
                   ) -> AdapterLayout:
    """Build the layout from a student tree (arrays or
    ``ShapeDtypeStruct``s; ``node_axis=True`` skips a leading ``[N]``
    axis when classifying shapes)."""
    skip = 1 if node_axis else 0
    items, treedef = jax.tree_util.tree_flatten_with_path(tree)
    names, is_mat, shapes = [], [], []
    for path, leaf in items:
        shape = tuple(np.shape(leaf))[skip:]
        floaty = hasattr(leaf, "dtype") and \
            jnp.issubdtype(leaf.dtype, jnp.floating)
        names.append(jax.tree_util.keystr(path))
        is_mat.append(bool(floaty and is_adapter_shape(shape, rank)))
        shapes.append(shape)
    return AdapterLayout(treedef, tuple(names), tuple(is_mat),
                         tuple(shapes), int(rank))


def split_student(layout: AdapterLayout, tree
                  ) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """Partition the tree's leaves into (matrix dict, rest dict), both
    keyed by the layout's stable leaf names."""
    leaves = jax.tree_util.tree_leaves(tree)
    assert len(leaves) == len(layout.names)
    mats = {n: l for n, l, m in zip(layout.names, leaves, layout.is_mat)
            if m}
    rest = {n: l for n, l, m in zip(layout.names, leaves, layout.is_mat)
            if not m}
    return mats, rest


def merge_student(layout: AdapterLayout, mats: Dict[str, Any],
                  rest: Dict[str, Any]):
    """Inverse of :func:`split_student`."""
    leaves = [mats[n] if m else rest[n]
              for n, m in zip(layout.names, layout.is_mat)]
    return jax.tree_util.tree_unflatten(layout.treedef, leaves)


def _omega(name: str, k: int, rank: int) -> jnp.ndarray:
    """The fixed projection basis Ω [k, r] of one matrix leaf — a pure
    function of the leaf *name*, so every node (and every engine)
    sketches into the same subspace family."""
    seed = zlib.crc32(name.encode()) & 0x7FFFFFFF
    key = jax.random.fold_in(jax.random.PRNGKey(_OMEGA_SEED), seed)
    return jax.random.normal(key, (k, rank), jnp.float32) \
        / np.sqrt(float(k))


def orthonormalize(y: jnp.ndarray) -> jnp.ndarray:
    """Orthonormal basis of the sketch columns (leading batch axes
    broadcast) via two-pass modified Gram-Schmidt.

    NOT ``jnp.linalg.qr``: that lowers to a ``geqrf`` custom call the
    SPMD partitioner cannot shard over the node batch axis, so on a
    federation mesh it ALL-GATHERS every node's sketch — phantom
    collective bytes in a purely node-local computation (caught by the
    ``launch/dryrun.py --adapters`` exact byte gate).  MGS is matmuls
    and reductions only, so the batch axis partitions cleanly, and at
    sketch widths r ≪ d the second pass restores QR-grade
    orthogonality.  An exactly-zero column (round-0 deltas are zero)
    normalizes to zero instead of an arbitrary basis vector — zero
    deltas make zero payloads."""
    r = int(y.shape[-1])
    cols = []
    for j in range(r):
        v = y[..., j]
        for _ in range(2):
            for q in cols:
                v = v - jnp.sum(q * v, axis=-1, keepdims=True) * q
        nrm = jnp.sqrt(jnp.sum(v * v, axis=-1, keepdims=True))
        cols.append(v / jnp.maximum(nrm, jnp.finfo(jnp.float32).tiny))
    return jnp.stack(cols, axis=-1)


def factorize_delta(delta: jnp.ndarray, name: str, rank: int
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Randomized QB of one delta (leading batch axes broadcast):
    ``B = Q(QR(Δ Ω))``, ``A = Bᵀ Δ`` — so ``B @ A = Q Qᵀ Δ`` is the
    rank-``r`` projection of Δ onto the sketched column space."""
    om = _omega(name, int(delta.shape[-1]), rank)
    y = fp32.matmul(delta, om)                     # [..., d, r]
    q = orthonormalize(y)
    a = fp32.matmul(jnp.swapaxes(q, -1, -2), delta)  # [..., r, k]
    return q, a


def factorize_deltas(layout: AdapterLayout, mats: Dict[str, Any],
                     refs: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    """Per-leaf wire factors of ``W − W_ref`` as the "adapters" payload
    group ``{leaf: {"A": [.., r, k], "B": [.., d, r]}}``."""
    out = {}
    for n in layout.mat_names:
        b, a = factorize_delta(mats[n] - refs[n], n, layout.rank)
        out[n] = {"A": a, "B": b}
    return out


def gram_update(factors: Dict[str, Dict[str, Any]],
                prev: Optional[Dict[str, Any]]) -> Dict[str, Any]:
    """Row-space gram carry: ``G ← GRAM_EMA·G_prev + AᵀA`` per leaf."""
    out = {}
    for n, f in factors.items():
        a = f["A"]
        g = fp32.matmul(jnp.swapaxes(a, -1, -2), a)  # [..., k, k]
        if prev is not None:
            g = g + GRAM_EMA * prev[n]
        out[n] = g
    return out


def init_adapter_state(layout: AdapterLayout, tree, *,
                       grams: bool = False) -> Dict[str, Any]:
    """Zero-round adapter carry for one student tree: the reference
    matrices (round-start values deltas are taken against) and, with
    ``grams``, zero gram statistics.  Rides ``NodeState.adapter_state``
    so checkpoints capture it for exact resume."""
    mats, _ = split_student(layout, tree)
    state: Dict[str, Any] = {"ref": {n: jnp.asarray(v, jnp.float32)
                                     for n, v in mats.items()}}
    if grams:
        state["grams"] = {
            n: jnp.zeros(tuple(np.shape(v))[:-2]
                         + (int(np.shape(v)[-1]),) * 2, jnp.float32)
            for n, v in mats.items()}
    return state


def zero_wire_payload(layout: AdapterLayout, tree, *, grams: bool = False
                      ) -> Dict[str, Any]:
    """Zero-filled model-side wire groups of one share — ``{"adapters",
    "student" [, "grams"]}`` with the tree's leading (node) axes kept.
    The error-feedback residual must mirror the payload *structure*, so
    this is what ``init_codec_state`` seeds from on the adapter wire."""
    mats, rest = split_student(layout, tree)
    adapters, gram_z = {}, {}
    for n in layout.mat_names:
        lead = tuple(np.shape(mats[n]))[:-2]
        d, k = tuple(np.shape(mats[n]))[-2:]
        adapters[n] = {
            "A": jnp.zeros(lead + (layout.rank, k), jnp.float32),
            "B": jnp.zeros(lead + (d, layout.rank), jnp.float32)}
        gram_z[n] = jnp.zeros(lead + (k, k), jnp.float32)
    out: Dict[str, Any] = {
        "adapters": adapters,
        "student": jax.tree_util.tree_map(
            lambda x: jnp.zeros(np.shape(x), jnp.float32), rest)}
    if grams:
        out["grams"] = gram_z
    return out


def adapter_payload_template(layout: AdapterLayout, *, grams: bool,
                             node_axis: bool = True):
    """Shape/dtype skeleton of the adapter payload groups (what the
    comm accountants meter): ``{"adapters": {leaf: {"A", "B"}}
    [, "grams": {leaf: G}]}``.  ``node_axis`` only affects how the
    layout was built — the template is always per-copy (node-free)."""
    del node_axis
    adapters, gram_t = {}, {}
    for n, m, shape in zip(layout.names, layout.is_mat, layout.shapes):
        if not m:
            continue
        lead, (d, k) = tuple(shape[:-2]), shape[-2:]
        r = layout.rank
        adapters[n] = {
            "A": jax.ShapeDtypeStruct(lead + (r, k), np.dtype(np.float32)),
            "B": jax.ShapeDtypeStruct(lead + (d, r),
                                      np.dtype(np.float32))}
        gram_t[n] = jax.ShapeDtypeStruct(lead + (k, k),
                                         np.dtype(np.float32))
    out = {"adapters": adapters}
    if grams:
        out["grams"] = gram_t
    return out
