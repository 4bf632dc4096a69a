"""Shared vectorized federation-round math (stacked node state).

Both round engines — the CPU simulator (``core/federation.py``) and the
TPU mesh path (``core/mesh_federation.py``) — run the gossip/aggregate
phase on **stacked node state**: every pytree leaf carries a leading
``[N, ...]`` node axis, so one program handles all N nodes at once
instead of a Python loop dispatching per node.

Contract (consumed by both engines):

* ``quantize_leaf_per_node`` / ``dequantize_leaf`` — Sec. III-D wire
  quantization applied independently per node slice (one scale per
  node per tensor), shape-preserving so sharded mesh tensors are never
  reshaped (a reshape would force GSPMD replication and silently
  inflate the measured wire bytes).
* ``quantize_dequantize_per_node`` — the receiver-side reconstruction
  of a whole stacked pytree (round-trip through integer codes).
* ``gossip_matrix`` — dataset-size-weighted neighborhood mixing
  weights; ``mix_node_trees`` applies them with the ProFe simulator
  convention that a node's *own* copy is never quantized (only what
  traveled is).
* ``neighborhood_prototype_aggregate`` — Eq. 4 instance-count-weighted
  prototype aggregation evaluated per node over its neighborhood in
  one einsum (the mesh path's all-node variant is the special case of
  an all-ones include matrix, i.e. ``prototypes.aggregate_prototypes``).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import fp32
from repro.core.quantization import _INT_DTYPES, _qmax
from repro.wirespec import WireSpec


def _is_array(x) -> bool:
    return hasattr(x, "shape") and hasattr(x, "dtype")


def _is_float(x) -> bool:
    return _is_array(x) and jnp.issubdtype(x.dtype, jnp.floating)


# ---------------------------------------------------------------------------
# per-node quantization (stacked [N, ...] leaves)
# ---------------------------------------------------------------------------

def quantize_leaf_per_node(x, bits: int):
    """x: [N, ...] fp — quantize each node's slice independently.
    Returns (codes intN [N, ...], scales fp32 [N]); the code container
    is the narrowest int dtype that holds ``bits`` (int8 for 4/8-bit,
    int16 for 16-bit), so the gather exchange's wire dtype follows the
    spec width.

    Shape-preserving (no reshape): flattening a sharded tensor would
    force GSPMD to replicate it, which would silently inflate the wire
    bytes the dry-run measures.
    """
    qm = _qmax(bits)
    x32 = x.astype(jnp.float32)
    reduce_axes = tuple(range(1, x.ndim))
    amax = jnp.max(jnp.abs(x32), axis=reduce_axes)                # [N]
    delta = jnp.maximum(amax / qm, jnp.finfo(jnp.float32).tiny)   # [N]
    bshape = (x.shape[0],) + (1,) * (x.ndim - 1)
    codes = jnp.floor(x32 / delta.reshape(bshape) + 0.5)
    codes = jnp.clip(codes, -qm - 1, qm).astype(_INT_DTYPES[bits])
    return codes, delta


def dequantize_leaf(codes, delta):
    """codes: [N, ...] int, delta: [N] fp32 -> fp32 [N, ...]."""
    bshape = (codes.shape[0],) + (1,) * (codes.ndim - 1)
    return codes.astype(jnp.float32) * delta.reshape(bshape)


def quantize_dequantize_per_node(tree, bits: int = 16, *,
                                 spec: Optional[WireSpec] = None,
                                 use_kernels: Optional[bool] = None,
                                 packed: bool = True, rng=None,
                                 state=None):
    """Receiver-side reconstruction of a stacked pytree: every float
    leaf [N, ...] goes through per-node codes and back to fp32.
    Non-float leaves pass through untouched.

    By default this consumes the *packed node wire codec*
    (``kernels/quantize/ops.pack_tree_nodes``): the same single
    ``[N, R, 512]`` buffer + per-(leaf, node) segment scales the mesh
    path physically exchanges, so the simulator, the dry-run, and the
    byte accounting all describe one wire format.  A :class:`WireSpec`
    quantizes each top-level leaf group at its own width (the
    mixed-precision wire); a bare ``bits`` int is the uniform special
    case.  Pallas kernels on TPU (``use_kernels`` defaults to the
    backend check), jnp elsewhere — bit-identical to the per-leaf math
    (``packed=False``), asserted in tests.

    ``state`` (a :class:`repro.core.wire_state.CodecState`, required
    when ``spec.error_feedback`` is set) switches to the stateful
    codec: the carried residual is added to the payload before
    quantization and the call returns ``(reconstruction, new_state)``
    — wire format unchanged, zero extra bytes.
    """
    if use_kernels is None:
        use_kernels = jax.default_backend() == "tpu"
    if spec is not None and spec.error_feedback and state is None:
        raise ValueError("WireSpec.error_feedback is set but no CodecState "
                         "was passed — the stateful codec needs the "
                         "carried per-node residual")
    if spec is not None and spec.stochastic_rounding and not packed:
        raise ValueError("the per-leaf reference path does not implement "
                         "stochastic rounding — use the packed codec "
                         "(silently rounding deterministically would "
                         "fake the unbiasedness)")
    if spec is not None and spec.uniform_bits is not None:
        bits = spec.uniform_bits
    if state is not None and not packed:
        from repro.core.wire_state import ef_quantize_dequantize_tree
        return ef_quantize_dequantize_tree(
            tree, spec if spec is not None else WireSpec.from_bits(bits),
            state, node_axis=True)
    if packed and isinstance(tree, dict):
        # flat-parameter-plane payload: the student rides a Plane, so
        # the pack step is a row slice off its buffer and the receiver
        # view comes back as a plane (zero repack on either end)
        from repro.optim.plane import Plane
        if isinstance(tree.get("student"), Plane):
            from repro.core.wire_state import CodecState, next_seq
            from repro.kernels.quantize.ops import (
                quantize_dequantize_plane_payload)
            if state is not None:
                recv, new_res = quantize_dequantize_plane_payload(
                    tree, bits, spec=spec, use_kernels=use_kernels,
                    rng=rng, residual=state.residual)
                return recv, CodecState(new_res, seq=next_seq(state.seq))
            return quantize_dequantize_plane_payload(
                tree, bits, spec=spec, use_kernels=use_kernels, rng=rng)
    if packed and any(_is_float(x) for x in jax.tree_util.tree_leaves(tree)):
        from repro.core.wire_state import CodecState, next_seq
        from repro.kernels.quantize.ops import (
            quantize_dequantize_tree_packed_nodes)
        if state is not None:
            recv, new_res = quantize_dequantize_tree_packed_nodes(
                tree, bits, spec=spec, use_kernels=use_kernels, rng=rng,
                residual=state.residual)
            return recv, CodecState(new_res, seq=next_seq(state.seq))
        return quantize_dequantize_tree_packed_nodes(
            tree, bits, spec=spec, use_kernels=use_kernels, rng=rng)
    if spec is not None and spec.uniform_bits is None:
        # per-leaf reference of the mixed wire: group width from the
        # leaf's top-level payload key — one source of truth with the
        # packed codec's layout (ops._leaf_group)
        from repro.kernels.quantize.ops import _leaf_group

        def rt_path(path, x):
            if not _is_float(x):
                return x
            b = spec.bits_for(_leaf_group(path))
            return dequantize_leaf(*quantize_leaf_per_node(x, b))
        return jax.tree_util.tree_map_with_path(rt_path, tree)
    if use_kernels:
        from repro.kernels.quantize.ops import quantize_dequantize_tree_packed
        return quantize_dequantize_tree_packed(tree, bits, node_axis=True)

    def rt(x):
        if not _is_float(x):
            return x
        codes, delta = quantize_leaf_per_node(x, bits)
        return dequantize_leaf(codes, delta)
    return jax.tree_util.tree_map(rt, tree)


# ---------------------------------------------------------------------------
# gossip mixing
# ---------------------------------------------------------------------------

def gossip_matrix(adj: np.ndarray, sizes) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Dataset-size-weighted neighborhood-mean weights.

    ``adj`` is either a static ``[N, N]`` adjacency or a round-stacked
    ``[R, N, N]`` topology schedule.  Returns ``(w_self, w_neigh)`` of
    shape ``([N], [N, N])`` respectively ``([R, N], [R, N, N])`` with
    ``w_self[i] + sum_j w_neigh[i, j] == 1`` per row: node i averages its
    own model (weight ``sizes[i]``) with each neighbour j's received
    model (weight ``sizes[j]``), normalized over ``{i} ∪ neigh(i)``.
    Computed in float64 (like the reference ``weighted_tree_mean``) and
    cast to fp32 for the device program.
    """
    a = np.asarray(adj, np.float64)
    s = np.asarray(sizes, np.float64)
    squeeze = a.ndim == 2
    if squeeze:
        a = a[None]
    n = a.shape[-1]
    w = a * s[None, None, :]
    denom = w.sum(axis=2) + s[None, :]      # own weight included
    denom = np.maximum(denom, 1e-30)
    w_neigh = w / denom[:, :, None]
    w_self = s[None, :] / denom
    assert w_neigh.shape[-2:] == (n, n)
    if squeeze:
        w_self, w_neigh = w_self[0], w_neigh[0]
    return jnp.asarray(w_self, jnp.float32), jnp.asarray(w_neigh, jnp.float32)


def gossip_matrix_dyn(adj, sizes) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Traceable fp32 variant of :func:`gossip_matrix` for device
    programs: ``adj`` is a static 0/1 ``[N, N]`` array baked into the
    program, ``sizes`` a traced ``[N]`` operand (the mesh round receives
    dataset sizes at run time, so the weights must be computed in-graph).
    """
    a = jnp.asarray(adj, jnp.float32)
    s = jnp.asarray(sizes, jnp.float32)
    w = a * s[None, :]
    denom = jnp.maximum(w.sum(axis=1) + s, 1e-30)
    return s / denom, w / denom[:, None]


def mix_node_trees(w_self, w_neigh, own_tree, recv_tree):
    """Per-node weighted mean over the node axis.

    ``own_tree`` leaves [N, ...] are each node's *local* (unquantized)
    copy; ``recv_tree`` is what traveled (de-quantized).  New leaf:
    ``w_self[i]·own[i] + Σ_j w_neigh[i,j]·recv[j]`` — one tensordot per
    leaf instead of a per-node Python loop.  ``(w_self, w_neigh)`` is one
    round's ``([N], [N, N])`` slice; a round-varying topology passes the
    current round's slice of its lowered ``[R, N(, N)]`` stacks as traced
    operands (same shapes every round, so the jitted round never
    retraces).
    """
    def mix(own, recv):
        recv32 = recv.astype(jnp.float32)
        mixed = fp32.tensordot(w_neigh, recv32, axes=1)
        bshape = (own.shape[0],) + (1,) * (own.ndim - 1)
        mixed = mixed + w_self.reshape(bshape) * own.astype(jnp.float32)
        return mixed.astype(own.dtype)
    return jax.tree_util.tree_map(mix, own_tree, recv_tree)


def weighted_node_mean(w, tree):
    """Global size-weighted mean over the node axis: leaf [N, ...] ->
    [...] (every node receives the identical aggregate — the full-mesh
    special case used by the TPU path)."""
    w32 = w.astype(jnp.float32)
    return jax.tree_util.tree_map(
        lambda x: fp32.tensordot(w32, x.astype(jnp.float32), axes=1), tree)


# ---------------------------------------------------------------------------
# Eq. 4 prototype aggregation, per node neighborhood
# ---------------------------------------------------------------------------

def include_matrix(adj: np.ndarray) -> jnp.ndarray:
    """adj + self-loops as fp32 ``[N, N]`` (or round-stacked
    ``[R, N, N]``): who contributes prototypes to whom (every node
    includes its own prototypes)."""
    m = np.asarray(adj, np.float64) + np.eye(np.asarray(adj).shape[-1])
    return jnp.asarray(np.minimum(m, 1.0), jnp.float32)


def neighborhood_prototype_aggregate(include, protos, counts):
    """Eq. 4 evaluated for every node's neighborhood at once.

    include: [N, N] 0/1 (who node i listens to, incl. itself) — one
             round's slice of a lowered topology schedule, passed as a
             traced operand so round-varying graphs never retrace,
    protos:  [N, C, P] (already de-quantized receiver-side view),
    counts:  [N, C] instance counts.
    Returns (global_protos [N, C, P], proto_mask [N, C]).
    """
    eff = include[:, :, None] * counts[None, :, :]          # [N, N, C]
    n_j = jnp.sum(eff, axis=1)                              # [N, C]
    w = eff / jnp.maximum(n_j, 1.0)[:, None, :]             # [N, N, C]
    glob = fp32.einsum("ijc,jcp->icp", w, protos.astype(jnp.float32))
    mask = (n_j > 0).astype(jnp.float32)
    return glob, mask


# ---------------------------------------------------------------------------
# adapter-rank wire: stacked share/merge (shared by the CPU engines and
# the mesh path's gather mode)
# ---------------------------------------------------------------------------

def adapter_share_nodes(student, adapter_state, *, rank: int,
                        grams: bool = False):
    """Share-side of the adapter wire over stacked ``[N, ...]`` state:
    factorize this round's per-matrix deltas against the carried
    reference, snapshot the reference forward to the current weights,
    and (optionally) advance the gram statistics.

    Returns ``(payload_groups, new_adapter_state, layout)`` where
    ``payload_groups = {"adapters": {leaf: {"A", "B"}}, "student":
    rest-dict [, "grams": {leaf: G}]}`` — ready to merge with
    ``{"protos", "counts"}`` and feed the packed wire codec."""
    from repro.core.adapters import (adapter_layout, factorize_deltas,
                                     gram_update, split_student)
    from repro.optim.plane import as_tree
    tree = as_tree(student)
    layout = adapter_layout(tree, rank, node_axis=True)
    mats, rest = split_student(layout, tree)
    factors = factorize_deltas(layout, mats, adapter_state["ref"])
    groups = {"adapters": factors, "student": rest}
    new_state = {"ref": mats}
    if grams:
        g = gram_update(factors, adapter_state.get("grams"))
        groups["grams"] = g
        new_state["grams"] = g
    return groups, new_state, layout


def adapter_merge_nodes(student, recv, w_self, w_neigh, *, rank: int,
                        grams: bool = False,
                        use_kernels: Optional[bool] = None):
    """Merge-side of the adapter wire: every receiver applies its
    neighbors' reconstructed low-rank deltas on top of its own current
    weights,

        W_i ← W_i + Σ_j w_neigh[i, j] · B_j @ Ã_j ,

    (the receiver's own training delta is already in ``W_i`` — no self
    term), while the dense rest leaves keep the classic gossip mean
    (own copy unquantized, ``mix_node_trees``).  ``recv`` is the
    receiver-side payload view ``{"adapters", "student" [, "grams"]}``;
    with grams the factors are RegMean-adjusted per receiver
    (:func:`repro.core.aggregation.regmean_adjust`), otherwise the
    gossip weights apply to the raw factors (naive averaging).
    Plane-backed students run the fused ``kernels/lowrank_apply``
    sweep over the buffer; trees run the materialized reference."""
    from repro.core.adapters import adapter_layout, split_student
    from repro.kernels.lowrank_apply.ops import (adapter_apply_plane,
                                                 adapter_apply_tree)
    from repro.optim.plane import as_tree, is_plane
    tree = as_tree(student)
    layout = adapter_layout(tree, rank, node_axis=True)
    _, rest_now = split_student(layout, tree)
    rest_mixed = mix_node_trees(w_self, w_neigh, rest_now,
                                recv["student"])
    factors = recv["adapters"]
    coeffs = w_neigh
    if grams:
        from repro.core.aggregation import regmean_adjust
        factors = {n: {"A": regmean_adjust(f["A"], recv["grams"][n],
                                           coeffs, per_recv=False),
                       "B": f["B"]}
                   for n, f in factors.items()}
    if is_plane(student):
        return adapter_apply_plane(student, layout, coeffs, factors,
                                   rest_mixed, use_kernels=use_kernels)
    return adapter_apply_tree(tree, layout, coeffs, factors, rest_mixed)
