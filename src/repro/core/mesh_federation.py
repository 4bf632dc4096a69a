"""ProFe federation round on the production mesh — physically sparse.

Mapping (DESIGN.md §2): each **pod is a federation node**.  All federation
state is stacked along a leading node dimension sharded over the ``pod``
mesh axis, so node divergence is explicit and *local training never
crosses pods* (the train step is vmapped over the node dim — XLA
partitions it over ``pod`` with zero cross-pod collectives).

The per-node quantize / de-quantize / weighted-mean / Eq. 4 math is the
shared stacked-node-state core in :mod:`repro.core.round_ops`; the wire
codec is the packed node format of :mod:`repro.kernels.quantize.ops`.

**Wire content.**  The whole quantized payload of one node — student
leaves *and* prototypes — is ONE contiguous byte buffer: the packed
``[N, R, 512]`` code buffer (``pack_tree_nodes`` /
``quantize_packed_buffer``) serialized by ``encode_wire`` to ``[N, B]``
int8, where ``B`` is exactly the bytes of the
:class:`repro.wirespec.WireSpec` in force — int16/int8 rows bitcast,
int4 rows nibble-packed two codes per byte, mixed precision (e.g. int4
student + int16 prototypes) segment by segment — plus per-(leaf, node)
segment scales ``[N, T]``.  The exchange therefore costs one collective
launch per round, not one per leaf, its payload shrinks with the spec
(int4 == 0.25x the int16 bytes), and the receiver decodes and applies
``w_self`` / ``w_neigh`` *directly on packed codes* (fused
dequant-and-accumulate, ``mix_packed`` — a single Pallas launch on TPU).

**Exchange modes** (``exchange=`` kwarg, both round factories):

* ``"ppermute"`` — physical sparse gossip: the adjacency is lowered by
  :func:`repro.core.topology.permutation_rounds` to per-round
  ``jax.lax.ppermute`` permutation lists, run under ``shard_map`` on the
  pod axis.  A ring round moves **O(degree)** bytes per node — degree
  collective-permutes of the packed buffer — so the physical wire bytes
  finally match the logical topology that
  ``comm.ScheduleCommAccountant`` charges (asserted by
  ``launch/dryrun.py --topology``).  Needs one device per node on the
  pod axis; **multi-axis pods take the row-sharded permute**: each of
  the M inner devices permutes only its row block of the encoded
  buffer (rows re-ordered by ``sharding.row_shard_order`` so every
  shard's byte count is static and identical), sidecars split/re-widen
  over the inner axes, so per-node pod bytes stay spec-exact —
  ``comm.packed_copy_bytes(..., inner=M)`` per copy.
* ``"packed"`` — one all-gather of the single encoded byte buffer over
  the pod axis, then the masked weighted mix on the decoded codes.  The
  gather-subset fallback for irregular graphs and the full-graph / legacy
  protocol path (where O(N) physical bytes *are* the logical cost).
* ``"gather"`` — the PR-2 reference: per-leaf all-gather of shape-
  preserving int16 codes + masked ``mix_node_trees``.  Kept as the
  semantics oracle the packed paths are asserted equivalent to.
* ``"auto"`` (default) — ``ppermute`` when the graph is regular and the
  pod axis has one device per node, else ``packed``.  Multi-axis pods
  always take the row-sharded permute: width groups that don't divide
  the inner devices ride appended all-zero pad rows
  (``row_shard_order``), so mixed-width payloads never fall back.

**Overlap** (``overlap=True`` on :func:`make_profe_round`): the permute
exchange is double-buffered — step ``s+1``'s collectives are issued
before step ``s``'s fused dequant-accumulate consumes its payload, and
the mix folds step by step (``mix_packed_accumulate``) instead of
concatenating a ``[S, R, 512]`` stack.  Issue order only: the same
payloads meet the same mix weights, and the collectives are
byte-identical.  Round-level overlap (running round ``t``'s gossip
concurrently with round ``t+1``'s local epochs, stale-by-one mixing)
lives in the engine — ``core/federation.py run_federation(overlap=
"rounds")``: round ``t >= 2`` mixes the payload quantized at round
``t-1``, round 0 is sequential and round 1 skips the mix, and with
error feedback the
``CodecState.seq`` counter pins which payload a carried residual
corrects (the residual entering quantize ``t`` is the one produced by
quantize ``t-1``, asserted in tests).

**Topologies.**  Pass ``adjacency`` (a 0/1 ``[N, N]`` phase of a
:class:`repro.core.topology.TopologySchedule`) for ring/star/random-k
rounds: students mix per node over ``{i} ∪ neigh(i)`` (own copy
unquantized, the CPU-simulator convention), prototypes aggregate per
neighborhood (Eq. 4).  Outputs stay node-distinct and sharded back to
``P("pod", ...)``.  With ``adjacency=None`` the paper's fully-connected
protocol runs: a size-weighted mean where every node ends identical.

``make_fedavg_round`` is the baseline: the same exchange machinery on
the *full-size* model at fp32 — the dry-run diff of collective bytes
between the two programs reproduces Table II on the mesh.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import fp32
from repro.core import topology as T
from repro.core.profe import normalize_protos
from repro.core.prototypes import aggregate_prototypes
from repro.core.round_ops import (dequantize_leaf, gossip_matrix_dyn,
                                  include_matrix, mix_node_trees,
                                  neighborhood_prototype_aggregate,
                                  quantize_leaf_per_node, weighted_node_mean)
from repro.core.wire_state import CodecState, ef_state_specs, next_seq
from repro.kernels.quantize import ops as Q
from repro.optim.plane import Plane, as_tree, is_plane, plane_from_tree
from repro.sharding import row_shard_order
from repro.wirespec import WireSpec, resolve_spec

EXCHANGES = ("auto", "gather", "packed", "ppermute")


def _constrain_over_pod(mesh, tree, specs_no_pod, axis):
    """Reshard [N, ...] leaves to P(axis, ...): ``axis=None`` replicates
    (the all-gather over the pod axis == the wire exchange), ``axis="pod"``
    shards the node dim back after the masked mix."""
    def cons(x, spec):
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(axis, *spec)))
    return jax.tree_util.tree_map(
        cons, tree, specs_no_pod,
        is_leaf=lambda x: not isinstance(x, (dict, list, tuple)))


def _replicate_over_pod(mesh, tree, specs_no_pod):
    return _constrain_over_pod(mesh, tree, specs_no_pod, None)


def _pod_size(mesh) -> int:
    return int(dict(mesh.shape).get("pod", 1))


def _inner_axes(mesh):
    """Non-pod mesh axes — the packed buffer's row dim shards over them
    so per-device wire bytes stay shard-sized on multi-axis pods."""
    inner = tuple(a for a in mesh.axis_names if a != "pod")
    return inner if inner else None


def _inner_size(mesh) -> int:
    n = 1
    for a in mesh.axis_names:
        if a != "pod":
            n *= int(dict(mesh.shape)[a])
    return n


def _resolve_exchange(exchange: str, adj, mesh) -> str:
    if exchange not in EXCHANGES:
        raise ValueError(f"exchange must be one of {EXCHANGES}, "
                         f"got {exchange!r}")
    if exchange == "ppermute":
        if adj is None:
            raise ValueError("exchange='ppermute' needs an adjacency")
        if _pod_size(mesh) != adj.shape[0]:
            raise ValueError(
                f"exchange='ppermute' needs one pod-axis device per node "
                f"(pod={_pod_size(mesh)}, N={adj.shape[0]})")
        # inner axes of size > 1 take the row-sharded permute: each
        # inner device permutes only its row block of the encoded
        # buffer (width groups that don't divide the inner size ride
        # appended zero pad rows — see row_shard_order)
        return exchange
    if exchange != "auto":
        return exchange
    if (adj is not None and _pod_size(mesh) == adj.shape[0]
            and T.is_regular(adj)):
        return "ppermute"
    return "packed"


def _constrain_buf(mesh, buf, pod_axis):
    inner = _inner_axes(mesh)
    spec = P(pod_axis, inner, None) if buf.ndim == 3 else P(pod_axis, None)
    return jax.lax.with_sharding_constraint(buf, NamedSharding(mesh, spec))


def _proto_recipe(payload, meta, key: str = "protos"):
    """Row span of the prototype leaf inside the packed buffer, located
    by its key path in the payload tree (recipe order == float-leaf
    flatten order, so sort-order assumptions never slice student rows
    as prototypes)."""
    recipe = meta[1]
    target = None
    idx = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(payload)[0]:
        if not (hasattr(leaf, "dtype")
                and jnp.issubdtype(leaf.dtype, jnp.floating)):
            continue
        if getattr(path[0], "key", None) == key:
            target = idx
        idx += 1
    if target is None:
        raise ValueError(f"no float leaf under {key!r} in the payload")
    packed = [it for it in recipe if it[0] == "packed"]
    _, shape, _dtype, row, nrows, _s = packed[target]
    return row, nrows, shape


def _pack_payload(protos, students, wire):
    """Plane-aware wire pack: ``(buf, seg_ids, meta, proto_loc, splice)``.

    ``proto_loc`` is ``(row, nrows, shape)`` of the prototype leaf inside
    the packed buffer.  When the students arrive as a
    :class:`~repro.optim.plane.Plane` the pack is a row splice off the
    plane buffer (zero repack — the student already lives in the wire
    layout) and ``splice = (plane, r_protos, span)`` lets the receiver
    splice the mixed rows straight back; per-leaf payloads take
    ``pack_tree_nodes`` and ``splice`` is ``None``."""
    if is_plane(students):
        buf, seg_ids, meta, r_p, span = Q.pack_plane_payload(
            protos, students, wire)
        return (buf, seg_ids, meta, (0, r_p, protos.shape),
                (students, r_p, span))
    payload = {"protos": protos, "student": students}
    buf, seg_ids, meta = Q.pack_tree_nodes(payload, wire)
    return buf, seg_ids, meta, _proto_recipe(payload, meta), None


def _splice_students(mesh, mixed, meta, students, splice, student_specs):
    """Receiver-side student reconstruction from the mixed buffer: the
    plane path slices its rows straight into a fresh plane (zero repack
    — the trailing alignment rows are re-zeroed, a fixed point of the
    mix), the per-leaf path unpacks to leaves."""
    if splice is not None:
        plane, r_p, span = splice
        sbuf = mixed[:, r_p:r_p + span]
        pad = plane.meta.rows - span
        if pad:
            sbuf = jnp.pad(sbuf, ((0, 0), (0, pad), (0, 0)))
        return Plane(_constrain_buf(mesh, sbuf, "pod"), plane.raw,
                     plane.meta)
    new_students = jax.tree_util.tree_map(
        lambda new, old: new.astype(old.dtype),
        Q.unpack_tree_nodes(mixed, meta)["student"], students)
    return _constrain_over_pod(mesh, new_students, student_specs, "pod")


def _plane_views_adapter(fn, stateful: bool):
    """The gather reference exchange is per-leaf math end to end, so a
    plane-backed caller is adapted at the boundary: leaf views go in,
    and the mixed leaves (and the EF residual) pack back into planes on
    the way out — the semantics oracle stays byte-for-byte the PR-2
    path."""
    def round_fn(students, protos, counts, sizes, *rest):
        if not is_plane(students):
            return fn(students, protos, counts, sizes, *rest)
        repack = jax.vmap(plane_from_tree)
        if stateful:
            (state,) = rest
            res = state.residual
            if is_plane(res.get("student")):
                state = CodecState(dict(res, student=as_tree(
                    res["student"])), seq=state.seq)
            s, g, m, ns = fn(as_tree(students), protos, counts, sizes,
                             state)
            nres = dict(ns.residual,
                        student=repack(ns.residual["student"]))
            return repack(s), g, m, CodecState(nres, seq=ns.seq)
        s, g, m = fn(as_tree(students), protos, counts, sizes)
        return repack(s), g, m
    return round_fn


def _perm_lowering(adj: np.ndarray):
    """Lower an adjacency to its ppermute schedule: ``(perms, srcs)`` —
    the permutation step lists and, per step, the receiver -> sender map
    (``-1`` = no sender reaches this node that step).  The single
    source of the valid/weight conventions both round factories share."""
    n = adj.shape[0]
    perms = T.permutation_rounds(adj)
    srcs = []
    for step in perms:
        src = np.full((n,), -1, np.int64)
        for s, d in step:
            src[d] = s
        srcs.append(src)
    return perms, srcs


def _step_weight(src, me, w_row):
    """This device's (valid, mix-weight) for one permutation step:
    zero when nobody sends to it, else its ``w_neigh`` entry for the
    sender."""
    src_me = jnp.asarray(src)[me]
    valid = (src_me >= 0).astype(jnp.float32)
    return valid, valid * w_row[0, jnp.maximum(src_me, 0)]


# ---------------------------------------------------------------------------
# ProFe round
# ---------------------------------------------------------------------------

PROTO_PASSES = ("exact", "fused")


def make_profe_round(mesh, student_specs, bits: int = 16,
                     adjacency: Optional[np.ndarray] = None,
                     exchange: str = "auto",
                     spec: Optional[WireSpec] = None,
                     overlap: bool = False,
                     proto_pass: str = "exact",
                     adapter_rank: int = 0,
                     adapter_grams: bool = False):
    """Returns round_fn(students, protos, counts, sizes) for stacked
    node state; students leaves [N, ...] sharded P("pod", *student_spec).

    ``students`` may also be a :class:`~repro.optim.plane.Plane` whose
    buffer is stacked ``[N, R, 512]`` (the flat-parameter engines): the
    packed and ppermute exchanges then splice the wire payload straight
    off the plane buffer and splice the mixed rows straight back (zero
    repack on either end, byte-identical wire traffic), and the round
    returns a plane.  A plane-backed EF residual quantizes the same
    way.  The gather reference path unwraps the plane to leaf views at
    the boundary.

    ``proto_pass="fused"`` adapts the round to the single-pass training
    engine: the caller hands the RAW Eq. 3 accumulators its training
    scan produced — ``round_fn(students, sums, counts, sizes, ...)``
    with ``sums [N, C, P]`` un-normalized — and the round normalizes
    (``sums / max(counts, 1)``, the shared
    :func:`repro.core.profe.normalize_protos`) before the exchange.
    Everything downstream (codec, exchange mode, EF arity) is identical
    to ``"exact"``, so a fused round given ``(sums, counts)`` equals an
    exact round given the normalized prototypes (asserted in tests).

    ``adjacency=None`` (the paper's fully-connected protocol): output is
    aggregated students (every node identical), global prototypes
    [C, P] + mask [C] (Eq. 4), replicated.

    With a 0/1 ``[N, N]`` ``adjacency`` (one phase of a
    ``TopologySchedule``): neighborhood gossip — students mix per node
    over ``{i} ∪ neigh(i)`` (own copy unquantized), prototypes aggregate
    per neighborhood.  Output: node-distinct students sharded
    P("pod", ...), prototypes [N, C, P] + mask [N, C] sharded
    P("pod", ...).

    ``exchange`` picks the wire mechanism (see module docstring); all
    modes are numerically equivalent — only the physical bytes differ.
    ``spec`` (a :class:`repro.wirespec.WireSpec`) sets the wire format —
    per-group widths incl. int8/int4 and mixed precision; ``bits`` is
    the uniform shorthand it defaults from.

    A spec with ``error_feedback`` makes the codec stateful: the round
    becomes ``round_fn(students, protos, counts, sizes, codec_state)``
    and additionally returns the updated
    :class:`repro.core.wire_state.CodecState` — the node-sharded
    residual tree (leaves ``P("pod", ...)``) is replayed into the
    payload before quantization and never crosses pods, so every
    exchange mode moves byte-identical collectives to the stateless
    spec (asserted by ``launch/dryrun.py --ef``).

    ``adapter_rank=r > 0`` switches to the adapter-rank wire: matrix
    leaves gossip low-rank delta factors (+ gram statistics with
    ``adapter_grams``) and aggregation becomes merge-based, so the
    round takes and returns an extra ``adapter_state`` operand —
    ``round_fn(students, protos, counts, sizes, adapter_state
    [, codec_state])``.  Needs an adjacency; all three exchanges move
    the factor payload (see :func:`_make_profe_round_adapter`).

    ``overlap=True`` pipelines the permute exchange: the mix is
    restructured into per-step ``mix_packed_accumulate`` folds and the
    ppermute for step ``s+1`` is issued BEFORE step ``s``'s
    dequant-accumulate consumes its data, so the latency-hiding
    scheduler can run the collective and the mix concurrently (double
    buffering — at most two in-flight step payloads).  The gather and
    packed exchanges have a single collective and ignore the knob.
    Overlap changes only issue order, never which payload reaches which
    mix weight, and moves byte-identical collectives.
    """
    if proto_pass not in PROTO_PASSES:
        raise ValueError(f"proto_pass must be one of {PROTO_PASSES}, "
                         f"got {proto_pass!r}")
    wire = spec if spec is not None else WireSpec.from_bits(bits)
    adj = None if adjacency is None else np.asarray(adjacency)
    mode = _resolve_exchange(exchange, adj, mesh)
    if adapter_rank:
        # adapter-rank wire: low-rank factor payload + merge-based
        # aggregation — the round gains an adapter_state operand (see
        # _make_profe_round_adapter for the signature)
        fn = _make_profe_round_adapter(mesh, student_specs, wire, adj,
                                       mode, rank=adapter_rank,
                                       grams=adapter_grams,
                                       overlap=overlap)
        if proto_pass == "exact":
            return fn

        def fused_adapter_round(students, sums, counts, *rest):
            return fn(students, normalize_protos(sums, counts), counts,
                      *rest)
        return fused_adapter_round
    if mode == "gather":
        fn = _plane_views_adapter(
            _make_profe_round_gather(mesh, student_specs, wire, adj),
            stateful=wire.error_feedback)
    elif mode == "ppermute":
        if _inner_size(mesh) == 1:
            fn = _make_profe_round_ppermute(mesh, student_specs, wire,
                                            adj, overlap=overlap)
        else:
            fn = _make_profe_round_ppermute_sharded(
                mesh, student_specs, wire, adj, overlap=overlap)
    else:
        fn = _make_profe_round_packed(mesh, student_specs, wire, adj)
    if proto_pass == "exact":
        return fn
    # fused: normalize the raw training-scan accumulators on the way in
    # (*rest carries the EF CodecState when the spec is stateful)

    def fused_round(students, sums, counts, *rest):
        return fn(students, normalize_protos(sums, counts), counts, *rest)
    return fused_round


def _quantize_with_state(mesh, wire: WireSpec, buf, seg_ids, meta,
                         ef_state: Optional[CodecState]):
    """The (optionally stateful) quantize step of the mesh codec:
    ``(codes, scales, new_state_or_None)``.  The residual packs into the
    identical buffer layout, stays node-sharded (``P("pod", ...)``), and
    updates in the same fused pass — it never feeds a collective, so
    the exchange bytes match the stateless codec exactly."""
    if ef_state is None:
        codes, scales = Q.quantize_packed_buffer(buf, seg_ids, meta[2],
                                                 seg_bits=meta[4],
                                                 use_kernels=False)
        return codes, scales, None
    res = ef_state.residual
    if isinstance(res, dict) and is_plane(res.get("student")):
        # plane-backed residual: its student rows already live in the
        # wire layout — splice, quantize in the shared sweep, splice the
        # fresh error back into a plane (zero repack, like the payload)
        res_buf, _i, _m, r_p, span = Q.pack_plane_payload(
            res["protos"], res["student"])
        res_buf = _constrain_buf(mesh, res_buf, "pod")
        codes, scales, new_res = Q.quantize_packed_buffer(
            buf, seg_ids, meta[2], seg_bits=meta[4], use_kernels=False,
            residual=res_buf, ef_decay=wire.ef_decay)
        new_res = _constrain_buf(mesh, new_res, "pod")
        n, c_cls, p_dim = res["protos"].shape
        pr = new_res[:, :r_p].reshape(n, -1)[:, :c_cls * p_dim] \
            .reshape(n, c_cls, p_dim)
        spl = res["student"]
        sbuf = new_res[:, r_p:r_p + span]
        pad = spl.meta.rows - span
        if pad:
            sbuf = jnp.pad(sbuf, ((0, 0), (0, pad), (0, 0)))
        residual = {"protos": pr,
                    "student": Plane(sbuf, spl.raw, spl.meta)}
        return codes, scales, CodecState(residual,
                                         seq=next_seq(ef_state.seq))
    res_buf, _ids, res_meta = Q.pack_tree_nodes(res)
    res_buf = _constrain_buf(mesh, res_buf, "pod")
    codes, scales, new_res = Q.quantize_packed_buffer(
        buf, seg_ids, meta[2], seg_bits=meta[4], use_kernels=False,
        residual=res_buf, ef_decay=wire.ef_decay)
    new_res = _constrain_buf(mesh, new_res, "pod")
    return codes, scales, CodecState(Q.unpack_tree_nodes(new_res, res_meta),
                                     seq=next_seq(ef_state.seq))


def _constrain_ef_state(mesh, state: CodecState, student_specs):
    res = state.residual
    if isinstance(res, dict) and is_plane(res.get("student")):
        pl = res["student"]
        return CodecState(residual={
            "protos": jax.lax.with_sharding_constraint(
                res["protos"], NamedSharding(mesh, P("pod", None, None))),
            "student": Plane(_constrain_buf(mesh, pl.buf, "pod"),
                             pl.raw, pl.meta)}, seq=state.seq)
    return CodecState(residual=_constrain_over_pod(
        mesh, res, ef_state_specs(student_specs).residual,
        "pod"), seq=state.seq)


def _wrap_ef(core, mesh, student_specs, wire: WireSpec):
    """Arity of the round follows the spec: stateless specs keep the
    4-arg ``round_fn``; error-feedback specs take and return the
    :class:`CodecState` (its leaves pinned node-sharded so the residual
    can never leak into a collective)."""
    if wire.error_feedback:
        def round_fn(students, protos, counts, sizes, codec_state):
            s, g, m, new_state = core(students, protos, counts, sizes,
                                      codec_state)
            return s, g, m, _constrain_ef_state(mesh, new_state,
                                                student_specs)
        return round_fn

    def round_fn(students, protos, counts, sizes):
        return core(students, protos, counts, sizes, None)[:3]
    return round_fn


def _make_profe_round_packed(mesh, student_specs, wire: WireSpec, adj):
    """Packed single-buffer exchange: quantize+pack+encode -> ONE
    all-gather of the [N, B] spec-byte wire buffer over the pod axis ->
    decode -> fused weighted mix on the codes -> unpack."""
    return _wrap_ef(_packed_round_core(mesh, student_specs, wire, adj),
                    mesh, student_specs, wire)


def _packed_round_core(mesh, student_specs, wire: WireSpec, adj):
    """The unwrapped 5-arg packed round."""
    include = None if adj is None else include_matrix(adj)

    def _round(students, protos, counts, sizes, ef_state):
        n = counts.shape[0]
        buf, seg_ids, meta, ploc, splice = _pack_payload(protos, students,
                                                         wire)
        seg_bits = meta[4]
        buf = _constrain_buf(mesh, buf, "pod")
        # jnp codec flavor: GSPMD partitions it over the mesh (the
        # Pallas kernels run per-device under shard_map, see ppermute)
        codes, scales, new_state = _quantize_with_state(
            mesh, wire, buf, seg_ids, meta, ef_state)

        # the exchange: ONE all-gather of the encoded [N, B] byte
        # buffer over the pod axis — B is exactly the spec bytes
        # (int16 rows bitcast, int4 rows nibble-packed).  The encode
        # runs per device under shard_map: its bitcast/nibble ops have
        # no GSPMD propagation rule, and left unconstrained XLA gathers
        # the *container*-width codes instead of the spec bytes.
        if _inner_size(mesh) == 1:
            enc = shard_map(
                lambda c: Q.encode_wire(c, seg_ids, seg_bits=seg_bits),
                mesh=mesh, in_specs=(P("pod", None, None),),
                out_specs=P("pod", None), check_vma=False)
            wire_buf = _constrain_buf(mesh, enc(codes), None)
            codes = Q.decode_wire(wire_buf, seg_ids, seg_bits=seg_bits)
            codes = jax.lax.with_sharding_constraint(
                codes, NamedSharding(mesh, P(None, None, None)))
        else:
            # multi-axis pods keep the PR-3 container-width gather (the
            # rows stay sharded over the inner axes; per-pod wire bytes
            # are not asserted on this fallback path)
            codes = _constrain_buf(mesh, codes, None)
        scales = _constrain_buf(mesh, scales, None)
        counts_r = jax.lax.with_sharding_constraint(
            counts, NamedSharding(mesh, P(None, None)))

        # receiver side: mixing weights applied directly on packed codes
        row_delta = scales[:, seg_ids]                         # [N, R]
        if adj is None:
            w = sizes / jnp.sum(sizes)                         # [N]
            w_self_v = jnp.zeros((n,), jnp.float32)
            w_rows = jnp.broadcast_to(w[None, :], (n, n))
        else:
            w_self_v, w_rows = gossip_matrix_dyn(adj, sizes)
        mixed = Q.mix_packed(buf, codes, row_delta, w_self_v, w_rows,
                             use_kernels=False)
        mixed = _constrain_buf(mesh, mixed, "pod")
        new_students = _splice_students(mesh, mixed, meta, students,
                                        splice, student_specs)

        # prototypes: receiver-side view straight from the packed codes
        prow, pnrows, pshape = ploc
        pdeq = codes[:, prow:prow + pnrows].astype(jnp.float32) * \
            row_delta[:, prow:prow + pnrows, None]
        cdim = pshape[1] * pshape[2]
        protos_rx = pdeq.reshape(n, -1)[:, :cdim].reshape(pshape)
        if adj is None:
            global_protos, proto_mask = aggregate_prototypes(protos_rx,
                                                             counts_r)
            return new_students, global_protos, proto_mask, new_state
        global_protos, proto_mask = neighborhood_prototype_aggregate(
            include, protos_rx, counts_r)
        global_protos = jax.lax.with_sharding_constraint(
            global_protos, NamedSharding(mesh, P("pod", None, None)))
        proto_mask = jax.lax.with_sharding_constraint(
            proto_mask, NamedSharding(mesh, P("pod", None)))
        return new_students, global_protos, proto_mask, new_state

    return _round


def _make_profe_round_ppermute(mesh, student_specs, wire: WireSpec,
                               adj: np.ndarray, overlap: bool = False):
    """Physical sparse gossip: degree-many ``jax.lax.ppermute`` steps of
    the encoded wire byte buffer on the pod axis (one device per node),
    fused dequant-and-accumulate receiver side.  Wire bytes per node per
    round = steps x |spec-encoded payload| = exactly what the accountant
    charges — int4 rows physically move a quarter of the int16 bytes."""
    perms, srcs = _perm_lowering(adj)

    def _round(students, protos, counts, sizes, ef_state):
        buf, seg_ids, meta, ploc, splice = _pack_payload(protos, students,
                                                         wire)
        seg_bits = meta[4]
        buf = _constrain_buf(mesh, buf, "pod")
        # the stateful quantize runs BEFORE the permutes — the residual
        # is a node-local operand, so the exchange below still moves
        # exactly degree x spec bytes
        codes, scales, new_state = _quantize_with_state(
            mesh, wire, buf, seg_ids, meta, ef_state)
        w_self_v, w_neigh = gossip_matrix_dyn(adj, sizes)
        prow, pnrows, pshape = ploc
        ccls, pdim = pshape[1], pshape[2]
        ids = jnp.asarray(seg_ids)

        def decode(w):
            return Q.decode_wire(w, seg_ids, seg_bits=seg_bits)

        @partial(shard_map, mesh=mesh,
                 in_specs=(P("pod", None, None), P("pod", None, None),
                           P("pod", None), P("pod", None),
                           P("pod"), P("pod", None)),
                 out_specs=(P("pod", None, None), P("pod", None, None),
                            P("pod", None)),
                 check_vma=False)
        def exchange(own_buf, codes, scales, counts, w_self, w_row):
            me = jax.lax.axis_index("pod")
            # serialize to the wire byte layout per device (inside the
            # shard_map: the encode's bitcast/nibble ops have no GSPMD
            # rule, and outside it XLA would replicate the codes —
            # gathering container bytes instead of spec bytes); the
            # decode of a permuted buffer is the receiver's exact view
            # of the codes, so the own copy skips the round-trip.
            wire_bytes = Q.encode_wire(codes, seg_ids, seg_bits=seg_bits)
            own_delta = scales[0, ids]
            own_pdeq = (codes[0, prow:prow + pnrows].astype(jnp.float32)
                        * own_delta[prow:prow + pnrows, None])
            own_pdeq = own_pdeq.reshape(-1)[:ccls * pdim].reshape(ccls,
                                                                  pdim)
            num = counts[0][:, None] * own_pdeq
            den = counts[0]

            if overlap:
                # pipelined exchange: double buffer — step s+1's three
                # ppermutes are issued BEFORE step s's fused
                # dequant-accumulate consumes its payload, so the
                # latency-hiding scheduler can run collective s+1 and
                # mix s concurrently.  The mix folds step by step
                # (mix_packed_init / mix_packed_accumulate), never
                # materializing the [S, R, 512] step stack.
                acc = Q.mix_packed_init(own_buf, w_self)
                inflight = (jax.lax.ppermute(wire_bytes, "pod", perms[0]),
                            jax.lax.ppermute(scales, "pod", perms[0]),
                            jax.lax.ppermute(counts, "pod", perms[0]))
                for s, src in enumerate(srcs):
                    rw, rs, rcnt = inflight
                    if s + 1 < len(perms):
                        inflight = (
                            jax.lax.ppermute(wire_bytes, "pod",
                                             perms[s + 1]),
                            jax.lax.ppermute(scales, "pod", perms[s + 1]),
                            jax.lax.ppermute(counts, "pod", perms[s + 1]))
                    rc = decode(rw)
                    rd = rs[0, ids]
                    valid, w_p = _step_weight(src, me, w_row)
                    acc = Q.mix_packed_accumulate(acc, rc, rd[None],
                                                  w_p[None, None])
                    pr = (rc[0, prow:prow + pnrows].astype(jnp.float32)
                          * rd[prow:prow + pnrows, None])
                    pr = pr.reshape(-1)[:ccls * pdim].reshape(ccls, pdim)
                    num = num + valid * rcnt[0][:, None] * pr
                    den = den + valid * rcnt[0]
                glob = num / jnp.maximum(den, 1.0)[:, None]
                mask = (den > 0).astype(jnp.float32)
                return acc, glob[None], mask[None]

            # neighbor collectives: one ppermute of the encoded wire
            # byte buffer (+ its scales and counts) per permutation step
            recv = []
            for step, src in zip(perms, srcs):
                rc = decode(jax.lax.ppermute(wire_bytes, "pod", step))
                rs = jax.lax.ppermute(scales, "pod", step)
                rcnt = jax.lax.ppermute(counts, "pod", step)
                valid, w_p = _step_weight(src, me, w_row)
                recv.append((rc, rs, rcnt, valid, w_p))

            # fused dequant-and-accumulate on the packed codes: the
            # neighbors' int16 buffers fold straight into the mix
            codes_stack = jnp.concatenate([r[0] for r in recv], axis=0)
            delta_stack = jnp.stack([r[1][0, ids] for r in recv])
            w_stack = jnp.stack([r[4] for r in recv])          # [S]
            mixed = Q.mix_packed(own_buf, codes_stack, delta_stack,
                                 w_self, w_stack[None, :])

            # Eq. 4 per neighborhood, accumulated across steps (own
            # prototypes enter quantized, like every receiver's view)
            for s, (rc, _rs, rcnt, valid, _w) in enumerate(recv):
                pr = (rc[0, prow:prow + pnrows].astype(jnp.float32)
                      * delta_stack[s, prow:prow + pnrows, None])
                pr = pr.reshape(-1)[:ccls * pdim].reshape(ccls, pdim)
                num = num + valid * rcnt[0][:, None] * pr
                den = den + valid * rcnt[0]
            glob = num / jnp.maximum(den, 1.0)[:, None]
            mask = (den > 0).astype(jnp.float32)
            return mixed, glob[None], mask[None]

        mixed, global_protos, proto_mask = exchange(
            buf, codes, scales, counts, w_self_v, w_neigh)
        new_students = _splice_students(mesh, mixed, meta, students,
                                        splice, student_specs)
        return new_students, global_protos, proto_mask, new_state

    return _wrap_ef(_round, mesh, student_specs, wire)


def _make_profe_round_ppermute_sharded(mesh, student_specs, wire: WireSpec,
                                       adj: np.ndarray, *,
                                       overlap: bool = False):
    """Row-sharded sparse gossip for multi-axis pods: each of the M inner
    devices of a pod permutes only ITS row block of the encoded wire
    buffer, so a ``(N, d, m)`` mesh moves spec-exact bytes per node —
    ``B + 4·T' + 4·C'`` per copy (``packed_copy_bytes(..., inner=M)``) —
    instead of falling back to the container-width gather.

    ``shard_map`` traces one program for every shard, so each device's
    encoded byte count must be a static constant: the buffer rows are
    re-ordered by :func:`repro.sharding.row_shard_order` so every shard
    holds the identical per-width row profile (the k-th equal slice of
    every width group).  A width group whose row count does not divide
    M rides appended all-zero pad rows (zero codes encode to zero bytes
    at the group's width and dequantize to zero — the mix math is
    unchanged, and ``packed_copy_bytes(..., inner=M)`` counts the pad
    rows), so every mixed-width payload splits.

    Scale/count sidecars shard over the inner axes too (padded to a
    multiple of M) and are re-widened receiver-side with an intra-pod
    ``all_gather`` over the inner axes — traffic on the data/model axes,
    never on ``pod``, so the per-node pod bytes the dry-run asserts stay
    spec-exact.  Prototype rows scatter from whichever shard holds them
    and combine with an intra-pod ``psum``."""
    perms, srcs = _perm_lowering(adj)
    M = _inner_size(mesh)
    inner = _inner_axes(mesh)
    inner_sizes = [int(dict(mesh.shape)[a]) for a in inner]

    def _round(students, protos, counts, sizes, ef_state):
        buf, seg_ids, meta, ploc, splice = _pack_payload(protos, students,
                                                         wire)
        seg_bits = meta[4]
        ids_np = np.asarray(seg_ids)
        row_b = np.asarray(seg_bits)[ids_np]
        order, inv_order, local_bits = row_shard_order(row_b, M)
        rloc = len(order) // M
        loc_seq = np.arange(rloc)
        n_pad = len(order) - len(ids_np)
        if n_pad:
            # non-splittable width groups ride appended all-zero rows;
            # a pad row borrows a segment id of its width group (sentinel
            # assignment mirrors row_shard_order: sequential, groups in
            # ascending width) so the receiver's scale lookup stays in
            # range — its codes are zero, so delta never matters
            pad_ids = []
            for b in sorted(set(row_b.tolist())):
                grp = np.nonzero(row_b == b)[0]
                pad_ids += [int(ids_np[grp[0]])] * ((-len(grp)) % M)
            ids_full = np.concatenate(
                [ids_np, np.asarray(pad_ids, ids_np.dtype)])
        else:
            ids_full = ids_np
        ids_g = ids_full[order]                # segment per row, shard order
        buf = _constrain_buf(mesh, buf, "pod")
        codes, scales, new_state = _quantize_with_state(
            mesh, wire, buf, seg_ids, meta, ef_state)
        w_self_v, w_neigh = gossip_matrix_dyn(adj, sizes)
        prow, pnrows, pshape = ploc
        ccls, pdim = pshape[1], pshape[2]

        # rows into shard order (pad rows appended zero); sidecars padded
        # to a multiple of M so they split over the inner axes with the
        # buffer
        if n_pad:
            buf = jnp.pad(buf, ((0, 0), (0, n_pad), (0, 0)))
            codes = jnp.pad(codes, ((0, 0), (0, n_pad), (0, 0)))
        buf_p = _constrain_buf(mesh, jnp.take(buf, jnp.asarray(order),
                                              axis=1), "pod")
        codes_p = _constrain_buf(mesh, jnp.take(codes, jnp.asarray(order),
                                                axis=1), "pod")
        nt = scales.shape[1]
        scales_p = jnp.pad(scales, ((0, 0), (0, (-nt) % M)))
        counts_p = jnp.pad(counts, ((0, 0), (0, (-ccls) % M)))
        side_sharding = NamedSharding(mesh, P("pod", inner))
        scales_p = jax.lax.with_sharding_constraint(scales_p, side_sharding)
        counts_p = jax.lax.with_sharding_constraint(counts_p, side_sharding)

        @partial(shard_map, mesh=mesh,
                 in_specs=(P("pod", inner, None), P("pod", inner, None),
                           P("pod", inner), P("pod", inner),
                           P("pod"), P("pod", None)),
                 out_specs=(P("pod", inner, None), P("pod", None, None),
                            P("pod", None)),
                 check_vma=False)
        def exchange(own_buf, codes_l, scales_l, counts_l, w_self, w_row):
            me = jax.lax.axis_index("pod")
            k = jnp.int32(0)                   # flattened inner index
            for a, sz in zip(inner, inner_sizes):
                k = k * sz + jax.lax.axis_index(a)
            # this shard's true segment ids / global row positions —
            # dynamic values over a static, shard-identical width profile
            loc_ids = jax.lax.dynamic_slice(jnp.asarray(ids_g),
                                            (k * rloc,), (rloc,))
            gpos = jax.lax.dynamic_slice(jnp.asarray(order),
                                         (k * rloc,), (rloc,))
            # encode THIS row block against its synthetic one-row-per-
            # segment profile: every shard's bytes are the same static
            # B/M, and summed over the pod they are exactly the spec B
            wire_bytes = Q.encode_wire(codes_l, loc_seq,
                                       seg_bits=local_bits)

            def widen(x):
                # sidecar shards back to full width — intra-pod traffic
                # on the inner axes only, never on "pod"
                return jax.lax.all_gather(x, inner, axis=1, tiled=True)

            def proto_part(cl, rd):
                # scatter this shard's prototype rows to their global
                # slots (alignment/student rows hit the dump slot), then
                # combine shards with an intra-pod psum
                deq = cl[0].astype(jnp.float32) * rd[:, None]
                ppos = gpos - prow
                pvalid = (ppos >= 0) & (ppos < pnrows)
                idx = jnp.where(pvalid, ppos, pnrows)
                scat = jnp.zeros((pnrows + 1, deq.shape[1]), jnp.float32)
                scat = scat.at[idx].add(
                    jnp.where(pvalid[:, None], deq, 0.0))
                full = jax.lax.psum(scat, inner)[:pnrows]
                return full.reshape(-1)[:ccls * pdim].reshape(ccls, pdim)

            own_rd = widen(scales_l)[0, loc_ids]
            cnt_own = widen(counts_l)[0, :ccls]
            num = cnt_own[:, None] * proto_part(codes_l, own_rd)
            den = cnt_own
            acc = Q.mix_packed_init(own_buf, w_self)

            def issue(step):
                return (jax.lax.ppermute(wire_bytes, "pod", step),
                        jax.lax.ppermute(scales_l, "pod", step),
                        jax.lax.ppermute(counts_l, "pod", step))

            inflight = issue(perms[0]) if overlap else None
            for s, (step, src) in enumerate(zip(perms, srcs)):
                if overlap:
                    rw, rs_l, rcnt_l = inflight
                    if s + 1 < len(perms):
                        inflight = issue(perms[s + 1])
                else:
                    rw, rs_l, rcnt_l = issue(step)
                rc = Q.decode_wire(rw, loc_seq, seg_bits=local_bits)
                rd = widen(rs_l)[0, loc_ids]
                rcnt = widen(rcnt_l)[0, :ccls]
                valid, w_p = _step_weight(src, me, w_row)
                acc = Q.mix_packed_accumulate(acc, rc, rd[None],
                                              w_p[None, None])
                pr = proto_part(rc, rd)
                num = num + valid * rcnt[:, None] * pr
                den = den + valid * rcnt
            glob = num / jnp.maximum(den, 1.0)[:, None]
            mask = (den > 0).astype(jnp.float32)
            return acc, glob[None], mask[None]

        mixed_p, global_protos, proto_mask = exchange(
            buf_p, codes_p, scales_p, counts_p, w_self_v, w_neigh)
        mixed = _constrain_buf(mesh, jnp.take(mixed_p,
                                              jnp.asarray(inv_order),
                                              axis=1), "pod")
        new_students = _splice_students(mesh, mixed, meta, students,
                                        splice, student_specs)
        return new_students, global_protos, proto_mask, new_state

    return _wrap_ef(_round, mesh, student_specs, wire)


def _make_profe_round_gather(mesh, student_specs, wire: WireSpec, adj):
    """PR-2 reference exchange: per-leaf all-gather of shape-preserving
    intN codes over the pod axis + masked ``mix_node_trees``.  The
    semantics oracle the packed/ppermute paths are asserted against;
    each leaf group quantizes at its spec width."""
    include = None if adj is None else include_matrix(adj)
    s_bits = wire.bits_for("student")
    p_bits = wire.bits_for("protos")

    def _round(students, protos, counts, sizes, ef_state):
        # 0. stateful codec: replay the carried residual into the
        #    payload (all node-local math, pre-exchange)
        if ef_state is not None:
            decay = jnp.float32(wire.ef_decay)
            eff_students = jax.tree_util.tree_map(
                lambda x, r: x.astype(jnp.float32) + decay * r,
                students, ef_state.residual["student"])
            eff_protos = protos.astype(jnp.float32) + \
                decay * ef_state.residual["protos"]
        else:
            eff_students, eff_protos = students, protos

        # 1. quantize per node (vmapped math, stays in-pod)
        q = jax.tree_util.tree_map(
            lambda x: quantize_leaf_per_node(x, s_bits), eff_students,
            is_leaf=lambda x: hasattr(x, "shape"))
        codes = jax.tree_util.tree_map(lambda t: t[0], q,
                                       is_leaf=lambda t: isinstance(t, tuple))
        scales = jax.tree_util.tree_map(lambda t: t[1], q,
                                        is_leaf=lambda t: isinstance(t, tuple))
        pq, pd = quantize_leaf_per_node(eff_protos, p_bits)
        if ef_state is not None:
            # fresh quantization error, from the pre-exchange view
            new_state = CodecState(residual={
                "protos": eff_protos - dequantize_leaf(pq, pd),
                "student": jax.tree_util.tree_map(
                    lambda e, c, d: e - dequantize_leaf(c, d),
                    eff_students, codes, scales)},
                seq=next_seq(ef_state.seq))
        else:
            new_state = None

        # 2. the exchange: all-gather int16 codes over the pod axis
        codes = _replicate_over_pod(mesh, codes, student_specs)
        scales = jax.tree_util.tree_map(
            lambda d: jax.lax.with_sharding_constraint(
                d, NamedSharding(mesh, P(None))), scales)
        pq = jax.lax.with_sharding_constraint(
            pq, NamedSharding(mesh, P(None, None, None)))
        counts_r = jax.lax.with_sharding_constraint(
            counts, NamedSharding(mesh, P(None, None)))

        # 3. local dequantize + size-weighted mix
        deq = jax.tree_util.tree_map(dequantize_leaf, codes, scales)
        protos_rx = dequantize_leaf(pq, pd)                    # [N, C, P]
        if adj is None:
            # full mesh: plain FedAvg over all nodes, every node identical
            w = sizes / jnp.sum(sizes)                         # [N]
            means = weighted_node_mean(w, deq)
            new_students = jax.tree_util.tree_map(
                lambda m, c: jnp.stack([m] * c.shape[0]).astype(jnp.float32),
                means, codes)
            global_protos, proto_mask = aggregate_prototypes(protos_rx,
                                                             counts_r)
            return new_students, global_protos, proto_mask, new_state

        # masked gossip: per-node weighted einsum over the gathered
        # codes; non-neighbor columns are zero, own copy unquantized
        w_self, w_neigh = gossip_matrix_dyn(adj, sizes)
        new_students = mix_node_trees(w_self, w_neigh, students, deq)
        new_students = _constrain_over_pod(mesh, new_students,
                                           student_specs, "pod")
        global_protos, proto_mask = neighborhood_prototype_aggregate(
            include, protos_rx, counts_r)
        global_protos = jax.lax.with_sharding_constraint(
            global_protos, NamedSharding(mesh, P("pod", None, None)))
        proto_mask = jax.lax.with_sharding_constraint(
            proto_mask, NamedSharding(mesh, P("pod", None)))
        return new_students, global_protos, proto_mask, new_state

    return _wrap_ef(_round, mesh, student_specs, wire)


def _unpack_stack(dq_stack, meta):
    """Per-step unpack of a received ``[N, S, R, C]`` dequantized buffer
    stack: :func:`Q.unpack_tree_nodes` with a step axis — float leaves
    come back ``[N, S, ...]``, raw entries pass through."""
    treedef, recipe = meta[0], meta[1]
    n, s = dq_stack.shape[:2]
    leaves = []
    for item in recipe:
        if item[0] == "raw":
            leaves.append(item[1])
            continue
        _, shape, _dtype, row, nrows, _s = item
        per = 1
        for d in shape[1:]:
            per *= d
        rows = dq_stack[:, :, row:row + nrows, :]
        leaves.append(rows.reshape(n, s, -1)[:, :, :per]
                      .reshape((n, s) + tuple(shape[1:])))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def _constrain_pod_lead(mesh, tree):
    """Pin the leading node axis of every leaf to the pod axis
    (trailing dims replicated) — the adapter-state convention."""
    return jax.tree_util.tree_map(
        lambda x: jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P("pod"))), tree)


def _make_profe_round_adapter(mesh, student_specs, wire: WireSpec,
                              adj, mode: str, *, rank: int,
                              grams: bool = False,
                              overlap: bool = False):
    """Adapter-rank wire on the mesh: every matrix leaf of the student
    gossips its per-round low-rank delta factors ``(B, A)`` (the
    "adapters" payload group, plus "grams" when RegMean statistics
    ride) instead of the dense parameters; the non-matrix rest and the
    prototypes keep the classic exchange.  Aggregation is merge-based —
    ``W_i ← W_i + Σ_j w_ij·B_j@Ã_j`` — so the round carries an
    ``adapter_state`` operand (the per-node reference snapshot deltas
    factorize against):

        round(students, protos, counts, sizes, adapter_state
              [, codec_state]) -> (students', global_protos, mask,
                                   adapter_state' [, codec_state'])

    All three exchange modes move the same logical payload; only the
    physical bytes differ:

    * ``gather`` — the semantics oracle: the shared
      :func:`repro.core.round_ops.quantize_dequantize_per_node` packed
      codec, codes replicated over the pod axis, node-local merge.
    * ``packed`` — ``Q.pack_tree_nodes`` of the factor payload → ONE
      all-gather of the spec-byte wire buffer (encode under shard_map
      at inner==1, exactly like the dense packed round) → dequantize →
      unpack → merge.
    * ``ppermute`` — degree-many permutes of the encoded factor wire
      bytes; every receiver decodes its own per-step view, so the
      factor banks come back receiver-specific ``[N, S, ...]`` and the
      merge runs the 4-D-gram RegMean branch.  ``overlap`` double
      buffers the permutes exactly like the dense path.

    Error feedback rides the generic tree-residual path of
    :func:`_quantize_with_state` — the residual mirrors the factor
    payload structure and never feeds a collective.  The full-mesh
    protocol (``adjacency=None``) is unsupported: merge-based
    aggregation is inherently neighborhood-wise (every node applies
    deltas onto its OWN weights), so "every node ends identical" does
    not hold."""
    from repro.core import round_ops as R
    from repro.core.adapters import merge_student, split_student
    from repro.core.aggregation import regmean_adjust
    if adj is None:
        raise ValueError("the adapter wire needs an explicit adjacency "
                         "(merge-based aggregation is neighborhood-wise; "
                         "the full protocol's identical-output semantics "
                         "do not apply)")
    include = include_matrix(adj)
    if mode == "ppermute" and _inner_size(mesh) > 1:
        raise ValueError("adapter_rank does not support the row-sharded "
                         "ppermute exchange (inner mesh axes > 1) — use "
                         "exchange='packed'")

    def _share(students, protos, adapter_state):
        groups, new_ast, layout = R.adapter_share_nodes(
            students, adapter_state, rank=rank, grams=grams)
        payload = dict(groups)
        payload["protos"] = protos
        return payload, _constrain_pod_lead(mesh, new_ast), layout

    def _finish(new_students, protos_rx, counts_r):
        new_students = _constrain_over_pod(mesh, new_students,
                                           student_specs, "pod")
        global_protos, proto_mask = neighborhood_prototype_aggregate(
            include, protos_rx, counts_r)
        global_protos = jax.lax.with_sharding_constraint(
            global_protos, NamedSharding(mesh, P("pod", None, None)))
        proto_mask = jax.lax.with_sharding_constraint(
            proto_mask, NamedSharding(mesh, P("pod", None)))
        return new_students, global_protos, proto_mask

    def _core_gather(students, protos, counts, sizes, ast, ef_state):
        payload, new_ast, _layout = _share(students, protos, ast)
        if ef_state is not None:
            recv, new_ef = R.quantize_dequantize_per_node(
                payload, spec=wire, state=ef_state, use_kernels=False)
        else:
            recv = R.quantize_dequantize_per_node(payload, spec=wire,
                                                  use_kernels=False)
            new_ef = None
        recv = dict(recv)
        protos_rx = recv.pop("protos")
        w_self_v, w_rows = gossip_matrix_dyn(adj, sizes)
        new_students = R.adapter_merge_nodes(students, recv, w_self_v,
                                             w_rows, rank=rank,
                                             grams=grams,
                                             use_kernels=False)
        counts_r = jax.lax.with_sharding_constraint(
            counts, NamedSharding(mesh, P(None, None)))
        return (*_finish(new_students, protos_rx, counts_r), new_ast,
                new_ef)

    def _core_packed(students, protos, counts, sizes, ast, ef_state):
        payload, new_ast, _layout = _share(students, protos, ast)
        buf, seg_ids, meta = Q.pack_tree_nodes(payload, spec=wire)
        seg_bits = meta[4]
        buf = _constrain_buf(mesh, buf, "pod")
        codes, scales, new_ef = _quantize_with_state(
            mesh, wire, buf, seg_ids, meta, ef_state)
        if _inner_size(mesh) == 1:
            enc = shard_map(
                lambda c: Q.encode_wire(c, seg_ids, seg_bits=seg_bits),
                mesh=mesh, in_specs=(P("pod", None, None),),
                out_specs=P("pod", None), check_vma=False)
            wire_buf = _constrain_buf(mesh, enc(codes), None)
            codes = Q.decode_wire(wire_buf, seg_ids, seg_bits=seg_bits)
            codes = jax.lax.with_sharding_constraint(
                codes, NamedSharding(mesh, P(None, None, None)))
        else:
            codes = _constrain_buf(mesh, codes, None)
        scales = _constrain_buf(mesh, scales, None)
        row_delta = scales[:, seg_ids]                         # [N, R]
        dq = codes.astype(jnp.float32) * row_delta[:, :, None]
        recv = dict(Q.unpack_tree_nodes(dq, meta))
        protos_rx = recv.pop("protos")
        w_self_v, w_rows = gossip_matrix_dyn(adj, sizes)
        new_students = R.adapter_merge_nodes(students, recv, w_self_v,
                                             w_rows, rank=rank,
                                             grams=grams,
                                             use_kernels=False)
        counts_r = jax.lax.with_sharding_constraint(
            counts, NamedSharding(mesh, P(None, None)))
        return (*_finish(new_students, protos_rx, counts_r), new_ast,
                new_ef)

    perms, srcs = (None, None) if mode != "ppermute" else \
        _perm_lowering(adj)

    def _core_ppermute(students, protos, counts, sizes, ast, ef_state):
        n = counts.shape[0]
        payload, new_ast, layout = _share(students, protos, ast)
        buf, seg_ids, meta = Q.pack_tree_nodes(payload, spec=wire)
        seg_bits = meta[4]
        buf = _constrain_buf(mesh, buf, "pod")
        codes, scales, new_ef = _quantize_with_state(
            mesh, wire, buf, seg_ids, meta, ef_state)
        w_self_v, w_neigh = gossip_matrix_dyn(adj, sizes)
        ids = jnp.asarray(seg_ids)

        @partial(shard_map, mesh=mesh,
                 in_specs=(P("pod", None, None), P("pod", None),
                           P("pod", None)),
                 out_specs=(P("pod", None, None, None),
                            P("pod", None, None)),
                 check_vma=False)
        def exchange(codes, scales, counts):
            # the physical wire: degree-many permutes of the encoded
            # spec-byte factor buffer (+ scales and counts).  Each
            # receiver dequantizes its per-step view locally — the
            # stacks leave the shard_map node-sharded, so the merge
            # below adds no collective beyond the permutes.
            wire_bytes = Q.encode_wire(codes, seg_ids, seg_bits=seg_bits)
            dqs, cnts = [], []
            if overlap:
                inflight = (
                    jax.lax.ppermute(wire_bytes, "pod", perms[0]),
                    jax.lax.ppermute(scales, "pod", perms[0]),
                    jax.lax.ppermute(counts, "pod", perms[0]))
                for s in range(len(perms)):
                    rw, rs, rcnt = inflight
                    if s + 1 < len(perms):
                        inflight = (
                            jax.lax.ppermute(wire_bytes, "pod",
                                             perms[s + 1]),
                            jax.lax.ppermute(scales, "pod", perms[s + 1]),
                            jax.lax.ppermute(counts, "pod", perms[s + 1]))
                    rc = Q.decode_wire(rw, seg_ids, seg_bits=seg_bits)
                    dqs.append(rc[0].astype(jnp.float32)
                               * rs[0, ids][:, None])
                    cnts.append(rcnt[0])
            else:
                for step in perms:
                    rw = jax.lax.ppermute(wire_bytes, "pod", step)
                    rs = jax.lax.ppermute(scales, "pod", step)
                    rcnt = jax.lax.ppermute(counts, "pod", step)
                    rc = Q.decode_wire(rw, seg_ids, seg_bits=seg_bits)
                    dqs.append(rc[0].astype(jnp.float32)
                               * rs[0, ids][:, None])
                    cnts.append(rcnt[0])
            return jnp.stack(dqs)[None], jnp.stack(cnts)[None]

        dq_stack, cnt_stack = exchange(codes, scales, counts)
        # step -> (valid, sender) is static; zero invalid steps'
        # payloads explicitly so isolated receivers merge exact zeros.
        # Statically re-sort each receiver's steps into ascending-sender
        # order (invalid steps last): the merge sums below then run in
        # the same term order as the gather/packed exchanges.  The
        # RegMean solve amplifies even a one-ulp reassociation of the
        # gram sum, so the summation order is part of the cross-mode
        # contract, not a cosmetic choice.
        valid = np.stack([(s >= 0) for s in srcs], 1).astype(np.float32)
        src_idx = np.stack([np.maximum(s, 0) for s in srcs], 1)
        order = np.argsort(np.where(valid > 0, src_idx, n), axis=1,
                           kind="stable")
        valid = np.take_along_axis(valid, order, axis=1)
        src_idx = np.take_along_axis(src_idx, order, axis=1)
        jorder = jnp.asarray(order)
        dq_stack = jnp.take_along_axis(
            dq_stack, jorder[:, :, None, None], axis=1)
        cnt_stack = jnp.take_along_axis(cnt_stack, jorder[:, :, None],
                                        axis=1)
        c_steps = jnp.asarray(valid) * jnp.take_along_axis(
            w_neigh, jnp.asarray(src_idx), axis=1)             # [N, S]
        dq_stack = dq_stack * jnp.asarray(valid)[:, :, None, None]
        cnt_stack = cnt_stack * jnp.asarray(valid)[:, :, None]
        recv = dict(_unpack_stack(dq_stack, meta))             # [N, S, ..]
        protos_rx = recv.pop("protos")                         # [N,S,C,P]

        # prototypes: own copy enters quantized, like every receiver's
        # view of it (dequantize the own codes locally)
        row_delta = scales[:, ids]
        own_dq = codes.astype(jnp.float32) * row_delta[:, :, None]
        own_p = dict(Q.unpack_tree_nodes(own_dq, meta))["protos"]
        num = counts[:, :, None] * own_p + \
            jnp.sum(cnt_stack[:, :, :, None] * protos_rx, axis=1)
        den = counts + jnp.sum(cnt_stack, axis=1)
        global_protos = num / jnp.maximum(den, 1.0)[:, :, None]
        proto_mask = (den > 0).astype(jnp.float32)
        global_protos = jax.lax.with_sharding_constraint(
            global_protos, NamedSharding(mesh, P("pod", None, None)))
        proto_mask = jax.lax.with_sharding_constraint(
            proto_mask, NamedSharding(mesh, P("pod", None)))

        # merge: rest leaves mix classically (own copy unquantized);
        # matrix leaves add the receiver-specific low-rank deltas
        mats_own, rest_own = split_student(layout, students)
        rest_rx = recv["student"]

        def mixr(own, rx):
            bshape = (n,) + (1,) * (own.ndim - 1)
            return w_self_v.reshape(bshape) * own.astype(jnp.float32) + \
                fp32.einsum("ns,ns...->n...", c_steps,
                            rx.astype(jnp.float32))
        rest_mixed = jax.tree_util.tree_map(mixr, rest_own, rest_rx)
        fac = recv["adapters"]
        mats_new = {}
        # the RegMean solve is receiver-local, but jnp.linalg.solve
        # lowers to a getrf custom call the partitioner cannot shard
        # over the node axis — run it under shard_map so each node
        # solves its own [S, k, k] systems and no phantom all-gather
        # rides the wire (the exact byte gate counts every collective)
        regmean_local = partial(
            shard_map, mesh=mesh,
            in_specs=(P("pod"), P("pod"), P("pod")),
            out_specs=P("pod"), check_vma=False)(
                lambda a_, g_, c_: regmean_adjust(a_, g_, c_,
                                                  per_recv=True))
        for nm in layout.mat_names:
            a4, b4 = fac[nm]["A"], fac[nm]["B"]
            if grams:
                a4 = regmean_local(a4, recv["grams"][nm], c_steps)
            delta = fp32.einsum("ns,ns...dr,ns...rk->n...dk", c_steps,
                                b4.astype(jnp.float32),
                                a4.astype(jnp.float32))
            mats_new[nm] = mats_own[nm].astype(jnp.float32) + delta
        new_students = merge_student(layout, mats_new, rest_mixed)
        new_students = _constrain_over_pod(mesh, new_students,
                                           student_specs, "pod")
        return new_students, global_protos, proto_mask, new_ast, new_ef

    core = {"gather": _core_gather, "packed": _core_packed,
            "ppermute": _core_ppermute}[mode]

    def round_fn(students, protos, counts, sizes, adapter_state, *rest):
        tree_in = as_tree(students) if is_plane(students) else students
        ef_state = rest[0] if rest else None
        s, g, m, na, ne = core(tree_in, protos, counts, sizes,
                               adapter_state, ef_state)
        if is_plane(students):
            s = jax.vmap(plane_from_tree)(s)
        if wire.error_feedback:
            # the adapter residual mirrors the factor payload (its own
            # structure, not the dense {"protos", "student"} one) —
            # node-sharded on the leading axis like every carried leaf
            ne = CodecState(_constrain_pod_lead(mesh, ne.residual),
                            seq=ne.seq)
            return s, g, m, na, ne
        return s, g, m, na
    return round_fn


# ---------------------------------------------------------------------------
# FedAvg baseline
# ---------------------------------------------------------------------------

def make_fedavg_round(mesh, model_specs,
                      adjacency: Optional[np.ndarray] = None,
                      exchange: str = "auto"):
    """Baseline exchange: full model, fp32, no quantization — the same
    packed-buffer / ppermute / gather machinery as ProFe so the dry-run
    byte diff between the two programs is apples-to-apples.

    ``models`` may be a :class:`~repro.optim.plane.Plane` with a stacked
    ``[N, R, 512]`` buffer (the flat-parameter engines): the packed and
    ppermute wires then ARE the plane buffer — the plane layout equals
    ``pack_tree_nodes``'s, so the whole-model payload splices off the
    buffer with zero repack, the fp32 mix runs on it directly, and the
    round returns a plane (trailing alignment rows are zero in every
    input, a fixed point of the mix).  The gather reference unwraps to
    leaf views at the boundary.

    ``adjacency=None``: global size-weighted mean, every node identical.
    With a 0/1 ``[N, N]`` adjacency: the neighborhood-weighted mix,
    node-distinct output sharded P("pod", ...).
    """
    adj = None if adjacency is None else np.asarray(adjacency)
    mode = _resolve_exchange(exchange, adj, mesh)

    if mode == "gather":
        def round_fn(models, sizes):
            if is_plane(models):
                return jax.vmap(plane_from_tree)(
                    round_fn(as_tree(models), sizes))
            gathered = _replicate_over_pod(mesh, models, model_specs)
            if adj is None:
                w = sizes / jnp.sum(sizes)
                means = weighted_node_mean(w, gathered)
                return jax.tree_util.tree_map(
                    lambda m, x: jnp.stack([m] * x.shape[0]).astype(x.dtype),
                    means, gathered)
            w_self, w_neigh = gossip_matrix_dyn(adj, sizes)
            mixed = mix_node_trees(w_self, w_neigh, models, gathered)
            return _constrain_over_pod(mesh, mixed, model_specs, "pod")
        return round_fn

    if mode == "ppermute":
        perms, srcs = _perm_lowering(adj)

        def round_fn(models, sizes):
            plane = models if is_plane(models) else None
            if plane is not None:
                # the plane buffer IS the pack_tree_nodes layout — the
                # whole-model wire splices off it with zero repack
                buf = plane.buf
            else:
                buf, seg_ids, meta = Q.pack_tree_nodes(models)
            buf = _constrain_buf(mesh, buf, "pod")
            w_self_v, w_neigh = gossip_matrix_dyn(adj, sizes)

            @partial(shard_map, mesh=mesh,
                     in_specs=(P("pod", None, None), P("pod"),
                               P("pod", None)),
                     out_specs=P("pod", None, None), check_vma=False)
            def exchange_fp32(own_buf, w_self, w_row):
                me = jax.lax.axis_index("pod")
                recv, ws = [], []
                for step, src in zip(perms, srcs):
                    recv.append(jax.lax.ppermute(own_buf, "pod", step))
                    _valid, w_p = _step_weight(src, me, w_row)
                    ws.append(w_p)
                stack = jnp.concatenate(recv, axis=0)          # [S, R, C]
                deltas = jnp.ones(stack.shape[:2], jnp.float32)
                return Q.mix_packed(own_buf, stack, deltas, w_self,
                                    jnp.stack(ws)[None, :])

            mixed = exchange_fp32(buf, w_self_v, w_neigh)
            if plane is not None:
                return Plane(_constrain_buf(mesh, mixed, "pod"),
                             plane.raw, plane.meta)
            out = jax.tree_util.tree_map(
                lambda new, old: new.astype(old.dtype),
                Q.unpack_tree_nodes(mixed, meta), models)
            return _constrain_over_pod(mesh, out, model_specs, "pod")
        return round_fn

    def round_fn(models, sizes):                               # packed
        plane = models if is_plane(models) else None
        if plane is not None:
            # zero-repack wire: the plane buffer is already the packed
            # node format, so the all-gather moves it verbatim
            n_nodes = plane.buf.shape[0]
            buf = plane.buf
        else:
            n_nodes = None
            for leaf in jax.tree_util.tree_leaves(models):
                n_nodes = leaf.shape[0]
                break
            buf, seg_ids, meta = Q.pack_tree_nodes(models)
        buf = _constrain_buf(mesh, buf, "pod")
        gathered = _constrain_buf(mesh, buf, None)   # ONE fp32 all-gather
        deltas = jnp.ones(gathered.shape[:2], jnp.float32)
        if adj is None:
            w = sizes / jnp.sum(sizes)
            w_self_v = jnp.zeros((n_nodes,), jnp.float32)
            w_rows = jnp.broadcast_to(w[None, :], (n_nodes, n_nodes))
        else:
            w_self_v, w_rows = gossip_matrix_dyn(adj, sizes)
        mixed = Q.mix_packed(buf, gathered, deltas, w_self_v, w_rows,
                             use_kernels=False)
        mixed = _constrain_buf(mesh, mixed, "pod")
        if plane is not None:
            return Plane(mixed, plane.raw, plane.meta)
        out = jax.tree_util.tree_map(
            lambda new, old: new.astype(old.dtype),
            Q.unpack_tree_nodes(mixed, meta), models)
        return _constrain_over_pod(mesh, out, model_specs, "pod")
    return round_fn
