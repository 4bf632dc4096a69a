"""Jitted public API over the quantize kernels.

Pads arbitrary tensors to (8,128)-aligned 2-D, runs the Pallas kernels
(interpret mode off-TPU), and restores the original shape.  Two tiers:

* per-tensor: :func:`quantize` / :func:`dequantize` /
  :func:`quantize_dequantize` — one fused single-launch kernel per
  tensor (absmax + quantize share the launch; see ``quantize.py``).
* packed tree: :func:`quantize_tree_packed` /
  :func:`dequantize_tree_packed` / :func:`quantize_dequantize_tree_packed`
  — every float leaf of a pytree is flattened into ONE padded ``[R, C]``
  buffer whose rows carry per-tensor segment ids, so a 100+-leaf student
  costs a handful of kernel launches (row-absmax, segment-max, row-scaled
  quantize) instead of hundreds.  ``node_axis=True`` treats each slice
  along a leaf's leading ``[N, ...]`` axis as its own segment — the
  stacked-node-state wire format of ``core/round_ops.py``.

The packed node codec is optionally *stateful* (error feedback,
``core/wire_state.py``): pass ``residual=`` to quantize the effective
payload ``x + decay·e`` and get the fresh quantization error back — a
single fused Pallas pass (residual-add → mixed-width quantize →
residual-update), zero extra wire bytes.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import fp32
from repro.kernels.quantize.quantize import (dequantize_pallas,
                                             dequantize_rows_pallas,
                                             fused_quantize_dequantize_pallas,
                                             fused_quantize_pallas,
                                             mix_packed_pallas,
                                             quantize_dequantize_rows_pallas,
                                             quantize_rows_ef_pallas,
                                             quantize_rows_mixed_pallas,
                                             quantize_rows_pallas,
                                             rowabs_pallas,
                                             rowabs_sum_pallas)
from repro.wirespec import WireSpec, canonical_group

_COLS = 512


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _to_2d(x) -> Tuple[jnp.ndarray, Tuple[int, ...]]:
    shape = x.shape
    flat = x.reshape(-1)
    n = flat.shape[0]
    cols = _COLS if n >= _COLS else 128
    pad = (-n) % cols
    flat = jnp.pad(flat, (0, pad))
    x2d = flat.reshape(-1, cols)
    rpad = (-x2d.shape[0]) % 8
    if rpad:
        x2d = jnp.pad(x2d, ((0, rpad), (0, 0)))
    return x2d, shape


def _from_2d(x2d, shape) -> jnp.ndarray:
    n = 1
    for s in shape:
        n *= s
    return x2d.reshape(-1)[:n].reshape(shape)


def _qmax_arr(bits: int) -> jnp.ndarray:
    """(1,1) runtime qmax, created OUTSIDE the jit boundary: as a jaxpr
    constant the Δ division ``amax / qmax`` gets strength-reduced to a
    reciprocal multiply by XLA:CPU fast-math (1 ulp off the oracle); as
    a traced argument it stays an exact IEEE division."""
    return jnp.full((1, 1), float((1 << (bits - 1)) - 1), jnp.float32)


@functools.partial(jax.jit, static_argnames=("bits",))
def _quantize_impl(x, qmax2d, bits: int):
    x2d, shape = _to_2d(x)
    codes2d, delta = fused_quantize_pallas(x2d, qmax2d, bits=bits,
                                           interpret=_interpret())
    return _from_2d(codes2d, shape), delta


def quantize(x, bits: int = 16):
    """-> (codes int32 [same shape], delta scalar fp32). Single fused
    launch: the absmax reduction and the quantize sweep share one
    kernel (phase axis on the grid), no host round-trip for delta."""
    return _quantize_impl(x, _qmax_arr(bits), bits)


@jax.jit
def dequantize(codes, delta):
    c2d, shape = _to_2d(codes.astype(jnp.int32))
    out = dequantize_pallas(c2d, delta, interpret=_interpret())
    return _from_2d(out, shape)


@functools.partial(jax.jit, static_argnames=("bits",))
def _quantize_dequantize_impl(x, qmax2d, bits: int):
    x2d, shape = _to_2d(x)
    out2d, _ = fused_quantize_dequantize_pallas(x2d, qmax2d, bits=bits,
                                                interpret=_interpret())
    return _from_2d(out2d, shape).astype(x.dtype)


def quantize_dequantize(x, bits: int = 16):
    """Receiver-side reconstruction in ONE launch — integer codes never
    round-trip through HBM."""
    return _quantize_dequantize_impl(x, _qmax_arr(bits), bits)


# ---------------------------------------------------------------------------
# packed tree path: one buffer, per-tensor segment scales
# ---------------------------------------------------------------------------

def _leaf_segments(leaf, node_axis: bool) -> int:
    return leaf.shape[0] if (node_axis and leaf.ndim >= 1) else 1


def _pack_leaf(leaf, node_axis: bool) -> jnp.ndarray:
    """-> [rows, _COLS] fp32; node_axis packs each leading-axis slice
    into its own whole rows (so rows never mix segments)."""
    if node_axis and leaf.ndim >= 1:
        n = leaf.shape[0]
        flat = leaf.reshape(n, -1).astype(jnp.float32)
        pad = (-flat.shape[1]) % _COLS
        flat = jnp.pad(flat, ((0, 0), (0, pad)))
        return flat.reshape(-1, _COLS)
    flat = leaf.reshape(-1).astype(jnp.float32)
    pad = (-flat.shape[0]) % _COLS
    return jnp.pad(flat, (0, pad)).reshape(-1, _COLS)


def _unpack_leaf(rows, shape, node_axis: bool) -> jnp.ndarray:
    if node_axis and len(shape) >= 1:
        n = shape[0]
        per = 1
        for s in shape[1:]:
            per *= s
        return rows.reshape(n, -1)[:, :per].reshape(shape)
    total = 1
    for s in shape:
        total *= s
    return rows.reshape(-1)[:total].reshape(shape)


def pack_tree(tree, *, node_axis: bool = False):
    """Flatten every float leaf into one ``[R, _COLS]`` fp32 buffer.

    Returns ``(buf, seg_ids [R] int32, meta)`` where meta is the static
    recipe (treedef, per-leaf shape/dtype/row-span/float flag, total
    segment count) :func:`unpack_tree` needs.  Non-float leaves are
    carried in meta untouched.
    """
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    parts: List[jnp.ndarray] = []
    seg_parts: List[np.ndarray] = []
    recipe = []
    seg = 0
    row = 0
    for leaf in leaves:
        is_float = hasattr(leaf, "dtype") and \
            jnp.issubdtype(leaf.dtype, jnp.floating)
        if not is_float:
            recipe.append(("raw", leaf))
            continue
        rows = _pack_leaf(leaf, node_axis)
        nseg = _leaf_segments(leaf, node_axis)
        rows_per_seg = rows.shape[0] // nseg
        seg_parts.append(np.repeat(np.arange(seg, seg + nseg), rows_per_seg))
        recipe.append(("packed", leaf.shape, leaf.dtype, row, rows.shape[0],
                       seg, nseg))
        parts.append(rows)
        seg += nseg
        row += rows.shape[0]
    if not parts:
        buf = jnp.zeros((8, _COLS), jnp.float32)
        seg_ids = np.zeros((8,), np.int32)
        return buf, jnp.asarray(seg_ids), (treedef, tuple(
            r if r[0] == "raw" else r for r in recipe), max(seg, 1))
    buf = jnp.concatenate(parts, axis=0)
    seg_ids = np.concatenate(seg_parts).astype(np.int32)
    rpad = (-buf.shape[0]) % 8
    if rpad:   # alignment rows: zeros tagged with the LAST segment id so
        # seg_ids stay sorted (segment_max relies on the sorted hint);
        # zero rows cannot raise that segment's absmax and the codes are
        # discarded at unpack
        buf = jnp.pad(buf, ((0, rpad), (0, 0)))
        seg_ids = np.concatenate(
            [seg_ids, np.full((rpad,), seg - 1, np.int32)])
    return buf, jnp.asarray(seg_ids), (treedef, tuple(recipe), seg)


def unpack_tree(buf, meta):
    """Inverse of :func:`pack_tree` (float leaves come back fp32)."""
    treedef, recipe, _ = meta
    leaves = []
    for item in recipe:
        if item[0] == "raw":
            leaves.append(item[1])
            continue
        _, shape, _dtype, row, nrows, _s, _n = item
        leaves.append(_unpack_leaf(buf[row:row + nrows], shape,
                                   node_axis=len(shape) >= 1 and _n > 1))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def _segment_deltas(buf, seg_ids, n_seg: int, bits: int):
    """Per-segment Δ from one row-absmax launch + a tiny segment-max."""
    qmax = (1 << (bits - 1)) - 1
    row_amax = rowabs_pallas(buf, interpret=_interpret())[:, 0]     # [R]
    seg_amax = jax.ops.segment_max(row_amax, seg_ids,
                                   num_segments=n_seg,
                                   indices_are_sorted=True)
    seg_amax = jnp.maximum(seg_amax, 0.0)    # empty segments -> -inf
    deltas = jnp.maximum(seg_amax / qmax, jnp.finfo(jnp.float32).tiny)
    return deltas, deltas[seg_ids][:, None]                         # [T],[R,1]


def quantize_tree_packed(tree, bits: int = 16, *, node_axis: bool = False
                         ) -> Dict[str, Any]:
    """Quantize a whole pytree in 2 kernel launches (+ a tiny segment
    reduction), independent of leaf count.  Returns the wire payload
    ``{"codes": [R,C] int32, "scales": [T] fp32, "meta", "bits"}``."""
    buf, seg_ids, meta = pack_tree(tree, node_axis=node_axis)
    deltas, row_delta = _segment_deltas(buf, seg_ids, meta[2], bits)
    codes = quantize_rows_pallas(buf, row_delta, bits=bits,
                                 interpret=_interpret())
    return {"codes": codes, "scales": deltas, "seg_ids": seg_ids,
            "meta": meta, "bits": bits}


def dequantize_tree_packed(payload):
    row_delta = payload["scales"][payload["seg_ids"]][:, None]
    buf = dequantize_rows_pallas(payload["codes"], row_delta,
                                 interpret=_interpret())
    return unpack_tree(buf, payload["meta"])


def quantize_dequantize_tree_packed(tree, bits: int = 16, *,
                                    node_axis: bool = False):
    """Receiver-side reconstruction of a whole pytree: 3 launches total
    (row-absmax, fused row-scaled round-trip), no integer HBM traffic."""
    buf, seg_ids, meta = pack_tree(tree, node_axis=node_axis)
    _, row_delta = _segment_deltas(buf, seg_ids, meta[2], bits)
    out = quantize_dequantize_rows_pallas(buf, row_delta, bits=bits,
                                          interpret=_interpret())
    return unpack_tree(out, meta)


# ---------------------------------------------------------------------------
# packed NODE wire format: one [N, R, _COLS] buffer per federation round
# ---------------------------------------------------------------------------
# The physical wire payload of the sparse-gossip exchange: every float
# leaf of a stacked [N, ...] pytree is flattened into node-major rows so
# slice [i] is node i's whole serialized payload — ONE contiguous wire
# buffer travels per round (one collective launch) instead of one tensor
# per leaf, with per-(leaf, node) segment scales [N, T] riding alongside.
# The wire format is parametric in a ``repro.wirespec.WireSpec``: codes
# are serialized by :func:`encode_wire` into a single ``[N, B]`` int8
# byte buffer — int16/int8 segments bitcast, int4 segments nibble-packed
# two codes per byte — so an int4 payload physically moves a quarter of
# the int16 bytes and mixed precision (int4 student + int16 prototypes)
# still rides one collective.  Bit-identical to quantizing each leaf's
# node slice alone (``round_ops.quantize_leaf_per_node``), asserted in
# tests; at uniform int16 the encoded bytes are byte-identical to the
# legacy int16 code buffer.

def _wire_int_dtype(bits: int):
    """Narrowest in-memory container for intN codes (int4 rides int8)."""
    return {4: jnp.int8, 8: jnp.int8, 16: jnp.int16, 32: jnp.int32}[bits]


def _leaf_group(path) -> str:
    """Top-level payload key of a leaf path — the WireSpec group."""
    if not path:
        return "student"
    key = getattr(path[0], "key", None)
    if key is None:
        key = getattr(path[0], "name", None)
    return canonical_group(str(key) if key is not None else "")


def pack_tree_nodes(tree, spec: Optional[WireSpec] = None):
    """Flatten every float leaf ``[N, ...]`` into one ``[N, R, _COLS]``
    fp32 buffer (node axis leading, so it shards/permutes over the pod
    axis untouched).

    Returns ``(buf, seg_ids [R] int32, meta)``; rows of one leaf never
    mix with another's, ``seg_ids[r]`` is the leaf segment of row ``r``
    (identical for every node — the layout is node-uniform).  Alignment
    rows pad R to a multiple of 8 and are tagged with the last segment
    (zeros cannot raise its absmax; their codes are discarded at unpack).

    ``meta`` is ``(treedef, recipe, n_seg, n_nodes, seg_bits)`` where
    ``seg_bits`` is the per-segment wire width ``[n_seg]`` resolved from
    ``spec`` by each leaf's top-level payload key (None when no spec —
    the caller picks a uniform width at quantize time).
    """
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    n_nodes = None
    parts: List[jnp.ndarray] = []
    seg_parts: List[np.ndarray] = []
    seg_bits: List[int] = []
    recipe = []
    seg = 0
    row = 0
    for path, leaf in flat:
        is_float = hasattr(leaf, "dtype") and \
            jnp.issubdtype(leaf.dtype, jnp.floating)
        if not is_float:
            recipe.append(("raw", leaf))
            continue
        if leaf.ndim < 1:
            raise ValueError("packed node format needs [N, ...] leaves")
        n = leaf.shape[0]
        if n_nodes is None:
            n_nodes = n
        elif n != n_nodes:
            raise ValueError(f"inconsistent node axis: {n} vs {n_nodes}")
        per = 1
        for s in leaf.shape[1:]:
            per *= s
        flat_leaf = leaf.reshape(n, per).astype(jnp.float32)
        pad = (-per) % _COLS
        if pad:
            flat_leaf = jnp.pad(flat_leaf, ((0, 0), (0, pad)))
        rows = flat_leaf.reshape(n, -1, _COLS)            # [N, r_leaf, C]
        r_leaf = rows.shape[1]
        seg_parts.append(np.full((r_leaf,), seg, np.int32))
        if spec is not None:
            seg_bits.append(spec.bits_for(_leaf_group(path)))
        recipe.append(("packed", leaf.shape, leaf.dtype, row, r_leaf, seg))
        parts.append(rows)
        seg += 1
        row += r_leaf
    if not parts:
        raise ValueError("packed node format needs at least one float leaf")
    buf = jnp.concatenate(parts, axis=1)                  # [N, R, C]
    seg_ids = np.concatenate(seg_parts)
    rpad = (-buf.shape[1]) % 8
    if rpad:
        buf = jnp.pad(buf, ((0, 0), (0, rpad), (0, 0)))
        seg_ids = np.concatenate([seg_ids,
                                  np.full((rpad,), seg - 1, np.int32)])
    bits_arr = np.asarray(seg_bits, np.int32) if spec is not None else None
    return buf, seg_ids, (treedef, tuple(recipe), seg, n_nodes, bits_arr)


def unpack_tree_nodes(buf, meta):
    """Inverse of :func:`pack_tree_nodes` (float leaves come back fp32)."""
    treedef, recipe = meta[0], meta[1]
    leaves = []
    for item in recipe:
        if item[0] == "raw":
            leaves.append(item[1])
            continue
        _, shape, _dtype, row, nrows, _s = item
        n = shape[0]
        per = 1
        for s in shape[1:]:
            per *= s
        rows = buf[:, row:row + nrows, :]
        leaves.append(rows.reshape(n, -1)[:, :per].reshape(shape))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def _seg_qmax(n_seg: int, bits: int, seg_bits: Optional[np.ndarray]
              ) -> np.ndarray:
    """Static per-segment qmax [T]: mixed widths from ``seg_bits``,
    else the uniform ``bits``."""
    if seg_bits is None:
        return np.full((n_seg,), (1 << (bits - 1)) - 1, np.float32)
    return ((1 << (np.asarray(seg_bits, np.int64) - 1)) - 1
            ).astype(np.float32)


def _node_row_deltas(buf, seg_ids, n_seg: int, bits: int,
                     use_kernels: bool,
                     seg_bits: Optional[np.ndarray] = None,
                     residual=None, ef_decay: float = 1.0):
    """Per-(node, leaf) Δ: one row-absmax sweep + a tiny per-node
    segment-max.  Returns (scales [N, T] fp32, row_delta [N, R] fp32).
    ``seg_bits`` makes Δ per-segment-width (mixed-precision specs);
    ``residual`` scales Δ from the *effective* payload
    ``buf + ef_decay·residual`` (the error-feedback codec) — on the
    kernel path the residual-add is fused into the absmax sweep, so the
    effective fp32 buffer never lands in HBM."""
    qmax = _seg_qmax(n_seg, bits, seg_bits)                       # [T]
    n, r, _c = buf.shape
    if use_kernels:
        if residual is None:
            row_amax = rowabs_pallas(buf.reshape(n * r, _c),
                                     interpret=_interpret()).reshape(n, r)
        else:
            row_amax = rowabs_sum_pallas(
                buf.reshape(n * r, _c), residual.reshape(n * r, _c),
                decay=ef_decay, interpret=_interpret()).reshape(n, r)
    else:
        eff = buf if residual is None else \
            buf + jnp.float32(ef_decay) * residual
        row_amax = jnp.max(jnp.abs(eff), axis=2)                  # [N, R]
    ids = jnp.asarray(seg_ids)
    seg_amax = jax.vmap(lambda ra: jax.ops.segment_max(
        ra, ids, num_segments=n_seg, indices_are_sorted=True))(row_amax)
    seg_amax = jnp.maximum(seg_amax, 0.0)
    deltas = jnp.maximum(seg_amax / qmax[None, :],
                         jnp.finfo(jnp.float32).tiny)
    return deltas, deltas[:, seg_ids]                             # [N,T],[N,R]


def quantize_packed_buffer(buf, seg_ids, n_seg: int, bits: int = 16, *,
                           seg_bits: Optional[np.ndarray] = None,
                           use_kernels: Optional[bool] = None,
                           rng=None, residual=None, ef_decay: float = 1.0):
    """Quantize an already-packed ``[N, R, C]`` buffer.  Returns
    ``(codes [N, R, C] wire-intN, scales [N, T] fp32)``.

    ``seg_bits`` (``[n_seg]`` static ints) quantizes each segment at its
    own width in the same sweep — the codes land in the narrowest
    container that holds the widest segment; :func:`encode_wire`
    serializes them to their true per-segment wire bytes.  ``rng``
    enables stochastic rounding (``floor(x/Δ + U[0,1))``, unbiased;
    jnp path only).

    ``residual`` (``[N, R, C]`` fp32) switches to the *stateful* codec:
    the effective payload ``buf + ef_decay·residual`` is quantized
    instead, and the fresh quantization error comes back as a third
    return value — ``(codes, scales, new_residual)``.  On the kernel
    path this is ONE fused launch (residual-add → mixed-width quantize
    → residual-update, :func:`quantize_rows_ef_pallas`); the effective
    fp32 buffer is never materialized.  Residuals never reach the wire:
    the codes/scales are byte-identical in format to the stateless
    path.
    """
    if use_kernels is None:
        use_kernels = jax.default_backend() == "tpu"
    n, r, c = buf.shape
    deltas, row_delta = _node_row_deltas(buf, seg_ids, n_seg, bits,
                                         use_kernels, seg_bits,
                                         residual=residual,
                                         ef_decay=ef_decay)
    row_qmax = _seg_qmax(n_seg, bits, seg_bits)[seg_ids]          # [R]
    max_bits = int(np.max(seg_bits)) if seg_bits is not None else bits
    wire_dtype = _wire_int_dtype(max_bits)
    if use_kernels and rng is None:
        if residual is not None:
            qm_col = jnp.asarray(np.tile(row_qmax, n)[:, None])
            codes2d, newres2d = quantize_rows_ef_pallas(
                buf.reshape(n * r, c), residual.reshape(n * r, c),
                row_delta.reshape(n * r, 1), qm_col, decay=ef_decay,
                interpret=_interpret())
            return (codes2d.reshape(n, r, c).astype(wire_dtype), deltas,
                    newres2d.reshape(n, r, c))
        if seg_bits is None or len(set(seg_bits.tolist())) == 1:
            codes = quantize_rows_pallas(
                buf.reshape(n * r, c), row_delta.reshape(n * r, 1),
                bits=int(seg_bits[0]) if seg_bits is not None else bits,
                interpret=_interpret()).reshape(n, r, c)
        else:
            qm_col = jnp.asarray(np.tile(row_qmax, n)[:, None])
            codes = quantize_rows_mixed_pallas(
                buf.reshape(n * r, c), row_delta.reshape(n * r, 1),
                qm_col, interpret=_interpret()).reshape(n, r, c)
    else:
        eff = buf if residual is None else \
            buf + jnp.float32(ef_decay) * residual
        offset = 0.5 if rng is None else \
            jax.random.uniform(rng, buf.shape, jnp.float32)
        codes = jnp.floor(eff / row_delta[:, :, None] + offset)
        qm = jnp.asarray(row_qmax)[None, :, None]
        codes = jnp.clip(codes, -qm - 1, qm)
        if residual is not None:
            new_res = eff - codes * row_delta[:, :, None]
            return codes.astype(wire_dtype), deltas, new_res
    return codes.astype(wire_dtype), deltas


# -- the serialized wire byte buffer ----------------------------------------

def _row_bits(seg_ids, bits, seg_bits) -> np.ndarray:
    sb = np.asarray(seg_bits, np.int64) if seg_bits is not None else None
    return (sb[seg_ids] if sb is not None
            else np.full((len(seg_ids),), bits, np.int64))


def nibble_pack(codes):
    """int4 codes [..., C] (C even, values in [-8, 7]) -> int8
    [..., C // 2]: even columns in the low nibble, odd in the high."""
    if codes.shape[-1] % 2:
        raise ValueError(f"nibble packing needs an even trailing dim, "
                         f"got {codes.shape}")
    c = codes.astype(jnp.int8)
    lo = jnp.bitwise_and(c[..., 0::2], jnp.int8(0xF))
    hi = jnp.left_shift(c[..., 1::2], 4)
    return jnp.bitwise_or(lo, hi)


def nibble_unpack(packed):
    """Inverse of :func:`nibble_pack`: int8 [..., B] -> sign-extended
    int8 codes [..., 2 * B]."""
    lo = jnp.right_shift(jnp.left_shift(packed, 4), 4)   # arithmetic: sign
    hi = jnp.right_shift(packed, 4)
    return jnp.stack([lo, hi], axis=-1).reshape(
        packed.shape[:-1] + (2 * packed.shape[-1],))


def _bits_row_groups(seg_ids, bits, seg_bits):
    """Static row grouping by wire width: [(width, row-index array)],
    ascending width, covering every row exactly once."""
    rb = _row_bits(seg_ids, bits, seg_bits)
    return [(int(b), np.nonzero(rb == b)[0])
            for b in sorted(set(rb.tolist()))]


def _encode_rows(codes_b, b: int):
    """[N, Rb, C] intN codes at width ``b`` -> [N, Rb * C * b / 8] int8."""
    n = codes_b.shape[0]
    if b == 4:
        return nibble_pack(codes_b.astype(jnp.int8)).reshape(n, -1)
    if b == 8:
        return codes_b.astype(jnp.int8).reshape(n, -1)
    wide = codes_b.astype(_wire_int_dtype(b))
    return jax.lax.bitcast_convert_type(wide, jnp.int8).reshape(n, -1)


def _decode_rows(wire_b, b: int, n_rows: int):
    """Inverse of :func:`_encode_rows` -> [N, n_rows, C] int32."""
    n = wire_b.shape[0]
    if b == 4:
        return nibble_unpack(wire_b.reshape(n, n_rows, _COLS // 2)
                             ).astype(jnp.int32)
    if b == 8:
        return wire_b.reshape(n, n_rows, _COLS).astype(jnp.int32)
    width = b // 8
    chunks = wire_b.reshape(n, n_rows, _COLS, width)
    return jax.lax.bitcast_convert_type(
        chunks, _wire_int_dtype(b)).astype(jnp.int32)


def encode_wire(codes, seg_ids, bits: int = 16, *,
                seg_bits: Optional[np.ndarray] = None):
    """Serialize packed codes ``[N, R, C]`` into the physical wire byte
    buffer ``[N, B]`` int8 — ONE contiguous array whose size is exactly
    the spec bytes (``B = Σ_rows C·bits_row/8``): int16/int32 rows are
    bitcast, int8 rows pass through, int4 rows nibble-pack two codes per
    byte.  At uniform int16 the bytes are identical to the legacy int16
    code buffer (pure bitcast).  The layout is static (derived from
    ``seg_ids``/``seg_bits``), so :func:`decode_wire` inverts it without
    any side-channel."""
    groups = _bits_row_groups(seg_ids, bits, seg_bits)
    if len(groups) == 1:
        return _encode_rows(codes, groups[0][0])
    return jnp.concatenate(
        [_encode_rows(jnp.take(codes, rows, axis=1), b)
         for b, rows in groups], axis=1)


def decode_wire(wire, seg_ids, bits: int = 16, *,
                seg_bits: Optional[np.ndarray] = None):
    """Inverse of :func:`encode_wire`: ``[N, B]`` int8 -> codes
    ``[N, R, C]`` int32 in original row order."""
    groups = _bits_row_groups(seg_ids, bits, seg_bits)
    if len(groups) == 1:
        return _decode_rows(wire, groups[0][0], len(seg_ids))
    parts, col = [], 0
    for b, rows in groups:
        nbytes = len(rows) * _COLS * b // 8
        parts.append(_decode_rows(wire[:, col:col + nbytes], b, len(rows)))
        col += nbytes
    perm = np.concatenate([rows for _, rows in groups])
    return jnp.take(jnp.concatenate(parts, axis=1), np.argsort(perm),
                    axis=1)


def wire_buffer_bytes(seg_ids, bits: int = 16, *,
                      seg_bits: Optional[np.ndarray] = None) -> int:
    """Static byte size B of one node's encoded wire buffer."""
    return int(np.sum(_row_bits(seg_ids, bits, seg_bits)) * _COLS // 8)


def quantize_tree_packed_nodes(tree, bits: int = 16, *,
                               spec: Optional[WireSpec] = None,
                               use_kernels: Optional[bool] = None,
                               rng=None, residual=None) -> Dict[str, Any]:
    """The wire payload of one federation round: quantize a stacked
    ``[N, ...]`` pytree into ``{"codes": [N, R, C] intN, "scales":
    [N, T] fp32, "seg_ids", "seg_bits", "meta", "bits"}`` — per-(leaf,
    node) scale segments, codes narrowed to the wire container dtype
    (int16 for uniform 16-bit).  With ``spec`` each leaf group is
    quantized at its own width (``seg_bits`` records it per segment);
    :func:`encode_wire` turns the codes into the physical byte buffer.
    A spec with ``stochastic_rounding`` set requires an explicit ``rng``
    (the noise source is the caller's to seed — silently falling back
    to deterministic rounding would fake the unbiasedness).

    ``residual`` (required when ``spec.error_feedback`` is set — the
    stateful codec must not silently drop its state) is a pytree of
    fp32 residuals for exactly the float leaves of ``tree`` (see
    ``core/wire_state.py``); it is packed into the identical buffer
    layout, added to the payload before quantization, and the payload
    gains an ``"ef_residual"`` entry holding the *updated* residual
    tree.  That entry never rides the wire — codes, scales, and the
    encoded byte buffer are format-identical to the stateless path."""
    if spec is not None and spec.stochastic_rounding and rng is None:
        raise ValueError("WireSpec.stochastic_rounding is set but no rng "
                         "was passed — stochastic rounding needs an "
                         "explicit PRNG key")
    if spec is not None and spec.error_feedback and residual is None:
        raise ValueError("WireSpec.error_feedback is set but no residual "
                         "was passed — the stateful codec needs the "
                         "carried per-node residual tree (CodecState)")
    buf, seg_ids, meta = pack_tree_nodes(tree, spec)
    seg_bits = meta[4]
    if residual is not None:
        res_buf, _res_ids, res_meta = pack_tree_nodes(residual)
        if res_buf.shape != buf.shape:
            raise ValueError(
                f"residual buffer {res_buf.shape} does not match the "
                f"payload buffer {buf.shape} — the residual tree must "
                f"mirror the payload's float leaves")
        codes, deltas, new_res = quantize_packed_buffer(
            buf, seg_ids, meta[2], bits, seg_bits=seg_bits,
            use_kernels=use_kernels, rng=rng, residual=res_buf,
            ef_decay=spec.ef_decay if spec is not None else 1.0)
        return {"codes": codes, "scales": deltas, "seg_ids": seg_ids,
                "seg_bits": seg_bits, "meta": meta, "bits": bits,
                "ef_residual": unpack_tree_nodes(new_res, res_meta)}
    codes, deltas = quantize_packed_buffer(buf, seg_ids, meta[2], bits,
                                           seg_bits=seg_bits,
                                           use_kernels=use_kernels, rng=rng)
    return {"codes": codes, "scales": deltas, "seg_ids": seg_ids,
            "seg_bits": seg_bits, "meta": meta, "bits": bits}


def dequantize_tree_packed_nodes(payload):
    """Receiver-side reconstruction from the packed node payload."""
    row_delta = payload["scales"][:, payload["seg_ids"]]
    deq = payload["codes"].astype(jnp.float32) * row_delta[:, :, None]
    return unpack_tree_nodes(deq, payload["meta"])


def _qdq_tree_leaf_local(tree, bits: int, *,
                         spec: Optional[WireSpec] = None, residual=None):
    """Leaf-local round-trip of the packed node codec: each float leaf
    is quantized against its own per-(leaf, node) scale segment exactly
    as the buffer path does — same absmax, qmax, tiny-guard, rounding,
    and clip — without materializing the ``[N, R, C]`` buffer.  The
    byte serialization AND the buffer layout are both lossless
    rearrangements, so the receiver view needs neither; skipping the
    pack + unpack copies roughly halves the round-trip on hosts without
    the Pallas kernels.

    The int code container is elided too: the clipped codes are
    integers in ``[-qm-1, qm]``, all exactly representable in fp32, so
    ``delta * codes`` straight off the fp32 rounding is bit-identical
    to ``dequantize_leaf(quantize_leaf_per_node(...))`` while skipping
    the fp32 -> intN -> fp32 element-wise converts on every leaf."""
    decay = jnp.float32(spec.ef_decay if spec is not None else 1.0)
    res_leaves = jax.tree_util.tree_leaves(residual) \
        if residual is not None else None
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    floats = sum(1 for _p, x in flat
                 if hasattr(x, "dtype")
                 and jnp.issubdtype(x.dtype, jnp.floating))
    if res_leaves is not None and len(res_leaves) != floats:
        raise ValueError(
            f"residual tree holds {len(res_leaves)} leaves for a payload "
            f"with {floats} float leaves — the residual tree must mirror "
            f"the payload's float leaves")
    res_iter = iter(res_leaves) if res_leaves is not None else None
    out, new_res = [], []
    for path, leaf in flat:
        if not (hasattr(leaf, "dtype")
                and jnp.issubdtype(leaf.dtype, jnp.floating)):
            out.append(leaf)
            continue
        b = spec.bits_for(_leaf_group(path)) if spec is not None else bits
        eff = leaf.astype(jnp.float32)
        if res_iter is not None:
            eff = eff + decay * next(res_iter)
        # fake-quant: same amax/delta/round/clip as quantize_leaf_per_node
        # + dequantize_leaf, minus the int container round-trip
        qm = (1 << (b - 1)) - 1
        reduce_axes = tuple(range(1, eff.ndim))
        amax = jnp.max(jnp.abs(eff), axis=reduce_axes)
        delta = jnp.maximum(amax / qm, jnp.finfo(jnp.float32).tiny)
        bshape = (eff.shape[0],) + (1,) * (eff.ndim - 1)
        d = delta.reshape(bshape)
        codes = jnp.clip(jnp.floor(eff / d + 0.5), -qm - 1, qm)
        deq = codes * d
        out.append(deq)
        if res_iter is not None:
            new_res.append(eff - deq)
    recv = jax.tree_util.tree_unflatten(treedef, out)
    if residual is not None:
        res_def = jax.tree_util.tree_structure(residual)
        return recv, jax.tree_util.tree_unflatten(res_def, new_res)
    return recv


def quantize_dequantize_tree_packed_nodes(tree, bits: int = 16, *,
                                          spec: Optional[WireSpec] = None,
                                          use_kernels: Optional[bool] = None,
                                          rng=None, residual=None,
                                          elide_layout: Optional[bool] = None):
    """Round-trip through the packed node wire format — what every
    receiver reconstructs.  Bit-identical to the per-leaf
    ``quantize_leaf_per_node``/``dequantize_leaf`` path (the
    encode/decode byte serialization is lossless, so it is elided
    here).  With ``residual`` (the stateful error-feedback codec)
    returns ``(reconstruction, new_residual_tree)`` instead.

    ``elide_layout`` (default: on whenever the Pallas kernels are off
    and rounding is deterministic) skips the buffer *layout* too: the
    pack → quantize → unpack pipeline spends most of its time copying
    the payload into and out of the ``[N, R, C]`` buffer, and the
    layout is as lossless as the serialization, so the receiver view is
    computed leaf-locally instead (bit-identity asserted in tests).
    The kernel path keeps the buffer — that IS the fused launch's
    operand — as does stochastic rounding (the packed sweep owns the
    noise shape)."""
    if use_kernels is None:
        use_kernels = jax.default_backend() == "tpu"
    if elide_layout is None:
        elide_layout = not use_kernels and rng is None
    if elide_layout:
        # mirror the packed path's contract errors before diverging
        if spec is not None and spec.stochastic_rounding and rng is None:
            raise ValueError("WireSpec.stochastic_rounding is set but no "
                             "rng was passed — stochastic rounding needs "
                             "an explicit PRNG key")
        if spec is not None and spec.error_feedback and residual is None:
            raise ValueError("WireSpec.error_feedback is set but no "
                             "residual was passed — the stateful codec "
                             "needs the carried per-node residual tree "
                             "(CodecState)")
        if rng is None:
            return _qdq_tree_leaf_local(tree, bits, spec=spec,
                                        residual=residual)
    payload = quantize_tree_packed_nodes(tree, bits, spec=spec,
                                         use_kernels=use_kernels, rng=rng,
                                         residual=residual)
    recv = dequantize_tree_packed_nodes(payload)
    if residual is not None:
        return recv, payload["ef_residual"]
    return recv


# ---------------------------------------------------------------------------
# flat-parameter-plane wire handoff: the pack step becomes a row slice
# ---------------------------------------------------------------------------
# A plane-backed student (``repro.optim.plane.Plane``) already stores its
# float leaves in EXACTLY this codec's row layout (per leaf: prod(shape)
# padded to _COLS columns, flatten order, trailing 8-alignment rows), so
# the round-boundary wire payload {"protos", "student"} never re-gathers
# the student: its packed rows are spliced straight off the plane buffer
# and only the (tiny) prototype rows are packed fresh.  Bit-identical to
# ``pack_tree_nodes`` on the leaf-view payload (asserted in tests).

def pack_plane_payload(protos, plane, spec: Optional[WireSpec] = None):
    """Pack the wire payload ``{"protos": [N, C, P], "student": Plane}``
    into the packed node wire format without re-packing the student.

    Returns ``(buf, seg_ids, meta, r_protos, span)`` — the first three
    exactly as :func:`pack_tree_nodes` would produce for the equivalent
    leaf-view payload (same treedef, recipe, segment ids and widths),
    plus the prototype row count and the student's leaf-row span so the
    receiver can splice the dequantized rows back into a plane."""
    n, c_cls, p_dim = protos.shape
    if plane.buf.ndim != 3 or plane.buf.shape[0] != n:
        raise ValueError(f"plane buffer {getattr(plane.buf, 'shape', None)} "
                         f"is not stacked over the payload's {n} nodes")
    per = c_cls * p_dim
    flat_p = protos.reshape(n, per).astype(jnp.float32)
    pad = (-per) % _COLS
    if pad:
        flat_p = jnp.pad(flat_p, ((0, 0), (0, pad)))
    rows_p = flat_p.reshape(n, -1, _COLS)                 # [N, r_p, C]
    r_p = rows_p.shape[1]

    recipe: List[Tuple] = [("packed", protos.shape, protos.dtype, 0, r_p, 0)]
    seg_parts: List[np.ndarray] = [np.zeros((r_p,), np.int32)]
    seg_bits: List[int] = [spec.bits_for("protos")] if spec is not None \
        else []
    seg = 1
    span = 0
    for item in plane.meta.recipe:
        if item[0] == "raw":
            recipe.append(("raw", plane.raw[item[1]]))
            continue
        _, shape, dtype, prow, r_leaf = item
        recipe.append(("packed", (n,) + tuple(shape), dtype,
                       r_p + prow, r_leaf, seg))
        seg_parts.append(np.full((r_leaf,), seg, np.int32))
        if spec is not None:
            seg_bits.append(spec.bits_for("student"))
        seg += 1
        span = max(span, prow + r_leaf)
    # the splice: the plane's leaf rows ARE the student's packed rows
    buf = jnp.concatenate([rows_p, plane.buf[:, :span]], axis=1)
    seg_ids = np.concatenate(seg_parts)
    rpad = (-buf.shape[1]) % 8
    if rpad:
        buf = jnp.pad(buf, ((0, 0), (0, rpad), (0, 0)))
        seg_ids = np.concatenate([seg_ids,
                                  np.full((rpad,), seg - 1, np.int32)])
    n_leaves = len(plane.meta.recipe)
    inner = jax.tree_util.tree_unflatten(plane.meta.treedef,
                                         list(range(n_leaves)))
    treedef = jax.tree_util.tree_structure({"protos": 0, "student": inner})
    bits_arr = np.asarray(seg_bits, np.int32) if spec is not None else None
    meta = (treedef, tuple(recipe), seg, n, bits_arr)
    return buf, seg_ids, meta, r_p, span


def quantize_dequantize_plane_payload(payload, bits: int = 16, *,
                                      spec: Optional[WireSpec] = None,
                                      use_kernels: Optional[bool] = None,
                                      rng=None, residual=None):
    """Receiver-side reconstruction of a plane-backed wire payload
    ``{"protos": [N, C, P], "student": Plane}`` — the plane twin of
    :func:`quantize_dequantize_tree_packed_nodes`, bit-identical to it
    on the equivalent leaf-view payload (asserted in tests).

    The student side never leaves the packed layout: its rows are
    spliced off the plane buffer, quantized in the shared buffer sweep,
    and the dequantized rows are spliced back into a fresh plane (the
    receiver view mixes buffer-against-buffer downstream — zero repack
    on either end; the plane's zero padding lanes quantize to zero, so
    the layout invariant survives the round-trip).  With ``residual``
    (``{"protos", "student": Plane}`` mirroring the payload — the
    error-feedback codec) returns ``(reconstruction, new_residual)``;
    wire format unchanged."""
    from repro.optim.plane import Plane
    protos, plane = payload["protos"], payload["student"]
    if use_kernels is None:
        use_kernels = jax.default_backend() == "tpu"
    if spec is not None and spec.stochastic_rounding and rng is None:
        raise ValueError("WireSpec.stochastic_rounding is set but no rng "
                         "was passed — stochastic rounding needs an "
                         "explicit PRNG key")
    if spec is not None and spec.error_feedback and residual is None:
        raise ValueError("WireSpec.error_feedback is set but no residual "
                         "was passed — the stateful codec needs the "
                         "carried per-node residual (CodecState)")
    buf, seg_ids, meta, r_p, span = pack_plane_payload(protos, plane, spec)

    def split(b):
        pr = b[:, :r_p].reshape(protos.shape[0], -1)
        pr = pr[:, :protos.shape[1] * protos.shape[2]].reshape(protos.shape)
        sbuf = b[:, r_p:r_p + span]
        if plane.meta.rows > span:
            sbuf = jnp.pad(sbuf,
                           ((0, 0), (0, plane.meta.rows - span), (0, 0)))
        return pr, sbuf

    if residual is not None:
        res_plane = residual["student"]
        res_buf = pack_plane_payload(residual["protos"], res_plane, None)[0]
        if res_buf.shape != buf.shape:
            raise ValueError(
                f"residual buffer {res_buf.shape} does not match the "
                f"payload buffer {buf.shape} — the residual must mirror "
                f"the payload layout")
        codes, deltas, new_res_buf = quantize_packed_buffer(
            buf, seg_ids, meta[2], bits, seg_bits=meta[4],
            use_kernels=use_kernels, rng=rng, residual=res_buf,
            ef_decay=spec.ef_decay if spec is not None else 1.0)
    else:
        codes, deltas = quantize_packed_buffer(
            buf, seg_ids, meta[2], bits, seg_bits=meta[4],
            use_kernels=use_kernels, rng=rng)
    row_delta = deltas[:, seg_ids]
    deq = codes.astype(jnp.float32) * row_delta[:, :, None]
    pr, sbuf = split(deq)
    recv = {"protos": pr, "student": Plane(sbuf, plane.raw, plane.meta)}
    if residual is not None:
        rp, rbuf = split(new_res_buf)
        return recv, {"protos": rp,
                      "student": Plane(rbuf, res_plane.raw, res_plane.meta)}
    return recv


def quantize_dequantize_plane_rows(plane, bits: int = 16):
    """Per-leaf fake-quant round-trip applied straight to a plane
    buffer: one Δ per leaf *segment* (max|x| over the segment's rows —
    padding lanes are zero and cannot raise it), then one elementwise
    round-trip sweep over the whole ``[R, C]`` buffer with the per-row
    Δ broadcast.  Bit-identical to
    ``core.quantization.quantize_dequantize_tree`` on the leaf views
    (same amax, qmax, tiny-guard, rounding and clip per element; the
    clipped codes are integers exactly representable in fp32, so the
    int container round-trip is elided as in ``_qdq_tree_leaf_local``).
    Deliberately eager, like the per-leaf reference it mirrors in the
    loop engine — a jitted whole-program version would let XLA:CPU
    strength-reduce the Δ division and drift an ulp.  Trailing
    8-alignment rows ride Δ=1 (zeros round-trip to zeros), so the
    plane's padding invariant survives."""
    from repro.optim.plane import Plane
    buf = plane.buf
    qm = (1 << (bits - 1)) - 1
    tiny = jnp.finfo(jnp.float32).tiny
    row_parts = []
    covered = 0
    for item in plane.meta.recipe:
        if item[0] != "leaf":
            continue
        _, _shape, _dtype, row, r_leaf = item
        amax = jnp.max(jnp.abs(buf[..., row:row + r_leaf, :]))
        d = jnp.maximum(amax / qm, tiny)
        row_parts.append(jnp.broadcast_to(d, (r_leaf,)))
        covered = row + r_leaf
    if plane.meta.rows > covered:
        row_parts.append(jnp.ones((plane.meta.rows - covered,),
                                  jnp.float32))
    rd = jnp.concatenate(row_parts)[:, None]
    codes = jnp.clip(jnp.floor(buf / rd + 0.5), -qm - 1, qm)
    return Plane(codes * rd, plane.raw, plane.meta)


def packed_wire_rows(tree, *, node_axis: bool = True) -> Tuple[int, int]:
    """Static layout of the packed node buffer: ``(R_padded, T)`` — rows
    per node (8-aligned) and scale-segment count.  Works on arrays or
    ``ShapeDtypeStruct``s (accounting never touches device data).
    ``node_axis=False`` treats leaves as per-copy skeletons without the
    leading ``[N]`` dim (the comm accountant's payload templates)."""
    rows = 0
    nseg = 0
    skip = 1 if node_axis else 0
    for leaf in jax.tree_util.tree_leaves(tree):
        if not (hasattr(leaf, "dtype")
                and jnp.issubdtype(leaf.dtype, jnp.floating)):
            continue
        per = 1
        for s in leaf.shape[skip:]:
            per *= s
        rows += -(-per // _COLS)
        nseg += 1
    return rows + ((-rows) % 8), nseg


def packed_wire_bytes_per_node(tree, bits: Optional[int] = 16, *,
                               node_axis: bool = True,
                               leaf_bits: Optional[Sequence[int]] = None,
                               inner: int = 1) -> int:
    """Physical bytes one node's packed payload occupies on the wire:
    the encoded byte buffer (fp32 rows when ``bits`` is None) incl.
    512-lane padding, plus one fp32 scale per leaf segment when
    quantized.  ``leaf_bits`` gives each float leaf its own width
    (parallel to the float leaves of ``tree``, in flatten order) —
    alignment rows carry the LAST leaf's width, mirroring
    :func:`pack_tree_nodes`' tagging.  ``inner`` is the inner-device
    count of the row-sharded multi-axis exchange: every wire WIDTH
    group's row count is padded up to a multiple of ``inner`` (the
    all-zero pad rows ``sharding.row_shard_order`` appends are physical
    bytes on the permute).  The 8-aligned rows of a uniform-width
    payload split without padding for ``inner`` in {2, 4, 8}.  This is
    the number the dry-run's HLO collective-bytes breakdown measures
    per exchanged copy."""
    if bits is None or leaf_bits is None:
        rows, nseg = packed_wire_rows(tree, node_axis=node_axis)
        rows += (-rows) % inner              # one width group
        if bits is None:                                  # fp32 (fedavg)
            return rows * _COLS * 4
        return rows * _COLS * bits // 8 + nseg * 4        # sub-byte exact
    skip = 1 if node_axis else 0
    rows = 0
    nseg = 0
    last_b = None
    width_rows: Dict[int, int] = {}
    floats = [leaf for leaf in jax.tree_util.tree_leaves(tree)
              if hasattr(leaf, "dtype")
              and jnp.issubdtype(leaf.dtype, jnp.floating)]
    if len(floats) != len(leaf_bits):
        raise ValueError(f"leaf_bits has {len(leaf_bits)} entries for "
                         f"{len(floats)} float leaves")
    for leaf, b in zip(floats, leaf_bits):
        per = 1
        for s in leaf.shape[skip:]:
            per *= s
        r = -(-per // _COLS)
        rows += r
        width_rows[int(b)] = width_rows.get(int(b), 0) + r
        nseg += 1
        last_b = b
    width_rows[int(last_b)] += (-rows) % 8            # alignment rows
    total_bits = 0
    for b, r in width_rows.items():
        r += (-r) % inner                 # row-sharded permute pad rows
        total_bits += r * _COLS * b
    return total_bits // 8 + nseg * 4


def mix_packed(own, codes, row_delta, w_self, w_rows, *,
               use_kernels: Optional[bool] = None) -> jnp.ndarray:
    """Receiver-side gossip mix applied directly on packed codes:
    ``out[m] = w_self[m]·own[m] + Σ_j w_rows[m, j]·codes[j]·Δ[j]``.

    One fused Pallas launch on TPU (interpret elsewhere when forced);
    the jnp flavor is the GSPMD-partitionable fallback the multi-axis
    mesh path and the CPU simulator use."""
    if use_kernels is None:
        use_kernels = jax.default_backend() == "tpu"
    if use_kernels:
        return mix_packed_pallas(own, codes, row_delta, w_self, w_rows,
                                 interpret=_interpret())
    deq = codes.astype(jnp.float32) * row_delta[:, :, None]
    mixed = fp32.einsum("mn,nrc->mrc", w_rows.astype(jnp.float32), deq)
    return mixed + w_self.astype(jnp.float32)[:, None, None] * \
        own.astype(jnp.float32)


def mix_packed_init(own, w_self) -> jnp.ndarray:
    """Open a step-wise :func:`mix_packed`: the self term
    ``w_self[m]·own[m]`` the per-step accumulates build on.  With the
    neighbor terms folded in by :func:`mix_packed_accumulate` one
    permutation step at a time, the pipelined exchange never
    materializes the ``[S, R, 512]`` step stack — the accumulator is
    one buffer, and step ``s``'s dequant-accumulate is off the critical
    path of issuing step ``s+1``'s permute."""
    return w_self.astype(jnp.float32)[:, None, None] * \
        own.astype(jnp.float32)


def mix_packed_accumulate(acc, codes, row_delta, w_rows, *,
                          use_kernels: Optional[bool] = None) -> jnp.ndarray:
    """Fold one exchange step into a running mix:
    ``acc[m] += Σ_j w_rows[m, j]·codes[j]·Δ[j]``.

    The step-wise twin of :func:`mix_packed` (same per-term math: each
    code dequantizes as ``code·Δ`` before the weighted add).  On TPU it
    reuses the fused dequant-accumulate kernel with the accumulator in
    the ``own`` slot at weight one."""
    if use_kernels is None:
        use_kernels = jax.default_backend() == "tpu"
    if use_kernels:
        return mix_packed_pallas(
            acc, codes, row_delta,
            jnp.ones((acc.shape[0],), jnp.float32), w_rows,
            interpret=_interpret())
    deq = codes.astype(jnp.float32) * row_delta[:, :, None]
    return acc + fp32.einsum("mn,nrc->mrc", w_rows.astype(jnp.float32),
                             deq)
