"""Pallas TPU kernels for wire quantization (paper Sec. III-D).

Bandwidth-bound elementwise op: every gossip payload is pushed through
``Q(x) = floor(x/Δ + 0.5)·Δ`` with Δ = max|x| / 32767 (16-bit).  The
kernels tile HBM→VMEM in (8,128)-aligned blocks (fp32 min tile).

**Fused single-launch kernels.**  The seed ran two ``pallas_call``s per
tensor (absmax walk, then quantize walk) with a host-side Δ round-trip
in between.  Here one kernel does both: the grid gains a leading
*phase* axis ``(2, nr, nc)`` — phase 0 accumulates the global abs-max
into the (1,1) Δ output, which lives in SMEM for the whole sweep (the
TPU stores no scalars to VMEM) and doubles as the reduction scratch,
and finalizes Δ at the last block; phase 1 re-reads the tiles and
writes codes.  One launch, no host synchronization:

* ``fused_quantize``            — x -> (int32 codes, Δ)
* ``fused_quantize_dequantize`` — x -> (Q(x)·Δ fp32, Δ); the receiver-
  side reconstruction the DFL simulator uses, saving the separate
  dequantize launch and the int round-trip through HBM
* ``dequantize``                — codes·Δ for payloads received as ints

**Row-scaled variants** (``*_rows``) take a per-row Δ column instead of
a scalar — the building block of the packed-tree path in ``ops.py``
that quantizes a 100+-leaf pytree in a handful of launches: all float
leaves are flattened into one padded ``[R, C]`` buffer whose rows carry
per-tensor segment scales.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_R = 256
BLOCK_C = 512
# VMEM the mix kernel's double-buffered blocks may take (the scoped
# default on v5e is 16 MiB; the rest is left for block temporaries)
MIX_VMEM_BUDGET = 6 << 20


def _qmaxf(bits: int) -> float:
    return float((1 << (bits - 1)) - 1)


def _masked_abs(x_ref, i, j, r, c, br, bc):
    """|block| with out-of-bounds lanes zeroed: partial edge blocks are
    padded by Pallas (NaN in interpret mode, undefined on hardware) and
    must not leak into the absmax reduction."""
    a = jnp.abs(x_ref[...].astype(jnp.float32))
    rows = jax.lax.broadcasted_iota(jnp.int32, a.shape, 0) + i * br
    cols = jax.lax.broadcasted_iota(jnp.int32, a.shape, 1) + j * bc
    return jnp.where((rows < r) & (cols < c), a, 0.0)


# ---------------------------------------------------------------------------
# fused single-launch absmax + quantize (scalar Δ)
# ---------------------------------------------------------------------------

def _fused_quantize_kernel(qmax: float, dequant: bool, dims, x_ref, qmax_ref,
                           out_ref, delta_ref):
    # qmax arrives BOTH static (for the clip bounds, which tolerate
    # constant folding) and as a (1,1) runtime input (for the Δ
    # division): dividing by a compile-time constant lets XLA strength-
    # reduce to a reciprocal multiply, off by 1 ulp from the fp32 oracle.
    r, c, br, bc = dims
    p = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)
    last = (i == pl.num_programs(1) - 1) & (j == pl.num_programs(2) - 1)

    @pl.when((p == 0) & (i == 0) & (j == 0))
    def _():
        delta_ref[0, 0] = 0.0

    @pl.when(p == 0)
    def _():
        bm = jnp.max(_masked_abs(x_ref, i, j, r, c, br, bc))
        delta_ref[0, 0] = jnp.maximum(delta_ref[0, 0], bm)

    @pl.when((p == 0) & last)
    def _():
        # amax -> Δ, once, while the block is still resident
        delta_ref[0, 0] = jnp.maximum(delta_ref[0, 0] / qmax_ref[0, 0],
                                      jnp.finfo(jnp.float32).tiny)

    @pl.when(p == 1)
    def _():
        # exact division (not reciprocal-multiply): bit-identical to the
        # fp32 oracle, and this kernel is bandwidth-bound anyway
        delta = delta_ref[0, 0]
        codes = jnp.floor(x_ref[...].astype(jnp.float32) / delta + 0.5)
        codes = jnp.clip(codes, -qmax - 1, qmax)
        if dequant:
            out_ref[...] = codes * delta
        else:
            out_ref[...] = codes.astype(jnp.int32)


def _fused_call(x2d, qmax2d, *, bits: int, dequant: bool, interpret: bool):
    r, c = x2d.shape
    br, bc = min(BLOCK_R, r), min(BLOCK_C, c)
    out_dtype = jnp.float32 if dequant else jnp.int32
    if qmax2d is None:   # standalone use: correct off-jit, see ops._qmax_arr
        qmax2d = jnp.full((1, 1), _qmaxf(bits), jnp.float32)
    out, delta = pl.pallas_call(
        functools.partial(_fused_quantize_kernel, _qmaxf(bits), dequant,
                          (r, c, br, bc)),
        grid=(2, pl.cdiv(r, br), pl.cdiv(c, bc)),
        in_specs=[pl.BlockSpec((br, bc), lambda p, i, j: (i, j)),
                  pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=[pl.BlockSpec((br, bc), lambda p, i, j: (i, j)),
                   pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_shape=[jax.ShapeDtypeStruct((r, c), out_dtype),
                   jax.ShapeDtypeStruct((1, 1), jnp.float32)],
        interpret=interpret,
    )(x2d.astype(jnp.float32), qmax2d)
    return out, delta[0, 0]


def fused_quantize_pallas(x2d, qmax2d=None, *, bits: int = 16,
                          interpret: bool = False):
    """x2d: [R, C] fp -> (int32 codes [R, C], Δ scalar fp32). One launch.

    int32 block output (TPU-native word size); the wire format narrows
    to int16/int8 on serialization — byte accounting uses ``bits``, not
    the in-memory dtype.  ``qmax2d``: optional (1,1) runtime qmax (pass
    one created outside any enclosing jit for bit-exact Δ on CPU).
    """
    return _fused_call(x2d, qmax2d, bits=bits, dequant=False,
                       interpret=interpret)


def fused_quantize_dequantize_pallas(x2d, qmax2d=None, *, bits: int = 16,
                                     interpret: bool = False):
    """x2d -> (Q(x)·Δ fp32 [R, C], Δ). The receiver-side view in one
    launch — codes never materialize in HBM."""
    return _fused_call(x2d, qmax2d, bits=bits, dequant=True,
                       interpret=interpret)


# ---------------------------------------------------------------------------
# dequantize (scalar Δ) — payloads that arrive as integer codes
# ---------------------------------------------------------------------------

def _dequantize_kernel(codes_ref, delta_ref, out_ref):
    out_ref[...] = codes_ref[...].astype(jnp.float32) * delta_ref[0, 0]


def dequantize_pallas(codes2d, delta, *, interpret: bool = False) -> jnp.ndarray:
    r, c = codes2d.shape
    br, bc = min(BLOCK_R, r), min(BLOCK_C, c)
    delta2d = jnp.reshape(delta.astype(jnp.float32), (1, 1))
    return pl.pallas_call(
        _dequantize_kernel,
        grid=(pl.cdiv(r, br), pl.cdiv(c, bc)),
        in_specs=[
            pl.BlockSpec((br, bc), lambda i, j: (i, j)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((br, bc), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((r, c), jnp.float32),
        interpret=interpret,
    )(codes2d, delta2d)


# ---------------------------------------------------------------------------
# row-scaled variants: per-row Δ column (packed-tree segments)
# ---------------------------------------------------------------------------

def _rowabs_kernel(dims, x_ref, out_ref):
    r, c, br, bc = dims
    i = pl.program_id(0)
    j = pl.program_id(1)
    bm = jnp.max(_masked_abs(x_ref, i, j, r, c, br, bc), axis=1,
                 keepdims=True)

    @pl.when(j == 0)
    def _():
        out_ref[...] = bm

    @pl.when(j > 0)
    def _():
        out_ref[...] = jnp.maximum(out_ref[...], bm)


def rowabs_pallas(x2d, *, interpret: bool = False) -> jnp.ndarray:
    """x2d: [R, C] -> per-row max|x| [R, 1], accumulated across column
    blocks (the out block for row-stripe i stays resident while j
    sweeps)."""
    r, c = x2d.shape
    br, bc = min(BLOCK_R, r), min(BLOCK_C, c)
    return pl.pallas_call(
        functools.partial(_rowabs_kernel, (r, c, br, bc)),
        grid=(pl.cdiv(r, br), pl.cdiv(c, bc)),
        in_specs=[pl.BlockSpec((br, bc), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((br, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, 1), jnp.float32),
        interpret=interpret,
    )(x2d.astype(jnp.float32))


def _rowabs_sum_kernel(decay: float, dims, x_ref, res_ref, out_ref):
    # per-row max|x + decay*res| without materializing the sum in HBM —
    # the Δ pass of the stateful (error-feedback) codec
    r, c, br, bc = dims
    i = pl.program_id(0)
    j = pl.program_id(1)
    s = x_ref[...].astype(jnp.float32) + \
        decay * res_ref[...].astype(jnp.float32)
    rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) + i * br
    cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + j * bc
    a = jnp.where((rows < r) & (cols < c), jnp.abs(s), 0.0)
    bm = jnp.max(a, axis=1, keepdims=True)

    @pl.when(j == 0)
    def _():
        out_ref[...] = bm

    @pl.when(j > 0)
    def _():
        out_ref[...] = jnp.maximum(out_ref[...], bm)


def rowabs_sum_pallas(x2d, res2d, *, decay: float = 1.0,
                      interpret: bool = False) -> jnp.ndarray:
    """x2d, res2d: [R, C] -> per-row max|x + decay*res| [R, 1] — the
    absmax sweep of the error-feedback codec, residual-add fused into
    the reduction (the effective payload never lands in HBM)."""
    r, c = x2d.shape
    br, bc = min(BLOCK_R, r), min(BLOCK_C, c)
    return pl.pallas_call(
        functools.partial(_rowabs_sum_kernel, decay, (r, c, br, bc)),
        grid=(pl.cdiv(r, br), pl.cdiv(c, bc)),
        in_specs=[pl.BlockSpec((br, bc), lambda i, j: (i, j)),
                  pl.BlockSpec((br, bc), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((br, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, 1), jnp.float32),
        interpret=interpret,
    )(x2d.astype(jnp.float32), res2d.astype(jnp.float32))


def _quantize_rows_ef_kernel(decay: float, x_ref, res_ref, delta_ref,
                             qmax_ref, codes_ref, newres_ref):
    # the stateful-codec sweep in ONE launch: residual-add → mixed-width
    # quantize (per-row Δ and qmax, like the mixed kernel) →
    # residual-update.  The effective fp32 payload x + decay*res exists
    # only as a block temporary — no materialized intermediate tree.
    delta = delta_ref[...]                                  # [br, 1]
    qmax = qmax_ref[...]                                    # [br, 1]
    eff = x_ref[...].astype(jnp.float32) + \
        decay * res_ref[...].astype(jnp.float32)
    codes = jnp.floor(eff / delta + 0.5)
    codes = jnp.clip(codes, -qmax - 1, qmax)
    codes_ref[...] = codes.astype(jnp.int32)
    newres_ref[...] = eff - codes * delta


def quantize_rows_ef_pallas(x2d, res2d, row_delta, row_qmax, *,
                            decay: float = 1.0, interpret: bool = False):
    """x2d, res2d: [R, C]; row_delta/row_qmax: [R, 1] -> (int32 codes
    [R, C], new residual fp32 [R, C]).  One launch: each row's effective
    payload (x + decay·res) is quantized at its own Δ *and* width, and
    the fresh quantization error is written back as the next round's
    residual — the error-feedback state update costs no extra sweep."""
    r, c = x2d.shape
    br, bc = min(BLOCK_R, r), min(BLOCK_C, c)
    return pl.pallas_call(
        functools.partial(_quantize_rows_ef_kernel, decay),
        grid=(pl.cdiv(r, br), pl.cdiv(c, bc)),
        in_specs=[
            pl.BlockSpec((br, bc), lambda i, j: (i, j)),
            pl.BlockSpec((br, bc), lambda i, j: (i, j)),
            pl.BlockSpec((br, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((br, 1), lambda i, j: (i, 0)),
        ],
        out_specs=[pl.BlockSpec((br, bc), lambda i, j: (i, j)),
                   pl.BlockSpec((br, bc), lambda i, j: (i, j))],
        out_shape=[jax.ShapeDtypeStruct((r, c), jnp.int32),
                   jax.ShapeDtypeStruct((r, c), jnp.float32)],
        interpret=interpret,
    )(x2d.astype(jnp.float32), res2d.astype(jnp.float32),
      row_delta.astype(jnp.float32), row_qmax.astype(jnp.float32))


def _quantize_rows_kernel(qmax: float, dequant: bool, x_ref, delta_ref,
                          out_ref):
    delta = delta_ref[...]                                  # [br, 1]
    codes = jnp.floor(x_ref[...].astype(jnp.float32) / delta + 0.5)
    codes = jnp.clip(codes, -qmax - 1, qmax)
    if dequant:
        out_ref[...] = codes * delta
    else:
        out_ref[...] = codes.astype(jnp.int32)


def _rows_call(x2d, row_delta, *, bits: int, dequant: bool, interpret: bool):
    r, c = x2d.shape
    br, bc = min(BLOCK_R, r), min(BLOCK_C, c)
    out_dtype = jnp.float32 if dequant else jnp.int32
    return pl.pallas_call(
        functools.partial(_quantize_rows_kernel, _qmaxf(bits), dequant),
        grid=(pl.cdiv(r, br), pl.cdiv(c, bc)),
        in_specs=[
            pl.BlockSpec((br, bc), lambda i, j: (i, j)),
            pl.BlockSpec((br, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((br, bc), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((r, c), out_dtype),
        interpret=interpret,
    )(x2d.astype(jnp.float32), row_delta.astype(jnp.float32))


def quantize_rows_pallas(x2d, row_delta, *, bits: int = 16,
                         interpret: bool = False) -> jnp.ndarray:
    """x2d: [R, C], row_delta: [R, 1] -> int32 codes, each row scaled by
    its own Δ (rows of one packed tensor share a segment Δ)."""
    return _rows_call(x2d, row_delta, bits=bits, dequant=False,
                      interpret=interpret)


def quantize_dequantize_rows_pallas(x2d, row_delta, *, bits: int = 16,
                                    interpret: bool = False) -> jnp.ndarray:
    """Fused per-row round-trip: the receiver-side view of a packed
    buffer in one launch."""
    return _rows_call(x2d, row_delta, bits=bits, dequant=True,
                      interpret=interpret)


def _quantize_rows_mixed_kernel(x_ref, delta_ref, qmax_ref, out_ref):
    # per-row clip bounds: mixed-precision packed buffers carry rows of
    # different wire widths through ONE launch (a WireSpec with, e.g.,
    # int4 student rows and int16 prototype rows)
    delta = delta_ref[...]                                  # [br, 1]
    qmax = qmax_ref[...]                                    # [br, 1]
    codes = jnp.floor(x_ref[...].astype(jnp.float32) / delta + 0.5)
    out_ref[...] = jnp.clip(codes, -qmax - 1, qmax).astype(jnp.int32)


def quantize_rows_mixed_pallas(x2d, row_delta, row_qmax, *,
                               interpret: bool = False) -> jnp.ndarray:
    """x2d: [R, C], row_delta/row_qmax: [R, 1] -> int32 codes; each row
    scaled by its own Δ *and* clipped to its own width's qmax — the
    single-launch quantize sweep of a mixed-precision WireSpec."""
    r, c = x2d.shape
    br, bc = min(BLOCK_R, r), min(BLOCK_C, c)
    return pl.pallas_call(
        _quantize_rows_mixed_kernel,
        grid=(pl.cdiv(r, br), pl.cdiv(c, bc)),
        in_specs=[
            pl.BlockSpec((br, bc), lambda i, j: (i, j)),
            pl.BlockSpec((br, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((br, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((br, bc), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((r, c), jnp.int32),
        interpret=interpret,
    )(x2d.astype(jnp.float32), row_delta.astype(jnp.float32),
      row_qmax.astype(jnp.float32))


def _mix_packed_kernel(n_nodes: int, own_ref, codes_ref, delta_ref,
                       wself_ref, wrows_ref, out_ref):
    # out[m] = w_self[m]*own[m] + sum_j wrows[m, j] * codes[j] * delta[j]
    # — the receiver side of the packed wire exchange in ONE launch: the
    # int codes are dequantized and folded into the gossip mix without
    # ever materializing the fp32 neighbor payloads in HBM.
    acc = wself_ref[...][:, 0][:, None, None] * own_ref[...]
    for j in range(n_nodes):            # n_nodes is static and small
        deq = codes_ref[j].astype(jnp.float32) * delta_ref[j]
        acc = acc + wrows_ref[...][:, j][:, None, None] * deq[None, :, :]
    out_ref[...] = acc


def mix_packed_pallas(own, codes, row_delta, w_self, w_rows, *,
                      interpret: bool = False) -> jnp.ndarray:
    """Fused dequantize-and-accumulate over packed wire buffers.

    own:       [M, R, C] fp32 — receiver's local (unquantized) buffer
    codes:     [N, R, C] int  — gathered/permuted neighbor wire codes
    row_delta: [N, R]    fp32 — per-row de-quantization scales
    w_self:    [M]       fp32 — own-copy mixing weight
    w_rows:    [M, N]    fp32 — neighbor mixing weights (zero = not mine)
    -> [M, R, C] fp32 mixed buffer.
    """
    m, r, c = own.shape
    n = codes.shape[0]
    bc = min(BLOCK_C, c)
    # every receiver and sender rides each block, so the row block
    # shrinks as M + N grows: own, codes and out blocks, double-buffered
    row_bytes = 2 * (2 * m + n) * bc * 4
    br = min(r, BLOCK_R, max(8, MIX_VMEM_BUDGET // row_bytes // 8 * 8))
    # fp32 "codes" (the FedAvg baseline permutes raw model buffers with
    # unit deltas) must NOT round-trip through int — only narrow wire
    # ints are upcast to the TPU-native word size
    if jnp.issubdtype(codes.dtype, jnp.floating):
        codes = codes.astype(jnp.float32)
    else:
        codes = codes.astype(jnp.int32)
    return pl.pallas_call(
        functools.partial(_mix_packed_kernel, n),
        grid=(pl.cdiv(r, br), pl.cdiv(c, bc)),
        in_specs=[
            pl.BlockSpec((m, br, bc), lambda i, j: (0, i, j)),
            pl.BlockSpec((n, br, bc), lambda i, j: (0, i, j)),
            pl.BlockSpec((n, br, 1), lambda i, j: (0, i, 0)),
            pl.BlockSpec((m, 1), lambda i, j: (0, 0)),
            pl.BlockSpec((m, n), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((m, br, bc), lambda i, j: (0, i, j)),
        out_shape=jax.ShapeDtypeStruct((m, r, c), jnp.float32),
        interpret=interpret,
    )(own.astype(jnp.float32), codes,
      row_delta.astype(jnp.float32)[:, :, None],
      w_self.astype(jnp.float32).reshape(m, 1),
      w_rows.astype(jnp.float32))


def _dequantize_rows_kernel(codes_ref, delta_ref, out_ref):
    out_ref[...] = codes_ref[...].astype(jnp.float32) * delta_ref[...]


def dequantize_rows_pallas(codes2d, row_delta, *,
                           interpret: bool = False) -> jnp.ndarray:
    r, c = codes2d.shape
    br, bc = min(BLOCK_R, r), min(BLOCK_C, c)
    return pl.pallas_call(
        _dequantize_rows_kernel,
        grid=(pl.cdiv(r, br), pl.cdiv(c, bc)),
        in_specs=[
            pl.BlockSpec((br, bc), lambda i, j: (i, j)),
            pl.BlockSpec((br, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((br, bc), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((r, c), jnp.float32),
        interpret=interpret,
    )(codes2d, row_delta.astype(jnp.float32))
