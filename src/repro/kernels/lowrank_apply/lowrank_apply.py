"""Pallas TPU kernel: fused low-rank apply over stacked matrix leaves.

One launch computes, for every receiver ``i`` and every ``(d, k)``
output tile,

    acc = 0
    for j in 0..S-1:                       # static unroll over senders
        acc += coeffs[i, j] * B[j, tile_d, :] @ A[j, :, tile_k]
    out[i, tile] = w[i, tile] + acc

— pure MXU matmuls (``jnp.dot(..., preferred_element_type=float32)``),
no gathers, and the dense per-sender ``[d, k]`` delta never exists in
memory: each sender contributes a ``[bd, r] @ [r, bk]`` product
straight into the accumulator.  The rank axis is never tiled, so the
per-element reduction matches the materialized reference
(``ref.lowrank_apply_ref``) bit for bit.

Grid ``(N, ⌈d/bd⌉, ⌈k/bk⌉)``; the full sender bank ``B [S, bd, r]``
rides VMEM per tile (S ≤ a few dozen nodes, r ~ 8 → KBs).  The
RegMean variant indexes the per-receiver adjusted factors
``Ã [N, S, r, bk]`` by the grid's receiver coordinate.  ``coeffs``
``[N, S]`` rides SMEM whole (a ``(1, S)`` VMEM block would break the
(8,128) tiling rule), scalar-read per receiver and sender.  Rank should
be a multiple of 8 on real TPU hardware for fp32 tiling (the r = 8
default is); interpret mode has no such constraint.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import fp32

BLOCK_D = 256
BLOCK_K = 512


def _apply_kernel(n_send: int, per_recv: bool, w_ref, c_ref, b_ref, a_ref,
                  out_ref):
    # delta accumulates sender-sequentially, then ONE add onto w — the
    # same per-element order as ref.lowrank_delta_ref, so the plane
    # sweep's buffer-native add stays bit-identical to the kernel
    i = pl.program_id(0)
    acc = None
    for j in range(n_send):
        aj = a_ref[0, j] if per_recv else a_ref[j]
        term = c_ref[i, j] * fp32.dot(
            b_ref[j].astype(jnp.float32), aj.astype(jnp.float32),
            preferred_element_type=jnp.float32)
        acc = term if acc is None else acc + term
    out_ref[0] = w_ref[0].astype(jnp.float32) + acc


def lowrank_apply_pallas(w, coeffs, b, a, *, interpret: bool = False):
    """``w`` [N, d, k]; ``coeffs`` [N, S]; ``b`` [S, d, r]; ``a``
    [S, r, k] or [N, S, r, k] -> [N, d, k] in ONE launch."""
    n, d, k = w.shape
    s, _, r = b.shape
    bd, bk = min(BLOCK_D, d), min(BLOCK_K, k)
    per_recv = a.ndim == 4
    a_spec = pl.BlockSpec((1, s, r, bk), lambda i, di, kj: (i, 0, 0, kj)) \
        if per_recv else \
        pl.BlockSpec((s, r, bk), lambda i, di, kj: (0, 0, kj))
    return pl.pallas_call(
        functools.partial(_apply_kernel, s, per_recv),
        grid=(n, pl.cdiv(d, bd), pl.cdiv(k, bk)),
        in_specs=[
            pl.BlockSpec((1, bd, bk), lambda i, di, kj: (i, di, kj)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((s, bd, r), lambda i, di, kj: (0, di, 0)),
            a_spec,
        ],
        out_specs=pl.BlockSpec((1, bd, bk), lambda i, di, kj: (i, di, kj)),
        out_shape=jax.ShapeDtypeStruct((n, d, k), jnp.float32),
        interpret=interpret,
    )(w.astype(jnp.float32), coeffs.astype(jnp.float32),
      b.astype(jnp.float32), a.astype(jnp.float32))
