"""Fused per-class feature accumulation, Pallas TPU kernel (Eq. 3).

sums[c, :]  = sum_b  1[labels_b == c] * f1[b, :]
counts[c]   = sum_b  1[labels_b == c]

The scanned Eq. 3 einsum materializes a ``[B, C]`` one-hot (``[N, B, C]``
stacked over nodes) only to contract it away immediately.  This kernel
never builds it: each ``(Cb, Bb)`` grid tile compares its label block
against its class-id block — a ``[Bb, Cb]`` mask that lives only in
VMEM registers — and feeds ``mask^T @ f1_block`` straight to the MXU.
The batch axis is the innermost grid dimension, so tiles accumulate
into the same ``[Cb, P]`` output block sequentially (zero-initialized
on the first batch tile, ``+=`` afterwards — the standard Pallas
reduction-grid pattern).

Counts ride along as a ``[C, 1]`` column (TPU wants >= 2-D refs; the
wrapper squeezes).  Out-of-range labels (the wrapper pads the batch
with ``label = C``) match no class row and contribute nothing.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import fp32

BLOCK_B = 128
BLOCK_C = 128


def _proto_accum_kernel(f1_ref, labels_ref, sums_ref, counts_ref, *,
                        block_c: int):
    ci = pl.program_id(0)
    bi = pl.program_id(1)

    f1 = f1_ref[...].astype(jnp.float32)            # [Bb, P]
    labels = labels_ref[...]                        # [Bb, 1] int32
    # class ids of this C tile: [Bb, Cb] iota along dim 1 (+ tile offset)
    cls = jax.lax.broadcasted_iota(jnp.int32, (f1.shape[0], block_c), 1) \
        + ci * block_c
    onehot = (labels == cls).astype(jnp.float32)    # [Bb, Cb], never [B, C]
    tile_sums = fp32.dot_general(                   # [Cb, P] on the MXU
        onehot, f1, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    tile_counts = jnp.sum(onehot, axis=0)[:, None]  # [Cb, 1]

    @pl.when(bi == 0)
    def _init():
        sums_ref[...] = tile_sums
        counts_ref[...] = tile_counts

    @pl.when(bi != 0)
    def _accum():
        sums_ref[...] += tile_sums
        counts_ref[...] += tile_counts


def proto_accum_pallas(f1, labels, n_classes: int, *,
                       block_b: int = BLOCK_B, block_c: int = BLOCK_C,
                       interpret: bool = False):
    """f1: [B, P] float, labels: [B, 1] int32 -> (sums [C, P],
    counts [C, 1]); B % block_b == 0 and C % block_c == 0 (the ops
    wrapper pads; padded labels must be >= n_classes)."""
    b, p_dim = f1.shape
    bb, bc = min(block_b, b), min(block_c, n_classes)
    if b % bb or n_classes % bc:
        raise ValueError(f"block-align inputs first: {(b, n_classes)} vs "
                         f"{(bb, bc)}")
    from functools import partial
    return pl.pallas_call(
        partial(_proto_accum_kernel, block_c=bc),
        grid=(n_classes // bc, b // bb),
        in_specs=[
            pl.BlockSpec((bb, p_dim), lambda ci, bi: (bi, 0)),
            pl.BlockSpec((bb, 1), lambda ci, bi: (bi, 0)),
        ],
        out_specs=[
            # the batch grid axis reduces in place: the index map ignores
            # bi, so every batch tile revisits the same [Cb, P] block
            pl.BlockSpec((bc, p_dim), lambda ci, bi: (ci, 0)),
            pl.BlockSpec((bc, 1), lambda ci, bi: (ci, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_classes, p_dim), jnp.float32),
            jax.ShapeDtypeStruct((n_classes, 1), jnp.float32),
        ],
        interpret=interpret,
    )(f1, labels)
