"""Fused temperature-KD loss Pallas kernel (paper Sec. III-A).

Computes per-row  KL(softmax(y_t/T) || log_softmax(y_s/T)) * T^2  without
materialising either [R, V] probability tensor.  With V up to 202k
(llama4-scout) the naive path makes 6+ HBM round-trips over logits; this
kernel streams vocab tiles once, holding flash-style online accumulators
in VMEM scratch:

    m_t, l_t  — teacher running max / normaliser
    m_s, l_s  — student running max / normaliser
    u         — running  Σ exp(y_t − m_t)·(y_t − y_s)

and finishes with  KL = u/l_t − (m_t − m_s) − (log l_t − log l_s).

Grid = (row_blocks [parallel], vocab_tiles [arbitrary]); accumulators
live in VMEM scratch and persist across the inner vocab dimension.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_R = 128
BLOCK_V = 1024
NEG_INF = -1e30


def _kd_kernel(nv: int, inv_t: float, ys_ref, yt_ref, out_ref,
               mt_ref, lt_ref, u_ref, ms_ref, ls_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        mt_ref[...] = jnp.full_like(mt_ref, NEG_INF)
        lt_ref[...] = jnp.zeros_like(lt_ref)
        u_ref[...] = jnp.zeros_like(u_ref)
        ms_ref[...] = jnp.full_like(ms_ref, NEG_INF)
        ls_ref[...] = jnp.zeros_like(ls_ref)

    ys = ys_ref[...].astype(jnp.float32) * inv_t           # [R, Vb]
    yt = yt_ref[...].astype(jnp.float32) * inv_t

    # teacher online update
    mt_old = mt_ref[...]
    mt_new = jnp.maximum(mt_old, jnp.max(yt, axis=-1, keepdims=True))
    corr = jnp.exp(mt_old - mt_new)
    pt = jnp.exp(yt - mt_new)
    lt_ref[...] = lt_ref[...] * corr + jnp.sum(pt, axis=-1, keepdims=True)
    u_ref[...] = u_ref[...] * corr + \
        jnp.sum(pt * (yt - ys), axis=-1, keepdims=True)
    mt_ref[...] = mt_new

    # student online normaliser
    ms_old = ms_ref[...]
    ms_new = jnp.maximum(ms_old, jnp.max(ys, axis=-1, keepdims=True))
    ls_ref[...] = ls_ref[...] * jnp.exp(ms_old - ms_new) + \
        jnp.sum(jnp.exp(ys - ms_new), axis=-1, keepdims=True)
    ms_ref[...] = ms_new

    @pl.when(j == nv - 1)
    def _finish():
        kl = u_ref[...] / lt_ref[...] \
            - (mt_ref[...] - ms_ref[...]) \
            - (jnp.log(lt_ref[...]) - jnp.log(ls_ref[...]))
        out_ref[...] = kl[:, 0] / (inv_t * inv_t)   # * T^2


def kd_loss_rows_pallas(student_logits, teacher_logits, temperature: float,
                        *, block_r: int = BLOCK_R, block_v: int = BLOCK_V,
                        interpret: bool = False) -> jnp.ndarray:
    """[R, V] x [R, V] -> per-row KD loss [R] (already * T^2)."""
    r, v = student_logits.shape
    br = min(block_r, r)
    bv = min(block_v, v)
    nr, nv = pl.cdiv(r, br), pl.cdiv(v, bv)
    if r % br or v % bv:
        raise ValueError(f"shapes must be block-aligned: {(r, v)} vs {(br, bv)}")
    return pl.pallas_call(
        functools.partial(_kd_kernel, nv, 1.0 / temperature),
        grid=(nr, nv),
        in_specs=[
            pl.BlockSpec((br, bv), lambda i, j: (i, j)),
            pl.BlockSpec((br, bv), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((br,), lambda i, j: (i,)),
        out_shape=jax.ShapeDtypeStruct((r,), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((br, 1), jnp.float32),   # m_t
            pltpu.VMEM((br, 1), jnp.float32),   # l_t
            pltpu.VMEM((br, 1), jnp.float32),   # u
            pltpu.VMEM((br, 1), jnp.float32),   # m_s
            pltpu.VMEM((br, 1), jnp.float32),   # l_s
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(student_logits, teacher_logits)
