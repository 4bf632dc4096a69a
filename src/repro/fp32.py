"""Full-fp32 contractions for the federation's wire, gossip and merge math.

At the default precision the TPU computes an fp32 ``dot`` in a single
bf16 pass of the MXU, while the CPU computes it in fp32.  The gossip
mix, the Eq. 3/4 prototype sums, the low-rank sketch and the RegMean /
low-rank merge must give the same fp32 result in every engine, on every
backend and in each Pallas kernel and its ``ref.py``, so each of those
contractions goes through the wrappers below, which ask for
``Precision.HIGHEST`` (fp32 on the MXU; what the CPU does anyway).
Model forward/backward passes keep the default precision.

    from repro import fp32
    mixed = fp32.einsum("mn,nrc->mrc", w, deq)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

PRECISION = jax.lax.Precision.HIGHEST


def einsum(spec: str, *operands, **kw):
    return jnp.einsum(spec, *operands, precision=PRECISION, **kw)


def tensordot(a, b, axes=2, **kw):
    return jnp.tensordot(a, b, axes, precision=PRECISION, **kw)


def matmul(a, b, **kw):
    return jnp.matmul(a, b, precision=PRECISION, **kw)


def dot(a, b, **kw):
    return jnp.dot(a, b, precision=PRECISION, **kw)


def dot_general(a, b, dimension_numbers, **kw):
    return jax.lax.dot_general(a, b, dimension_numbers,
                               precision=PRECISION, **kw)
