"""Jitted wrappers for the Eq. 3 prototype accumulation.

``proto_accumulate`` is the single op both round engines (and the loop
engine's :func:`~repro.core.profe.compute_local_prototypes`) route the
per-batch accumulation through:

* jnp flavor (CPU default) — the one-hot einsum the engines have always
  run, kept verbatim so ``proto_pass="exact"`` stays *bit-identical* to
  the pre-kernel engines (asserted in tests);
* Pallas flavor (TPU default, interpret mode in tests) — the fused
  kernel that never materializes the ``[B, C]`` one-hot: labels compare
  against class-id tiles in VMEM and the mask feeds the MXU directly.

``proto_accumulate_nodes`` is the stacked-engine view: vmapped over the
leading ``[N, ...]`` node axis (the Pallas flavor batches through the
kernel's grid), replacing the scanned
``jnp.einsum("nbc,nbp->ncp", ...)`` and its ``[N, B, C]`` intermediate.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro import fp32
from repro.kernels.proto_accum.proto_accum import (BLOCK_B, BLOCK_C,
                                                   proto_accum_pallas)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _use_kernels(flag) -> bool:
    return jax.default_backend() == "tpu" if flag is None else flag


def _accum_pallas(f1, labels, n_classes: int):
    b, _ = f1.shape
    bb = min(BLOCK_B, max(8, b))
    bc = min(BLOCK_C, max(8, n_classes))
    bpad, cpad = (-b) % bb, (-n_classes) % bc
    # padded batch rows carry label == n_classes + cpad: out of every
    # class tile's id range, so they match nothing and contribute zeros
    labels2 = labels.astype(jnp.int32)[:, None]
    if bpad:
        f1 = jnp.pad(f1, ((0, bpad), (0, 0)))
        labels2 = jnp.pad(labels2, ((0, bpad), (0, 0)),
                          constant_values=n_classes + cpad)
    sums, counts = proto_accum_pallas(f1, labels2, n_classes + cpad,
                                      block_b=bb, block_c=bc,
                                      interpret=_interpret())
    return sums[:n_classes], counts[:n_classes, 0]


@partial(jax.jit, static_argnames=("n_classes", "use_kernels"))
def proto_accumulate(f1, labels, n_classes: int, *, use_kernels=None):
    """One batch of Eq. 3: f1 [B, P] + labels [B] -> (sums [C, P],
    counts [C]).  ``use_kernels=None`` -> Pallas on TPU, jnp elsewhere."""
    if _use_kernels(use_kernels):
        return _accum_pallas(f1.astype(jnp.float32), labels, n_classes)
    onehot = jax.nn.one_hot(labels, n_classes, dtype=jnp.float32)
    f1 = f1.astype(jnp.float32)
    return (fp32.einsum("bc,bp->cp", onehot, f1),
            jnp.sum(onehot, axis=0))


@partial(jax.jit, static_argnames=("n_classes", "use_kernels"))
def proto_accumulate_nodes(f1, labels, n_classes: int, *, use_kernels=None):
    """Stacked-node batch: f1 [N, B, P] + labels [N, B] ->
    (sums [N, C, P], counts [N, C])."""
    return jax.vmap(
        lambda f, l: proto_accumulate(f, l, n_classes,
                                      use_kernels=use_kernels))(f1, labels)
