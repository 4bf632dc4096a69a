"""Pure-jnp oracle for the prototype-accumulate kernel: the historical
one-hot einsum, exactly as the engines' Eq. 3 pass has always computed
it — the ``ops`` fast path must stay bit-identical to this on CPU."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import fp32


def proto_accum_ref(f1, labels, n_classes: int):
    """f1: [B, P], labels: [B] -> (sums [C, P], counts [C]) via the
    explicit [B, C] one-hot contraction."""
    onehot = jax.nn.one_hot(labels, n_classes, dtype=jnp.float32)
    f1 = f1.astype(jnp.float32)
    sums = fp32.einsum("bc,bp->cp", onehot, f1)
    counts = jnp.sum(onehot, axis=0)
    return sums, counts
