"""Prototype learning (paper Sec. III-B, following FedProto with CE loss).

* Eq. 3 — local prototype C_i^(j): class-mean of representations f_1(x).
* Eq. 4 — global prototype: instance-count-weighted mean over the nodes
  that know class j.
* Eq. 5 — nearest-prototype inference: argmin_j ||f_1(x) - C̄(j)||_2.
* Eq. 6 — prototype MSE loss against the global prototype of the true
  class (skipped for classes no node has seen yet).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro import fp32


def local_prototypes(f1, labels, n_classes: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Eq. 3. f1: [N, P], labels: [N] int -> (protos [C, P], counts [C]).

    Classes absent locally get a zero prototype and count 0.
    """
    onehot = jax.nn.one_hot(labels, n_classes, dtype=jnp.float32)   # [N, C]
    counts = jnp.sum(onehot, axis=0)                                # [C]
    sums = fp32.einsum("nc,np->cp", onehot, f1.astype(jnp.float32))
    protos = sums / jnp.maximum(counts, 1.0)[:, None]
    return protos, counts


def aggregate_prototypes(protos, counts) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Eq. 4. protos: [M, C, P], counts: [M, C] -> (global [C, P], mask [C]).

    C̄(j) = 1/|N_j| * sum_{i in N_j} |D_{i,j}| / N_j * C_i^(j)
    where N_j = total instances of class j and |N_j| = #nodes knowing j.

    NOTE: the paper's Eq. 4 carries FedProto's 1/|N_j| prefactor on top of
    the |D_ij|/N_j weights; the weights already sum to 1 over nodes, so the
    prefactor rescales prototypes by the inverse number of contributing
    nodes.  We implement the standard weighted mean (prefactor dropped),
    which matches FedProto's released code; toggleable via
    ``strict_eq4=True`` in :func:`aggregate_prototypes_strict`.
    """
    n_j = jnp.sum(counts, axis=0)                                   # [C]
    w = counts / jnp.maximum(n_j, 1.0)[None, :]                     # [M, C]
    glob = fp32.einsum("mc,mcp->cp", w, protos.astype(jnp.float32))
    mask = (n_j > 0).astype(jnp.float32)
    return glob, mask


def aggregate_prototypes_strict(protos, counts) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Literal Eq. 4 (with the 1/|N_j| prefactor)."""
    n_j = jnp.sum(counts, axis=0)
    nodes_knowing = jnp.sum((counts > 0).astype(jnp.float32), axis=0)
    w = counts / jnp.maximum(n_j, 1.0)[None, :]
    glob = fp32.einsum("mc,mcp->cp", w, protos.astype(jnp.float32))
    glob = glob / jnp.maximum(nodes_knowing, 1.0)[:, None]
    mask = (n_j > 0).astype(jnp.float32)
    return glob, mask


def proto_mse_loss(f1, global_protos, labels, proto_mask) -> jnp.ndarray:
    """Eq. 6: MSE(f_1(x), C̄(true class)), masked to classes with a
    global prototype."""
    target = global_protos[labels]                                  # [N, P]
    valid = proto_mask[labels]                                      # [N]
    d = f1.astype(jnp.float32) - target
    per_ex = jnp.mean(jnp.square(d), axis=-1) * valid
    return jnp.sum(per_ex) / jnp.maximum(jnp.sum(valid), 1.0)


def nearest_prototype_predict(f1, global_protos, proto_mask) -> jnp.ndarray:
    """Eq. 5: label of the nearest global prototype (L2)."""
    d2 = pairwise_sq_dists(f1, global_protos)                       # [N, C]
    d2 = jnp.where(proto_mask[None, :] > 0, d2, jnp.inf)
    return jnp.argmin(d2, axis=-1)


def pairwise_sq_dists(x, protos) -> jnp.ndarray:
    """||x - c||^2 via the MXU-friendly expansion x² - 2xc + c²."""
    x = x.astype(jnp.float32)
    protos = protos.astype(jnp.float32)
    x2 = jnp.sum(jnp.square(x), axis=-1, keepdims=True)             # [N,1]
    c2 = jnp.sum(jnp.square(protos), axis=-1)[None, :]              # [1,C]
    xc = x @ protos.T                                               # [N,C]
    return jnp.maximum(x2 - 2.0 * xc + c2, 0.0)
