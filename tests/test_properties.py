"""Property-based tests (hypothesis) on the system's invariants; the
invariants are also exercised deterministically in test_core.py.
"""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core import distillation as D
from repro.core import prototypes as P
from repro.core import quantization as Q
from repro.core import topology as T
from repro.core.metrics import macro_f1

SETTINGS = dict(max_examples=25, deadline=None)


@settings(**SETTINGS)
@given(st.lists(st.floats(-1e4, 1e4, allow_nan=False, width=32),
                min_size=2, max_size=200),
       st.sampled_from([8, 16]))
def test_quantize_roundtrip_bounded(values, bits):
    """|x' - x| <= delta/2 (+fp rounding) for any finite input."""
    x = jnp.asarray(values, jnp.float32)
    rt = Q.quantize_dequantize_tree(x, bits)
    qmax = (1 << (bits - 1)) - 1
    delta = max(float(jnp.max(jnp.abs(x))) / qmax, 1e-30)
    err = float(jnp.max(jnp.abs(rt - x)))
    assert err <= delta / 2 * 1.05 + 1e-6


@settings(**SETTINGS)
@given(st.integers(1, 6), st.integers(2, 8), st.integers(1, 5))
def test_kd_loss_nonnegative(rows, classes, seed):
    rng = np.random.default_rng(seed)
    ys = jnp.asarray(rng.standard_normal((rows, classes)) * 5, jnp.float32)
    yt = jnp.asarray(rng.standard_normal((rows, classes)) * 5, jnp.float32)
    assert float(D.kd_loss(ys, yt, 2.0)) >= -1e-6  # KL >= 0


@settings(**SETTINGS)
@given(st.integers(2, 30), st.integers(2, 6), st.integers(0, 99))
def test_global_prototypes_convex(n_samples, n_classes, seed):
    """Eq. 4: the global prototype lies in the convex hull of node
    prototypes (weights are a convex combination per class)."""
    rng = np.random.default_rng(seed)
    m = 3
    protos = jnp.asarray(rng.standard_normal((m, n_classes, 4)), jnp.float32)
    counts = jnp.asarray(rng.integers(0, n_samples, (m, n_classes)),
                         jnp.float32)
    glob, mask = P.aggregate_prototypes(protos, counts)
    for c in range(n_classes):
        if float(mask[c]) == 0:
            continue
        lo = np.asarray(protos[:, c]).min(0) - 1e-4
        hi = np.asarray(protos[:, c]).max(0) + 1e-4
        g = np.asarray(glob[c])
        w = np.asarray(counts[:, c])
        active = w > 0
        lo_a = np.asarray(protos[:, c])[active].min(0) - 1e-4
        hi_a = np.asarray(protos[:, c])[active].max(0) + 1e-4
        assert (g >= lo_a).all() and (g <= hi_a).all()


@settings(**SETTINGS)
@given(st.integers(2, 12), st.sampled_from(["full", "ring", "star"]))
def test_adjacency_symmetric_no_selfloop(n, topo):
    a = T.adjacency(n, topo)
    assert (a == a.T).all()
    assert not a.diagonal().any()
    # connected: BFS reaches everyone
    seen = {0}
    frontier = [0]
    while frontier:
        cur = frontier.pop()
        for j in np.nonzero(a[cur])[0]:
            if j not in seen:
                seen.add(int(j))
                frontier.append(int(j))
    assert seen == set(range(n))


@settings(**SETTINGS)
@given(st.integers(1, 50), st.integers(2, 6), st.integers(0, 9))
def test_macro_f1_in_unit_interval(n, k, seed):
    rng = np.random.default_rng(seed)
    y1 = rng.integers(0, k, n)
    y2 = rng.integers(0, k, n)
    f = macro_f1(y1, y2, k)
    assert 0.0 <= f <= 1.0
    assert macro_f1(y1, y1, k) == 1.0


@settings(**SETTINGS)
@given(st.integers(0, 10))
def test_alpha_decay_monotone(r):
    a_now = float(D.alpha_at_round(0.7, 0.01, r))
    a_next = float(D.alpha_at_round(0.7, 0.01, r + 1))
    assert a_next <= a_now
    assert a_now >= 0


@settings(**SETTINGS)
@given(st.integers(1, 20), st.integers(1, 8), st.integers(0, 9))
def test_local_prototypes_counts_sum(n, k, seed):
    rng = np.random.default_rng(seed)
    f1 = jnp.asarray(rng.standard_normal((n, 6)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, k, n))
    _, counts = P.local_prototypes(f1, labels, k)
    assert float(jnp.sum(counts)) == n
