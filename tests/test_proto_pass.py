"""The single-pass (fused) Eq. 3 round vs the exact post-training pass,
plus the satellites that rode in with it: the batched all-node eval,
the stale-mixing self-weight floor, and the mesh engine's fused-round
adapter.

The contract under test:

* ``proto_pass="exact"`` is *bit-identical* to the historical engines —
  the exact pass is the same one-hot einsum, scanned in the same order;
* ``proto_pass="fused"`` trades the second forward pass for prototypes
  built from the evolving student — same learning to a small tolerance,
  same wire bytes, and its scan body traces a bounded number of times
  regardless of how many rounds run;
* the floor recovers stale-by-one mixing without breaking row-stochastic
  gossip.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import spans
from repro.config import FederationConfig, TrainConfig, get_config
from repro.core import federation as F
from repro.core import profe
from repro.core import topology as T
from repro.core.federation import run_federation, run_federation_loop
from repro.core.profe import normalize_protos, proto_labels
from repro.data import batches, make_image_dataset, partition, train_test_split
from repro.kernels.proto_accum.ref import proto_accum_ref
from repro.models import derive_student, forward, init_params

RNG = np.random.default_rng(7)
N_NODES = 3


@pytest.fixture(scope="module")
def mnist_like():
    cfg = get_config("mnist-cnn")
    data = make_image_dataset(0, 900, cfg.input_hw, cfg.num_classes)
    train_d, test_d = train_test_split(data, 0.1, 0)
    parts = partition(train_d["label"], N_NODES, "iid", 0)
    node_data = [{k: v[i] for k, v in train_d.items()} for i in parts]
    return cfg, node_data, test_d


TRAIN = TrainConfig(batch_size=64, learning_rate=1e-3, optimizer="adamw",
                    remat=False)


def _stacked_students(student_cfg, n):
    params = [init_params(student_cfg, jax.random.PRNGKey(i))
              for i in range(n)]
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *params)


# ---------------------------------------------------------------------------
# exact mode: bit-identical to the historical engines
# ---------------------------------------------------------------------------

def test_exact_proto_pass_bit_identical_to_historical_einsum(mnist_like):
    """The factored exact pass (scan + shared proto_accumulate op) vs a
    replica of the pre-kernel engine: per-batch [N, B, C] one-hot einsum
    in a host loop.  Sums, counts, and the normalized prototypes must
    match bit for bit — 'exact' means exact."""
    cfg, node_data, _ = mnist_like
    student = derive_student(cfg)
    ncls = cfg.num_classes
    stacked = _stacked_students(student, N_NODES)
    pxb, pvalid = F._stack_round_batches(node_data, 64, [0] * N_NODES, 1)

    got_sums, got_counts = F._make_proto_pass(student, ncls)(
        stacked, pxb, pvalid)

    sums = jnp.zeros((N_NODES, ncls, student.proto_dim), jnp.float32)
    counts = jnp.zeros((N_NODES, ncls), jnp.float32)
    for t in range(pvalid.shape[0]):
        batch = jax.tree_util.tree_map(lambda x: x[t], pxb)
        v = pvalid[t]
        out = jax.vmap(lambda p, b: forward(student, p, b, remat=False))(
            stacked, batch)
        onehot = jax.nn.one_hot(proto_labels(student, batch), ncls,
                                dtype=jnp.float32)
        sums = sums + jnp.einsum("nbc,nbp->ncp", onehot, out.f1) \
            * v[:, None, None]
        counts = counts + jnp.sum(onehot, axis=1) * v[:, None]

    np.testing.assert_array_equal(np.asarray(got_counts), np.asarray(counts))
    np.testing.assert_array_equal(np.asarray(got_sums), np.asarray(sums))
    np.testing.assert_array_equal(
        np.asarray(normalize_protos(got_sums, got_counts)),
        np.asarray(sums / jnp.maximum(counts, 1.0)[..., None]))


def test_compute_local_prototypes_scan_matches_host_loop(mnist_like):
    """The loop engine's scanned Eq. 3 pass == a host loop of the
    historical per-batch einsum, bit for bit (uniform batch stream)."""
    cfg, node_data, _ = mnist_like
    student = derive_student(cfg)
    ncls = cfg.num_classes
    params = init_params(student, jax.random.PRNGKey(3))

    got_p, got_c = profe.compute_local_prototypes(
        student, params, batches(node_data[0], 64, seed=5), ncls)

    sums = jnp.zeros((ncls, student.proto_dim), jnp.float32)
    counts = jnp.zeros((ncls,), jnp.float32)
    for b in batches(node_data[0], 64, seed=5):
        out = forward(student, params, b, remat=False)
        s_add, c_add = proto_accum_ref(out.f1, proto_labels(student, b),
                                       ncls)
        sums, counts = sums + s_add, counts + c_add

    np.testing.assert_array_equal(np.asarray(got_c), np.asarray(counts))
    np.testing.assert_array_equal(np.asarray(got_p),
                                  np.asarray(normalize_protos(sums, counts)))


# ---------------------------------------------------------------------------
# fused mode: same learning, same wire, bounded tracing
# ---------------------------------------------------------------------------

def test_fused_matches_exact_final_f1(mnist_like):
    """The fused single-pass round must land within a small tolerance of
    the exact two-pass round — the accuracy cost of prototypes built
    from the evolving (pre-final) student — with IDENTICAL wire bytes
    (the payload skeleton does not change)."""
    cfg, node_data, test_d = mnist_like
    res = {}
    for pp in ("exact", "fused"):
        fed = FederationConfig(num_nodes=N_NODES, rounds=2, local_epochs=1,
                               algorithm="profe", proto_pass=pp)
        res[pp] = run_federation(cfg, fed, TRAIN, node_data, test_d)
        assert res[pp].extras["proto_pass"] == pp
    assert res["fused"].extras["avg_sent_gb"] == \
        res["exact"].extras["avg_sent_gb"]
    assert abs(res["fused"].f1_per_round[-1]
               - res["exact"].f1_per_round[-1]) < 0.2


def test_fused_stacked_matches_fused_loop(mnist_like):
    """Both engines implement the SAME fused semantics (in-scan Eq. 3
    from the step's own f1) — stacked vs reference loop within
    numerical noise, bytes identical."""
    cfg, node_data, test_d = mnist_like
    fed = FederationConfig(num_nodes=N_NODES, rounds=2, local_epochs=1,
                           algorithm="profe", proto_pass="fused")
    new = run_federation(cfg, fed, TRAIN, node_data, test_d)
    old = run_federation_loop(cfg, fed, TRAIN, node_data, test_d)
    assert new.extras["avg_sent_gb"] == old.extras["avg_sent_gb"]
    np.testing.assert_allclose(new.f1_per_round, old.f1_per_round, atol=0.05)


def test_fused_scan_body_traces_rounds_independent(mnist_like):
    """The fused training scan must not reintroduce per-round
    retracing: the round program's trace count under ``fed.dispatch``
    after a 3-round run equals the count after a 1-round run (rounds <=
    4 keeps ``teacher_on`` static across rounds, so there is exactly one
    program variant)."""
    cfg, node_data, test_d = mnist_like
    counts = {}
    for rounds in (1, 3):
        spans.reset()
        fed = FederationConfig(num_nodes=N_NODES, rounds=rounds,
                               local_epochs=1, algorithm="profe",
                               proto_pass="fused")
        run_federation(cfg, fed, TRAIN, node_data, test_d)
        counts[rounds] = spans.counters()["fed.dispatch.traces"]
    assert counts[1] >= 1 and counts[3] == counts[1], counts


def test_invalid_proto_pass_rejected(mnist_like):
    cfg, node_data, test_d = mnist_like
    fed = FederationConfig(num_nodes=N_NODES, rounds=1, algorithm="profe",
                           proto_pass="bogus")
    with pytest.raises(ValueError, match="proto_pass"):
        run_federation(cfg, fed, TRAIN, node_data, test_d)
    with pytest.raises(ValueError, match="proto_pass"):
        run_federation_loop(cfg, fed, TRAIN, node_data, test_d)


# ---------------------------------------------------------------------------
# batched all-node eval
# ---------------------------------------------------------------------------

def test_batched_eval_matches_per_node_loop(mnist_like):
    """The eval program over every node of a stacked student (one
    vmapped forward) == the program per unstacked node: same per-node
    (f1, acc) to numerical noise, and ``_eval_nodes`` routes through it
    without changing the recorded extras shape."""
    cfg, node_data, test_d = mnist_like
    student = derive_student(cfg)
    stacked = _stacked_students(student, N_NODES)
    per_node = [jax.tree_util.tree_map(lambda x: x[i], stacked)
                for i in range(N_NODES)]

    got = F._eval_params(student, stacked, test_d, node=np.arange(N_NODES))
    want = [F._eval_params(student, p, test_d) for p in per_node]
    for (gf, ga), (wf, wa) in zip(got, want):
        assert abs(gf - wf) < 0.02
        assert abs(ga - wa) < 0.02

    extras_b, extras_l = {}, {}
    f1_b, acc_b = F._eval_nodes(student, stacked, N_NODES, test_d, True,
                                extras_b)
    f1_l, acc_l = F._eval_nodes(student, per_node, N_NODES, test_d, True,
                                extras_l)
    assert abs(f1_b - f1_l) < 0.02 and abs(acc_b - acc_l) < 0.02
    assert len(extras_b["f1_per_round_nodes"][0]) == N_NODES
    np.testing.assert_allclose(extras_b["f1_per_round_nodes"],
                               extras_l["f1_per_round_nodes"], atol=0.02)


# ---------------------------------------------------------------------------
# stale-mixing self-weight floor
# ---------------------------------------------------------------------------

def test_apply_self_floor_rows_stay_stochastic():
    """Floored gossip stays row-stochastic: self >= floor wherever the
    node has neighbors, neighbor mass rescaled to 1 - self, isolated
    nodes untouched."""
    n = 5
    adj = T.adjacency(n, "full")
    sizes = [10, 20, 30, 40, 50]
    w_self, w_neigh = F.R.gossip_matrix(adj, sizes)
    w_self_st = jnp.stack([w_self, w_self])             # [R=2, N]
    w_neigh_st = jnp.stack([w_neigh, w_neigh])
    fs, fn_ = F._apply_self_floor(w_self_st, w_neigh_st, 0.5)
    fs, fn_ = np.asarray(fs), np.asarray(fn_)
    assert np.all(fs >= 0.5 - 1e-6)
    np.testing.assert_allclose(fs + fn_.sum(-1), np.ones((2, n)), rtol=1e-5)
    # neighbor weight RATIOS are preserved (pure rescale)
    w_n = np.asarray(w_neigh)
    ratio = fn_[0, 0, 1:] / w_n[0, 1:]
    np.testing.assert_allclose(ratio, ratio[0] * np.ones(n - 1), rtol=1e-5)
    # a node whose self-weight already clears the floor is also floored
    # only up to max(): floor below every self-weight is a no-op
    gs, gn = F._apply_self_floor(w_self_st, w_neigh_st, 1e-6)
    np.testing.assert_allclose(np.asarray(gs), np.asarray(w_self_st),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(gn), np.asarray(w_neigh_st),
                               rtol=1e-6)


def test_apply_self_floor_isolated_nodes_unchanged():
    """A node with no neighbors holds self-weight 1 (nothing to mix) —
    the floor must pass it through and keep its neighbor row zero."""
    w_self_st = jnp.asarray([[0.2, 1.0]], jnp.float32)
    w_neigh_st = jnp.asarray([[[0.0, 0.8], [0.0, 0.0]]], jnp.float32)
    fs, fn_ = F._apply_self_floor(w_self_st, w_neigh_st, 0.6)
    np.testing.assert_allclose(np.asarray(fs), [[0.6, 1.0]], rtol=1e-6)
    np.testing.assert_allclose(np.asarray(fn_),
                               [[[0.0, 0.4], [0.0, 0.0]]], rtol=1e-6)


def test_apply_self_floor_validates_range():
    w = jnp.ones((1, 2)) * 0.5
    wn = jnp.zeros((1, 2, 2))
    for bad in (0.0, 1.0, -0.3, 2.0):
        with pytest.raises(ValueError, match="stale_self_floor"):
            F._apply_self_floor(w, wn, bad)


def test_stale_floor_requires_rounds_overlap(mnist_like):
    cfg, node_data, test_d = mnist_like
    fed = FederationConfig(num_nodes=N_NODES, rounds=1, algorithm="profe")
    for ov in (None, "none"):
        with pytest.raises(ValueError, match="stale_self_floor"):
            run_federation(cfg, fed, TRAIN, node_data, test_d,
                           overlap=ov, stale_self_floor=0.5)


def test_stale_floor_run_learns(mnist_like):
    """overlap='rounds' with the floor on the dense full graph must
    produce a non-degenerate learner (macro-F1 chance level for 10
    classes is ~0.02) and record the knob in extras."""
    cfg, node_data, test_d = mnist_like
    fed = FederationConfig(num_nodes=N_NODES, rounds=2, local_epochs=1,
                           algorithm="profe")
    res = run_federation(cfg, fed, TRAIN, node_data, test_d,
                         overlap="rounds", stale_self_floor=0.5)
    assert res.extras["stale_self_floor"] == 0.5
    assert res.f1_per_round[-1] > 0.1


# ---------------------------------------------------------------------------
# mesh engine: the fused-round adapter
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("exchange", ["gather", "packed"])
def test_mesh_fused_round_matches_exact_given_normalized(exchange):
    """``make_profe_round(..., proto_pass='fused')`` takes RAW Eq. 3
    sums and must equal the exact round fed the normalized prototypes —
    the adapter IS ``normalize_protos`` and nothing else."""
    from jax.sharding import PartitionSpec as P
    from repro.core.mesh_federation import make_profe_round
    from repro.launch.wire import fed_mesh
    n, c, p = 4, 5, 16
    mesh = fed_mesh(1)
    specs = {"w": P(None, None), "b": P(None,)}
    students = {
        "w": jnp.asarray(RNG.standard_normal((n, 33, 20)), jnp.float32),
        "b": jnp.asarray(RNG.standard_normal((n, 7)), jnp.float32)}
    counts = jnp.asarray(RNG.integers(0, 4, (n, c)), jnp.float32)
    sums = jnp.asarray(RNG.standard_normal((n, c, p)), jnp.float32) \
        * counts[..., None]
    sizes = jnp.asarray([1.0, 2.0, 3.0, 4.0])
    adj = T.adjacency(n, "ring")

    exact = make_profe_round(mesh, specs, bits=16, adjacency=adj,
                             exchange=exchange)
    fused = make_profe_round(mesh, specs, bits=16, adjacency=adj,
                             exchange=exchange, proto_pass="fused")
    with mesh:
        want = jax.jit(exact)(students, normalize_protos(sums, counts),
                              counts, sizes)
        got = jax.jit(fused)(students, sums, counts, sizes)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_mesh_rejects_unknown_proto_pass():
    from jax.sharding import PartitionSpec as P
    from repro.core.mesh_federation import make_profe_round
    from repro.launch.wire import fed_mesh
    with pytest.raises(ValueError, match="proto_pass"):
        make_profe_round(fed_mesh(1), {"w": P(None,)}, bits=16,
                         proto_pass="bogus")
