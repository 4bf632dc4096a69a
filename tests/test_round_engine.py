"""The vectorized stacked-node-state round engine vs the per-node
reference loop: same comm bytes exactly, same learning to numerical
noise — plus the round_ops contract and the no-retrace guarantee of the
hoisted prototype accumulator."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import FederationConfig, TrainConfig, get_config
from repro.core import profe
from repro.core import round_ops as R
from repro.core import topology as T
from repro.core.aggregation import weighted_tree_mean
from repro.core.federation import run_federation, run_federation_loop
from repro.core.prototypes import aggregate_prototypes
from repro.core.quantization import quantize_dequantize_tree
from repro.data import batches, make_image_dataset, partition, train_test_split

RNG = np.random.default_rng(11)
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


# ---------------------------------------------------------------------------
# stacked engine == reference loop
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("algo", ["profe", "fedavg"])
def test_stacked_round_matches_reference_loop(mnist_like, algo):
    cfg, node_data, test_d = mnist_like
    fed = FederationConfig(num_nodes=N_NODES, rounds=2, local_epochs=1,
                           algorithm=algo)
    new = run_federation(cfg, fed, TRAIN, node_data, test_d)
    old = run_federation_loop(cfg, fed, TRAIN, node_data, test_d)
    # byte accounting must be *identical* (same payloads, same topology)
    assert new.extras["avg_sent_gb"] == old.extras["avg_sent_gb"]
    assert new.extras["avg_received_gb"] == old.extras["avg_received_gb"]
    # learning curve within numerical noise (fp32 reassociation only)
    np.testing.assert_allclose(new.f1_per_round, old.f1_per_round, atol=0.05)
    np.testing.assert_allclose(new.acc_per_round, old.acc_per_round,
                               atol=0.05)


@pytest.mark.parametrize("algo,topo", [
    ("profe", "ring"),
    ("fedavg", "star"),
    ("fedavg", "dynamic:ring,star"),
])
def test_stacked_matches_loop_on_sparse_topologies(mnist_like, algo, topo):
    """Ring/star/time-varying gossip: the stacked engine's per-round
    traced gossip matrices must reproduce the reference loop — comm
    bytes byte-identical (vectorized accounting vs per-edge meter on
    fewer edges than full), learning to numerical noise."""
    cfg, node_data, test_d = mnist_like
    fed = FederationConfig(num_nodes=N_NODES, rounds=2, local_epochs=1,
                           algorithm=algo, topology=topo)
    new = run_federation(cfg, fed, TRAIN, node_data, test_d)
    old = run_federation_loop(cfg, fed, TRAIN, node_data, test_d)
    assert new.extras["avg_sent_gb"] == old.extras["avg_sent_gb"]
    assert new.extras["avg_received_gb"] == old.extras["avg_received_gb"]
    assert dict(new.comm.sent) == dict(old.comm.sent)
    assert dict(new.comm.by_round) == dict(old.comm.by_round)
    np.testing.assert_allclose(new.f1_per_round, old.f1_per_round, atol=0.05)
    np.testing.assert_allclose(new.acc_per_round, old.acc_per_round,
                               atol=0.05)


def test_random_k_topology_runs_on_stacked_engine(mnist_like):
    """random-k gossip through the stacked engine: seeded graph, bytes
    match the schedule's edge count exactly."""
    cfg, node_data, test_d = mnist_like
    fed = FederationConfig(num_nodes=N_NODES, rounds=1, local_epochs=1,
                           algorithm="fedavg", topology="random-k2")
    r = run_federation(cfg, fed, TRAIN, node_data, test_d)
    sched = T.make_schedule(N_NODES, "random-k2", rounds=1, seed=fed.seed)
    copies = int(sched.directed_edge_counts()[0])
    total = sum(r.comm.sent.values())
    assert total > 0 and total % copies == 0


def test_ragged_nodes_fall_back_to_loop(mnist_like):
    """A node smaller than one batch can't be stacked; the driver must
    still produce a result (reference-loop fallback)."""
    cfg, node_data, test_d = mnist_like
    ragged = [
        {k: v[:40] for k, v in node_data[0].items()},   # < batch_size
        node_data[1], node_data[2],
    ]
    fed = FederationConfig(num_nodes=N_NODES, rounds=1, algorithm="fedavg")
    r = run_federation(cfg, fed, TRAIN, ragged, test_d)
    assert len(r.f1_per_round) == 1


# ---------------------------------------------------------------------------
# round_ops contract
# ---------------------------------------------------------------------------

def test_gossip_matrix_rows_sum_to_one():
    adj = T.adjacency(5, "ring")
    sizes = [10, 20, 30, 40, 50]
    w_self, w_neigh = R.gossip_matrix(adj, sizes)
    rows = np.asarray(w_self) + np.asarray(w_neigh).sum(axis=1)
    np.testing.assert_allclose(rows, np.ones(5), rtol=1e-6)
    # non-neighbors contribute nothing
    assert float(np.asarray(w_neigh)[0, 2]) == 0.0


def test_mix_node_trees_matches_weighted_tree_mean():
    """The one-einsum mix must equal the per-node reference aggregation
    (own model unquantized + de-quantized neighbor copies)."""
    n, bits = 4, 16
    adj = T.adjacency(n, "full")
    sizes = [100, 200, 300, 400]
    stacked = {"w": jnp.asarray(RNG.standard_normal((n, 7, 5)), jnp.float32),
               "b": jnp.asarray(RNG.standard_normal((n, 11)), jnp.float32)}
    recv = R.quantize_dequantize_per_node(stacked, bits, use_kernels=False)
    w_self, w_neigh = R.gossip_matrix(adj, sizes)
    got = R.mix_node_trees(w_self, w_neigh, stacked, recv)
    for i in range(n):
        own = jax.tree_util.tree_map(lambda x: x[i], stacked)
        neigh = T.neighbors(adj, i)
        rx = [quantize_dequantize_tree(
            jax.tree_util.tree_map(lambda x: x[j], stacked), bits)
            for j in neigh]
        want = weighted_tree_mean([own] + rx,
                                  [sizes[i]] + [sizes[j] for j in neigh])
        for g, w in zip(jax.tree_util.tree_leaves(
                jax.tree_util.tree_map(lambda x: x[i], got)),
                jax.tree_util.tree_leaves(want)):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=1e-5, atol=1e-6)


def test_neighborhood_prototype_aggregate_matches_eq4():
    n, c, p = 4, 6, 8
    adj = T.adjacency(n, "ring")
    protos = jnp.asarray(RNG.standard_normal((n, c, p)), jnp.float32)
    counts = jnp.asarray(RNG.integers(0, 5, (n, c)), jnp.float32)
    include = R.include_matrix(adj)
    gp, mask = R.neighborhood_prototype_aggregate(include, protos, counts)
    for i in range(n):
        sel = np.array(T.neighbors(adj, i) + [i])
        want_gp, want_mask = aggregate_prototypes(protos[sel], counts[sel])
        np.testing.assert_allclose(np.asarray(gp[i]), np.asarray(want_gp),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_array_equal(np.asarray(mask[i]),
                                      np.asarray(want_mask))


def _fp32_contractions():
    """Wire, gossip and merge contractions of every engine, each as
    ``(fn, operands)`` at toy shapes."""
    from repro.core.adapters import factorize_delta
    from repro.core.aggregation import regmean_adjust
    from repro.core.prototypes import local_prototypes
    from repro.kernels.lowrank_apply.ref import lowrank_delta_ref
    from repro.kernels.proto_accum.ref import proto_accum_ref
    from repro.kernels.quantize.ops import mix_packed
    n, c, p = 3, 4, 8
    f = jnp.ones((n, c, p), jnp.float32)
    w, wn = jnp.ones((n,)), jnp.ones((n, n))
    grams = jnp.ones((n, p, p)) + jnp.eye(p)
    labels = jnp.arange(6) % c
    return {
        "mix_node_trees": (R.mix_node_trees, (w, wn, {"a": f}, {"a": f})),
        "weighted_node_mean": (R.weighted_node_mean, (w, {"a": f})),
        "eq4": (R.neighborhood_prototype_aggregate,
                (wn, f, jnp.ones((n, c)))),
        "eq3": (lambda x, y: local_prototypes(x, y, c),
                (jnp.ones((6, p)), labels)),
        "eq3_ref": (lambda x, y: proto_accum_ref(x, y, c),
                    (jnp.ones((6, p)), labels)),
        "mix_packed": (lambda o, q, d, ws, wr: mix_packed(
            o, q, d, ws, wr, use_kernels=False),
            (f, jnp.ones((n, c, p), jnp.int16), jnp.ones((n, c)), w, wn)),
        "sketch": (lambda d: factorize_delta(d, "w", 2), (f[0],)),
        "regmean": (lambda a, g, cf: regmean_adjust(a, g, cf,
                                                    per_recv=False),
                    (jnp.ones((n, 2, p)), grams, wn)),
        "lowrank_delta": (lowrank_delta_ref,
                          (wn, jnp.ones((n, c, 2)), jnp.ones((n, 2, p)))),
    }


@pytest.mark.parametrize("name", sorted(_fp32_contractions()))
def test_merge_contractions_ask_for_fp32(name):
    """Every contraction of the wire, gossip and merge math asks for
    ``Precision.HIGHEST`` (through ``repro.fp32``): at the default
    precision the TPU would round its fp32 operands to bf16, and the
    engines would stop agreeing on the chip."""
    fn, args = _fp32_contractions()[name]
    dots = [ln for ln in jax.jit(fn).lower(*args).as_text().splitlines()
            if "stablehlo.dot_general" in ln]
    assert dots, f"{name} lowers to no dot_general"
    for ln in dots:
        assert "precision = [HIGHEST, HIGHEST]" in ln, ln


def test_per_node_quantization_matches_per_tensor():
    """One scale per node slice == quantizing each node's tensor alone."""
    stacked = jnp.asarray(RNG.standard_normal((3, 17, 9)) * 5, jnp.float32)
    codes, deltas = R.quantize_leaf_per_node(stacked, 16)
    for i in range(3):
        want = quantize_dequantize_tree(stacked[i], 16)
        got = R.dequantize_leaf(codes, deltas)[i]
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# packed node wire codec (the physical exchange payload)
# ---------------------------------------------------------------------------

def _payload_tree(n=3):
    return {
        "student": {
            "w": jnp.asarray(RNG.standard_normal((n, 17, 9)) * 5,
                             jnp.float32),
            "b": jnp.asarray(RNG.standard_normal((n, 11)), jnp.float32),
            "deep": [jnp.asarray(RNG.standard_normal((n, 40, 30)),
                                 jnp.float32)],
            "step": jnp.ones((n,), jnp.int32),
        },
        "protos": jnp.asarray(RNG.standard_normal((n, 6, 8)), jnp.float32),
    }


@pytest.mark.parametrize("use_kernels", [False, True],
                         ids=["jnp", "pallas-interpret"])
def test_packed_wire_codec_bit_identical_per_leaf(use_kernels):
    """The [N, R, 512] single-buffer wire format round-trips
    bit-identically to quantizing each leaf's node slice alone
    (``quantize_leaf_per_node``/``dequantize_leaf``) — codes, scales,
    and reconstruction, in CPU interpreter mode for the Pallas flavor."""
    from repro.kernels.quantize import ops as q_ops
    tree = _payload_tree()
    payload = q_ops.quantize_tree_packed_nodes(tree, 16,
                                               use_kernels=use_kernels)
    assert payload["codes"].dtype == jnp.int16          # the wire dtype
    back = q_ops.dequantize_tree_packed_nodes(payload)

    seg_of = {}                                          # leaf row-span
    flat, _ = jax.tree_util.tree_flatten(tree)
    packed_items = [it for it in payload["meta"][1] if it[0] == "packed"]
    float_leaves = [x for x in flat
                    if jnp.issubdtype(x.dtype, jnp.floating)]
    assert len(packed_items) == len(float_leaves)
    for leaf, item in zip(float_leaves, packed_items):
        _, shape, _dt, row, nrows, seg = item
        codes_ref, delta_ref = R.quantize_leaf_per_node(leaf, 16)
        # scales: one per (leaf, node), exactly the per-leaf deltas
        np.testing.assert_array_equal(
            np.asarray(payload["scales"][:, seg]), np.asarray(delta_ref))
        # codes: the leaf's rows of the buffer hold the per-leaf codes
        n = shape[0]
        per = int(np.prod(shape[1:]))
        rows = payload["codes"][:, row:row + nrows, :]
        got_codes = rows.reshape(n, -1)[:, :per].reshape(shape)
        np.testing.assert_array_equal(np.asarray(got_codes),
                                      np.asarray(codes_ref.astype(jnp.int16)))
    # reconstruction == per-leaf dequantize, bit for bit
    want = jax.tree_util.tree_map(
        lambda x: R.dequantize_leaf(*R.quantize_leaf_per_node(x, 16))
        if jnp.issubdtype(x.dtype, jnp.floating) else x, tree)
    for g, w in zip(jax.tree_util.tree_leaves(back),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_quantize_dequantize_per_node_packed_routing():
    """The simulator's receiver-side reconstruction consumes the packed
    codec by default and stays bit-identical to the per-leaf path."""
    tree = _payload_tree()
    got = R.quantize_dequantize_per_node(tree, 16, use_kernels=False)
    want = R.quantize_dequantize_per_node(tree, 16, use_kernels=False,
                                          packed=False)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_mix_packed_kernel_matches_mix_node_trees():
    """Fused dequant-and-accumulate on packed codes (Pallas, interpret
    mode on CPU) == qdq + ``mix_node_trees`` reference."""
    from repro.kernels.quantize import ops as q_ops
    n = 4
    tree = {"w": jnp.asarray(RNG.standard_normal((n, 23, 12)), jnp.float32),
            "b": jnp.asarray(RNG.standard_normal((n, 5)), jnp.float32)}
    sizes = [10.0, 20.0, 30.0, 40.0]
    adj = T.adjacency(n, "ring")
    w_self, w_neigh = R.gossip_matrix(adj, sizes)
    buf, seg_ids, meta = q_ops.pack_tree_nodes(tree)
    codes, scales = q_ops.quantize_packed_buffer(buf, seg_ids, meta[2], 16,
                                                 use_kernels=False)
    row_delta = scales[:, seg_ids]
    for uk in (False, True):
        mixed = q_ops.mix_packed(buf, codes, row_delta, w_self, w_neigh,
                                 use_kernels=uk)
        got = q_ops.unpack_tree_nodes(mixed, meta)
        recv = R.quantize_dequantize_per_node(tree, 16, use_kernels=False)
        want = R.mix_node_trees(w_self, w_neigh, tree, recv)
        for k in tree:
            np.testing.assert_allclose(np.asarray(got[k]),
                                       np.asarray(want[k]),
                                       rtol=1e-5, atol=1e-6)
    # fp32 "codes" (the FedAvg baseline permutes raw model buffers with
    # unit deltas): the kernel must NOT round-trip them through int
    ones = jnp.ones(buf.shape[:2], jnp.float32)
    raw_jnp = q_ops.mix_packed(buf, buf, ones, w_self, w_neigh,
                               use_kernels=False)
    raw_pal = q_ops.mix_packed(buf, buf, ones, w_self, w_neigh,
                               use_kernels=True)
    np.testing.assert_allclose(np.asarray(raw_pal), np.asarray(raw_jnp),
                               rtol=1e-6, atol=1e-7)


def test_packed_copy_bytes_matches_kernel_layout():
    """comm's analytic packed-codec bytes == the kernels' buffer layout
    (+ the raw fp32 counts side channel)."""
    from repro.core.comm import packed_copy_bytes
    from repro.kernels.quantize import ops as q_ops
    tree = _payload_tree(1)
    payload = {
        "model": jax.tree_util.tree_map(lambda x: x[0], tree["student"]),
        "protos": tree["protos"][0],
        "counts": jnp.ones((6,), jnp.float32),
    }
    want = q_ops.packed_wire_bytes_per_node(
        {"protos": tree["protos"], "student": tree["student"]},
        16) + 6 * 4
    # the int32 "step" leaf rides raw in both accountings
    want += 1 * 4
    assert packed_copy_bytes(payload, 16) == want


# ---------------------------------------------------------------------------
# mesh exchange equivalence: ppermute ring == masked all-gather
# ---------------------------------------------------------------------------

def _mesh_round_fixtures(n):
    from jax.sharding import PartitionSpec as P
    from repro.launch.wire import fed_mesh
    mesh = fed_mesh(n)
    students = {
        "w": jnp.asarray(RNG.standard_normal((n, 33, 20)), jnp.float32),
        "b": jnp.asarray(RNG.standard_normal((n, 7)), jnp.float32)}
    specs = {"w": P(None, None), "b": P(None,)}
    C, Pd = 5, 16
    protos = jnp.asarray(RNG.standard_normal((n, C, Pd)), jnp.float32)
    counts = jnp.asarray(RNG.integers(0, 4, (n, C)), jnp.float32)
    sizes = jnp.asarray(RNG.integers(50, 200, (n,)), jnp.float32)
    return mesh, students, specs, protos, counts, sizes


@pytest.mark.mesh
@pytest.mark.parametrize("topo", ["ring", "random-k2"])
def test_ppermute_round_matches_masked_gather(topo):
    """Physical sparse gossip == the masked all-gather reference:
    students exact-mix (same quantized codes, different summation order
    only), prototypes Eq. 4, on a one-device-per-node federation mesh."""
    n = 8
    if jax.device_count() < n:
        pytest.skip(f"needs {n} devices, have {jax.device_count()}")
    from repro.core.mesh_federation import make_profe_round
    mesh, students, specs, protos, counts, sizes = _mesh_round_fixtures(n)
    adj = T.make_schedule(n, topo, seed=0).adjacency_at(0)

    outs = {}
    for ex in ("gather", "ppermute"):
        fn = make_profe_round(mesh, specs, bits=16, adjacency=adj,
                              exchange=ex)
        with mesh:
            outs[ex] = jax.jit(fn)(students, protos, counts, sizes)
    s_ref, g_ref, m_ref = outs["gather"]
    s, g, m = outs["ppermute"]
    for k in s_ref:
        np.testing.assert_allclose(np.asarray(s[k]), np.asarray(s_ref[k]),
                                   rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(m), np.asarray(m_ref))
    # sparse gossip keeps nodes distinct
    assert float(jnp.max(jnp.abs(s["w"][1] - s["w"][4]))) > 0


@pytest.mark.mesh
def test_ppermute_ring_moves_degree_not_n_bytes():
    """The compiled ring round's pod-axis bytes are EXACTLY the
    accountant's packed-codec prediction (degree x payload) and well
    under the full-graph all-gather exchange."""
    n = 8
    if jax.device_count() < n:
        pytest.skip(f"needs {n} devices, have {jax.device_count()}")
    from repro.core.comm import ScheduleCommAccountant
    from repro.core.mesh_federation import make_profe_round
    from repro.launch.hlo_analysis import analyze_hlo
    mesh, students, specs, protos, counts, sizes = _mesh_round_fixtures(n)
    sched = T.make_schedule(n, "ring", seed=0)
    adj = sched.adjacency_at(0)

    def lower_coll(adjacency, exchange):
        fn = make_profe_round(mesh, specs, bits=16, adjacency=adjacency,
                              exchange=exchange)
        with mesh:
            hlo = jax.jit(fn).lower(students, protos, counts,
                                    sizes).compile().as_text()
        return analyze_hlo(hlo)

    ring = lower_coll(adj, "ppermute")
    full_bytes = lower_coll(None, "packed").coll_total
    payload = {
        "model": jax.tree_util.tree_map(lambda x: x[0], students),
        "protos": protos[0], "counts": counts[0]}
    pred = ScheduleCommAccountant(sched).predicted_node_bytes(
        payload, 0, 16, wire="packed")
    # the payload permutes are EXACTLY degree x packed payload (the
    # remaining collectives are the tiny [N] sizes gather)
    assert ring.coll.get("collective-permute") == pred.max(), \
        (ring.coll, pred)
    assert ring.coll_total < 0.5 * full_bytes


@pytest.mark.parametrize("adjacency", [None, "ring"])
def test_packed_gather_round_matches_per_leaf_gather(adjacency):
    """exchange='packed' (single-buffer all-gather + fused mix) ==
    exchange='gather' (per-leaf reference) on a 1-device mesh."""
    from jax.sharding import PartitionSpec as P
    from repro.core.mesh_federation import make_fedavg_round, make_profe_round
    from repro.launch.wire import fed_mesh
    n = 4
    # node-stacked state on a 1x1x1 mesh: GSPMD shards trivially
    mesh = fed_mesh(1)
    specs = {"w": P(None, None), "b": P(None,)}
    students = {
        "w": jnp.asarray(RNG.standard_normal((n, 33, 20)), jnp.float32),
        "b": jnp.asarray(RNG.standard_normal((n, 7)), jnp.float32)}
    protos = jnp.asarray(RNG.standard_normal((n, 5, 16)), jnp.float32)
    counts = jnp.asarray(RNG.integers(0, 4, (n, 5)), jnp.float32)
    sizes = jnp.asarray([1.0, 2.0, 3.0, 4.0])
    adj = None if adjacency is None else T.adjacency(n, adjacency)

    outs = {}
    for ex in ("gather", "packed"):
        fn = make_profe_round(mesh, specs, bits=16, adjacency=adj,
                              exchange=ex)
        with mesh:
            outs[ex] = jax.jit(fn)(students, protos, counts, sizes)
    for got, want in zip(jax.tree_util.tree_leaves(outs["packed"]),
                         jax.tree_util.tree_leaves(outs["gather"])):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=2e-4)

    fa = {}
    for ex in ("gather", "packed"):
        fn = make_fedavg_round(mesh, specs, adjacency=adj, exchange=ex)
        with mesh:
            fa[ex] = jax.jit(fn)(students, sizes)
    for got, want in zip(jax.tree_util.tree_leaves(fa["packed"]),
                         jax.tree_util.tree_leaves(fa["gather"])):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# hoisted prototype accumulator: traces once, not once per round × node
# ---------------------------------------------------------------------------

def test_proto_accumulator_traces_once(mnist_like):
    cfg, node_data, _ = mnist_like
    from repro.models import init_params
    ncls = cfg.num_classes
    profe._proto_acc_step.cache_clear()
    profe.PROTO_ACC_TRACES.clear()
    for trial in range(3):                      # 3 "rounds" × 2 "nodes"
        for node in range(2):
            params = init_params(cfg, jax.random.PRNGKey(trial * 2 + node))
            profe.compute_local_prototypes(
                cfg, params, batches(node_data[node], 64, seed=trial), ncls)
    assert profe.PROTO_ACC_TRACES[(cfg.name, ncls)] == 1, \
        profe.PROTO_ACC_TRACES


# ---------------------------------------------------------------------------
# packed codec: CPU fast path (layout elided) == buffer path
# ---------------------------------------------------------------------------

def test_packed_codec_elide_layout_bit_identity():
    """The leaf-local fake-quant fast path (``elide_layout=True``, the
    CPU default) == the full pack -> quantize -> unpack buffer path,
    bit for bit — stateless, mixed-precision, and error-feedback
    flavors.  The buffer path stays the wire truth (it IS what the
    mesh exchange encodes); the elided path is how simulator receivers
    compute the identical reconstruction without the layout copies."""
    from repro.core.wire_state import init_codec_state
    from repro.kernels.quantize import ops as q_ops
    from repro.wirespec import WireSpec
    tree = _payload_tree()

    def both(spec, **kw):
        return [q_ops.quantize_dequantize_tree_packed_nodes(
            tree, spec=spec, use_kernels=False, elide_layout=el, **kw)
            for el in (True, False)]

    for bits in ("16", "8", "4", "4/16"):
        el, buf = both(WireSpec.parse(bits))
        for g, w in zip(jax.tree_util.tree_leaves(el),
                        jax.tree_util.tree_leaves(buf)):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    # EF: reconstruction AND the carried residual
    st = init_codec_state(tree)
    el, buf = both(WireSpec.parse("4+ef"), residual=st.residual)
    for g, w in zip(jax.tree_util.tree_leaves(el),
                    jax.tree_util.tree_leaves(buf)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# ---------------------------------------------------------------------------
# pipelined mesh exchange (overlap=) + row-sharded multi-axis pods
# ---------------------------------------------------------------------------

def _pod_mesh(n, d):
    from jax.sharding import Mesh
    devs = np.asarray(jax.devices()[:n * d]).reshape(n, d, 1)
    return Mesh(devs, ("pod", "data", "model"))


@pytest.mark.mesh
@pytest.mark.parametrize("ex", ["gather", "packed", "ppermute"])
def test_mesh_overlap_matches_sequential(ex):
    """``overlap=True`` double-buffers the permute steps (step s+1
    issued while step s's fused mix runs) — same result as the
    sequential schedule.  gather/packed have no step loop; the knob is
    a no-op there and the outputs are bit-identical."""
    n = 8
    if jax.device_count() < n:
        pytest.skip(f"needs {n} devices, have {jax.device_count()}")
    from repro.core.mesh_federation import make_profe_round
    mesh, students, specs, protos, counts, sizes = _mesh_round_fixtures(n)
    adj = T.make_schedule(n, "ring", seed=0).adjacency_at(0)
    outs = {}
    for ov in (False, True):
        fn = make_profe_round(mesh, specs, bits=16, adjacency=adj,
                              exchange=ex, overlap=ov)
        with mesh:
            outs[ov] = jax.jit(fn)(students, protos, counts, sizes)
    for got, want in zip(jax.tree_util.tree_leaves(outs[True]),
                         jax.tree_util.tree_leaves(outs[False])):
        if ex == "ppermute":
            # the double-buffered accumulate reassociates the neighbor
            # sum — fp32 noise only
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=1e-5, atol=1e-5)
        else:
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.mesh
def test_row_sharded_permute_matches_gather():
    """(4,2,1) pod mesh: each inner device permutes only its row block
    of the encoded wire buffer — same round outputs as the per-leaf
    gather reference, overlap on or off."""
    n, d = 4, 2
    if jax.device_count() < n * d:
        pytest.skip(f"needs {n * d} devices, have {jax.device_count()}")
    from repro.core.mesh_federation import make_profe_round
    _, students, specs, protos, counts, sizes = _mesh_round_fixtures(n)
    mesh = _pod_mesh(n, d)
    adj = T.make_schedule(n, "ring", seed=0).adjacency_at(0)
    outs = {}
    for tag, kw in (("gather", dict(exchange="gather")),
                    ("sharded", dict(exchange="ppermute")),
                    ("sharded+ovl", dict(exchange="ppermute",
                                         overlap=True))):
        fn = make_profe_round(mesh, specs, bits=16, adjacency=adj, **kw)
        with mesh:
            outs[tag] = jax.jit(fn)(students, protos, counts, sizes)
    for got, want in zip(jax.tree_util.tree_leaves(outs["sharded"]),
                         jax.tree_util.tree_leaves(outs["gather"])):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-4)
    for got, want in zip(jax.tree_util.tree_leaves(outs["sharded+ovl"]),
                         jax.tree_util.tree_leaves(outs["sharded"])):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.mesh
def test_row_sharded_mixed_spec_pads_non_splittable():
    """Mixed 4/16 on a (4,2,1) pod mesh: a payload whose width groups
    split over the 2 inner devices runs row-sharded and matches the
    packed gather; a payload whose groups DON'T split rides appended
    all-zero pad rows — explicit ``exchange='ppermute'`` no longer
    raises, ``auto`` takes the same row-sharded permute, and both match
    the packed reference."""
    n, d = 4, 2
    if jax.device_count() < n * d:
        pytest.skip(f"needs {n * d} devices, have {jax.device_count()}")
    from repro.core.mesh_federation import make_profe_round
    from repro.wirespec import WireSpec
    _, students, specs, _protos, _counts, sizes = _mesh_round_fixtures(n)
    mesh = _pod_mesh(n, d)
    adj = T.make_schedule(n, "ring", seed=0).adjacency_at(0)
    wire = WireSpec.parse("4/16")

    # splittable: protos [n, 8, 128] -> 2 int16 rows; student rows pad
    # to a multiple of 8 -> both groups divide M=2
    protos_b = jnp.asarray(RNG.standard_normal((n, 8, 128)), jnp.float32)
    counts_b = jnp.asarray(RNG.integers(0, 4, (n, 8)), jnp.float32)
    outs = {}
    for ex in ("packed", "ppermute"):
        fn = make_profe_round(mesh, specs, adjacency=adj, spec=wire,
                              exchange=ex)
        with mesh:
            outs[ex] = jax.jit(fn)(students, protos_b, counts_b, sizes)
    for got, want in zip(jax.tree_util.tree_leaves(outs["ppermute"]),
                         jax.tree_util.tree_leaves(outs["packed"])):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-3)

    # non-splittable: protos [n, 5, 16] -> 3 int16 rows (odd) — the
    # int16 group pads with one zero row per row_shard_order
    protos_s = jnp.asarray(RNG.standard_normal((n, 5, 16)), jnp.float32)
    counts_s = jnp.asarray(RNG.integers(0, 4, (n, 5)), jnp.float32)
    outs = {}
    for ex in ("auto", "ppermute", "packed"):
        fn = make_profe_round(mesh, specs, adjacency=adj, spec=wire,
                              exchange=ex)
        with mesh:
            outs[ex] = jax.jit(fn)(students, protos_s, counts_s, sizes)
    for got, want in zip(jax.tree_util.tree_leaves(outs["ppermute"]),
                         jax.tree_util.tree_leaves(outs["packed"])):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-3)
    # auto resolves to the same row-sharded permute program
    for got, want in zip(jax.tree_util.tree_leaves(outs["auto"]),
                         jax.tree_util.tree_leaves(outs["ppermute"])):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
