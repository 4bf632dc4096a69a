"""The stateful wire codec: error-feedback residuals through the packed
kernels (bit-identical to the per-leaf reference), zero wire-byte
overhead, checkpoint round-trips, both round engines, and the mesh
exchange."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import FederationConfig, TrainConfig, get_config
from repro.core import federation as F
from repro.core import round_ops as R
from repro.core import topology as T
from repro.core.comm import ScheduleCommAccountant, packed_copy_bytes
from repro.core.quantization import tree_wire_bytes
from repro.core.wire_state import (CodecState, ef_quantize_dequantize_tree,
                                   init_codec_state)
from repro.kernels.quantize import ops as q_ops
from repro.wirespec import WireSpec

RNG = np.random.default_rng(21)

EF4 = WireSpec.parse("4+ef")
EF_MIXED = WireSpec(student_bits=4, proto_bits=16, error_feedback=True)


def _payload(n=3):
    return {
        "protos": jnp.asarray(RNG.standard_normal((n, 6, 8)), jnp.float32),
        "student": {
            "w": jnp.asarray(RNG.standard_normal((n, 17, 9)) * 5,
                             jnp.float32),
            "b": jnp.asarray(RNG.standard_normal((n, 11)), jnp.float32),
            "step": jnp.ones((n,), jnp.int32),
        },
    }


def _leaves(tree):
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(tree)]


def _assert_trees_equal(a, b):
    for x, y in zip(_leaves(a), _leaves(b)):
        np.testing.assert_array_equal(x, y)


# ---------------------------------------------------------------------------
# spec + state plumbing
# ---------------------------------------------------------------------------

def test_wirespec_ef_parsing_and_stateless_twin():
    assert EF4.error_feedback and EF4.describe() == "int4+ef"
    assert EF4.stateless() == WireSpec.from_bits(4)
    assert WireSpec.parse("4/16+ef").describe() == \
        "student=int4,protos=int16+ef"
    assert not WireSpec.parse("4").error_feedback
    with pytest.raises(ValueError, match="ef_decay"):
        WireSpec(student_bits=4, error_feedback=True, ef_decay=1.5)


def test_init_codec_state_mirrors_float_leaves():
    tree = _payload()
    st = init_codec_state(tree)
    res = jax.tree_util.tree_leaves(st.residual)
    floats = [x for x in jax.tree_util.tree_leaves(tree)
              if jnp.issubdtype(x.dtype, jnp.floating)]
    assert len(res) == len(floats)          # the int leaf holds no residual
    for r, x in zip(res, floats):
        assert r.shape == x.shape and r.dtype == jnp.float32
        assert float(jnp.abs(r).max()) == 0.0


def test_ef_spec_requires_state():
    tree = _payload()
    with pytest.raises(ValueError, match="CodecState"):
        R.quantize_dequantize_per_node(tree, spec=EF4, use_kernels=False)
    with pytest.raises(ValueError, match="residual"):
        q_ops.quantize_tree_packed_nodes(tree, spec=EF4, use_kernels=False)


# ---------------------------------------------------------------------------
# codec-flavor bit identity (jitted: all flavors share the compiled
# residual arithmetic — XLA contracts the update's mul-subtract)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("spec", [EF4, EF_MIXED],
                         ids=lambda s: s.describe())
def test_ef_packed_bit_identical_per_leaf_reference(spec):
    """Five EF rounds through the packed codec (jnp AND Pallas-interpret
    flavors) == the per-leaf reference, bit for bit — reconstruction
    and carried residual alike."""
    tree = _payload()
    st0 = init_codec_state(tree)
    fns = {
        "jnp": jax.jit(lambda t, s: R.quantize_dequantize_per_node(
            t, spec=spec, use_kernels=False, state=s)),
        "pallas": jax.jit(lambda t, s: R.quantize_dequantize_per_node(
            t, spec=spec, use_kernels=True, state=s)),
        "per-leaf": jax.jit(lambda t, s: R.quantize_dequantize_per_node(
            t, spec=spec, packed=False, state=s)),
    }
    views = {}
    for name, fn in fns.items():
        s = st0
        outs = []
        for _ in range(5):
            o, s = fn(tree, s)
            outs.append(o)
        views[name] = (outs, s)
    ref_outs, ref_state = views["jnp"]
    for name in ("pallas", "per-leaf"):
        outs, state = views[name]
        for o, ro in zip(outs, ref_outs):
            _assert_trees_equal(o, ro)
        _assert_trees_equal(state, ref_state)
    # the residual is real state: non-zero after round 1 at int4
    assert max(float(np.abs(x).max())
               for x in _leaves(ref_state.residual)) > 0


def test_ef_seq_pins_residual_to_payload_across_rounds():
    """The sequence number witnesses payload/residual pairing across 5
    carried rounds: after quantizing payload ``t`` (0-based) the state
    holds ``seq == t + 1``, and the carried residual corrects exactly
    the payload it was quantized against — the invariant the stale-by-
    one pipeline (``overlap='rounds'``) leans on when round ``t``'s
    wire view is mixed while round ``t+1`` trains.  Verified against an
    independently recomputed per-leaf recursion, bit for bit."""
    payloads = [_payload() for _ in range(5)]
    fn = jax.jit(lambda t, s: R.quantize_dequantize_per_node(
        t, spec=EF4, use_kernels=False, state=s))
    ref_fn = jax.jit(lambda t, s: ef_quantize_dequantize_tree(
        t, EF4, s, node_axis=True))
    state = init_codec_state(payloads[0])
    assert int(state.seq) == 0
    ref_state = init_codec_state(payloads[0])
    for t, tree in enumerate(payloads):
        recv, state = fn(tree, state)
        assert int(state.seq) == t + 1
        # reference recursion: eff_t = p_t + decay*res_t; res_{t+1} =
        # eff_t - deq_t — res_{t+1} is quantized against payload t, so
        # a receiver holding (recv_t, seq=t+1) knows which stale
        # payload the next correction applies to
        ref_recv, ref_state = ref_fn(tree, ref_state)
        _assert_trees_equal(recv, ref_recv)
        _assert_trees_equal(state.residual, ref_state.residual)
        assert int(ref_state.seq) == t + 1
    # the carried residual is payload-specific: replaying round 4's
    # payload against round 2's residual changes the reconstruction
    _, st2 = fn(payloads[0], init_codec_state(payloads[0]))
    _, st2 = fn(payloads[1], st2)
    wrong, _ = fn(payloads[4], st2)          # seq mismatch: 2 vs 4
    right, _ = fn(payloads[4], CodecState(ref_state.residual,
                                          seq=jnp.int32(4)))
    diffs = [float(np.abs(a - b).max())
             for a, b in zip(_leaves(wrong), _leaves(right))]
    assert max(diffs) > 0


def test_ef_zero_residual_round_matches_stateless():
    """Round 1 (zero residual) reconstructs exactly like the stateless
    spec — EF changes nothing until there is an error to feed back."""
    tree = _payload()
    recv, new_st = R.quantize_dequantize_per_node(
        tree, spec=EF4, use_kernels=False, state=init_codec_state(tree))
    stateless = R.quantize_dequantize_per_node(
        tree, spec=EF4.stateless(), use_kernels=False)
    _assert_trees_equal(recv, stateless)
    # and the new residual is exactly payload - reconstruction
    floats = [x for x in jax.tree_util.tree_leaves(tree)
              if jnp.issubdtype(x.dtype, jnp.floating)]
    recv_floats = [x for x in jax.tree_util.tree_leaves(recv)
                   if jnp.issubdtype(x.dtype, jnp.floating)]
    for r, x, d in zip(_leaves(new_st.residual), floats, recv_floats):
        np.testing.assert_allclose(r, np.asarray(x) - np.asarray(d),
                                   rtol=0, atol=1e-6)


def test_ef_decay_scales_carried_residual():
    tree = _payload()
    st = init_codec_state(tree)
    _, st = R.quantize_dequantize_per_node(tree, spec=EF4,
                                           use_kernels=False, state=st)
    half = WireSpec(student_bits=4, error_feedback=True, ef_decay=0.5)
    got, _ = R.quantize_dequantize_per_node(tree, spec=half,
                                            use_kernels=False, state=st)
    want, _ = R.quantize_dequantize_per_node(
        tree, spec=EF4, use_kernels=False,
        state=CodecState(jax.tree_util.tree_map(lambda r: 0.5 * r,
                                                st.residual)))
    _assert_trees_equal(got, want)


def test_ef_mean_reconstruction_converges_to_input():
    """The point of error feedback: over repeated rounds of the SAME
    payload, the time-average of what receivers see converges to the
    true value, while the stateless int4 wire stays biased."""
    tree = _payload()
    x = np.asarray(tree["student"]["w"])
    fn = jax.jit(lambda t, s: R.quantize_dequantize_per_node(
        t, spec=EF4, use_kernels=False, state=s))
    s = init_codec_state(tree)
    deqs = []
    for _ in range(8):
        out, s = fn(tree, s)
        deqs.append(np.asarray(out["student"]["w"]))
    err_ef = np.abs(np.mean(deqs, axis=0) - x).mean()
    stateless = R.quantize_dequantize_per_node(
        tree, spec=EF4.stateless(), use_kernels=False)
    err_nef = np.abs(np.asarray(stateless["student"]["w"]) - x).mean()
    assert err_ef < 0.35 * err_nef, (err_ef, err_nef)


# ---------------------------------------------------------------------------
# zero wire bytes: every accountant sees the stateless format
# ---------------------------------------------------------------------------

def test_ef_costs_zero_wire_bytes_in_every_accountant():
    tree = _payload()
    payload = {
        "model": jax.tree_util.tree_map(lambda x: x[0], tree["student"]),
        "protos": tree["protos"][0],
        "counts": jnp.ones((6,), jnp.float32),
    }
    for spec in (EF4, EF_MIXED):
        assert packed_copy_bytes(payload, spec) == \
            packed_copy_bytes(payload, spec.stateless())
        assert tree_wire_bytes(payload, spec) == \
            tree_wire_bytes(payload, spec.stateless())
        acct = ScheduleCommAccountant(T.make_schedule(6, "ring"))
        for wire in ("dense", "packed"):
            np.testing.assert_array_equal(
                acct.predicted_node_bytes(payload, 0, spec, wire=wire),
                acct.predicted_node_bytes(payload, 0, spec.stateless(),
                                          wire=wire))
    # the physical byte buffer of the EF payload is the stateless size
    st = init_codec_state(tree)
    p = q_ops.quantize_tree_packed_nodes(tree, spec=EF4, use_kernels=False,
                                         residual=st.residual)
    wire = q_ops.encode_wire(p["codes"], p["seg_ids"],
                             seg_bits=p["seg_bits"])
    assert wire.shape[1] == q_ops.wire_buffer_bytes(
        p["seg_ids"], seg_bits=p["seg_bits"])


# ---------------------------------------------------------------------------
# both round engines + checkpoint round-trip
# ---------------------------------------------------------------------------

N_NODES = 3


@pytest.fixture(scope="module")
def mnist_like():
    cfg = get_config("mnist-cnn")
    from repro.data import make_image_dataset, partition, train_test_split
    data = make_image_dataset(0, 900, cfg.input_hw, cfg.num_classes)
    train_d, test_d = train_test_split(data, 0.1, 0)
    parts = partition(train_d["label"], N_NODES, "iid", 0)
    node_data = [{k: v[i] for k, v in train_d.items()} for i in parts]
    return cfg, node_data, test_d


TRAIN = TrainConfig(batch_size=64, learning_rate=1e-3, optimizer="adamw",
                    remat=False)


def test_stacked_matches_loop_with_error_feedback(mnist_like):
    """EF on, int4 ring: the stacked engine's carried CodecState and the
    loop engine's per-node dicts give the same wire views — comm bytes
    byte-identical (EF adds none), learning to numerical noise."""
    cfg, node_data, test_d = mnist_like
    fed = FederationConfig(num_nodes=N_NODES, rounds=2, local_epochs=1,
                           algorithm="profe", topology="ring",
                           quantize_bits=4, error_feedback=True)
    new = F.run_federation(cfg, fed, TRAIN, node_data, test_d)
    old = F.run_federation_loop(cfg, fed, TRAIN, node_data, test_d)
    assert new.extras["avg_sent_gb"] == old.extras["avg_sent_gb"]
    assert dict(new.comm.sent) == dict(old.comm.sent)
    np.testing.assert_allclose(new.f1_per_round, old.f1_per_round,
                               atol=0.05)
    # EF moved zero extra bytes vs the stateless int4 run
    fed_sl = FederationConfig(num_nodes=N_NODES, rounds=2, local_epochs=1,
                              algorithm="profe", topology="ring",
                              quantize_bits=4)
    sl = F.run_federation(cfg, fed_sl, TRAIN, node_data, test_d)
    assert sl.extras["avg_sent_gb"] == new.extras["avg_sent_gb"]
    assert sl.extras["wire_bytes_packed_per_copy"] == \
        new.extras["wire_bytes_packed_per_copy"]


def _stacked_round_harness(tmp_seed=0, *, adapter_rank=0,
                           adapter_grams=False):
    """A tiny jitted stacked EF round driven by federation internals —
    the checkpoint/resume fixture (optionally on the adapter-rank
    wire, whose reference/gram carry rides ``NodeState.adapter_state``
    and whose EF residual mirrors the factor payload)."""
    from repro.data import make_image_dataset, partition
    from repro.models import derive_student
    from repro.optim import make_optimizer

    n_nodes = 2
    cfg = get_config("mnist-cnn").replace(cnn_channels=(4, 8))
    fed = FederationConfig(num_nodes=n_nodes, rounds=1, local_epochs=1,
                           algorithm="profe", quantize_bits=4,
                           error_feedback=True, seed=tmp_seed,
                           adapter_rank=adapter_rank,
                           adapter_grams=adapter_grams)
    train = TrainConfig(batch_size=8, learning_rate=1e-3,
                        optimizer="adamw", remat=False)
    data = make_image_dataset(0, 32 * n_nodes, cfg.input_hw,
                              cfg.num_classes)
    parts = partition(data["label"], n_nodes, "iid", 0)
    node_data = [{k: v[i] for k, v in data.items()} for i in parts]
    sizes = [len(d["label"]) for d in node_data]

    student_cfg = derive_student(cfg)
    opt = make_optimizer(train.optimizer, train.learning_rate,
                         weight_decay=train.weight_decay,
                         momentum=train.momentum)
    step, wire_model, share_protos, bits, model_cfgs = F._algo_wiring(
        "profe", cfg, student_cfg, fed, train, opt, opt, jit=False)
    assert bits.error_feedback
    ncls = F._n_proto_classes(cfg)
    stacked = F._stack_states(
        F._init_states("profe", model_cfgs, fed, opt, opt, ncls))
    ef_payload = {"protos": jnp.zeros(
        (n_nodes, ncls, student_cfg.proto_dim), jnp.float32)}
    if adapter_rank:
        from repro.core.adapters import (adapter_layout,
                                         init_adapter_state,
                                         zero_wire_payload)
        a_layout = adapter_layout(stacked.student, adapter_rank,
                                  node_axis=True)
        stacked = stacked._replace(adapter_state=init_adapter_state(
            a_layout, stacked.student, grams=adapter_grams))
        # the EF residual mirrors the adapter payload structure
        ef_payload.update(zero_wire_payload(a_layout, stacked.student,
                                            grams=adapter_grams))
    else:
        ef_payload["student"] = stacked.student
    stacked = stacked._replace(
        wire_state=init_codec_state(ef_payload, n_nodes=n_nodes))
    sched = T.make_schedule(n_nodes, fed.topology, rounds=fed.rounds,
                            seed=fed.seed)
    w_self, w_neigh, include = sched.lower(sizes)
    round_fn = F._make_round_fn(step, student_cfg, ncls,
                                share_protos=True, wire_model="student",
                                bits=bits, adapter_rank=adapter_rank,
                                adapter_grams=adapter_grams)

    def run_round(state, rnd):
        xb, valid = F._stack_round_batches(
            node_data, train.batch_size,
            [fed.seed + rnd * 997 + i for i in range(n_nodes)], 1)
        pxb, pvalid = F._stack_round_batches(
            node_data, train.batch_size, [fed.seed + rnd] * n_nodes, 1)
        return round_fn(state, xb, valid, pxb, pvalid,
                        w_self[0], w_neigh[0], include[0],
                        teacher_on=True, all_valid=True)[0]

    return stacked, run_round


def test_codec_state_survives_checkpoint_roundtrip(tmp_path):
    """CodecState residuals ride NodeState through ckpt save/restore
    mid-federation; the resumed run matches the uninterrupted run
    EXACTLY (same jitted program, same state, bit-equal outputs)."""
    from repro.checkpoint import load_checkpoint, save_checkpoint
    state, run_round = _stacked_round_harness()
    for rnd in range(2):
        state = run_round(state, rnd)
    # mid-federation residual is non-trivial at int4
    assert max(float(np.abs(x).max())
               for x in _leaves(state.wire_state.residual)) > 0

    path = os.path.join(tmp_path, "fed_state.npz")
    save_checkpoint(path, state, metadata={"round": 2})
    # the residual leaves actually landed in the checkpoint
    npz = np.load(path)
    n_res = len(jax.tree_util.tree_leaves(state.wire_state.residual))
    assert n_res > 0 and len(npz.files) >= n_res

    restored = load_checkpoint(path, state)
    _assert_trees_equal(restored, state)

    cont = run_round(state, 2)          # uninterrupted
    resumed = run_round(jax.tree_util.tree_map(jnp.asarray, restored), 2)
    _assert_trees_equal(cont, resumed)  # incl. wire_state residuals


def test_adapter_state_survives_checkpoint_roundtrip(tmp_path):
    """The adapter wire's per-node reference snapshot and gram EMA ride
    ``NodeState.adapter_state`` through ckpt save/restore; the resumed
    run matches the uninterrupted run EXACTLY — losing the reference
    would silently re-ship whole-weight deltas next round."""
    from repro.checkpoint import load_checkpoint, save_checkpoint
    state, run_round = _stacked_round_harness(adapter_rank=2,
                                              adapter_grams=True)
    ref0 = [np.asarray(x) for x in _leaves(state.adapter_state["ref"])]
    for rnd in range(2):
        state = run_round(state, rnd)
    # mid-federation carry is non-trivial: the reference advanced to
    # the last shared weights and the gram EMA accumulated
    assert max(float(np.abs(a - b).max())
               for a, b in zip(_leaves(state.adapter_state["ref"]),
                               ref0)) > 0
    assert max(float(np.abs(x).max())
               for x in _leaves(state.adapter_state["grams"])) > 0

    path = os.path.join(tmp_path, "fed_state.npz")
    save_checkpoint(path, state, metadata={"round": 2})
    restored = load_checkpoint(path, state)
    _assert_trees_equal(restored, state)

    cont = run_round(state, 2)          # uninterrupted
    resumed = run_round(jax.tree_util.tree_map(jnp.asarray, restored), 2)
    _assert_trees_equal(cont, resumed)  # incl. adapter refs + grams


# ---------------------------------------------------------------------------
# mesh exchange
# ---------------------------------------------------------------------------

def _mesh_fixtures(n):
    from jax.sharding import PartitionSpec as P
    from repro.launch.wire import fed_mesh
    mesh = fed_mesh(1)
    specs = {"w": P(None, None), "b": P(None,)}
    students = {
        "w": jnp.asarray(RNG.standard_normal((n, 33, 20)), jnp.float32),
        "b": jnp.asarray(RNG.standard_normal((n, 7)), jnp.float32)}
    protos = jnp.asarray(RNG.standard_normal((n, 5, 16)), jnp.float32)
    counts = jnp.asarray(RNG.integers(0, 4, (n, 5)), jnp.float32)
    sizes = jnp.asarray([1.0, 2.0, 3.0, 4.0])
    return mesh, specs, students, protos, counts, sizes


@pytest.mark.parametrize("spec", [EF4, EF_MIXED],
                         ids=lambda s: s.describe())
def test_mesh_round_ef_packed_matches_gather(spec):
    """Stateful codec on the mesh: exchange='packed' == the per-leaf
    gather oracle — round outputs to tolerance, carried residual bit
    for bit — and a second round consumes the returned state."""
    from repro.core.mesh_federation import make_profe_round
    n = 4
    mesh, specs, students, protos, counts, sizes = _mesh_fixtures(n)
    adj = T.adjacency(n, "ring")
    state0 = init_codec_state({"protos": protos, "student": students})
    outs = {}
    for ex in ("gather", "packed"):
        fn = make_profe_round(mesh, specs, adjacency=adj, exchange=ex,
                              spec=spec)
        with mesh:
            outs[ex] = jax.jit(fn)(students, protos, counts, sizes,
                                   state0)
    for got, want in zip(_leaves(outs["packed"][:3]),
                         _leaves(outs["gather"][:3])):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=2e-4)
    # carried residual: equal up to one ulp of the payload element.
    # XLA:CPU contracts the residual's ``x - code*scale`` into an FMA on
    # vector lanes but not on a leaf's scalar remainder lane, so the two
    # programs may round the last element of the 7-wide leaf differently
    # (a code or scale mismatch would differ by a whole quantization step)
    got_st, want_st = outs["packed"][3], outs["gather"][3]
    assert int(got_st.seq) == int(want_st.seq)
    for g, w, x in zip(_leaves(got_st.residual), _leaves(want_st.residual),
                       _leaves({"protos": protos, "student": students})):
        assert np.all(np.abs(g - w) <= np.spacing(np.abs(x)))
    fn = make_profe_round(mesh, specs, adjacency=adj, exchange="packed",
                          spec=spec)
    with mesh:
        out2 = jax.jit(fn)(students, protos, counts, sizes,
                           outs["packed"][3])
    assert max(float(np.abs(x).max())
               for x in _leaves(out2[3].residual)) > 0


@pytest.mark.mesh
def test_ppermute_ef_ring_moves_stateless_bytes_exactly():
    """The compiled int4+ef ring ppermute moves EXACTLY the stateless
    int4 collective bytes AND the accountant's packed prediction — the
    residual is node-local state, never a collective operand."""
    n = 8
    if jax.device_count() < n:
        pytest.skip(f"needs {n} devices, have {jax.device_count()}")
    from jax.sharding import PartitionSpec as P
    from repro.core.mesh_federation import make_profe_round
    from repro.launch.hlo_analysis import analyze_hlo
    from repro.launch.wire import fed_mesh
    mesh = fed_mesh(n)
    specs = {"w": P(None, None), "b": P(None,)}
    students = {
        "w": jnp.asarray(RNG.standard_normal((n, 33, 20)), jnp.float32),
        "b": jnp.asarray(RNG.standard_normal((n, 7)), jnp.float32)}
    protos = jnp.asarray(RNG.standard_normal((n, 5, 16)), jnp.float32)
    counts = jnp.asarray(RNG.integers(0, 4, (n, 5)), jnp.float32)
    sizes = jnp.asarray(RNG.integers(50, 200, (n,)), jnp.float32)
    sched = T.make_schedule(n, "ring", seed=0)
    adj = sched.adjacency_at(0)
    payload = {"model": jax.tree_util.tree_map(lambda x: x[0], students),
               "protos": protos[0], "counts": counts[0]}
    acct = ScheduleCommAccountant(sched)

    colls = {}
    for spec in (EF4, EF4.stateless()):
        fn = make_profe_round(mesh, specs, adjacency=adj,
                              exchange="ppermute", spec=spec)
        args = (students, protos, counts, sizes)
        if spec.error_feedback:
            args += (init_codec_state({"protos": protos,
                                       "student": students}),)
        with mesh:
            hlo = jax.jit(fn).lower(*args).compile().as_text()
        colls[spec.describe()] = analyze_hlo(hlo).coll
    pred = acct.predicted_node_bytes(payload, 0, EF4, wire="packed").max()
    assert colls["int4+ef"].get("collective-permute") == pred
    assert colls["int4+ef"].get("collective-permute") == \
        colls["int4"].get("collective-permute")
