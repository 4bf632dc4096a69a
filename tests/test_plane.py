"""Flat parameter plane: leaf <-> plane round-trips, the fused
clip+update optimizer sweep vs the per-leaf reference (bit-identical),
the zero-repack wire splice, and the plane-backed round engines.

Bit-identity assertions jit BOTH sides: eager and compiled XLA contract
FMAs differently (a 1-ulp drift that is not a defect), so the honest
comparison is jitted-vs-jitted.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import load_checkpoint, save_checkpoint
from repro.config import FederationConfig, TrainConfig, get_config
from repro.core import federation as F
from repro.core.federation import run_federation, run_federation_loop
from repro.data import make_image_dataset, partition, train_test_split
from repro.kernels.opt_update import ops as ou_ops
from repro.kernels.quantize import ops as Q
from repro.optim import clip_by_global_norm, make_optimizer
from repro.optim.plane import (Plane, as_tree, is_plane,
                               make_plane_optimizer, plane_from_tree,
                               plane_global_norm, plane_to_tree)
from repro.wirespec import WireSpec

RNG = np.random.default_rng(7)
N_NODES = 3


def _f32(shape):
    return jnp.asarray(RNG.standard_normal(shape), jnp.float32)


def _odd_float_tree():
    # deliberately not multiples of the 512-column plane lanes
    return {
        "conv": {"w": _f32((3, 3, 1, 5)), "b": _f32((5,))},
        "dense": {"w": _f32((129, 513)), "b": _f32((513,))},
        "odd": _f32((7, 11, 13)),
    }


@pytest.fixture(scope="module")
def mnist_like():
    cfg = get_config("mnist-cnn")
    data = make_image_dataset(0, 1200, cfg.input_hw, cfg.num_classes)
    train_d, test_d = train_test_split(data, 0.1, 0)
    parts = partition(train_d["label"], N_NODES, "iid", 0)
    node_data = [{k: v[i] for k, v in train_d.items()} for i in parts]
    return cfg, node_data, test_d


TRAIN = TrainConfig(batch_size=64, learning_rate=1e-3, optimizer="adamw",
                    remat=False)


# ---------------------------------------------------------------------------
# leaf <-> plane round-trip
# ---------------------------------------------------------------------------

def test_plane_round_trip_preserves_tree():
    tree = dict(_odd_float_tree(),
                step=jnp.asarray(3, jnp.int32),            # non-float -> raw
                half=_f32((17,)).astype(jnp.bfloat16))     # non-f32 float
    plane = plane_from_tree(tree)
    assert is_plane(plane)
    assert plane.buf.dtype == jnp.float32
    assert plane.buf.shape[-1] == 512 and plane.buf.shape[-2] % 8 == 0
    back = plane_to_tree(plane)
    assert (jax.tree_util.tree_structure(back)
            == jax.tree_util.tree_structure(tree))
    for (ka, a), (kb, b) in zip(
            jax.tree_util.tree_flatten_with_path(tree)[0],
            jax.tree_util.tree_flatten_with_path(back)[0]):
        assert ka == kb
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    # as_tree is a no-op on plain trees, a view on planes
    assert as_tree(tree) is tree
    assert float(jnp.max(jnp.abs(as_tree(plane)["odd"] - tree["odd"]))) == 0


def test_plane_global_norm_matches_per_leaf():
    tree = _odd_float_tree()
    plane = plane_from_tree(tree)
    _, want = jax.jit(lambda t: clip_by_global_norm(t, 1.0))(tree)
    got = jax.jit(plane_global_norm)(plane)
    assert float(got) == float(want)


def test_plane_is_a_pytree_that_stacks():
    trees = [_odd_float_tree() for _ in range(3)]
    planes = [plane_from_tree(t) for t in trees]
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *planes)
    assert is_plane(stacked) and stacked.buf.ndim == 3
    views = as_tree(stacked)
    np.testing.assert_array_equal(np.asarray(views["dense"]["w"][1]),
                                  np.asarray(trees[1]["dense"]["w"]))


# ---------------------------------------------------------------------------
# fused clip+update sweep == per-leaf reference, 5 carried steps
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["sgd", "adamw", "adafactor"])
def test_fused_update_bit_identical_to_per_leaf(name):
    tree = _odd_float_tree()
    clip = 0.5
    opt_l = make_optimizer(name, 1e-2, weight_decay=0.01, momentum=0.9)
    opt_p = make_plane_optimizer(name, 1e-2, weight_decay=0.01,
                                 momentum=0.9, grad_clip=clip)

    @jax.jit
    def leaf_step(g, s, p):
        g, _ = clip_by_global_norm(g, clip)
        return opt_l.update(g, s, p)

    plane_step = jax.jit(opt_p.update)
    lp, ls = tree, opt_l.init(tree)
    pp, ps = plane_from_tree(tree), opt_p.init(plane_from_tree(tree))
    for i in range(5):
        g = jax.tree_util.tree_map(lambda x: jnp.sin(x * (i + 1)), tree)
        lp, ls = leaf_step(g, ls, lp)
        pp, ps = plane_step(plane_from_tree(g), ps, pp)
        got = as_tree(pp)
        for path, want in jax.tree_util.tree_flatten_with_path(lp)[0]:
            have = got
            for p_ in path:
                have = have[p_.key]
            np.testing.assert_array_equal(np.asarray(have),
                                          np.asarray(want),
                                          err_msg=f"step {i} {path}")


@pytest.mark.parametrize("name", ["sgd", "adamw"])
def test_pallas_interpret_bit_identical_to_ref(name):
    g, p = _f32((2, 16, 512)), _f32((2, 16, 512))
    mu = _f32((2, 16, 512)) * 0.1
    lr, scale = jnp.float32(1e-2), jnp.float32(0.7)
    if name == "sgd":
        def run(uk):
            return jax.jit(lambda g, p, mu: ou_ops.fused_sgd_update(
                g, p, mu, lr, scale, momentum=0.9, weight_decay=0.01,
                use_kernels=uk))(g, p, mu)
    else:
        nu = jnp.abs(_f32((2, 16, 512))) * 0.01
        bc1, bc2 = jnp.float32(1 - 0.9), jnp.float32(1 - 0.999)

        def run(uk):
            return jax.jit(lambda g, p, mu, nu: ou_ops.fused_adamw_update(
                g, p, mu, nu, lr, scale, bc1, bc2, b1=0.9, b2=0.999,
                eps=1e-8, weight_decay=0.01, use_kernels=uk))(g, p, mu, nu)
    for a, b in zip(run(False), run(True)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fused_update_traces_once():
    tree = _odd_float_tree()
    opt = make_plane_optimizer("adamw", 1e-3, grad_clip=1.0)
    p = plane_from_tree(tree)
    s = opt.init(p)
    g = plane_from_tree(jax.tree_util.tree_map(jnp.sin, tree))
    step = jax.jit(opt.update)
    ou_ops.OPT_UPDATE_TRACES.clear()
    for _ in range(5):
        p, s = step(g, s, p)
    jax.block_until_ready(p.buf)
    assert ou_ops.OPT_UPDATE_TRACES == {"adamw": 1}


def test_make_plane_optimizer_rejects_unknown():
    with pytest.raises(ValueError, match="lion"):
        make_plane_optimizer("lion", 1e-3)


def test_plane_adafactor_state_is_per_segment():
    """Factored second moments live per buffer *segment*: every 2-D+
    leaf with both trailing dims > 1 carries {vr, vc} of the LEAF's
    shape (not the padded rows), everything else a dense {v}."""
    tree = _odd_float_tree()
    opt = make_plane_optimizer("adafactor", 1e-3, grad_clip=1.0)
    p = plane_from_tree(tree)
    s = opt.init(p)
    leaves = [it for it in p.meta.recipe if it[0] == "leaf"]
    assert len(s["fac"]) == len(leaves)
    for (_tag, shape, _dt, _row, _r), v in zip(leaves, s["fac"]):
        if len(shape) >= 2 and shape[-1] > 1 and shape[-2] > 1:
            assert set(v) == {"vr", "vc"}
            assert v["vr"].shape == tuple(shape[:-1])
            assert v["vc"].shape == tuple(shape[:-2] + shape[-1:])
        else:
            assert set(v) == {"v"} and v["v"].shape == tuple(shape)


def test_adafactor_apply_pallas_interpret_matches_ref():
    upd, p = _f32((2, 16, 512)), _f32((2, 16, 512))
    lr = jnp.float32(1e-2)
    a = jax.jit(lambda u, q: ou_ops.adafactor_apply_ref(
        u.reshape(-1, 512), q.reshape(-1, 512), lr=lr,
        weight_decay=0.01))(upd, p)
    b = jax.jit(lambda u, q: ou_ops.adafactor_apply_pallas(
        u.reshape(-1, 512), q.reshape(-1, 512), ou_ops._s11(lr),
        weight_decay=0.01, interpret=True))(upd, p)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_plane_view_tree_grads_match_view_grads():
    """The custom-vjp plane forward must emit the SAME gradients as
    autodiff through the per-leaf views, already packed as one [R, 512]
    buffer with the padding lanes exactly zero."""
    from repro.optim.plane import plane_to_tree, plane_view_tree
    tree = _odd_float_tree()
    plane = plane_from_tree(tree)

    def loss_of(view_fn):
        def loss(pl):
            t = view_fn(pl)
            return sum(jnp.sum(jnp.sin(l) * l)
                       for l in jax.tree_util.tree_leaves(t))
        return loss

    g_vjp = jax.jit(jax.grad(loss_of(plane_view_tree)))(plane)
    g_ref = jax.jit(jax.grad(loss_of(plane_to_tree)))(plane)
    assert is_plane(g_vjp)
    np.testing.assert_array_equal(np.asarray(g_vjp.buf),
                                  np.asarray(g_ref.buf))
    # padding-lane-zero invariant: repacking the views is the identity
    repacked = plane_from_tree(as_tree(g_vjp))
    np.testing.assert_array_equal(np.asarray(g_vjp.buf),
                                  np.asarray(repacked.buf))


# ---------------------------------------------------------------------------
# checkpoint: plane-backed state round-trips and resumes bit-identically
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["adamw", "adafactor"])
def test_checkpoint_resume_matches_uninterrupted(tmp_path, name):
    """Exact resume: the plane-backed optimizer state (incl. adafactor's
    per-segment factored moments) survives the checkpoint round-trip and
    the resumed run equals the uninterrupted one bit for bit."""
    tree = _odd_float_tree()
    opt = make_plane_optimizer(name, 1e-2, grad_clip=1.0)
    step = jax.jit(opt.update)
    g = plane_from_tree(jax.tree_util.tree_map(jnp.sin, tree))
    p, s = plane_from_tree(tree), opt.init(plane_from_tree(tree))
    for _ in range(2):
        p, s = step(g, s, p)
    path = str(tmp_path / "state")
    save_checkpoint(path, {"params": p, "opt": s})
    like = jax.tree_util.tree_map(jnp.zeros_like, {"params": p, "opt": s})
    restored = load_checkpoint(path, like)
    p2, s2 = restored["params"], restored["opt"]
    assert is_plane(p2)
    for a, b in zip(jax.tree_util.tree_leaves(s),
                    jax.tree_util.tree_leaves(s2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for _ in range(3):
        p, s = step(g, s, p)
        p2, s2 = step(g, s2, p2)
    np.testing.assert_array_equal(np.asarray(p.buf), np.asarray(p2.buf))


def test_checkpoint_plane_node_state_round_trips(tmp_path):
    from repro.core.profe import init_node_state
    from repro.models import derive_student
    cfg = get_config("mnist-cnn").replace(cnn_channels=(2, 4))
    student_cfg = derive_student(cfg)
    opt_t = make_optimizer("adamw", 1e-3)
    opt_s = make_plane_optimizer("adamw", 1e-3, grad_clip=1.0)
    st = init_node_state(cfg, student_cfg, jax.random.PRNGKey(0), opt_s,
                         opt_t, cfg.num_classes, plane=True, proto_ema=0.5)
    assert is_plane(st.student)
    path = str(tmp_path / "node")
    save_checkpoint(path, st)
    like = jax.tree_util.tree_map(jnp.zeros_like, st)
    back = load_checkpoint(path, like)
    assert is_plane(back.student)
    np.testing.assert_array_equal(np.asarray(back.student.buf),
                                  np.asarray(st.student.buf))


# ---------------------------------------------------------------------------
# zero-repack wire splice
# ---------------------------------------------------------------------------

def _stacked_payload(n=3, C=5, Pd=16):
    students = {"w": _f32((n, 129, 33)), "b": _f32((n, 7))}
    protos = _f32((n, C, Pd))
    return students, jax.vmap(plane_from_tree)(students), protos


@pytest.mark.parametrize("spec", [None, WireSpec.parse("4/16")])
def test_pack_plane_payload_matches_pack_tree_nodes(spec):
    students, plane, protos = _stacked_payload()
    payload = {"protos": protos, "student": students}
    args = (payload,) if spec is None else (payload, spec)
    b1, i1, m1 = Q.pack_tree_nodes(*args)
    b2, i2, m2, r_p, span = Q.pack_plane_payload(protos, plane, spec)
    np.testing.assert_array_equal(np.asarray(b1), np.asarray(b2))
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    assert m1[0] == m2[0]                       # treedef
    assert m1[1] == m2[1]                       # recipe
    np.testing.assert_array_equal(np.asarray(m1[4]), np.asarray(m2[4]))
    # the splice coordinates really address the student rows
    assert r_p + span <= b2.shape[1]
    back = Q.unpack_tree_nodes(b2, m2)
    np.testing.assert_array_equal(np.asarray(back["protos"]),
                                  np.asarray(protos))
    np.testing.assert_array_equal(np.asarray(back["student"]["w"]),
                                  np.asarray(students["w"]))


@pytest.mark.parametrize("bits", ["16", "4+ef"])
def test_plane_codec_bit_identical_to_view_codec(bits):
    from repro.core.round_ops import quantize_dequantize_per_node
    from repro.core.wire_state import init_codec_state
    spec = WireSpec.parse(bits)
    students, plane, protos = _stacked_payload()
    pv = {"protos": protos, "student": students}
    pp = {"protos": protos, "student": plane}
    if spec.error_feedback:
        f = jax.jit(lambda t, s: quantize_dequantize_per_node(
            t, spec=spec, state=s))
        rv, sv = f(pv, init_codec_state(pv))
        rp, sp = f(pp, init_codec_state(pp))
        # second round exercises the carried residual
        rv2, _ = f(rv, sv)
        rp2, _ = f(rp, sp)
        resv = as_tree(sp.residual["student"])
        for k in students:
            np.testing.assert_array_equal(
                np.asarray(resv[k]), np.asarray(sv.residual["student"][k]))
    else:
        f = jax.jit(lambda t: quantize_dequantize_per_node(t, spec=spec))
        rv, rp = f(pv), f(pp)
        rv2 = rp2 = None
    assert is_plane(rp["student"])
    for pair in ((rv, rp), (rv2, rp2)):
        if pair[0] is None:
            continue
        views = as_tree(pair[1]["student"])
        np.testing.assert_array_equal(np.asarray(pair[0]["protos"]),
                                      np.asarray(pair[1]["protos"]))
        for k in students:
            np.testing.assert_array_equal(np.asarray(views[k]),
                                          np.asarray(pair[0]["student"][k]))


@pytest.mark.mesh
@pytest.mark.parametrize("exchange", ["gather", "packed", "ppermute"])
def test_mesh_round_plane_matches_views(exchange):
    n = 4
    if jax.device_count() < n:
        pytest.skip(f"needs {n} devices, have {jax.device_count()}")
    from jax.sharding import PartitionSpec as P
    from repro.core import topology as T
    from repro.core.mesh_federation import make_profe_round
    from repro.launch.wire import fed_mesh
    mesh = fed_mesh(n)
    students = {"w": _f32((n, 33, 20)), "b": _f32((n, 7))}
    plane = jax.vmap(plane_from_tree)(students)
    specs = {"w": P(None, None), "b": P(None,)}
    protos, counts = _f32((n, 5, 16)), jnp.ones((n, 5), jnp.float32)
    sizes = jnp.ones((n,), jnp.float32)
    adj = T.make_schedule(n, "ring", seed=0).adjacency_at(0)
    fn = make_profe_round(mesh, specs, bits=16, adjacency=adj,
                          exchange=exchange)
    with mesh:
        s_t, g_t, m_t = jax.jit(fn)(students, protos, counts, sizes)
        s_p, g_p, m_p = jax.jit(fn)(plane, protos, counts, sizes)
    assert is_plane(s_p)
    views = as_tree(s_p)
    for k in students:
        np.testing.assert_array_equal(np.asarray(views[k]),
                                      np.asarray(s_t[k]))
    np.testing.assert_array_equal(np.asarray(g_p), np.asarray(g_t))
    np.testing.assert_array_equal(np.asarray(m_p), np.asarray(m_t))


# ---------------------------------------------------------------------------
# engines: plane on/off bit-identity, mode validation, EMA carry
# ---------------------------------------------------------------------------

def test_plane_on_off_f1_bitwise_identical(mnist_like):
    cfg, node_data, test_d = mnist_like
    runs = {}
    for mode in ("on", "off"):
        fed = FederationConfig(num_nodes=N_NODES, rounds=2, local_epochs=1,
                               algorithm="profe", topology="ring",
                               param_plane=mode)
        runs[mode] = run_federation(cfg, fed, TRAIN, node_data, test_d)
    assert runs["on"].extras["param_plane"] is True
    assert runs["off"].extras["param_plane"] is False
    assert runs["on"].f1_per_round == runs["off"].f1_per_round
    # the wire payload is the same student either way
    for k in ("wire_bytes_per_copy", "wire_bytes_packed_per_copy",
              "avg_sent_gb"):
        assert runs["on"].extras[k] == runs["off"].extras[k]


def test_plane_qdq_rows_bit_identical_to_tree():
    """The loop engine's plane-resident wire: per-row-span qdq on the
    [R, 512] buffer == the per-leaf eager reference, bitwise, and the
    result stays a plane (padding rows untouched at delta=1)."""
    from repro.core.quantization import quantize_dequantize_tree
    tree = _odd_float_tree()
    plane = plane_from_tree(tree)
    for bits in (16, 8):
        got = Q.quantize_dequantize_plane_rows(plane, bits)
        assert is_plane(got)
        want = quantize_dequantize_tree(tree, bits)
        views = as_tree(got)
        for path, w in jax.tree_util.tree_flatten_with_path(want)[0]:
            have = views
            for p_ in path:
                have = have[p_.key]
            np.testing.assert_array_equal(np.asarray(have), np.asarray(w),
                                          err_msg=f"bits={bits} {path}")
        # buffer stays repack-identical (padding lanes zero)
        np.testing.assert_array_equal(np.asarray(got.buf),
                                      np.asarray(plane_from_tree(want).buf))


def test_weighted_plane_mean_bit_identical_to_tree_mix():
    """The loop engine's plane-resident gossip mix: mixing the [R, 512]
    buffers row-for-row == mixing the leaf views and repacking
    (pack is placement-only, the mix is linear)."""
    from repro.core.aggregation import weighted_plane_mean, \
        weighted_tree_mean
    trees = [_odd_float_tree() for _ in range(3)]
    planes = [plane_from_tree(t) for t in trees]
    w = [3.0, 1.0, 2.0]
    got = weighted_plane_mean(planes, w)
    want = plane_from_tree(weighted_tree_mean(trees, w))
    assert is_plane(got)
    np.testing.assert_array_equal(np.asarray(got.buf), np.asarray(want.buf))


def test_plane_loop_engine_on_off_f1_bitwise_identical(mnist_like):
    """End to end: the loop engine's plane-resident wire + mix (no tree
    rebuild at the round boundary) must reproduce the per-leaf path
    bit for bit, quantized wire included."""
    cfg, node_data, test_d = mnist_like
    runs = {}
    for mode in ("on", "off"):
        fed = FederationConfig(num_nodes=N_NODES, rounds=2, local_epochs=1,
                               algorithm="profe", topology="ring",
                               quantize_bits=16, param_plane=mode)
        runs[mode] = run_federation_loop(cfg, fed, TRAIN, node_data, test_d)
    assert runs["on"].extras["param_plane"] is True
    assert runs["off"].extras["param_plane"] is False
    assert runs["on"].f1_per_round == runs["off"].f1_per_round
    assert runs["on"].extras["avg_sent_gb"] == \
        runs["off"].extras["avg_sent_gb"]


def test_plane_loop_engine_matches_stacked(mnist_like):
    cfg, node_data, test_d = mnist_like
    fed = FederationConfig(num_nodes=N_NODES, rounds=2, local_epochs=1,
                           algorithm="profe", topology="ring",
                           param_plane="on")
    stacked = run_federation(cfg, fed, TRAIN, node_data, test_d)
    loop = run_federation_loop(cfg, fed, TRAIN, node_data, test_d)
    assert loop.extras["param_plane"] is True
    # engines reassociate fp32 differently — numerical noise only
    np.testing.assert_allclose(loop.f1_per_round, stacked.f1_per_round,
                               atol=0.05)
    assert loop.extras["avg_sent_gb"] == stacked.extras["avg_sent_gb"]


def test_param_plane_on_rejects_unsupported():
    import dataclasses
    cfg = get_config("mnist-cnn")
    from repro.models import derive_student
    lion = TrainConfig(batch_size=64, learning_rate=1e-3,
                       optimizer="lion", remat=False)
    fed = FederationConfig(num_nodes=2, rounds=1, algorithm="profe",
                           param_plane="on")
    with pytest.raises(ValueError, match="param_plane"):
        F._plane_mode(fed, lion, "profe", derive_student(cfg))
    with pytest.raises(ValueError, match="param_plane"):
        F._plane_mode(dataclasses.replace(fed, param_plane="maybe"), TRAIN,
                      "profe", derive_student(cfg))
    # auto quietly falls back instead
    auto = dataclasses.replace(fed, param_plane="auto")
    assert F._plane_mode(auto, lion, "profe", derive_student(cfg)) is False
    assert F._plane_mode(auto, TRAIN, "fedavg",
                         derive_student(cfg)) is False
    # adafactor has a fused plane update now: auto engages, on accepts
    ada = dataclasses.replace(lion, optimizer="adafactor")
    assert F._plane_mode(auto, ada, "profe", derive_student(cfg)) is True
    assert F._plane_mode(fed, ada, "profe", derive_student(cfg)) is True


def test_proto_ema_carries_and_matches_loop(mnist_like):
    cfg, node_data, test_d = mnist_like
    fed = FederationConfig(num_nodes=N_NODES, rounds=2, local_epochs=1,
                           algorithm="profe", topology="ring",
                           proto_ema=0.5)
    stacked = run_federation(cfg, fed, TRAIN, node_data, test_d)
    loop = run_federation_loop(cfg, fed, TRAIN, node_data, test_d)
    assert stacked.extras["proto_ema"] == 0.5
    np.testing.assert_allclose(loop.f1_per_round, stacked.f1_per_round,
                               atol=0.05)


def test_proto_ema_blends_round_two_prototypes():
    """Round 1 must be untouched (the carry starts at zero); round 2's
    raw counts must blend ``new + ema * previous`` and the resulting
    prototypes must differ from the memoryless pass."""
    from repro.models import derive_student
    cfg = get_config("mnist-cnn").replace(cnn_channels=(4, 8))
    data = make_image_dataset(0, 64, cfg.input_hw, cfg.num_classes)
    fed = FederationConfig(num_nodes=2, rounds=2, local_epochs=1,
                           algorithm="profe", proto_ema=0.5)
    train = TrainConfig(batch_size=16, learning_rate=1e-3,
                        optimizer="adamw", remat=False)
    opt = make_optimizer("adamw", 1e-3)
    student_cfg = derive_student(cfg)
    step, _, _, _, mcfgs = F._algo_wiring("profe", cfg, student_cfg, fed,
                                          train, opt, opt, jit=False)
    ncls = F._n_proto_classes(cfg)
    stacked = F._stack_states(
        F._init_states("profe", mcfgs, fed, opt, opt, ncls))
    B, T, N = 16, 2, 2
    img = jnp.asarray(data["image"][:B * T * N].reshape(
        T, N, B, *data["image"].shape[1:]))
    lab = jnp.asarray(data["label"][:B * T * N].reshape(T, N, B))
    xb, valid = {"image": img, "label": lab}, jnp.ones((T, N), jnp.float32)

    outs = {}
    for ema in (0.5, 0.0):
        tp = F._make_round_parts(step, mcfgs[1], ncls, share_protos=True,
                                 wire_model="student", bits=None,
                                 proto_ema=ema)[0]
        jt = jax.jit(tp, static_argnames=("teacher_on", "all_valid"))
        st = stacked if ema else stacked._replace(proto_acc=None)
        s1, p1, c1, _ = jt(st, xb, valid, xb, valid, teacher_on=True,
                        all_valid=True)
        s2, p2, c2, _ = jt(s1, xb, valid, xb, valid, teacher_on=True,
                        all_valid=True)
        outs[ema] = (p1, c1, p2, c2, s2)
    p1e, c1e, p2e, c2e, s2e = outs[0.5]
    p1o, c1o, p2o, c2o, _ = outs[0.0]
    np.testing.assert_array_equal(np.asarray(p1e), np.asarray(p1o))
    np.testing.assert_array_equal(np.asarray(c1e), np.asarray(c1o))
    # round 2: counts blend new + 0.5 * previous, prototypes move
    np.testing.assert_allclose(np.asarray(c2e), np.asarray(c2o * 1.5),
                               rtol=1e-6)
    assert float(jnp.max(jnp.abs(p2e - p2o))) > 0
    # and the carry holds the blended raw accumulators
    np.testing.assert_array_equal(np.asarray(s2e.proto_acc[1]),
                                  np.asarray(c2e))
