"""Infrastructure tests: checkpointing, sharding rules, HLO analyzer,
mesh-level federation round (1-device mesh), comm accounting."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.checkpoint import load_checkpoint, save_checkpoint
from repro.config import get_config
from repro.launch.hlo_analysis import analyze_hlo
from repro.launch.mesh import make_host_mesh
from repro.launch.wire import fed_mesh
from repro.models import derive_student, init_params


# ---------------------------------------------------------------------------
# checkpoint
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip(tmp_path):
    cfg = get_config("mnist-cnn")
    params = init_params(cfg, jax.random.PRNGKey(0))
    path = str(tmp_path / "ck.npz")
    save_checkpoint(path, params, metadata={"round": 3})
    restored = load_checkpoint(path, jax.tree_util.tree_map(
        lambda x: jnp.zeros_like(x), params))
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(restored)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))


def test_checkpoint_mismatch_raises(tmp_path):
    path = str(tmp_path / "ck.npz")
    save_checkpoint(path, {"a": jnp.ones(3)})
    with pytest.raises(ValueError):
        load_checkpoint(path, {"b": jnp.ones(3)})


# ---------------------------------------------------------------------------
# compile cache and meshes (the entry points' launch helpers)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("env_dir", [None, "/some/cache"],
                         ids=["repo-default", "from-env"])
def test_enable_compile_cache_directory(monkeypatch, env_dir):
    """``$JAX_COMPILATION_CACHE_DIR`` wins and nothing is set in code;
    without it the cache goes to ``<repo>/.jax_cache``.  The config
    update is recorded, not applied: tests never turn the cache on."""
    from repro.launch import compile_cache as cc
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append((k, v)))
    if env_dir is None:
        monkeypatch.delenv(cc.ENV_VAR, raising=False)
        want = str(cc.REPO_ROOT / ".jax_cache")
        assert (cc.REPO_ROOT / "src" / "repro").is_dir()
        assert cc.enable_compile_cache() == want
        assert updates == [("jax_compilation_cache_dir", want)]
    else:
        monkeypatch.setenv(cc.ENV_VAR, env_dir)
        assert cc.enable_compile_cache() == env_dir
        assert updates == []


def test_launch_meshes_have_auto_axes():
    """The round programs place state with ``with_sharding_constraint``,
    which Explicit mesh axes (``jax.make_mesh``'s default) refuse."""
    from jax.sharding import AxisType
    for mesh in (make_host_mesh(), fed_mesh(1)):
        assert set(mesh.axis_types) == {AxisType.Auto}


# ---------------------------------------------------------------------------
# sharding rules
# ---------------------------------------------------------------------------

def test_param_specs_shapes_and_divisibility():
    from repro.sharding import param_specs
    mesh = make_host_mesh()
    cfg = get_config("yi-6b").smoke()
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    specs = param_specs(cfg, shapes, mesh)
    # same tree structure
    assert jax.tree_util.tree_structure(
        jax.tree_util.tree_map(lambda s: 0, specs,
                               is_leaf=lambda x: isinstance(x, P))) == \
        jax.tree_util.tree_structure(
            jax.tree_util.tree_map(lambda x: 0, shapes))
    flat = jax.tree_util.tree_leaves(specs,
                                     is_leaf=lambda x: isinstance(x, P))
    assert all(isinstance(s, P) for s in flat)


def test_spec_rank_matches_leaf_rank():
    from repro.sharding import param_specs
    mesh = make_host_mesh()
    for arch in ["grok-1-314b", "mamba2-130m", "recurrentgemma-9b",
                 "whisper-small"]:
        cfg = get_config(arch).smoke()
        shapes = jax.eval_shape(lambda c=cfg: init_params(c, jax.random.PRNGKey(0)))
        specs = param_specs(cfg, shapes, mesh)
        flat_shapes = jax.tree_util.tree_leaves(shapes)
        flat_specs = jax.tree_util.tree_leaves(
            specs, is_leaf=lambda x: isinstance(x, P))
        for sh, sp in zip(flat_shapes, flat_specs):
            assert len(sp) == len(sh.shape), (arch, sh.shape, tuple(sp))


def test_opt_state_specs_structure():
    from repro.sharding import opt_state_specs
    pspecs = {"w": P("data", "model"), "b": P(None)}
    ad = opt_state_specs("adamw", pspecs)
    assert ad["mu"]["w"] == P("data", "model")
    af = opt_state_specs("adafactor", pspecs)
    assert af["v"]["w"]["vr"] == P("data")
    assert af["v"]["w"]["vc"] == P("model")


# ---------------------------------------------------------------------------
# HLO analyzer
# ---------------------------------------------------------------------------

HLO_SAMPLE = """
HloModule test

%body (p: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %p = (s32[], f32[8,8]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[8,8] get-tuple-element(%p), index=1
  %w = f32[8,8]{1,0} constant({...})
  %dot.1 = f32[8,8]{1,0} dot(%x, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %one = s32[] constant(1)
  %i2 = s32[] add(%i, %one)
  ROOT %t = (s32[], f32[8,8]) tuple(%i2, %dot.1)
}

%cond (p2: (s32[], f32[8,8])) -> pred[] {
  %p2 = (s32[], f32[8,8]) parameter(0)
  %i3 = s32[] get-tuple-element(%p2), index=0
  %n = s32[] constant(10)
  ROOT %lt = pred[] compare(%i3, %n), direction=LT
}

ENTRY %main (a: f32[8,8]) -> f32[8,8] {
  %a = f32[8,8]{1,0} parameter(0)
  %zero = s32[] constant(0)
  %t0 = (s32[], f32[8,8]) tuple(%zero, %a)
  %wh = (s32[], f32[8,8]) while(%t0), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"10"}}
  %ar = f32[8,8]{1,0} all-reduce(%a), replica_groups={}, to_apply=%body
  ROOT %out = f32[8,8]{1,0} get-tuple-element(%wh), index=1
}
"""


def test_analyze_hlo_trip_count_multiplies():
    cost = analyze_hlo(HLO_SAMPLE)
    # dot: 2*8*8*8 = 1024 flops, x10 trips
    assert cost.flops == pytest.approx(10 * 1024, rel=0.3)


def test_analyze_hlo_collectives():
    cost = analyze_hlo(HLO_SAMPLE)
    # all-reduce of f32[8,8] = 256 B operand -> ring convention 2x
    assert cost.coll.get("all-reduce", 0) == 512


# ---------------------------------------------------------------------------
# mesh federation round on a 1x1 mesh (semantics, not scale)
# ---------------------------------------------------------------------------

def test_mesh_profe_round_math():
    from repro.core.mesh_federation import make_profe_round
    from repro.sharding import param_specs
    mesh = fed_mesh(1)
    cfg = get_config("yi-6b").smoke()
    student_cfg = derive_student(cfg)
    s0 = init_params(student_cfg, jax.random.PRNGKey(0))
    s1 = init_params(student_cfg, jax.random.PRNGKey(1))
    stacked = jax.tree_util.tree_map(lambda a, b: jnp.stack([a, b]), s0, s1)
    shapes = jax.eval_shape(lambda: init_params(student_cfg,
                                                jax.random.PRNGKey(0)))
    specs = param_specs(student_cfg, shapes, mesh)
    protos = jnp.stack([jnp.ones((4, cfg.proto_dim)),
                        3 * jnp.ones((4, cfg.proto_dim))])
    counts = jnp.asarray([[1.0, 0, 2, 0], [3.0, 0, 2, 0]])
    sizes = jnp.asarray([1.0, 1.0])

    round_fn = make_profe_round(mesh, specs, bits=16)
    with mesh:
        new_students, glob, mask = jax.jit(round_fn)(stacked, protos, counts,
                                                     sizes)
    # all nodes end with the same aggregated student
    for leaf in jax.tree_util.tree_leaves(new_students):
        np.testing.assert_allclose(np.asarray(leaf[0]), np.asarray(leaf[1]),
                                   atol=1e-6)
    # aggregation ~= plain average (sizes equal), up to quantization error
    leaf0 = jax.tree_util.tree_leaves(new_students)[0]
    want = (jax.tree_util.tree_leaves(s0)[0] +
            jax.tree_util.tree_leaves(s1)[0]) / 2
    np.testing.assert_allclose(np.asarray(leaf0[0]), np.asarray(want),
                               atol=2e-3)
    # Eq.4: class 0 weighted 1:3 -> 1*0.25 + 3*0.75 = 2.5
    np.testing.assert_allclose(np.asarray(glob[0]),
                               np.full(cfg.proto_dim, 2.5), atol=1e-2)
    np.testing.assert_array_equal(np.asarray(mask), [1, 0, 1, 0])


@pytest.mark.parametrize("topo", ["ring", "star", "random-k2"])
def test_mesh_masked_topology_round(topo):
    """Neighborhood-masked gossip on the pod axis: ring/star/random-k
    ProFe rounds keep nodes distinct and match the CPU round_ops
    reference (own copy unquantized, Eq. 4 per neighborhood)."""
    from repro.core import round_ops as R
    from repro.core import topology as T
    from repro.core.mesh_federation import (make_fedavg_round,
                                            make_profe_round)
    from repro.sharding import param_specs
    n = 4
    adj = T.make_schedule(n, topo, seed=0).adjacency_at(0)
    mesh = fed_mesh(1)
    cfg = get_config("yi-6b").smoke()
    student_cfg = derive_student(cfg)
    params = [init_params(student_cfg, jax.random.PRNGKey(i))
              for i in range(n)]
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *params)
    shapes = jax.eval_shape(lambda: init_params(student_cfg,
                                                jax.random.PRNGKey(0)))
    specs = param_specs(student_cfg, shapes, mesh)
    C, Pdim = 4, student_cfg.proto_dim
    protos = jnp.stack([(i + 1.0) * jnp.ones((C, Pdim)) for i in range(n)])
    counts = jnp.asarray([[1.0, 0, 2, 0], [3.0, 0, 2, 0],
                          [2.0, 1, 0, 0], [0.0, 2, 1, 1]])
    sizes = jnp.asarray([1.0, 2.0, 3.0, 4.0])

    round_fn = make_profe_round(mesh, specs, bits=16, adjacency=adj)
    with mesh:
        new_students, glob, mask = jax.jit(round_fn)(stacked, protos,
                                                     counts, sizes)
    assert glob.shape == (n, C, Pdim) and mask.shape == (n, C)

    # CPU reference: masked mix with own copy unquantized.  The fused
    # device program may round codes sitting exactly on a .5 boundary
    # the other way, so allow one quantization step of slack.
    recv = R.quantize_dequantize_per_node(stacked, 16, use_kernels=False)
    w_self, w_neigh = R.gossip_matrix_dyn(adj, sizes)
    want = R.mix_node_trees(w_self, w_neigh, stacked, recv)
    for g, w in zip(jax.tree_util.tree_leaves(new_students),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-5, atol=2e-4)
    protos_rx = R.dequantize_leaf(*R.quantize_leaf_per_node(protos, 16))
    want_gp, want_mask = R.neighborhood_prototype_aggregate(
        R.include_matrix(adj), protos_rx, counts)
    np.testing.assert_allclose(np.asarray(glob), np.asarray(want_gp),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(mask), np.asarray(want_mask))
    # sparse gossip keeps nodes distinct (unlike the full-mesh round)
    if topo in ("ring", "star"):
        leaf = jax.tree_util.tree_leaves(new_students)[0]
        assert float(jnp.max(jnp.abs(leaf[1] - leaf[2]))) > 0

    # FedAvg baseline with the same mask, no quantization
    fed_fn = make_fedavg_round(mesh, specs, adjacency=adj)
    with mesh:
        mixed = jax.jit(fed_fn)(stacked, sizes)
    want_f = R.mix_node_trees(w_self, w_neigh, stacked, stacked)
    for g, w in zip(jax.tree_util.tree_leaves(mixed),
                    jax.tree_util.tree_leaves(want_f)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-5, atol=1e-6)
