"""Microbenchmark: one full federation round, seed Python loop vs the
jitted stacked round (``core/federation.py`` round engine).

The seed trained N nodes with nested Python loops — a jitted step call
per batch per node, a *freshly re-jitted* prototype accumulator per
round × node, and per-node Python gossip.  The stacked engine compiles
the whole round (scan over batches, vmap over nodes, round_ops
gossip/aggregate) into one program, so dispatch cost per round is O(1)
in node count.  This benchmark records that gap per node count so the
perf trajectory is tracked in ``BENCH_round_step.json``.

    PYTHONPATH=src python benchmarks/round_step.py --nodes 2 4 8

**Per-phase breakdown** (``--phases``): decomposes the jitted round into
train / proto (Eq. 3, exact pass AND the fused in-scan marginal) /
codec (wire round-trip) / mix (gossip+aggregate) phase timings, an
optimizer A/B (fused plane clip+update sweep vs the per-leaf
reference, paired-interleaved), a grad-path A/B (custom-vjp plane
backward vs autodiff through the leaf views), a gossip-mix A/B
(buffer-native stacked mix vs tree mix + plane rebuild), plus
whole-round exact-vs-fused wall times — the numbers behind the ``proto_pass="fused"`` single-pass
round and the flat parameter plane.  Each phase is jitted
standalone (no donation) so constant inputs can be replayed; the fused
proto cost is the marginal ``fused_train - train`` (clamped at 0)
because the fused pass has no standalone program — it lives inside the
training scan.  Written into ``BENCH_round_step.json`` under
``nodes[n]["phases"]`` and gated by ``check_regression.py`` (fresh
exact proto phase vs committed, and committed fused-cheaper-than-exact
invariants):

    PYTHONPATH=src python benchmarks/round_step.py --nodes 2 4 8 --phases

**Wire-exchange microbench** (``--wire``): the packed single-buffer
codec vs the per-leaf path (jitted round-trip ms), and the gather vs
ppermute exchange on an (N, 1, 1) federation mesh (per-node HLO
collective bytes + wall ms per round).  Recorded in
``BENCH_wire_exchange.json`` and gated by ``check_regression.py``:

    PYTHONPATH=src python benchmarks/round_step.py --wire

(re-executes itself with forced host devices when the exchange needs
more nodes than the backend exposes).
"""
from __future__ import annotations

import argparse
import json
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import FederationConfig, TrainConfig, get_config
from repro.core import federation as F
from repro.core import round_ops as R
from repro.core import topology as T
from repro.core.aggregation import weighted_tree_mean
from repro.core.profe import proto_labels
from repro.core.prototypes import aggregate_prototypes
from repro.core.quantization import quantize_dequantize_tree
from repro.data import batches, make_image_dataset, partition
from repro.models import derive_student, forward
from repro.optim import (clip_by_global_norm, make_optimizer,
                         make_plane_optimizer)
from repro.optim.plane import (as_tree, is_plane, plane_from_tree,
                               plane_to_tree, plane_view_tree)
from repro.wirespec import WireSpec, resolve_bits
from repro.launch.compile_cache import enable_compile_cache


def _block(tree):
    for leaf in jax.tree_util.tree_leaves(tree):
        if hasattr(leaf, "block_until_ready"):
            leaf.block_until_ready()


def _setup(n_nodes: int, samples_per_node: int, batch_size: int,
           channels=(8, 16)):
    # A reduced CNN keeps the round in the dispatch-bound regime the
    # refactor targets: per-batch compute is a few ms, so the measured
    # gap is the Python-side multiplier (N x T jitted dispatches plus a
    # re-traced prototype accumulator per round x node) that the stacked
    # round removes — not the conv throughput of the host CPU, which no
    # round engine can change.
    cfg = get_config("mnist-cnn").replace(cnn_channels=tuple(channels))
    fed = FederationConfig(num_nodes=n_nodes, rounds=1, local_epochs=1,
                           algorithm="profe")
    train = TrainConfig(batch_size=batch_size, learning_rate=1e-3,
                        optimizer="adamw", remat=False)
    data = make_image_dataset(0, samples_per_node * n_nodes, cfg.input_hw,
                              cfg.num_classes)
    parts = partition(data["label"], n_nodes, "iid", 0)
    node_data = [{k: v[i] for k, v in data.items()} for i in parts]
    return cfg, fed, train, node_data


def _wiring(cfg, fed, train, *, jit: bool, plane=None):
    """Mirrors ``run_federation``'s wiring, including the flat-parameter-
    plane resolution: ``plane=None`` resolves ``fed.param_plane`` exactly
    like the engines do (so the timed jitted round runs the same fused
    clip+update path a real run would), ``plane=False`` pins the
    per-leaf reference (the seed loop's representation)."""
    student_cfg = derive_student(cfg)
    opt = make_optimizer(train.optimizer, train.learning_rate,
                         weight_decay=train.weight_decay,
                         momentum=train.momentum)
    use_plane = (F._plane_mode(fed, train, fed.algorithm, student_cfg)
                 if plane is None else plane)
    opt_s = opt
    if use_plane:
        opt_s = make_plane_optimizer(train.optimizer, train.learning_rate,
                                     weight_decay=train.weight_decay,
                                     momentum=train.momentum,
                                     grad_clip=train.grad_clip)
    step, wire_model, share_protos, bits, model_cfgs = F._algo_wiring(
        fed.algorithm, cfg, student_cfg, fed, train, opt_s, opt, jit=jit)
    ncls = F._n_proto_classes(cfg)
    states = F._init_states(fed.algorithm, model_cfgs, fed, opt_s, opt, ncls,
                            plane=use_plane)
    return step, bits, ncls, model_cfgs, states, student_cfg


def legacy_round(step, states, node_data, cfg, student_cfg, fed, train,
                 adj, sizes, ncls, bits, rnd: int):
    """One round exactly as the seed ran it: per-node Python loops and a
    per-round re-jitted Eq. 3 accumulator closure."""
    n_nodes = fed.num_nodes
    for i in range(n_nodes):
        st = states[i]
        for batch in batches(node_data[i], train.batch_size,
                             seed=fed.seed + rnd * 997 + i,
                             epochs=fed.local_epochs):
            st, _ = step(st, batch, teacher_on=True)
        states[i] = st._replace(round_idx=jnp.int32(rnd + 1))

    protos, counts = [], []
    for i in range(n_nodes):
        params = states[i].student
        sums = jnp.zeros((ncls, student_cfg.proto_dim), jnp.float32)
        cts = jnp.zeros((ncls,), jnp.float32)

        @jax.jit   # seed behavior: fresh closure => re-trace every call
        def acc(sums, counts, batch):
            out = forward(student_cfg, params, batch, remat=False)
            onehot = jax.nn.one_hot(proto_labels(student_cfg, batch), ncls,
                                    dtype=jnp.float32)
            return (sums + jnp.einsum("nc,np->cp", onehot, out.f1),
                    counts + jnp.sum(onehot, axis=0))

        for batch in batches(node_data[i], train.batch_size,
                             seed=fed.seed + rnd):
            sums, cts = acc(sums, cts, batch)
        protos.append(sums / jnp.maximum(cts, 1.0)[:, None])
        counts.append(cts)

    recv = [[] for _ in range(n_nodes)]
    recv_sz = [[] for _ in range(n_nodes)]
    for i in range(n_nodes):
        rx = quantize_dequantize_tree(states[i].student,
                                      resolve_bits(bits, "student"))
        for j in T.neighbors(adj, i):
            recv[j].append(rx)
            recv_sz[j].append(sizes[i])
    all_p = jnp.stack([quantize_dequantize_tree(p, resolve_bits(bits, "protos"))
                       for p in protos])
    all_c = jnp.stack(counts)
    for i in range(n_nodes):
        neigh = T.neighbors(adj, i) + [i]
        gp, mask = aggregate_prototypes(all_p[np.array(neigh)],
                                        all_c[np.array(neigh)])
        new_student = weighted_tree_mean([states[i].student] + recv[i],
                                         [sizes[i]] + recv_sz[i])
        states[i] = states[i]._replace(student=new_student, global_protos=gp,
                                       proto_mask=mask)
    _block(states[0])
    return states


def measure(n_nodes: int, *, samples_per_node: int, batch_size: int,
            rounds: int, jitted_only: bool = False):
    """``jitted_only`` skips the (4-5x slower) seed-loop measurement —
    for callers like ``check_regression.py`` that only gate on
    ``jitted_ms``."""
    cfg, fed, train, node_data = _setup(n_nodes, samples_per_node, batch_size)
    adj = T.adjacency(n_nodes, fed.topology)
    sizes = [len(d["label"]) for d in node_data]
    n_steps = sum(len(d["label"]) // batch_size for d in node_data)

    # --- seed Python-loop engine --------------------------------------
    t_legacy = []
    if not jitted_only:
        step, bits, ncls, model_cfgs, states, student_cfg = _wiring(
            cfg, fed, train, jit=True, plane=False)
        states = legacy_round(step, states, node_data, cfg, student_cfg, fed,
                              train, adj, sizes, ncls, bits, 0)  # warmup
        for rnd in range(1, rounds + 1):
            t0 = time.perf_counter()
            states = legacy_round(step, states, node_data, cfg, student_cfg,
                                  fed, train, adj, sizes, ncls, bits, rnd)
            t_legacy.append((time.perf_counter() - t0) * 1e3)

    # --- jitted stacked round -----------------------------------------
    step_p, bits, ncls, model_cfgs, states, student_cfg = _wiring(
        cfg, fed, train, jit=False)
    stacked = F._stack_states(states)
    w_self, w_neigh = R.gossip_matrix(adj, sizes)
    include = R.include_matrix(adj)
    round_fn = F._make_round_fn(step_p, student_cfg, ncls, share_protos=True,
                                wire_model="student", bits=bits)

    def jitted_round(stacked, rnd):
        xb, valid = F._stack_round_batches(
            node_data, batch_size,
            [fed.seed + rnd * 997 + i for i in range(n_nodes)],
            fed.local_epochs)
        pxb, pvalid = F._stack_round_batches(
            node_data, batch_size, [fed.seed + rnd] * n_nodes, 1)
        out, _loss = round_fn(
            stacked, xb, valid, pxb, pvalid, w_self, w_neigh, include,
            teacher_on=True, all_valid=bool(np.all(np.asarray(valid) == 1.0)))
        _block(out)
        return out

    stacked = jitted_round(stacked, 0)                        # warmup/compile
    t_jit = []
    for rnd in range(1, rounds + 1):
        t0 = time.perf_counter()
        stacked = jitted_round(stacked, rnd)
        t_jit.append((time.perf_counter() - t0) * 1e3)

    jit_ms = statistics.median(t_jit)
    out = {
        "jitted_ms": round(jit_ms, 2),
        "local_steps_per_round": n_steps,
        "steps_per_s_jitted": round(n_steps / (jit_ms / 1e3), 1),
    }
    if not jitted_only:
        legacy_ms = statistics.median(t_legacy)
        out.update({
            "legacy_ms": round(legacy_ms, 2),
            "speedup": round(legacy_ms / jit_ms, 2),
            "steps_per_s_legacy": round(n_steps / (legacy_ms / 1e3), 1),
        })
    return out


# ---------------------------------------------------------------------------
# per-phase breakdown (--phases)
# ---------------------------------------------------------------------------

def measure_phases(n_nodes: int, *, samples_per_node: int, batch_size: int,
                   rounds: int):
    """Phase timings of the jitted stacked round at one node count.

    Every phase body comes from ``F._make_round_parts`` (the exact
    traced code both engines run) but is jitted here WITHOUT donation,
    so the same inputs replay across timed reps on any backend.
    ``proto_fused_ms`` is the marginal cost of folding Eq. 3 into the
    training scan: ``fused train_phase - plain train_phase``, clamped
    at zero (the fused pass has no standalone program to time)."""
    cfg, fed, train, node_data = _setup(n_nodes, samples_per_node,
                                        batch_size)
    adj = T.adjacency(n_nodes, fed.topology)
    sizes = [len(d["label"]) for d in node_data]
    step_p, bits, ncls, model_cfgs, states, student_cfg = _wiring(
        cfg, fed, train, jit=False)
    stacked = F._stack_states(states)
    w_self, w_neigh = R.gossip_matrix(adj, sizes)
    include = R.include_matrix(adj)
    xb, valid = F._stack_round_batches(
        node_data, batch_size, [fed.seed + 997 + i for i in range(n_nodes)],
        fed.local_epochs)
    pxb, pvalid = F._stack_round_batches(
        node_data, batch_size, [fed.seed + 1] * n_nodes, 1)
    av = bool(np.all(np.asarray(valid) == 1.0))
    empty = ({}, jnp.zeros((0, n_nodes), jnp.float32))

    def parts(proto_pass, share=True):
        return F._make_round_parts(step_p, student_cfg, ncls,
                                   share_protos=share,
                                   wire_model="student", bits=bits,
                                   proto_pass=proto_pass)

    def compose(p3):
        tr, sh, mx = p3

        def round_fn(state, xb, valid, pxb, pvalid, teacher_on,
                     all_valid=False):
            state, protos, counts, _loss = tr(state, xb, valid, pxb,
                                              pvalid, teacher_on, all_valid)
            state, rs, prx = sh(state, protos)
            return mx(state, rs, prx, counts, w_self, w_neigh, include)

        return jax.jit(round_fn,
                       static_argnames=("teacher_on", "all_valid"))

    sj = jax.jit
    stat = dict(static_argnames=("teacher_on", "all_valid"))
    train_only = sj(parts("exact", share=False)[0], **stat)
    exact3 = parts("exact")
    train_fused = sj(parts("fused")[0], **stat)
    share_jit = sj(exact3[1])
    mix_jit = sj(exact3[2])
    proto_jit = sj(F._make_proto_pass(student_cfg, ncls))

    e0, e1 = empty
    # the fused proto cost is a DIFFERENCE of two ~train-sized timings —
    # interleave them (like the codec A/B) so drift cancels per pair
    train_ms, fused_train_ms = _paired_ms(
        lambda: train_only(stacked, xb, valid, e0, e1, teacher_on=True,
                           all_valid=av),
        lambda: train_fused(stacked, xb, valid, e0, e1, teacher_on=True,
                            all_valid=av), rounds=max(rounds, 5))
    proto_exact_ms = _median_ms(
        lambda: proto_jit(stacked.student, pxb, pvalid), rounds=rounds)
    sums, counts = proto_jit(stacked.student, pxb, pvalid)
    protos = sums / jnp.maximum(counts, 1.0)[..., None]
    codec_ms = _median_ms(lambda: share_jit(stacked, protos),
                          rounds=rounds)
    _st, recv_student, protos_rx = share_jit(stacked, protos)
    mix_ms = _median_ms(
        lambda: mix_jit(stacked, recv_student, protos_rx, counts, w_self,
                        w_neigh, include), rounds=rounds)
    round_exact = compose(parts("exact"))
    round_fused = compose(parts("fused"))
    round_exact_ms, round_fused_ms = _paired_ms(
        lambda: round_exact(stacked, xb, valid, pxb, pvalid,
                            teacher_on=True, all_valid=av),
        lambda: round_fused(stacked, xb, valid, e0, e1, teacher_on=True,
                            all_valid=av), rounds=max(rounds, 5))

    # optimizer sweep in isolation: fused plane clip+update (one pass
    # over the [N, R, 512] buffer, one global-norm reduction) vs the
    # per-leaf reference (leaf-walk clip + leaf-walk update), on
    # identical operands — another close A/B, so interleaved like the
    # codec pair.  The params double as grads: same shapes, realistic
    # magnitudes, no RNG in the timed path.
    views = as_tree(stacked.student)
    planes = stacked.student if is_plane(stacked.student) \
        else jax.vmap(plane_from_tree)(views)
    opt_leaf = make_optimizer(train.optimizer, train.learning_rate,
                              weight_decay=train.weight_decay,
                              momentum=train.momentum)
    opt_plane = make_plane_optimizer(train.optimizer, train.learning_rate,
                                     weight_decay=train.weight_decay,
                                     momentum=train.momentum,
                                     grad_clip=train.grad_clip)
    leaf_state = jax.vmap(opt_leaf.init)(views)
    plane_state = jax.vmap(opt_plane.init)(planes)

    @jax.jit
    def upd_leaf(params, grads, state):
        def one(p, g, s):
            g, _ = clip_by_global_norm(g, train.grad_clip)
            return opt_leaf.update(g, s, p)
        return jax.vmap(one)(params, grads, state)

    @jax.jit
    def upd_fused(params, grads, state):
        return jax.vmap(lambda g, s, p: opt_plane.update(g, s, p))(
            grads, state, params)

    update_per_leaf_ms, update_fused_ms = _paired_ms(
        lambda: upd_leaf(views, views, leaf_state),
        lambda: upd_fused(planes, planes, plane_state),
        rounds=max(rounds, 10))

    # plane-resident grad path: the custom-vjp backward packs the
    # per-leaf cotangents into ONE [R, 512] buffer, vs autodiff through
    # the plane_to_tree views (XLA's slice transposes: per-leaf pad +
    # add into the buffer).  Same forward math on both sides, so the
    # pair isolates the backward packing cost — interleaved A/B.
    def _loss(tree):
        return sum(jnp.sum(jnp.sin(l) * l)
                   for l in jax.tree_util.tree_leaves(tree))

    @jax.jit
    def grad_plane(ps):
        return jax.vmap(jax.grad(lambda p: _loss(plane_view_tree(p))))(
            ps).buf

    @jax.jit
    def grad_repack(ps):
        return jax.vmap(jax.grad(lambda p: _loss(plane_to_tree(p))))(
            ps).buf

    # both grad/mix pairs are sub-ms dispatch-bound ops with ~10%
    # margins — 100 pairs keep the medians outside this container's
    # timer noise (still ~0.1 s per pair set)
    grad_repack_ms, grad_plane_ms = _paired_ms(
        lambda: grad_repack(planes),
        lambda: grad_plane(planes), rounds=max(rounds, 100))

    # plane-resident gossip mix: the round's weighted mean applied
    # straight to the stacked [N, R, 512] buffer vs the tree reference
    # (R.mix_node_trees over the leaf views + the vmap(plane_from_tree)
    # rebuild the plane path deletes at the round boundary).
    @jax.jit
    def mix_plane(ps):
        bufs = ps.buf
        return w_self[:, None, None] * bufs + jnp.tensordot(
            w_neigh, bufs, axes=1)

    @jax.jit
    def mix_tree(ps):
        v = as_tree(ps)
        mixed = R.mix_node_trees(w_self, w_neigh, v, v)
        return jax.vmap(plane_from_tree)(mixed).buf

    mix_tree_ms, mix_plane_ms = _paired_ms(
        lambda: mix_tree(planes),
        lambda: mix_plane(planes), rounds=max(rounds, 100))

    # adapter-wire merge A/B: the fused low-rank sweep over the plane
    # buffer's matrix leaf-row spans (kernels/lowrank_apply) vs the
    # materialized reference (per-leaf apply + plane rebuild), on
    # identical factors.  Refs at 0.9x the weights give every leaf a
    # realistic nonzero delta; the rest leaves pass through unmixed —
    # the pair isolates the apply, not the gossip mean.
    from repro.core.adapters import (adapter_layout, factorize_deltas,
                                     split_student)
    from repro.kernels.lowrank_apply.ops import (adapter_apply_plane,
                                                 adapter_apply_tree)
    a_layout = adapter_layout(views, 8, node_axis=True)
    a_mats, a_rest = split_student(a_layout, views)
    a_refs = {k: 0.9 * v for k, v in a_mats.items()}
    a_factors = jax.jit(
        lambda m, r: factorize_deltas(a_layout, m, r))(a_mats, a_refs)
    _block(a_factors)

    @jax.jit
    def apply_dense(ps):
        tree = adapter_apply_tree(as_tree(ps), a_layout, w_neigh,
                                  a_factors, a_rest)
        return jax.vmap(plane_from_tree)(tree).buf

    @jax.jit
    def apply_fused(ps):
        # use_kernels resolves per-backend (Pallas on TPU, ref math on
        # CPU) — on CPU the pair still isolates the plane-span splice
        # vs the materialize + plane_from_tree rebuild
        return adapter_apply_plane(ps, a_layout, w_neigh, a_factors,
                                   a_rest).buf

    apply_dense_ms, apply_fused_ms = _paired_ms(
        lambda: apply_dense(planes),
        lambda: apply_fused(planes), rounds=max(rounds, 100))
    return {
        "train_ms": train_ms,
        "proto_exact_ms": proto_exact_ms,
        "proto_fused_ms": round(max(0.0, fused_train_ms - train_ms), 3),
        "codec_ms": codec_ms,
        "mix_ms": mix_ms,
        "update_per_leaf_ms": update_per_leaf_ms,
        "update_fused_ms": update_fused_ms,
        "grad_repack_ms": grad_repack_ms,
        "grad_plane_ms": grad_plane_ms,
        "mix_tree_ms": mix_tree_ms,
        "mix_plane_ms": mix_plane_ms,
        "apply_dense_ms": apply_dense_ms,
        "apply_fused_ms": apply_fused_ms,
        "round_exact_ms": round_exact_ms,
        "round_fused_ms": round_fused_ms,
        "fused_round_speedup": round(round_exact_ms
                                     / max(round_fused_ms, 1e-9), 3),
    }


# ---------------------------------------------------------------------------
# wire-exchange microbench (--wire)
# ---------------------------------------------------------------------------

def _median_ms(fn, *args, rounds: int = 20):
    _block(fn(*args))                                   # compile/warmup
    ts = []
    for _ in range(rounds):
        t0 = time.perf_counter()
        _block(fn(*args))
        ts.append((time.perf_counter() - t0) * 1e3)
    return round(statistics.median(ts), 3)


def _paired_ms(fn_a, fn_b, *args, rounds: int = 20):
    """Interleaved A/B timing: one loop alternates the two jitted fns so
    container-load drift hits both samples of every pair equally — the
    honest way to compare two codecs whose compiled math is this close.
    Returns (median_a_ms, median_b_ms)."""
    _block(fn_a(*args))                                 # compile/warmup
    _block(fn_b(*args))
    ta, tb = [], []
    for _ in range(rounds):
        t0 = time.perf_counter()
        _block(fn_a(*args))
        ta.append((time.perf_counter() - t0) * 1e3)
        t0 = time.perf_counter()
        _block(fn_b(*args))
        tb.append((time.perf_counter() - t0) * 1e3)
    return (round(statistics.median(ta), 3),
            round(statistics.median(tb), 3))


def measure_wire(n_nodes: int = 8, topology: str = "ring", *,
                 arch: str = "mnist-cnn", bits="16",
                 rounds: int = 20, inner: int = 1,
                 adapter_rank: int = 0, adapter_grams: bool = False):
    """Packed vs per-leaf codec (jitted qdq round-trip) and gather vs
    ppermute exchange (HLO collective bytes + wall ms) for one gossip
    round of a stacked student + prototypes payload, at one wire spec
    (``bits``: ``"16"`` | ``"8"`` | ``"4"`` | ``"<student>/<protos>"``).

    ``inner > 1`` shapes each federation node as ``inner`` data-axis
    devices (the ``--pods RxC`` rows): the ppermute exchange lowers the
    row-sharded permute and the recorded bytes are the POD-axis
    per-node attribution from the HLO device groups.

    ``adapter_rank > 0`` swaps matrix leaves onto the adapter-rank wire
    (rank-``r`` delta factors instead of dense parameters): the codec
    pair round-trips the factored payload groups and the exchange rows
    carry the adapter round's bytes/ms (plus the adapter carry as a
    round operand)."""
    from repro.core.mesh_federation import make_profe_round
    from repro.launch import wire as W
    from repro.models import init_params
    from repro.sharding import param_specs

    spec = WireSpec.parse(bits)
    # single owner of the arch -> (student, proto-classes) derivation,
    # so the timed payload matches the payload whose bytes are lowered
    _cfg, student_cfg, _struct, ncls = W._student_setup(arch)
    params = [init_params(student_cfg, jax.random.PRNGKey(i))
              for i in range(n_nodes)]
    students = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *params)
    protos = jnp.asarray(
        np.random.default_rng(0).standard_normal(
            (n_nodes, ncls, student_cfg.proto_dim)), jnp.float32)
    ast_args = ()
    if adapter_rank:
        if inner > 1:
            raise ValueError("adapter rows need --pods R (no row-sharded "
                             "permute lowering for the adapter wire)")
        from repro.core.adapters import adapter_layout, init_adapter_state
        layout = adapter_layout(students, adapter_rank, node_axis=True)
        refs = [init_params(student_cfg, jax.random.PRNGKey(1000 + i))
                for i in range(n_nodes)]
        ast = init_adapter_state(
            layout, jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                           *refs), grams=adapter_grams)
        ast_args = (ast,)
        groups, _, _ = R.adapter_share_nodes(students, ast,
                                             rank=adapter_rank,
                                             grams=adapter_grams)
        payload = dict(groups)
        payload["protos"] = protos
    else:
        payload = {"protos": protos, "student": students}

    # error-feedback specs time the stateful codec (residual replayed +
    # updated each call) — the EF rows in BENCH_wire_exchange.json gate
    # that the residual pass stays within the codec-ms threshold
    ef_args = ()
    if spec.error_feedback:
        from repro.core.wire_state import init_codec_state
        ef_args = (init_codec_state(payload),)
        qdq_leaf = jax.jit(lambda t, s: R.quantize_dequantize_per_node(
            t, spec=spec, packed=False, state=s))
        qdq_packed = jax.jit(lambda t, s: R.quantize_dequantize_per_node(
            t, spec=spec, state=s))
    else:
        qdq_leaf = jax.jit(lambda t: R.quantize_dequantize_per_node(
            t, spec=spec, packed=False))
        qdq_packed = jax.jit(lambda t: R.quantize_dequantize_per_node(
            t, spec=spec))
    leaf_ms, packed_ms = _paired_ms(qdq_leaf, qdq_packed, payload,
                                    *ef_args, rounds=rounds)
    codec = {"per_leaf_ms": leaf_ms, "packed_ms": packed_ms}

    # exchange: bytes from compiled HLO, wall ms on the federation mesh
    report = W.measure_exchange_bytes(arch, n_nodes, topology, bits=spec,
                                      inner=inner,
                                      adapter_rank=adapter_rank,
                                      adapter_grams=adapter_grams)
    mesh = W.fed_mesh(n_nodes, (inner, 1))
    shapes = jax.eval_shape(lambda: init_params(student_cfg,
                                                jax.random.PRNGKey(0)))
    specs = param_specs(student_cfg, shapes, mesh)
    adj = T.make_schedule(n_nodes, topology, seed=0).adjacency_at(0)
    counts = jnp.ones((n_nodes, ncls), jnp.float32)
    sizes = jnp.ones((n_nodes,), jnp.float32)
    for ex, rep in report["exchanges"].items():
        if "error" in rep:
            continue
        fn = make_profe_round(mesh, specs, spec=spec, adjacency=adj,
                              exchange=ex, adapter_rank=adapter_rank,
                              adapter_grams=adapter_grams)
        with mesh:
            jitted = jax.jit(fn)
            rep["round_ms"] = _median_ms(
                jitted, students, protos, counts, sizes, *ast_args,
                *ef_args, rounds=rounds)
    return {"codec": codec, "exchange": report}


def _wire_bits_sweep(n_nodes, topology, wire_bits, rounds, inner,
                     adapter_ranks=(), adapter_bits=("4",)):
    per_bits = {}
    rows = [(b, 0) for b in wire_bits]
    if inner == 1:
        # adapter rows, labeled "<bits>+adapters<rank>" (the label the
        # regression gate keys on); multi-axis pods have no row-sharded
        # lowering for the adapter wire, so RxC rows skip them
        rows += [(b, r) for r in adapter_ranks if r
                 for b in adapter_bits]
    for b, rank in rows:
        label = f"{b}+adapters{rank}" if rank else b
        res = measure_wire(n_nodes, topology, bits=b, rounds=rounds,
                           inner=inner, adapter_rank=rank)
        per_bits[label] = res
        ex = res["exchange"]["exchanges"]
        print(f"== bits={label} ==")
        print(f"codec qdq: per-leaf {res['codec']['per_leaf_ms']:7.2f} ms   "
              f"packed {res['codec']['packed_ms']:7.2f} ms")
        for name, rep in ex.items():
            if "error" in rep:
                print(f"  {name:9s} {rep['error']}")
                continue
            print(f"  {name:9s} {rep['collective_bytes_per_node']/1e3:9.1f} "
                  f"KB/node   "
                  f"{rep.get('round_ms', float('nan')):7.2f} ms/round")
        if "ppermute" in ex and "error" not in ex["ppermute"]:
            full = res["exchange"].get("full_gather_bytes_per_node") or 0
            if full:
                frac = ex["ppermute"]["collective_bytes_per_node"] / full
                res["ppermute_vs_full_gather"] = round(frac, 4)
                print(f"  ppermute wire = {frac:.2%} of the full-graph "
                      f"all-gather exchange")
    base = per_bits.get("16", {}).get("exchange", {}).get(
        "exchanges", {}).get("ppermute", {}).get("collective_bytes_per_node")
    if base:
        for b, res in per_bits.items():
            p = res["exchange"]["exchanges"].get("ppermute", {})
            if "collective_bytes_per_node" in p:
                res["ppermute_vs_int16"] = round(
                    p["collective_bytes_per_node"] / base, 4)
    return per_bits


def run_wire(args):
    from repro.launch.wire import parse_pods
    shapes = [parse_pods(p) for p in args.pods]
    out = {
        "benchmark": "wire exchange: packed single-buffer codec vs "
                     "per-leaf, gather vs ppermute neighbor collectives "
                     f"({args.wire_topology}, pods={list(args.pods)}, "
                     "mnist-cnn student+protos payload), per wire spec",
        "backend": jax.default_backend(),
        "config": {"nodes": shapes[0][0],
                   "topology": args.wire_topology,
                   "timed_rounds": args.rounds,
                   "bits": list(args.wire_bits),
                   "pods": list(args.pods),
                   "adapter_ranks": list(args.wire_adapters),
                   "adapter_bits": list(args.wire_adapter_bits)},
        "per_pods": {},
    }
    for pods_str, (n, inner) in zip(args.pods, shapes):
        print(f"==== pods={pods_str} ({n} nodes x {inner} devices) ====")
        out["per_pods"][pods_str] = _wire_bits_sweep(
            n, args.wire_topology, args.wire_bits, args.rounds, inner,
            adapter_ranks=args.wire_adapters,
            adapter_bits=args.wire_adapter_bits)
    # the first pod shape keeps the legacy top-level key so existing
    # readers (tables, plots) see the single-axis rows unchanged
    out["per_bits"] = out["per_pods"][args.pods[0]]
    with open(args.out, "w") as f:
        json.dump(out, f, indent=2)
    print(f"wrote {args.out}")


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", nargs="+", type=int, default=[2, 4, 8])
    ap.add_argument("--samples-per-node", type=int, default=32)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--out", default="BENCH_round_step.json")
    ap.add_argument("--phases", action="store_true",
                    help="also record the per-phase breakdown "
                         "(train/proto/codec/mix, exact vs fused round) "
                         "under nodes[n]['phases']")
    ap.add_argument("--wire", action="store_true",
                    help="wire-exchange microbench instead of the round "
                         "step (writes BENCH_wire_exchange.json)")
    ap.add_argument("--wire-nodes", type=int, default=8)
    ap.add_argument("--wire-topology", default="ring")
    ap.add_argument("--wire-bits", nargs="+",
                    default=["16", "8", "4", "4/16"],
                    help="wire specs to sweep: 16 | 8 | 4 (uniform) or "
                         "<student>/<protos> (mixed)")
    ap.add_argument("--wire-adapters", nargs="+", type=int, default=[8],
                    metavar="RANK",
                    help="adapter ranks to add as extra --wire rows "
                         "(labeled '<bits>+adapters<rank>'); [] skips "
                         "them")
    ap.add_argument("--wire-adapter-bits", nargs="+", default=["4"],
                    help="wire specs the adapter rows run at")
    ap.add_argument("--pods", nargs="+", default=None,
                    help="pod shapes to sweep in --wire mode: 'R' or "
                         "'RxC' (R nodes x C inner devices; C > 1 rows "
                         "record the row-sharded permute's pod-axis "
                         "bytes).  Default: --wire-nodes as a single "
                         "(R, 1) shape")
    args = ap.parse_args()

    if args.wire:
        from repro.launch.wire import ensure_host_device_flag, parse_pods
        if args.pods is None:
            args.pods = [str(args.wire_nodes)]
        need = max(n * c for n, c in map(parse_pods, args.pods))
        if args.out == "BENCH_round_step.json":
            args.out = "BENCH_wire_exchange.json"
        # the wire sweep measures bytes on virtual host devices: pin the
        # CPU and the device count before the first jax call
        jax.config.update("jax_platforms", "cpu")
        ensure_host_device_flag(need)
        args.rounds = max(args.rounds, 10)
        run_wire(args)
        return

    results = {}
    for n in args.nodes:
        print(f"== N={n} nodes ==")
        r = measure(n, samples_per_node=args.samples_per_node,
                    batch_size=args.batch_size, rounds=args.rounds)
        results[str(n)] = r
        print(f"  legacy {r['legacy_ms']:8.1f} ms/round   "
              f"jitted {r['jitted_ms']:8.1f} ms/round   "
              f"speedup {r['speedup']:.2f}x")
        if args.phases:
            ph = measure_phases(n, samples_per_node=args.samples_per_node,
                                batch_size=args.batch_size,
                                rounds=args.rounds)
            r["phases"] = ph
            print(f"  phases: train {ph['train_ms']:7.1f}  "
                  f"proto exact {ph['proto_exact_ms']:6.1f} / "
                  f"fused +{ph['proto_fused_ms']:5.1f}  "
                  f"codec {ph['codec_ms']:6.1f}  mix {ph['mix_ms']:6.1f} ms")
            print(f"  update: per-leaf {ph['update_per_leaf_ms']:6.2f}  "
                  f"fused {ph['update_fused_ms']:6.2f} ms")
            print(f"  grad: repack {ph['grad_repack_ms']:6.2f}  "
                  f"plane {ph['grad_plane_ms']:6.2f} ms   "
                  f"mix: tree {ph['mix_tree_ms']:6.2f}  "
                  f"plane {ph['mix_plane_ms']:6.2f} ms")
            print(f"  apply: dense {ph['apply_dense_ms']:6.2f}  "
                  f"fused {ph['apply_fused_ms']:6.2f} ms")
            print(f"  round: exact {ph['round_exact_ms']:7.1f}  "
                  f"fused {ph['round_fused_ms']:7.1f} ms  "
                  f"({ph['fused_round_speedup']:.2f}x)")

    out = {
        "benchmark": "one full ProFe federation round (train + Eq.3 protos "
                     "+ gossip + aggregate), reduced mnist-cnn (8,16), "
                     "dispatch-bound regime",
        "backend": jax.default_backend(),
        "config": {"samples_per_node": args.samples_per_node,
                   "batch_size": args.batch_size,
                   "timed_rounds": args.rounds,
                   "algorithm": "profe", "local_epochs": 1},
        "nodes": results,
    }
    with open(args.out, "w") as f:
        json.dump(out, f, indent=2)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
