"""Decentralized-federated-learning simulator (paper Sec. IV setup).

Runs N nodes over a :class:`~repro.core.topology.TopologySchedule` for R
rounds of E local epochs, handling — per algorithm — what travels on the
wire, at what precision, and how it is aggregated.  The schedule (static
full/ring/star, seeded random-k/Erdős–Rényi, or a time-varying
``[R, N, N]`` stack) lowers once to gossip/include matrices whose
per-round slices enter the jitted round as traced operands.
Communication is metered analytically from the same schedule (Table II,
vectorized ``ScheduleCommAccountant``); per-round global-test F1 is the
Fig. 2 curve; wall-time per algorithm is Table III.

**Round engine.**  Node state is *stacked*: every :class:`NodeState`
leaf carries a leading ``[N, ...]`` node axis, and one jitted program
executes an entire round —

1. local training: ``jax.lax.scan`` over the pre-stacked batch/epoch
   axis with ``jax.vmap(step)`` over nodes (a per-node validity mask
   handles unequal local batch counts),
2. Eq. 3 prototype accumulation through the ``kernels/proto_accum`` op
   (one-hot einsum on CPU, the fused Pallas kernel on TPU): either a
   scanned second pass over a dedicated batch stream
   (``proto_pass="exact"``, the paper's post-training pass) or folded
   into step 1's training scan (``proto_pass="fused"`` — the
   single-pass round: each step's ``f1`` feeds the accumulators
   directly, eliminating one full forward pass per node per round),
3. gossip + aggregation: the shared stacked-node-state math in
   :mod:`repro.core.round_ops` (per-node quantize→exchange→weighted
   mean, per-neighborhood Eq. 4) — the same functions the TPU mesh path
   (``core/mesh_federation.py``) runs,

with the node state donated to the round program so it is updated in
place.  Node count is therefore no longer a Python-side multiplier:
dispatch cost per round is O(1) in N.

:func:`run_federation_loop` keeps the per-node Python-loop reference
(the seed implementation) — it defines the semantics the stacked round
must reproduce, serves ragged node datasets the stacked layout cannot
express, and is the baseline ``benchmarks/round_step.py`` measures the
jitted round against.

This is the *node-level* simulator (paper-faithful, CPU).  The
production mapping of the same round structure onto a TPU mesh ("pod"
axis = federation node) lives in ``repro/launch`` and
``repro/core/mesh_federation.py``.
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import spans
from repro.config.base import FederationConfig, ModelConfig, TrainConfig
from repro.core import baselines as B
from repro.core import round_ops as R
from repro.core import topology as T
from repro.core.aggregation import weighted_plane_mean, weighted_tree_mean
from repro.core.comm import CommMeter, ScheduleCommAccountant
from repro.core.distillation import teacher_active
from repro.core.metrics import accuracy, macro_f1
from repro.core.profe import (NodeState, compute_local_prototypes,
                              init_node_state, make_profe_step,
                              normalize_protos, proto_labels)
from repro.core.prototypes import aggregate_prototypes
from repro.core.quantization import quantize_dequantize_tree
from repro.data import batches
from repro.data.loader import batch_index_lists
from repro.kernels.proto_accum.ops import (proto_accumulate,
                                           proto_accumulate_nodes)
from repro.kernels.quantize.ops import quantize_dequantize_plane_rows
from repro.models import derive_student, forward, init_params
from repro.optim import make_optimizer, make_plane_optimizer
from repro.optim.plane import as_tree, plane_from_tree
from repro.wirespec import WireSpec

# The CPU-unroll-capped scan lives in ``core/scanning.py`` (shared with
# the loop engine's one-program Eq. 3 pass in ``core/profe.py``); the
# historical names stay importable from here (used by tests/benchmarks).
from repro.core.scanning import _DEFAULT_CPU_UNROLL_CAP  # noqa: F401  isort:skip
from repro.core.scanning import cpu_unroll_cap  # noqa: F401  isort:skip
from repro.core.scanning import scan as _scan  # isort:skip

PROTO_PASSES = ("exact", "fused")


@dataclass
class FederationResult:
    f1_per_round: List[float] = field(default_factory=list)
    acc_per_round: List[float] = field(default_factory=list)
    comm: Optional[CommMeter] = None
    elapsed_s: float = 0.0
    algorithm: str = ""
    extras: Dict[str, Any] = field(default_factory=dict)


def _n_proto_classes(cfg: ModelConfig) -> int:
    return cfg.num_classes if cfg.family in ("cnn", "resnet") \
        else cfg.n_proto_classes


# ---------------------------------------------------------------------------
# per-algorithm wiring (shared by the stacked and the loop engine)
# ---------------------------------------------------------------------------

def _algo_wiring(algo: str, teacher_cfg: ModelConfig,
                 student_cfg: ModelConfig, fed: FederationConfig,
                 train: TrainConfig, opt_s, opt_t, *, jit: bool):
    """Returns (step, wire_model, share_protos, wire, model_cfgs).

    wire_cfg: which model travels; share_protos: prototypes on the wire;
    wire: the :class:`repro.wirespec.WireSpec` of the payload (None =
    fp32 wire) — per-group widths from ``fed.quantize_bits`` /
    ``fed.proto_quantize_bits``.
    """
    remat = train.remat
    if algo == "profe":
        step = make_profe_step(teacher_cfg, student_cfg, fed, opt_s, opt_t,
                               grad_clip=train.grad_clip, remat=remat, jit=jit)
        # adapter-rank wire: the factor (and gram) payload groups get
        # their own widths when configured; bits_for falls back to the
        # student width otherwise
        overrides = []
        if fed.adapter_rank and fed.adapter_quantize_bits:
            overrides.append(("adapters", fed.adapter_quantize_bits))
        if fed.adapter_rank and fed.adapter_grams and fed.gram_quantize_bits:
            overrides.append(("grams", fed.gram_quantize_bits))
        wire = WireSpec(student_bits=fed.quantize_bits,
                        proto_bits=fed.proto_quantize_bits,
                        error_feedback=fed.error_feedback,
                        ef_decay=fed.error_feedback_decay,
                        overrides=tuple(overrides)) \
            if fed.quantize_bits else None
        if fed.adapter_rank and wire is None:
            raise ValueError("adapter_rank needs the quantized wire codec "
                             "(set fed.quantize_bits)")
        return step, "student", True, wire, (teacher_cfg, student_cfg)
    if algo == "fedavg":
        step = B.make_fedavg_step(teacher_cfg, opt_s,
                                  grad_clip=train.grad_clip, remat=remat,
                                  jit=jit)
        # "student" slot holds the model
        return step, "student", False, None, (teacher_cfg, teacher_cfg)
    if algo == "fedproto":
        step = B.make_fedproto_step(teacher_cfg, fed, opt_s,
                                    grad_clip=train.grad_clip, remat=remat,
                                    jit=jit)
        return step, None, True, None, (teacher_cfg, teacher_cfg)
    if algo == "fml":
        step = B.make_fml_step(teacher_cfg, student_cfg, fed, opt_t, opt_s,
                               grad_clip=train.grad_clip, remat=remat,
                               jit=jit)
        return step, "student", False, None, (teacher_cfg, student_cfg)
    if algo == "fedgpd":
        step = B.make_fedgpd_step(teacher_cfg, fed, opt_s,
                                  grad_clip=train.grad_clip, remat=remat,
                                  jit=jit)
        return step, "student", True, None, (teacher_cfg, teacher_cfg)
    raise ValueError(f"unknown algorithm {algo!r}")


PLANE_MODES = ("auto", "on", "off")


def _plane_mode(fed: FederationConfig, train: TrainConfig, algo: str,
                student_cfg: ModelConfig) -> bool:
    """Resolve ``fed.param_plane`` to a concrete on/off for this run.

    ``"auto"`` enables the flat parameter plane exactly where the fused
    clip+update sweep is the per-leaf reference's equal: the profe
    student (the only wire model the plane splice is built for) under
    sgd/adamw/adafactor with an all-float32 parameter tree (adafactor's
    factored moments live per buffer *segment* —
    ``make_plane_optimizer``).  ``"on"`` asserts those conditions
    (raises otherwise); everything else — optimizers without a fused
    plane update, mixed-dtype models, the baseline algorithms — keeps
    the per-leaf reference path."""
    mode = fed.param_plane
    if mode not in PLANE_MODES:
        raise ValueError(f"param_plane must be one of {PLANE_MODES}, "
                         f"got {mode!r}")
    if mode == "off":
        return False
    why = None
    if algo != "profe":
        why = f"algorithm {algo!r} (the plane is wired through the " \
              "profe student)"
    elif train.optimizer not in ("sgd", "adamw", "adafactor"):
        why = f"optimizer {train.optimizer!r} (no fused plane update " \
              "in kernels/opt_update)"
    else:
        tmpl = jax.eval_shape(
            functools.partial(init_params, student_cfg),
            jax.random.PRNGKey(0))
        if any(l.dtype != jnp.float32
               for l in jax.tree_util.tree_leaves(tmpl)):
            why = "student has non-float32 leaves (the plane buffer " \
                  "is fp32)"
    if why is None:
        return True
    if mode == "on":
        raise ValueError(f"param_plane='on' is unsupported here: {why}")
    return False


def _init_states(algo: str, model_cfgs, fed: FederationConfig, opt_s, opt_t,
                 ncls: int, *, plane: bool = False) -> List[NodeState]:
    needs_teacher = algo in ("profe", "fml")
    states: List[NodeState] = []
    for i in range(fed.num_nodes):
        rng = jax.random.PRNGKey(fed.seed * 1000 + i)
        if needs_teacher:
            st = init_node_state(model_cfgs[0], model_cfgs[1], rng, opt_s,
                                 opt_t, ncls, plane=plane,
                                 proto_ema=fed.proto_ema)
        else:
            params = init_params(model_cfgs[0], rng)
            proto_acc = None
            if fed.proto_ema and fed.proto_ema > 0:
                proto_acc = (jnp.zeros((ncls, model_cfgs[0].proto_dim),
                                       jnp.float32),
                             jnp.zeros((ncls,), jnp.float32))
            st = NodeState(student=params, teacher={}, opt_s=opt_s.init(params),
                           opt_t={}, global_protos=jnp.zeros(
                               (ncls, model_cfgs[0].proto_dim), jnp.float32),
                           proto_mask=jnp.zeros((ncls,), jnp.float32),
                           round_idx=jnp.zeros((), jnp.int32),
                           proto_acc=proto_acc)
        states.append(st)
    return states


def _payload_template(wire_model, share_protos, stacked: NodeState,
                      ncls: int, proto_dim: int, *, node_axis: bool = True,
                      adapter_rank: int = 0, adapter_grams: bool = False):
    """Shape/dtype skeleton of one node's wire payload — the comm meter
    reads only sizes and dtypes, so metering never touches device data.
    ``node_axis=False`` reads a per-node state (reference loop) instead
    of a stacked ``[N, ...]`` one.  With ``adapter_rank`` > 0 the matrix
    leaves leave the ``"model"`` group and meter as their low-rank
    ``"adapters"`` factors (plus per-layer ``"grams"`` when on) — the
    wire shrinkage IS this template change."""
    payload: Dict[str, Any] = {}
    if wire_model is not None:
        skip = 1 if node_axis else 0
        # as_tree: a plane-backed student meters by its LEAF shapes (the
        # logical wire payload), never by the padded buffer
        tree = as_tree(stacked.student)
        if adapter_rank:
            from repro.core.adapters import (adapter_layout,
                                             adapter_payload_template,
                                             split_student)
            layout = adapter_layout(tree, adapter_rank,
                                    node_axis=node_axis)
            payload.update(adapter_payload_template(layout,
                                                    grams=adapter_grams))
            _, rest = split_student(layout, tree)
            tree = rest
        payload["model"] = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape[skip:], x.dtype),
            tree)
    if share_protos:
        payload["protos"] = jax.ShapeDtypeStruct((ncls, proto_dim),
                                                 np.dtype(np.float32))
        payload["counts"] = jax.ShapeDtypeStruct((ncls,),
                                                 np.dtype(np.float32))
    return payload


def _packed_sent_gb(sched, rounds: int, packed_per_copy: int,
                    n_nodes: int) -> float:
    """Average per-node GB the packed mesh exchange moves over a run:
    directed copies per round (from the schedule) x the per-copy packed
    bytes — the physical twin of ``avg_sent_gb``."""
    edges = sched.directed_edge_counts()
    copies = sum(int(edges[sched.phase_index(rnd)])
                 for rnd in range(rounds))
    return float(copies * packed_per_copy / max(n_nodes, 1) / 1e9)


# ---------------------------------------------------------------------------
# stacked batch staging
# ---------------------------------------------------------------------------

def _stack_round_batches(node_data, batch_size: int, seeds, epochs: int
                         ) -> Optional[Tuple[Dict[str, jnp.ndarray],
                                             jnp.ndarray]]:
    """Gather every node's round batches into ``[T, N, B, ...]`` leaves
    plus a ``[T, N]`` validity mask (nodes with fewer local batches are
    padded with their first batch, masked out of the state update).

    Returns None when the per-node batch shapes are ragged (some node
    holds fewer than ``batch_size`` samples) — the caller falls back to
    the per-node loop engine.
    """
    per_node = []
    for data, seed in zip(node_data, seeds):
        n = len(next(iter(data.values())))
        per_node.append(batch_index_lists(n, batch_size, seed, epochs=epochs))
    if any(not idxs for idxs in per_node):
        return None                       # empty node: loop engine handles it
    lens = {idx.shape[0] for idxs in per_node for idx in idxs}
    if len(lens) != 1:
        return None                       # ragged batch shapes: can't stack
    n_steps = max(len(idxs) for idxs in per_node)
    valid = np.zeros((n_steps, len(node_data)), np.float32)
    for i, idxs in enumerate(per_node):
        valid[:len(idxs), i] = 1.0
        while len(idxs) < n_steps:        # pad: repeat batch 0, masked out
            idxs.append(idxs[0])
    stacked = {
        k: jnp.asarray(np.stack(
            [np.stack([node_data[i][k][per_node[i][t]]
                       for i in range(len(node_data))])
             for t in range(n_steps)]))
        for k in node_data[0]
    }
    return stacked, jnp.asarray(valid)


def _stack_states(states: List[NodeState]) -> NodeState:
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *states)


def _masked_select(v, new_tree, old_tree):
    """Per-node select: leaf [N, ...] from ``new`` where v[n] else ``old``."""
    def sel(n, o):
        return jnp.where(v.reshape((v.shape[0],) + (1,) * (n.ndim - 1))
                         .astype(bool), n, o)
    return jax.tree_util.tree_map(sel, new_tree, old_tree)


# ---------------------------------------------------------------------------
# the jitted round program
# ---------------------------------------------------------------------------

def _make_proto_pass(proto_cfg: ModelConfig, ncls: int):
    """The exact (post-training) Eq. 3 pass over a stacked ``[T, N, B,
    ...]`` proto batch stream: scan over T, vmap the forward over nodes,
    accumulate per-class sums/counts through the shared
    ``proto_accumulate_nodes`` op (the historical one-hot einsum on CPU,
    the Pallas kernel on TPU — no ``[N, B, C]`` one-hot intermediate).

    Factored out of :func:`_make_round_parts` so
    ``benchmarks/round_step.py --phases`` can jit and time this pass in
    isolation (the "proto" phase of the exact round)."""

    @jax.named_scope("round.protos")
    def proto_pass(students, pxb, pvalid):
        students = as_tree(students)   # plane buffers forward as views
        proto_dim = proto_cfg.proto_dim
        n_nodes = pvalid.shape[1]
        sums0 = jnp.zeros((n_nodes, ncls, proto_dim), jnp.float32)
        counts0 = jnp.zeros((n_nodes, ncls), jnp.float32)

        def pbody(carry, inp):
            sums, counts = carry
            batch, v = inp
            out = jax.vmap(
                lambda p, b: forward(proto_cfg, p, b, remat=False))(
                    students, batch)
            labels = proto_labels(proto_cfg, batch)        # [N, B]
            s_add, c_add = proto_accumulate_nodes(out.f1, labels, ncls)
            sums = sums + s_add * v[:, None, None]
            counts = counts + c_add * v[:, None]
            return (sums, counts), ()

        (sums, counts), _ = _scan(pbody, (sums0, counts0), (pxb, pvalid),
                                  pvalid.shape[0])
        return sums, counts

    return proto_pass


def _mean_loss(losses, valid):
    """Mean of the per-step, per-node losses ``[T, N]`` over the valid
    steps (the padded steps of nodes with fewer batches drop out)."""
    return jnp.sum(losses * valid) / jnp.maximum(jnp.sum(valid), 1.0)


def _make_round_parts(step: Callable, proto_cfg: ModelConfig, ncls: int, *,
                      share_protos: bool, wire_model: Optional[str],
                      bits: Optional[int] | WireSpec,
                      proto_pass: str = "exact", proto_ema: float = 0.0,
                      adapter_rank: int = 0, adapter_grams: bool = False):
    """The three phases of one stacked round, as plain traceable
    functions:

    * ``train_phase`` — local epochs (scan over the batch axis, vmap
      over nodes) + Eq. 3 prototype accumulation → ``(state, protos,
      counts, loss)``, ``loss`` the student loss averaged over the
      round's valid steps and nodes,
    * ``share_phase`` — the wire codec round-trip of this state's
      payload (what every receiver reconstructs; updates the
      error-feedback ``CodecState`` in place) → ``(state, recv_student,
      protos_rx)``,
    * ``mix_phase`` — gossip weights on the received views + Eq. 4
      aggregation → ``state``.

    ``proto_pass`` selects how Eq. 3 runs inside ``train_phase``:
    ``"exact"`` streams the dedicated proto batches a second time after
    training (the paper's post-training pass, bit-identical to the
    historical engines); ``"fused"`` accumulates sums/counts inside the
    training scan from the ``f1`` the step's loss already computed —
    one forward per batch instead of two, prototypes built from the
    evolving student.  Fused mode ignores ``pxb``/``pvalid`` (drivers
    pass an empty placeholder and skip staging the proto stream).

    ``proto_ema`` > 0 carries the RAW Eq. 3 accumulators across rounds
    (``NodeState.proto_acc``): this round's sums/counts become
    ``new + proto_ema * previous`` before the shared normalization, so
    prototypes smooth over the per-round minibatch noise.  In fused
    mode the decayed carry warm-starts the scan accumulators; in exact
    mode it is added after the pass — either way the blended raw
    accumulators are stored back into the carry for the next round.

    The sequential engine jits their composition as ONE program
    (:func:`_make_round_fn`); the pipelined engine
    (``run_federation(overlap=...)``) jits each phase separately so the
    driver can re-order dispatch.  Phases unused by an algorithm pass
    ``()`` placeholders (no pytree leaves), so both drivers share one
    code path for every algorithm."""
    if proto_pass not in PROTO_PASSES:
        raise ValueError(f"proto_pass must be one of {PROTO_PASSES}, "
                         f"got {proto_pass!r}")
    spec = WireSpec.from_bits(bits) if bits else None
    adapters = bool(adapter_rank) and wire_model is not None \
        and share_protos and spec is not None
    fused = share_protos and proto_pass == "fused"
    exact_pass = _make_proto_pass(proto_cfg, ncls) \
        if share_protos and not fused else None

    def train_phase(state: NodeState, xb, valid, pxb, pvalid,
                    teacher_on: bool, all_valid: bool = False):
        # 1) local training: scan over the batch axis, vmap over nodes.
        # ``all_valid`` (static) skips the per-step mask merge when every
        # node runs the same number of batches (the common, iid case).
        if fused:
            # single-pass round: the carry grows (sums, counts) and the
            # body feeds the step's own f1 straight into Eq. 3 —
            # padded/invalid steps are masked out of the accumulators
            # exactly like they are masked out of the state
            proto_dim = proto_cfg.proto_dim
            n_nodes = valid.shape[1]
            if proto_ema and proto_ema > 0:
                # EMA carry: warm-start the accumulators at the decayed
                # previous round's raw sums/counts
                sums0 = proto_ema * state.proto_acc[0]
                counts0 = proto_ema * state.proto_acc[1]
            else:
                sums0 = jnp.zeros((n_nodes, ncls, proto_dim), jnp.float32)
                counts0 = jnp.zeros((n_nodes, ncls), jnp.float32)

            def fbody(carry, inp):
                st, sums, counts = carry
                batch, v = inp
                new, m = jax.vmap(
                    lambda s, b: step(s, b, teacher_on))(st, batch)
                with jax.named_scope("round.protos"):
                    labels = proto_labels(proto_cfg, batch)    # [N, B]
                    s_add, c_add = proto_accumulate_nodes(m["f1"], labels,
                                                          ncls)
                    sums = sums + s_add * v[:, None, None]
                    counts = counts + c_add * v[:, None]
                st = new if all_valid else _masked_select(v, new, st)
                return (st, sums, counts), m["loss_s"]

            (state, sums, counts), losses = _scan(
                fbody, (state, sums0, counts0), (xb, valid),
                valid.shape[0])
            state = state._replace(round_idx=state.round_idx + 1)
            if proto_ema and proto_ema > 0:
                state = state._replace(proto_acc=(sums, counts))
            with jax.named_scope("round.protos"):
                protos = normalize_protos(sums, counts)
            return state, protos, counts, _mean_loss(losses, valid)

        def body(carry, inp):
            batch, v = inp
            new, m = jax.vmap(lambda s, b: step(s, b, teacher_on))(carry,
                                                                   batch)
            return (new if all_valid else _masked_select(v, new, carry)), \
                m["loss_s"]

        state, losses = _scan(body, state, (xb, valid), valid.shape[0])
        state = state._replace(round_idx=state.round_idx + 1)
        loss = _mean_loss(losses, valid)
        if not share_protos:
            return state, (), (), loss

        # 2) Eq. 3 prototype accumulation: the factored exact pass
        #    (post-training student forward over the proto stream)
        sums, counts = exact_pass(state.student, pxb, pvalid)
        with jax.named_scope("round.protos"):
            if proto_ema and proto_ema > 0:
                sums = sums + proto_ema * state.proto_acc[0]
                counts = counts + proto_ema * state.proto_acc[1]
                state = state._replace(proto_acc=(sums, counts))
            protos = normalize_protos(sums, counts)
        return state, protos, counts, loss

    @jax.named_scope("round.codec")
    def share_phase(state: NodeState, protos):
        # 3a) the wire: receiver-side reconstruction.  A node's own
        #    model copy never crosses it (mixes unquantized);
        #    prototypes (own included) mix from the receiver-side view,
        #    exactly like the reference loop.  The view is
        #    reconstructed through the packed node wire codec — student
        #    and prototypes ride ONE [N, R, 512] buffer with per-(leaf,
        #    node) segment scales, exactly what the mesh path's sparse
        #    exchange physically moves (bit-identical to per-leaf
        #    codes).  With error feedback the codec is stateful: the
        #    per-node residual (state.wire_state, part of the donated
        #    carry) is replayed into the payload and updated in the
        #    same pass — its ``seq`` counter advances once per share,
        #    pinning which payload the carried residual corrects when
        #    the pipelined driver mixes stale-by-one.
        if adapters:
            # adapter-rank wire: the matrix leaves' round delta leaves
            # as low-rank factors (its own payload group, its own spec
            # width), the dense rest + protos ride alongside, and the
            # reference snapshot advances to the just-shared student —
            # share-time snapshotting keeps the scheme exact under the
            # stale-by-one pipeline (the mix adds merged deltas ON TOP
            # of the current student, never rebuilding from the ref).
            groups, new_ad, _ = R.adapter_share_nodes(
                state.student, state.adapter_state, rank=adapter_rank,
                grams=adapter_grams)
            state = state._replace(adapter_state=new_ad)
            payload = dict(groups)
            payload["protos"] = protos
            if spec.error_feedback:
                recv, new_ws = R.quantize_dequantize_per_node(
                    payload, spec=spec, state=state.wire_state)
                state = state._replace(wire_state=new_ws)
            else:
                recv = R.quantize_dequantize_per_node(payload, spec=spec)
            recv = dict(recv)
            protos_rx = recv.pop("protos")
            return state, recv, protos_rx
        if wire_model is not None and spec and share_protos:
            payload = {"protos": protos, "student": state.student}
            if spec.error_feedback:
                recv, new_ws = R.quantize_dequantize_per_node(
                    payload, spec=spec, state=state.wire_state)
                state = state._replace(wire_state=new_ws)
            else:
                recv = R.quantize_dequantize_per_node(payload, spec=spec)
            return state, recv["student"], recv["protos"]
        recv_student = (R.quantize_dequantize_per_node(
            state.student, spec.bits_for("student"))
            if (wire_model is not None and spec)
            else (state.student if wire_model is not None else ()))
        protos_rx = (R.dequantize_leaf(
            *R.quantize_leaf_per_node(protos, spec.bits_for("protos")))
            if (share_protos and spec) else
            (protos if share_protos else ()))
        return state, recv_student, protos_rx

    @jax.named_scope("round.mix")
    def mix_phase(state: NodeState, recv_student, protos_rx, counts,
                  w_self, w_neigh, include) -> NodeState:
        # 3b) gossip + aggregation (shared round_ops core)
        if adapters:
            # merge-based aggregation: neighbors' low-rank deltas apply
            # straight onto the current student (RegMean-adjusted when
            # grams ride), the dense rest keeps the classic gossip mix
            state = state._replace(student=R.adapter_merge_nodes(
                state.student, recv_student, w_self, w_neigh,
                rank=adapter_rank, grams=adapter_grams))
        elif wire_model is not None:
            state = state._replace(student=R.mix_node_trees(
                w_self, w_neigh, state.student, recv_student))
        if share_protos:
            gp, mask = R.neighborhood_prototype_aggregate(include, protos_rx,
                                                          counts)
            state = state._replace(global_protos=gp, proto_mask=mask)
        return state

    return train_phase, share_phase, mix_phase


def _make_round_fn(step: Callable, proto_cfg: ModelConfig, ncls: int, *,
                   share_protos: bool, wire_model: Optional[str],
                   bits: Optional[int] | WireSpec,
                   proto_pass: str = "exact", proto_ema: float = 0.0,
                   adapter_rank: int = 0, adapter_grams: bool = False):
    """One full federation round as a single compiled program over
    stacked node state: scan(vmap(step)) → Eq. 3 proto pass (exact
    second stream, or fused into the training scan — ``proto_pass``) →
    round_ops gossip/aggregate → ``(state, loss)``, ``loss`` the
    round's mean student loss.  ``teacher_on`` is a static arg (two
    program variants, exactly like the per-node step).

    The gossip/include matrices ``(w_self [N], w_neigh [N, N],
    include [N, N])`` are *traced operands* — the driver passes the
    current round's slice of the lowered ``TopologySchedule`` stacks, so
    a round-varying topology never rebuilds or retraces the program."""
    train_phase, share_phase, mix_phase = _make_round_parts(
        step, proto_cfg, ncls, share_protos=share_protos,
        wire_model=wire_model, bits=bits, proto_pass=proto_pass,
        proto_ema=proto_ema, adapter_rank=adapter_rank,
        adapter_grams=adapter_grams)

    def round_fn(state: NodeState, xb, valid, pxb, pvalid,
                 w_self, w_neigh, include,
                 teacher_on: bool, all_valid: bool = False):
        state, protos, counts, loss = train_phase(
            state, xb, valid, pxb, pvalid, teacher_on, all_valid)
        state, recv_student, protos_rx = share_phase(state, protos)
        return mix_phase(state, recv_student, protos_rx, counts,
                         w_self, w_neigh, include), loss

    donate = (0,) if jax.default_backend() != "cpu" else ()
    return jax.jit(round_fn, static_argnames=("teacher_on", "all_valid"),
                   donate_argnums=donate)


def _make_phase_fns(step: Callable, proto_cfg: ModelConfig, ncls: int, *,
                    share_protos: bool, wire_model: Optional[str],
                    bits: Optional[int] | WireSpec,
                    proto_pass: str = "exact", proto_ema: float = 0.0,
                    adapter_rank: int = 0, adapter_grams: bool = False):
    """The pipelined engine's three jitted programs — the same traced
    phase bodies as the sequential :func:`_make_round_fn`, so splitting
    the round changes jit boundaries (and therefore dispatch order),
    never the math."""
    train_phase, share_phase, mix_phase = _make_round_parts(
        step, proto_cfg, ncls, share_protos=share_protos,
        wire_model=wire_model, bits=bits, proto_pass=proto_pass,
        proto_ema=proto_ema, adapter_rank=adapter_rank,
        adapter_grams=adapter_grams)
    donate = (0,) if jax.default_backend() != "cpu" else ()
    return (jax.jit(train_phase,
                    static_argnames=("teacher_on", "all_valid"),
                    donate_argnums=donate),
            jax.jit(share_phase, donate_argnums=donate),
            jax.jit(mix_phase, donate_argnums=donate))


# ---------------------------------------------------------------------------
# driver (stacked engine)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _eval_fn(cfg: ModelConfig):
    """The per-round evaluation as one jitted program per test-batch
    shape.  It takes the student as the round engine holds it (a
    :class:`Plane` or a pytree), the node to evaluate and one test
    batch, and returns only the argmax predictions.  ``node`` None: an
    unstacked student, ``[B]`` predictions; an int: that node of a
    node-stacked student, ``[B]``; an int vector ``[K]``: those nodes
    through one vmapped forward, ``[K, B]``.  A language model's
    predictions carry its ``T`` axis after ``B``.  Cached by config, so
    the rounds after the first re-use the compiled program."""

    def predict(params, batch):
        out = forward(cfg, as_tree(params), batch, remat=False)
        return jnp.argmax(out.logits, -1)

    def run(students, node, batch):
        if node is None:
            return predict(students, batch)
        students = jax.tree_util.tree_map(lambda x: x[node], students)
        if node.ndim:
            return jax.vmap(predict, in_axes=(0, None))(students, batch)
        return predict(students, batch)

    return jax.jit(run)


def _eval_params(cfg: ModelConfig, students, test_data, node=None,
                 batch_size: int = 256):
    """Global-test (macro-F1, accuracy) with the classifier head (a
    language model: next-token accuracy), from :func:`_eval_fn`'s
    program: one call per test batch, every call dispatched before any
    prediction is read.  ``node`` as there; an int vector gives one
    (macro-F1, accuracy) pair per node, in a list."""
    fn = _eval_fn(cfg)
    if node is not None:
        node = np.asarray(node, np.int32)
    n = len(next(iter(test_data.values())))
    preds = [fn(students, node, {k: v[i:i + batch_size]
                                 for k, v in test_data.items()})
             for i in range(0, n, batch_size)]
    spans.count("fed.eval.programs", len(preds))
    vector = np.ndim(node) == 1
    rows = len(node) if vector else 1
    y_pred = np.concatenate([np.asarray(p).reshape(rows, -1)
                             for p in preds], axis=1)
    cnn = cfg.family in ("cnn", "resnet")
    y_true = np.asarray(test_data["label" if cnn else "labels"]).reshape(-1)
    ncls = _n_proto_classes(cfg) if cnn else int(min(cfg.vocab_size, 4096))
    scores = [(macro_f1(y_true, p, ncls), accuracy(y_true, p))
              for p in y_pred]
    return scores if vector else scores[0]


def _eval_nodes(eval_cfg, students, n_nodes: int, test_data,
                eval_all_nodes: bool, extras: Dict[str, Any]):
    """Per-round evaluation of ``students``: the stacked engine's
    node-stacked student, or the loop engine's list of per-node
    students.  Default: node 0 (cheap; exact on full graphs where every
    node ends identical).  ``eval_all_nodes`` evaluates every node and
    returns the mean — the per-node curves and spread land in extras,
    so sparse-topology divergence is visible (Fig. 2 as mean±spread over
    nodes); a stacked student takes one vmapped program per test batch
    for it."""
    per_node = isinstance(students, list)
    if not eval_all_nodes:
        return _eval_params(eval_cfg, students[0], test_data) if per_node \
            else _eval_params(eval_cfg, students, test_data, node=0)
    if per_node:
        scores = [_eval_params(eval_cfg, s, test_data) for s in students]
    else:
        scores = _eval_params(eval_cfg, students, test_data,
                              node=np.arange(n_nodes))
    f1s = [p[0] for p in scores]
    accs = [p[1] for p in scores]
    extras.setdefault("f1_per_round_nodes", []).append(f1s)
    extras.setdefault("acc_per_round_nodes", []).append(accs)
    extras.setdefault("f1_std_per_round", []).append(float(np.std(f1s)))
    return float(np.mean(f1s)), float(np.mean(accs))


def _apply_self_floor(w_self_st, w_neigh_st, floor: float):
    """Floor every node's self-weight in the lowered gossip stacks.

    Stale-by-one mixing (``overlap="rounds"``) on dense graphs can
    collapse: size-proportional gossip weights give a node's own model
    only ``1/N`` mass, so mixing N-1 stale neighbor payloads every
    round drags all nodes toward last round's average and training
    never progresses (N=20 full graph: F1 falls to chance, recorded in
    ``reports/table3_time.json``).  Raising the self-weight to
    ``max(w_self, floor)`` and rescaling neighbor weights by
    ``(1 - new_self) / sum(w_neigh)`` keeps rows summing to 1 while
    bounding the stale mass per round.  Isolated nodes (no neighbors)
    already hold self-weight 1 and pass through unchanged."""
    if not 0.0 < floor < 1.0:
        raise ValueError(f"stale_self_floor must be in (0, 1), "
                         f"got {floor!r}")
    w_self = np.asarray(w_self_st, np.float32)          # [R, N]
    w_neigh = np.asarray(w_neigh_st, np.float32)        # [R, N, N]
    neigh_sum = w_neigh.sum(axis=-1)
    has_neigh = neigh_sum > 0
    new_self = np.where(has_neigh, np.maximum(w_self, floor), w_self)
    scale = np.where(has_neigh, (1.0 - new_self)
                     / np.maximum(neigh_sum, 1e-12), 0.0)
    return (jnp.asarray(new_self),
            jnp.asarray(w_neigh * scale[..., None]))


OVERLAPS = (None, "none", "rounds")


def run_federation(teacher_cfg: ModelConfig, fed: FederationConfig,
                   train: TrainConfig, node_data: List[Dict[str, np.ndarray]],
                   test_data: Dict[str, np.ndarray],
                   *, verbose: bool = False,
                   eval_all_nodes: bool = False,
                   overlap: Optional[str] = None,
                   stale_self_floor: Optional[float] = None
                   ) -> FederationResult:
    """Run one algorithm end-to-end; fed.algorithm selects it.

    Uses the vectorized stacked-node-state round engine; falls back to
    :func:`run_federation_loop` when node datasets are too ragged to
    stack (some node smaller than one batch; ``overlap`` is ignored
    there — the reference loop is always sequential).

    ``fed.proto_pass`` selects the Eq. 3 pass: ``"exact"`` (default,
    post-training second stream, bit-identical to the historical
    engines) or ``"fused"`` (in-scan accumulation, one forward per
    batch — the single-pass round; no proto batch stream is staged).

    ``stale_self_floor`` (only with ``overlap="rounds"``) floors every
    node's gossip self-weight via :func:`_apply_self_floor` — the knob
    that recovers stale-by-one mixing on dense graphs, where the 1/N
    self-weight otherwise lets N-1 stale payloads swamp each round's
    training (full-graph N=20 collapse in reports/table3_time.json).

    ``overlap`` selects the round pipeline:

    * ``None`` (default) — the sequential engine: one jitted program
      per round (train → share → mix), host staging and evaluation
      strictly between rounds.
    * ``"none"`` — the pipelined driver without staleness: the round
      splits into three jitted phase programs (same traced bodies, so
      results are bit-identical to the sequential engine, asserted in
      tests) and the host stages round ``t+1``'s batches while round
      ``t``'s device programs are in flight (JAX async dispatch).
    * ``"rounds"`` — stale-by-one mixing: round ``t`` mixes the payload
      *shared at round ``t-1``* (``state_t^+ = mix(state_t^-,
      payload_{t-1})``), so round ``t``'s share runs concurrently with
      round ``t+1``'s local epochs — the round's critical path moves
      from ``train + gossip`` toward ``max(train, gossip)``.  Round 0
      is a sequential round (share and mix in the same round): the
      nodes start from independent initializations, and mixing their
      unaligned models stale-by-one dragged F1 down for several rounds.
      Round 1 then trains and shares without a mix, and round ``t >=
      2`` mixes payload ``t-1``.  With error feedback the
      ``CodecState.seq`` counter pins the pairing: the residual carried
      into share ``t`` is the one produced by share ``t-1`` (asserted
      across carried rounds in tests).  A run of R >= 2 rounds applies
      R-1 mixes; the final round's payload is shared but never consumed.

    The driver's steps are :mod:`repro.spans` spans: ``fed.run`` around
    the call, ``fed.init`` around the node state's set-up, ``fed.round``
    (a profiler step) around each round, and inside it ``fed.stage``,
    ``fed.dispatch``, ``fed.meter``, ``fed.eval`` and ``fed.sync``.
    """
    with spans.span("fed.run"):
        return _run_federation(teacher_cfg, fed, train, node_data,
                               test_data, verbose=verbose,
                               eval_all_nodes=eval_all_nodes,
                               overlap=overlap,
                               stale_self_floor=stale_self_floor)


def _run_federation(teacher_cfg: ModelConfig, fed: FederationConfig,
                    train: TrainConfig,
                    node_data: List[Dict[str, np.ndarray]],
                    test_data: Dict[str, np.ndarray], *, verbose: bool,
                    eval_all_nodes: bool, overlap: Optional[str],
                    stale_self_floor: Optional[float]) -> FederationResult:
    if overlap not in OVERLAPS:
        raise ValueError(f"overlap must be one of {OVERLAPS}, "
                         f"got {overlap!r}")
    if fed.proto_pass not in PROTO_PASSES:
        raise ValueError(f"proto_pass must be one of {PROTO_PASSES}, "
                         f"got {fed.proto_pass!r}")
    if stale_self_floor is not None and overlap != "rounds":
        raise ValueError("stale_self_floor only applies to the "
                         "stale-by-one pipeline (overlap='rounds'), "
                         f"got overlap={overlap!r}")
    algo = fed.algorithm
    student_cfg = derive_student(teacher_cfg)
    n_nodes = fed.num_nodes
    assert len(node_data) == n_nodes
    sched = T.make_schedule(n_nodes, fed.topology, rounds=fed.rounds,
                            seed=fed.seed)
    ncls = _n_proto_classes(teacher_cfg)
    sizes = [len(next(iter(d.values()))) for d in node_data]

    opt_s = make_optimizer(train.optimizer, train.learning_rate,
                           weight_decay=train.weight_decay,
                           momentum=train.momentum)
    opt_t = make_optimizer(train.optimizer, train.learning_rate,
                           weight_decay=train.weight_decay,
                           momentum=train.momentum)
    use_plane = _plane_mode(fed, train, algo, student_cfg)
    if use_plane:
        # flat parameter plane: the student optimizer becomes the fused
        # clip+update sweep over the [N, R, 512] buffer (the clip moves
        # inside the optimizer — the step skips its per-leaf clip pass)
        opt_s = make_plane_optimizer(train.optimizer, train.learning_rate,
                                     weight_decay=train.weight_decay,
                                     momentum=train.momentum,
                                     grad_clip=train.grad_clip)

    step, wire_model, share_protos, bits, model_cfgs = _algo_wiring(
        algo, teacher_cfg, student_cfg, fed, train, opt_s, opt_t, jit=False)

    # stage round 0's batches up front so raggedness is known before any
    # state is allocated (fallback keeps the per-node reference path)
    with spans.span("fed.stage"):
        probe = _stack_round_batches(
            node_data, train.batch_size,
            [fed.seed + 0 * 997 + i for i in range(n_nodes)],
            fed.local_epochs)
    if probe is None:
        return run_federation_loop(teacher_cfg, fed, train, node_data,
                                   test_data, verbose=verbose,
                                   eval_all_nodes=eval_all_nodes)

    meter = ScheduleCommAccountant(sched)
    eval_cfg = model_cfgs[1] if algo in ("profe", "fml") else model_cfgs[0]
    proto_cfg = eval_cfg
    needs_teacher = algo in ("profe", "fml")
    adapters_on = bool(fed.adapter_rank) and wire_model is not None \
        and share_protos and isinstance(bits, WireSpec)
    with spans.span("fed.init"):
        stacked = _stack_states(
            _init_states(algo, model_cfgs, fed, opt_s, opt_t, ncls,
                         plane=use_plane))
        if adapters_on:
            # adapter-rank wire: the per-node reference snapshot (and gram
            # carry) rides the stacked NodeState through the jitted round
            from repro.core.adapters import adapter_layout, init_adapter_state
            a_layout = adapter_layout(as_tree(stacked.student),
                                      fed.adapter_rank, node_axis=True)
            stacked = stacked._replace(adapter_state=init_adapter_state(
                a_layout, as_tree(stacked.student), grams=fed.adapter_grams))
        if isinstance(bits, WireSpec) and bits.error_feedback:
            # stateful codec: zero residual per node, shaped like the wire
            # payload — carried inside the stacked NodeState from here on
            from repro.core.wire_state import init_codec_state
            ef_payload = {"protos": jnp.zeros(
                (n_nodes, ncls, proto_cfg.proto_dim), jnp.float32)}
            if adapters_on:
                # the residual mirrors the adapter payload structure:
                # factor-shaped zeros + the dense rest (+ gram zeros)
                from repro.core.adapters import zero_wire_payload
                ef_payload.update(zero_wire_payload(
                    a_layout, as_tree(stacked.student),
                    grams=fed.adapter_grams))
            else:
                ef_payload["student"] = stacked.student
            stacked = stacked._replace(
                wire_state=init_codec_state(ef_payload, n_nodes=n_nodes))

    # the lowered schedule: [R, N]/[R, N, N] stacks indexed per round and
    # fed to the jitted round as traced operands (R == 1 for static)
    w_self_st, w_neigh_st, include_st = sched.lower(sizes)
    if stale_self_floor is not None:
        w_self_st, w_neigh_st = _apply_self_floor(w_self_st, w_neigh_st,
                                                  stale_self_floor)
    # fused mode never streams the proto batches — the training scan
    # accumulates Eq. 3 itself, so the drivers skip staging them
    stream_protos = share_protos and fed.proto_pass != "fused"
    round_fn = _make_round_fn(step, proto_cfg, ncls,
                              share_protos=share_protos,
                              wire_model=wire_model, bits=bits,
                              proto_pass=fed.proto_pass,
                              proto_ema=fed.proto_ema,
                              adapter_rank=fed.adapter_rank if adapters_on
                              else 0, adapter_grams=fed.adapter_grams)
    payload = _payload_template(wire_model, share_protos, stacked, ncls,
                                proto_cfg.proto_dim,
                                adapter_rank=fed.adapter_rank if adapters_on
                                else 0, adapter_grams=fed.adapter_grams)

    result = FederationResult(comm=meter, algorithm=algo)
    result.extras["proto_pass"] = fed.proto_pass
    result.extras["param_plane"] = use_plane
    if adapters_on:
        result.extras["adapter_rank"] = fed.adapter_rank
        result.extras["adapter_grams"] = fed.adapter_grams
    if fed.proto_ema:
        result.extras["proto_ema"] = fed.proto_ema
    if stale_self_floor is not None:
        result.extras["stale_self_floor"] = stale_self_floor
    # one consistent wire number: the logical (Table II) bytes per copy
    # next to the physical packed-codec bytes the mesh exchange moves
    from repro.core.comm import packed_copy_bytes
    from repro.core.quantization import tree_wire_bytes
    result.extras["wire_bytes_per_copy"] = tree_wire_bytes(payload, bits)
    result.extras["wire_bytes_packed_per_copy"] = \
        packed_copy_bytes(payload, bits)
    # per-node GB actually moved by the packed mesh exchange over the
    # whole run (degree-weighted, per round) — the physical twin of
    # avg_sent_gb, so one result row carries the full bytes-vs-F1
    # tradeoff without a second accounting script
    result.extras["avg_sent_packed_gb"] = _packed_sent_gb(
        sched, fed.rounds, result.extras["wire_bytes_packed_per_copy"],
        n_nodes)
    round_times: List[float] = []
    result.extras["round_times_s"] = round_times
    losses: List[float] = []
    result.extras["loss_per_round"] = losses
    result.extras["engine"] = "stacked"

    def finish_round(rnd: int, loss, tag: str) -> None:
        # metering is analytic and vectorized — per-copy bytes from the
        # payload skeleton times the schedule's degree vectors,
        # byte-identical to the reference loop's per-edge meter
        with spans.span("fed.meter"):
            meter.record_round(payload, kind=algo, round_idx=rnd, bits=bits)
        with spans.span("fed.eval"):
            f1, acc = _eval_nodes(eval_cfg, stacked.student, n_nodes,
                                  test_data, eval_all_nodes, result.extras)
        result.f1_per_round.append(f1)
        result.acc_per_round.append(acc)
        with spans.span("fed.sync"):
            losses.append(float(loss))
        if verbose:
            print(f"[{tag}] round {rnd + 1}/{fed.rounds} "
                  f"f1={f1:.4f} acc={acc:.4f} "
                  f"sent={meter.avg_sent_gb():.4f}GB")

    empty = ({}, jnp.zeros((0, n_nodes), jnp.float32))
    if overlap is not None:
        train_jit, share_jit, mix_jit = _make_phase_fns(
            step, proto_cfg, ncls, share_protos=share_protos,
            wire_model=wire_model, bits=bits, proto_pass=fed.proto_pass,
            proto_ema=fed.proto_ema,
            adapter_rank=fed.adapter_rank if adapters_on else 0,
            adapter_grams=fed.adapter_grams)
        staged_next = probe
        with spans.span("fed.stage"):
            proto_next = _stack_round_batches(
                node_data, train.batch_size, [fed.seed] * n_nodes, 1) \
                if stream_protos else empty
        recv_prev = None
        for rnd in range(fed.rounds):
            with spans.span("fed.round", step=rnd) as this_round:
                t_on = teacher_active(fed.alpha_s, fed.alpha_limit, rnd) \
                    if algo == "profe" else needs_teacher
                xb, valid = staged_next
                pxb, pvalid = proto_next
                p = sched.phase_index(rnd)
                with spans.span("fed.dispatch"):
                    stacked, protos, counts, loss = train_jit(
                        stacked, xb, valid, pxb, pvalid, teacher_on=t_on,
                        all_valid=bool(np.all(np.asarray(valid) == 1.0)))
                    if overlap == "rounds" and rnd > 0:
                        # stale-by-one: mix the payload shared LAST round
                        # into this round's trained state, then share this
                        # round's payload — its consumption waits until
                        # round t+1, so the device runs it concurrently
                        # with whatever the host (and the next round's
                        # training) does meanwhile.  Round 0 mixes
                        # synchronously (the else branch): nodes start
                        # from independent initializations, and a stale
                        # mix of unaligned models collapses the first
                        # rounds
                        if recv_prev is not None:
                            stacked = mix_jit(stacked, *recv_prev,
                                              w_self_st[p], w_neigh_st[p],
                                              include_st[p])
                        stacked, recv_student, protos_rx = share_jit(
                            stacked, protos)
                        recv_prev = (recv_student, protos_rx, counts)
                    else:
                        stacked, recv_student, protos_rx = share_jit(
                            stacked, protos)
                        stacked = mix_jit(stacked, recv_student, protos_rx,
                                          counts, w_self_st[p],
                                          w_neigh_st[p], include_st[p])
                # round t's phase programs are dispatched, not finished
                # (JAX async dispatch): stage round t+1's batches on the
                # host while the device runs them — the pipeline's
                # host/device overlap, and the measured critical-path win
                if rnd + 1 < fed.rounds:
                    with spans.span("fed.stage"):
                        staged_next = _stack_round_batches(
                            node_data, train.batch_size,
                            [fed.seed + (rnd + 1) * 997 + i
                             for i in range(n_nodes)], fed.local_epochs)
                        assert staged_next is not None  # raggedness is static
                        proto_next = _stack_round_batches(
                            node_data, train.batch_size,
                            [fed.seed + rnd + 1] * n_nodes, 1) \
                            if stream_protos else empty
                finish_round(rnd, loss, f"{algo}/overlap={overlap}")
            round_times.append(this_round.seconds)
    else:
        for rnd in range(fed.rounds):
            with spans.span("fed.round", step=rnd) as this_round:
                t_on = teacher_active(fed.alpha_s, fed.alpha_limit, rnd) \
                    if algo == "profe" else needs_teacher
                with spans.span("fed.stage"):
                    staged = probe if rnd == 0 else _stack_round_batches(
                        node_data, train.batch_size,
                        [fed.seed + rnd * 997 + i for i in range(n_nodes)],
                        fed.local_epochs)
                    proto_staged = _stack_round_batches(
                        node_data, train.batch_size,
                        [fed.seed + rnd] * n_nodes, 1) \
                        if stream_protos else empty
                xb, valid = staged
                pxb, pvalid = proto_staged
                p = sched.phase_index(rnd)
                with spans.span("fed.dispatch"):
                    stacked, loss = round_fn(
                        stacked, xb, valid, pxb, pvalid, w_self_st[p],
                        w_neigh_st[p], include_st[p], teacher_on=t_on,
                        all_valid=bool(np.all(np.asarray(valid) == 1.0)))
                finish_round(rnd, loss, algo)
            round_times.append(this_round.seconds)

    result.elapsed_s = sum(round_times)
    result.extras["avg_sent_gb"] = meter.avg_sent_gb()
    result.extras["avg_received_gb"] = meter.avg_received_gb()
    return result


# ---------------------------------------------------------------------------
# reference engine: the per-node Python loop (seed semantics)
# ---------------------------------------------------------------------------

def run_federation_loop(teacher_cfg: ModelConfig, fed: FederationConfig,
                        train: TrainConfig,
                        node_data: List[Dict[str, np.ndarray]],
                        test_data: Dict[str, np.ndarray],
                        *, verbose: bool = False,
                        eval_all_nodes: bool = False) -> FederationResult:
    """Per-node Python-loop round engine (the seed implementation).

    Kept as the executable definition of round semantics: the stacked
    engine must match it to numerical noise (asserted in tests), ragged
    node datasets fall back to it, and ``benchmarks/round_step.py``
    measures the jitted round against it.  It walks the same
    :class:`~repro.core.topology.TopologySchedule` as the stacked engine
    (per-round adjacency for time-varying specs) but keeps the per-edge
    ``CommMeter`` loop — the reference the vectorized accounting is
    asserted byte-identical to.

    ``fed.proto_pass="fused"`` is honored here too (the reference
    semantics of the stacked fused round): Eq. 3 sums/counts accumulate
    from each training step's ``f1`` metric instead of the
    post-training :func:`~repro.core.profe.compute_local_prototypes`
    stream.
    """
    algo = fed.algorithm
    if fed.proto_pass not in PROTO_PASSES:
        raise ValueError(f"proto_pass must be one of {PROTO_PASSES}, "
                         f"got {fed.proto_pass!r}")
    fused = fed.proto_pass == "fused"
    student_cfg = derive_student(teacher_cfg)
    n_nodes = fed.num_nodes
    assert len(node_data) == n_nodes
    sched = T.make_schedule(n_nodes, fed.topology, rounds=fed.rounds,
                            seed=fed.seed)
    meter = CommMeter(n_nodes)
    ncls = _n_proto_classes(teacher_cfg)
    sizes = [len(next(iter(d.values()))) for d in node_data]

    opt_s = make_optimizer(train.optimizer, train.learning_rate,
                           weight_decay=train.weight_decay,
                           momentum=train.momentum)
    opt_t = make_optimizer(train.optimizer, train.learning_rate,
                           weight_decay=train.weight_decay,
                           momentum=train.momentum)
    # same plane resolution as the stacked engine, so the per-node
    # reference runs the identical fused clip+update math (the wire /
    # meter / mix boundaries below unwrap the plane to leaf views)
    use_plane = _plane_mode(fed, train, algo, student_cfg)
    if use_plane:
        opt_s = make_plane_optimizer(train.optimizer, train.learning_rate,
                                     weight_decay=train.weight_decay,
                                     momentum=train.momentum,
                                     grad_clip=train.grad_clip)

    step, wire_model, share_protos, bits, model_cfgs = _algo_wiring(
        algo, teacher_cfg, student_cfg, fed, train, opt_s, opt_t, jit=True)
    needs_teacher = algo in ("profe", "fml")
    states = _init_states(algo, model_cfgs, fed, opt_s, opt_t, ncls,
                          plane=use_plane)
    eval_cfg = model_cfgs[1] if algo in ("profe", "fml") else model_cfgs[0]
    proto_cfg = eval_cfg
    adapters_on = bool(fed.adapter_rank) and wire_model is not None \
        and share_protos and isinstance(bits, WireSpec)
    a_layout = None
    if adapters_on:
        from repro.core.adapters import adapter_layout, init_adapter_state
        a_layout = adapter_layout(as_tree(states[0].student),
                                  fed.adapter_rank)
        for i in range(n_nodes):
            states[i] = states[i]._replace(
                adapter_state=init_adapter_state(
                    a_layout, as_tree(states[i].student),
                    grams=fed.adapter_grams))
    # stateful wire codec: per-node residual dicts, the reference
    # semantics of the stacked engine's carried CodecState
    ef = isinstance(bits, WireSpec) and bits.error_feedback \
        and wire_model is not None and share_protos
    ef_qdq = None
    ef_plane = ef and use_plane and not adapters_on
    if ef:
        from repro.core.wire_state import (ef_quantize_dequantize_tree,
                                           init_codec_state)
        for i in range(n_nodes):
            if adapters_on:
                # the residual mirrors the adapter payload structure
                from repro.core.adapters import zero_wire_payload
                res0 = {"protos": jnp.zeros((ncls, proto_cfg.proto_dim),
                                            jnp.float32)}
                res0.update(zero_wire_payload(
                    a_layout, as_tree(states[i].student),
                    grams=fed.adapter_grams))
                states[i] = states[i]._replace(
                    wire_state=init_codec_state(res0))
            elif ef_plane:
                # plane-resident EF: the student residual is carried as
                # a zero plane buffer — row spans, not leaf views —
                # so the EF wire round-trips buffer-native and the mix
                # below never rebuilds a tree (PR 9's narrow fallback
                # retired; bit-identity to the tree reference asserted
                # in tests)
                states[i] = states[i]._replace(
                    wire_state=init_codec_state({
                        "protos": jnp.zeros(
                            (ncls, proto_cfg.proto_dim), jnp.float32),
                        "student": states[i].student}))
            else:
                states[i] = states[i]._replace(
                    wire_state=init_codec_state({
                        "protos": jnp.zeros(
                            (ncls, proto_cfg.proto_dim), jnp.float32),
                        "student": as_tree(states[i].student)}))
        # jitted like the stacked round program, so both engines see the
        # same compiled residual arithmetic (XLA contracts the
        # mul-subtract of the residual update into an FMA; an eager
        # reference would drift by an ulp and the drift compounds)
        if ef_plane:
            from repro.core.wire_state import ef_quantize_dequantize_plane
            ef_qdq = jax.jit(
                lambda t, s: ef_quantize_dequantize_plane(t, bits, s))
        else:
            ef_qdq = jax.jit(
                lambda t, s: ef_quantize_dequantize_tree(t, bits, s))
    result = FederationResult(comm=meter, algorithm=algo)
    result.extras["engine"] = "loop"
    result.extras["proto_pass"] = fed.proto_pass
    result.extras["param_plane"] = use_plane
    if fed.proto_ema:
        result.extras["proto_ema"] = fed.proto_ema
    # same wire-byte extras as the stacked engine, so a run that fell
    # back to the reference loop still fills the one-row fig2 artifact
    from repro.core.comm import packed_copy_bytes
    from repro.core.quantization import tree_wire_bytes
    payload_t = _payload_template(wire_model, share_protos, states[0],
                                  ncls, proto_cfg.proto_dim,
                                  node_axis=False,
                                  adapter_rank=fed.adapter_rank
                                  if adapters_on else 0,
                                  adapter_grams=fed.adapter_grams)
    result.extras["wire_bytes_per_copy"] = tree_wire_bytes(payload_t, bits)
    result.extras["wire_bytes_packed_per_copy"] = \
        packed_copy_bytes(payload_t, bits)
    result.extras["avg_sent_packed_gb"] = _packed_sent_gb(
        sched, fed.rounds, result.extras["wire_bytes_packed_per_copy"],
        n_nodes)
    round_times: List[float] = []
    result.extras["round_times_s"] = round_times
    t0 = time.time()

    for rnd in range(fed.rounds):
        t_r = time.time()
        adj = sched.adjacency_at(rnd)
        t_on = teacher_active(fed.alpha_s, fed.alpha_limit, rnd) \
            if algo == "profe" else needs_teacher
        # 1) local training (fused mode also streams each step's f1
        #    metric into the Eq. 3 accumulators — the single-pass round)
        protos, counts = [], []
        ema = fed.proto_ema if share_protos else 0.0
        for i in range(n_nodes):
            st = states[i]
            if fused and share_protos:
                if ema and ema > 0:
                    # EMA carry: warm-start at the decayed previous
                    # round's raw accumulators (stacked-engine order)
                    sums_i = ema * st.proto_acc[0]
                    counts_i = ema * st.proto_acc[1]
                else:
                    sums_i = jnp.zeros((ncls, proto_cfg.proto_dim),
                                       jnp.float32)
                    counts_i = jnp.zeros((ncls,), jnp.float32)
            for batch in batches(node_data[i], train.batch_size,
                                 seed=fed.seed + rnd * 997 + i,
                                 epochs=fed.local_epochs):
                st, m = step(st, batch, teacher_on=t_on)
                if fused and share_protos:
                    s_add, c_add = proto_accumulate(
                        m["f1"], proto_labels(proto_cfg, batch), ncls)
                    sums_i = sums_i + s_add
                    counts_i = counts_i + c_add
            states[i] = st._replace(round_idx=jnp.int32(rnd + 1))
            if fused and share_protos:
                if ema and ema > 0:
                    states[i] = states[i]._replace(
                        proto_acc=(sums_i, counts_i))
                protos.append(normalize_protos(sums_i, counts_i))
                counts.append(counts_i)

        # 2) payload construction (+ local prototypes where the algo
        #    uses them; fused mode already accumulated them in-pass)
        if share_protos and not fused:
            for i in range(n_nodes):
                sums_i, ct = compute_local_prototypes(
                    proto_cfg, states[i].student,
                    batches(node_data[i], train.batch_size,
                            seed=fed.seed + rnd), ncls, raw=True)
                if ema and ema > 0:
                    sums_i = sums_i + ema * states[i].proto_acc[0]
                    ct = ct + ema * states[i].proto_acc[1]
                    states[i] = states[i]._replace(proto_acc=(sums_i, ct))
                protos.append(normalize_protos(sums_i, ct))
                counts.append(ct)

        # 3) gossip: metering + (de-quantized) receive buffers.  With
        #    error feedback every node's payload goes through the
        #    stateful codec exactly once per round (residual replayed +
        #    updated, isolated nodes included — matching the stacked
        #    engine, which quantizes all nodes unconditionally).
        # 3-pre) adapter share: factorize each node's round delta into
        #     the wire factor groups (+ gram carry) and advance the
        #     reference snapshot to the just-shared student — the
        #     reference semantics of the stacked adapter_share_nodes
        adapter_pay: List[Any] = []
        if adapters_on:
            from repro.core.adapters import (factorize_deltas, gram_update,
                                             split_student)
            for i in range(n_nodes):
                mats_i, rest_i = split_student(
                    a_layout, as_tree(states[i].student))
                ast = states[i].adapter_state
                factors_i = factorize_deltas(a_layout, mats_i, ast["ref"])
                new_ast = {"ref": mats_i}
                pay = {"adapters": factors_i, "student": rest_i}
                if fed.adapter_grams:
                    g = gram_update(factors_i, ast.get("grams"))
                    pay["grams"] = g
                    new_ast["grams"] = g
                states[i] = states[i]._replace(adapter_state=new_ast)
                adapter_pay.append(pay)
        ef_recv: List[Any] = []
        if ef:
            for i in range(n_nodes):
                if adapters_on:
                    pay_i = dict(adapter_pay[i])
                    pay_i["protos"] = protos[i]
                elif ef_plane:
                    # plane-resident EF payload: the student rides as
                    # its Plane, residual spans mirror its row layout
                    pay_i = {"protos": protos[i],
                             "student": states[i].student}
                else:
                    pay_i = {"protos": protos[i],
                             "student": as_tree(states[i].student)}
                recv_i, new_ws = ef_qdq(pay_i, states[i].wire_state)
                states[i] = states[i]._replace(wire_state=new_ws)
                ef_recv.append(recv_i)
        recv_models: List[List[Any]] = [[] for _ in range(n_nodes)]
        recv_sizes: List[List[float]] = [[] for _ in range(n_nodes)]
        recv_pay: List[Any] = []
        for i in range(n_nodes):
            neigh = T.neighbors(adj, i)
            payload = {}
            if adapters_on:
                payload["adapters"] = adapter_pay[i]["adapters"]
                payload["model"] = adapter_pay[i]["student"]
                if fed.adapter_grams:
                    payload["grams"] = adapter_pay[i]["grams"]
            elif wire_model is not None:
                payload["model"] = as_tree(states[i].student)
            if share_protos:
                payload["protos"] = protos[i]
                payload["counts"] = counts[i]
            meter.record_broadcast(i, neigh, payload, kind=algo, round_idx=rnd,
                                   bits=bits)
            if adapters_on:
                # receiver-side factor view: per-leaf scales at each
                # group's spec width (== the packed codec's per-(leaf,
                # node) scale segments)
                if ef:
                    recv_pay.append({k: v for k, v in ef_recv[i].items()
                                     if k != "protos"})
                else:
                    recv_pay.append({
                        k: quantize_dequantize_tree(v, bits.bits_for(k))
                        for k, v in adapter_pay[i].items()})
            elif wire_model is not None:
                if ef:
                    model_rx = ef_recv[i]["student"]
                elif use_plane:
                    # plane-resident wire: quantize the [R, 512] buffer
                    # per leaf row span — bit-identical to the per-leaf
                    # qdq, and the receive buffer stays a Plane so the
                    # mix below never rebuilds a tree.
                    model_rx = quantize_dequantize_plane_rows(
                        states[i].student, bits.bits_for("student")) \
                        if bits else states[i].student
                else:
                    model_rx = quantize_dequantize_tree(
                        as_tree(states[i].student),
                        bits.bits_for("student")) \
                        if bits else as_tree(states[i].student)
                for j in neigh:
                    recv_models[j].append(model_rx)
                    recv_sizes[j].append(sizes[i])

        # 4) aggregation
        if share_protos:
            protos_rx = [r["protos"] for r in ef_recv] if ef else \
                [quantize_dequantize_tree(p, bits.bits_for("protos"))
                 if bits else p for p in protos]
            all_p = jnp.stack(protos_rx)
            all_c = jnp.stack(counts)
            for i in range(n_nodes):
                neigh = T.neighbors(adj, i) + [i]
                gp, mask = aggregate_prototypes(all_p[np.array(neigh)],
                                                all_c[np.array(neigh)])
                states[i] = states[i]._replace(global_protos=gp,
                                               proto_mask=mask)
        if adapters_on:
            # merge-based aggregation: each receiver applies its
            # neighbors' dequantized low-rank deltas ON TOP of its own
            # current student (no self term — the node's own training
            # delta is already in W); the dense rest keeps the classic
            # size-weighted gossip.  Reference semantics of the stacked
            # adapter_merge_nodes, built from stacked factor banks so
            # the same lowrank_apply_ref contraction runs here.
            from repro.core.adapters import merge_student, split_student
            from repro.core.aggregation import regmean_adjust
            from repro.kernels.lowrank_apply.ref import lowrank_apply_ref
            b_bank = {n: jnp.stack([p["adapters"][n]["B"]
                                    for p in recv_pay])
                      for n in a_layout.mat_names}
            a_bank = {n: jnp.stack([p["adapters"][n]["A"]
                                    for p in recv_pay])
                      for n in a_layout.mat_names}
            g_bank = {n: jnp.stack([p["grams"][n] for p in recv_pay])
                      for n in a_layout.mat_names} \
                if fed.adapter_grams else None
            coeffs_np = np.zeros((n_nodes, n_nodes), np.float32)
            for i in range(n_nodes):
                neigh = T.neighbors(adj, i)
                tot = sizes[i] + sum(sizes[j] for j in neigh)
                for j in neigh:
                    coeffs_np[i, j] = sizes[j] / tot
            coeffs = jnp.asarray(coeffs_np)
            new_models = []
            for i in range(n_nodes):
                neigh = T.neighbors(adj, i)
                if not neigh:
                    new_models.append(states[i].student)
                    continue
                mats_i, rest_i = split_student(
                    a_layout, as_tree(states[i].student))
                rest_mix = weighted_tree_mean(
                    [rest_i] + [recv_pay[j]["student"] for j in neigh],
                    [sizes[i]] + [sizes[j] for j in neigh])
                new_mats = {}
                for nm in a_layout.mat_names:
                    a_use = a_bank[nm]
                    if fed.adapter_grams:
                        a_use = regmean_adjust(a_bank[nm], g_bank[nm],
                                               coeffs[i][None],
                                               per_recv=False)[0]
                    new_mats[nm] = lowrank_apply_ref(
                        mats_i[nm][None], coeffs[i][None],
                        b_bank[nm], a_use)[0]
                mixed = merge_student(a_layout, new_mats, rest_mix)
                new_models.append(plane_from_tree(mixed) if use_plane
                                  else mixed)
            for i in range(n_nodes):
                states[i] = states[i]._replace(student=new_models[i])
        elif wire_model is not None:
            new_models = []
            for i in range(n_nodes):
                if not recv_models[i]:
                    new_models.append(states[i].student)
                elif use_plane:
                    # plane-resident mix: splice the dequantized [R, 512]
                    # buffers straight into the stacked plane — no leaf
                    # views, no plane_from_tree rebuild at the round
                    # boundary (bit-identical to the tree mix; see
                    # weighted_plane_mean).  The EF wire now decodes to
                    # planes too (ef_quantize_dequantize_plane), so the
                    # tree-mix + rebuild fallback this path used to take
                    # under error feedback is retired.
                    new_models.append(weighted_plane_mean(
                        [states[i].student] + recv_models[i],
                        [sizes[i]] + recv_sizes[i]))
                else:
                    new_models.append(weighted_tree_mean(
                        [as_tree(states[i].student)] + recv_models[i],
                        [sizes[i]] + recv_sizes[i]))
            for i in range(n_nodes):
                states[i] = states[i]._replace(student=new_models[i])

        # 5) evaluation (node 0 by default — exact on full topologies
        #    where all nodes share the model; eval_all_nodes for spread)
        f1, acc = _eval_nodes(eval_cfg, [st.student for st in states],
                              n_nodes, test_data, eval_all_nodes,
                              result.extras)
        result.f1_per_round.append(f1)
        result.acc_per_round.append(acc)
        round_times.append(time.time() - t_r)
        if verbose:
            print(f"[{algo}] round {rnd + 1}/{fed.rounds} "
                  f"f1={f1:.4f} acc={acc:.4f} "
                  f"sent={meter.avg_sent_gb():.4f}GB")

    result.elapsed_s = time.time() - t0
    result.extras["avg_sent_gb"] = meter.avg_sent_gb()
    result.extras["avg_received_gb"] = meter.avg_received_gb()
    return result
