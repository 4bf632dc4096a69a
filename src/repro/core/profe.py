"""ProFe node-local training step (paper Sec. III-C, Eq. 8/9) and round
payload handling (quantize → gossip → aggregate).

Each node holds a *teacher* (the full architecture, never communicated)
and a *student* (the aggregation model).  Per batch:

    L_s = L_CE(y_s, y) + β_s L_MSE(f_s1, C̄(j))
          + α_s [ L_KD(y_s, y_t) + L_MSE(f_s1, f_t1) ]          (Eq. 8)
    L_t = L_CE(y_t, y) + β_t L_MSE(f_t1, C̄(j))                 (Eq. 9)

α_s follows the professor-importance decay (halved per round, zero below
``alpha_limit``); once zero, the teacher forward/update is skipped
entirely (compile-time static branch — two step variants are jitted).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config.base import FederationConfig, ModelConfig, TrainConfig
from repro.core import distillation as D
from repro.core import prototypes as P
from repro.core.scanning import scan
from repro.kernels.proto_accum.ops import proto_accumulate
from repro.models import forward
from repro.optim import Optimizer, clip_by_global_norm
from repro.optim.plane import (Plane, as_tree, plane_from_tree,
                               plane_view_tree)


class NodeState(NamedTuple):
    student: Any
    teacher: Any
    opt_s: Any
    opt_t: Any
    global_protos: jnp.ndarray   # [C, P]
    proto_mask: jnp.ndarray      # [C]
    round_idx: jnp.ndarray       # scalar int32
    # stateful wire codec (None unless the WireSpec enables error
    # feedback): a core.wire_state.CodecState whose residual tree
    # mirrors the node's wire payload {"protos", "student"}.  Riding
    # inside NodeState means the stacked engine carries it through the
    # donated round program and checkpoints capture it for exact resume.
    wire_state: Any = None
    # EMA prototype carry (None unless FederationConfig.proto_ema > 0):
    # last round's raw Eq. 3 accumulators ``(sums [C, P], counts [C])``,
    # decayed into the next round's accumulation before normalization.
    # Same checkpoint/donation story as wire_state.
    proto_acc: Any = None
    # adapter-rank wire carry (None unless FederationConfig.adapter_rank
    # > 0): ``{"ref": {leaf: W}, ["grams": {leaf: G}]}`` — the per-node
    # reference matrices deltas factorize against (snapshotted at share
    # time) and, with adapter_grams, the EMA'd row-space gram
    # statistics (core/adapters.py).  Same checkpoint/donation story.
    adapter_state: Any = None


def proto_labels(cfg: ModelConfig, batch) -> jnp.ndarray:
    """The prototype class of each example: the true label for classifiers,
    the sequence's domain tag for LM tasks (DESIGN.md §5)."""
    if cfg.family in ("cnn", "resnet"):
        return batch["label"]
    return batch["domains"]


def task_ce(cfg: ModelConfig, logits, batch) -> jnp.ndarray:
    """Task cross-entropy: classification CE, or next-token CE for LMs."""
    if cfg.family in ("cnn", "resnet"):
        return D.ce_loss(logits, batch["label"])
    return D.ce_loss(logits, batch["labels"])


def student_loss(student_cfg: ModelConfig, sp, batch, global_protos,
                 proto_mask, alpha, beta_s: float, temperature: float,
                 teacher_out=None, *, remat: bool = True):
    """Eq. 8. ``teacher_out=None`` means the professor has decayed away."""
    out = forward(student_cfg, sp, batch, remat=remat)
    labels_p = proto_labels(student_cfg, batch)
    loss = task_ce(student_cfg, out.logits, batch)
    loss = loss + beta_s * P.proto_mse_loss(out.f1, global_protos, labels_p,
                                            proto_mask)
    if teacher_out is not None:
        kd = D.kd_loss(out.logits, teacher_out.logits, temperature)
        rep = D.repr_mse_loss(out.f1, teacher_out.f1)
        loss = loss + alpha * (kd + rep)
    loss = loss + out.aux * getattr(student_cfg, "router_aux_weight", 0.0)
    return loss, out


def teacher_loss(teacher_cfg: ModelConfig, tp, batch, global_protos,
                 proto_mask, beta_t: float, *, remat: bool = True):
    """Eq. 9: L_t = L_CE + beta_t * L_MSE(f_t1, C̄(j))."""
    out = forward(teacher_cfg, tp, batch, remat=remat)
    labels_p = proto_labels(teacher_cfg, batch)
    loss = task_ce(teacher_cfg, out.logits, batch)
    loss = loss + beta_t * P.proto_mse_loss(out.f1, global_protos, labels_p,
                                            proto_mask)
    loss = loss + out.aux * getattr(teacher_cfg, "router_aux_weight", 0.0)
    return loss, out


def make_profe_step(teacher_cfg: ModelConfig, student_cfg: ModelConfig,
                    fed: FederationConfig, opt_s: Optimizer, opt_t: Optimizer,
                    *, grad_clip: float = 1.0, remat: bool = True,
                    jit: bool = True):
    """Returns ``step(state, batch, teacher_on) -> (state, metrics)``,
    jitted with a static teacher_on flag.

    ``jit=False`` returns the pure step instead — the stacked round
    engine vmaps it over the node axis inside its own jitted round
    program (jitting here too would be redundant nesting)."""

    def _step(state: NodeState, batch, teacher_on: bool):
        alpha = D.alpha_at_round(fed.alpha_s, fed.alpha_limit, state.round_idx)
        metrics = {}

        teacher = state.teacher
        opt_t_state = state.opt_t
        teacher_out = None
        if teacher_on:
            def t_loss(tp):
                out = forward(teacher_cfg, tp, batch, remat=remat)
                labels_p = proto_labels(teacher_cfg, batch)
                l = task_ce(teacher_cfg, out.logits, batch)
                l = l + fed.beta_t * P.proto_mse_loss(
                    out.f1, state.global_protos, labels_p, state.proto_mask)
                l = l + out.aux * getattr(teacher_cfg, "router_aux_weight", 0.0)
                return l, out

            with jax.named_scope("round.teacher"):
                (lt, teacher_out), gt = jax.value_and_grad(
                    t_loss, has_aux=True)(teacher)
                gt, _ = clip_by_global_norm(gt, grad_clip)
                teacher, opt_t_state = opt_t.update(gt, opt_t_state, teacher)
            metrics["loss_t"] = lt
            teacher_out = jax.tree_util.tree_map(jax.lax.stop_gradient,
                                                 teacher_out)

        def s_loss(sp):
            # plane_view_tree: a plane-backed student forwards through
            # the same slice+reshape views as as_tree, but the custom
            # vjp packs the backward straight into one [R, C] buffer
            # cotangent (padding lanes zero) — no per-leaf scatter-adds
            return student_loss(student_cfg, plane_view_tree(sp), batch,
                                state.global_protos,
                                state.proto_mask, alpha, fed.beta_s,
                                fed.kd_temperature, teacher_out, remat=remat)

        with jax.named_scope("round.student"):
            (ls, out_s), gs = jax.value_and_grad(s_loss, has_aux=True)(
                state.student)
            if isinstance(state.student, Plane):
                # fused path: the plane optimizer clips + updates in one
                # sweep over the buffer and reports the pre-clip norm
                student, opt_s_state = opt_s.update(gs, state.opt_s,
                                                    state.student)
                gnorm = opt_s_state["gnorm"]
            else:
                gs, gnorm = clip_by_global_norm(gs, grad_clip)
                student, opt_s_state = opt_s.update(gs, state.opt_s,
                                                    state.student)
        # the f1 the loss already computed rides out in metrics so the
        # fused Eq. 3 pass (proto_pass="fused") can accumulate it
        # without a second forward; exact mode never reads it (DCE'd)
        metrics.update(loss_s=ls, grad_norm_s=gnorm, alpha=alpha,
                       f1=out_s.f1)

        new_state = state._replace(student=student, teacher=teacher,
                                   opt_s=opt_s_state, opt_t=opt_t_state)
        return new_state, metrics

    if not jit:
        return _step
    return jax.jit(_step, static_argnames=("teacher_on",))


def init_node_state(teacher_cfg: ModelConfig, student_cfg: ModelConfig,
                    rng, opt_s: Optimizer, opt_t: Optimizer,
                    n_classes: int, *, plane: bool = False,
                    proto_ema: float = 0.0) -> NodeState:
    """``plane=True`` packs the student into a flat parameter plane
    (``opt_s`` must then be a ``make_plane_optimizer``); ``proto_ema``
    > 0 allocates the zero EMA accumulator carry."""
    from repro.models import init_params
    k1, k2 = jax.random.split(rng)
    teacher = init_params(teacher_cfg, k1)
    student = init_params(student_cfg, k2)
    if plane:
        student = plane_from_tree(student)
    proto_acc = None
    if proto_ema and proto_ema > 0:
        proto_acc = (jnp.zeros((n_classes, student_cfg.proto_dim),
                               jnp.float32),
                     jnp.zeros((n_classes,), jnp.float32))
    return NodeState(
        student=student,
        teacher=teacher,
        opt_s=opt_s.init(student),
        opt_t=opt_t.init(teacher),
        global_protos=jnp.zeros((n_classes, student_cfg.proto_dim), jnp.float32),
        proto_mask=jnp.zeros((n_classes,), jnp.float32),
        round_idx=jnp.zeros((), jnp.int32),
        proto_acc=proto_acc,
    )


# ---------------------------------------------------------------------------
# round-boundary: local prototypes (Eq. 3)
# ---------------------------------------------------------------------------

def normalize_protos(sums, counts):
    """Eq. 3 class means from raw accumulators: ``sums / max(counts, 1)``
    — the one normalization every proto path (exact, fused, mesh)
    shares, so streamed and post-hoc prototypes divide identically."""
    return sums / jnp.maximum(counts, 1.0)[..., None]


# Trace bookkeeping for the cached accumulator: the body of ``acc`` runs
# only when jax (re)traces it, so the counter measures exactly the
# retrace behavior the cache is meant to eliminate (asserted in tests).
PROTO_ACC_TRACES: Dict[Tuple[str, int], int] = {}


@functools.lru_cache(maxsize=None)
def _proto_acc_step(cfg: ModelConfig, n_classes: int):
    """One jitted Eq. 3 accumulation step, cached by (config, classes).

    The seed defined ``@jax.jit def acc`` *inside*
    :func:`compute_local_prototypes`, closing over ``params`` — a fresh
    function object per call, so jax re-traced it every round × node.
    Hoisting it here (params as an argument) makes the trace happen once
    per (cfg, n_classes, batch shape) for the whole federation run.
    Kept as the ragged fallback of :func:`compute_local_prototypes`
    (uneven batch shapes cannot stack for the scanned pass).
    """
    key = (cfg.name, n_classes)

    def acc(params, sums, counts, batch):
        PROTO_ACC_TRACES[key] = PROTO_ACC_TRACES.get(key, 0) + 1
        out = forward(cfg, params, batch, remat=False)
        labels_p = proto_labels(cfg, batch)
        s_add, c_add = proto_accumulate(out.f1, labels_p, n_classes)
        return sums + s_add, counts + c_add

    return jax.jit(acc)


@functools.lru_cache(maxsize=None)
def _proto_scan_fn(cfg: ModelConfig, n_classes: int):
    """The whole Eq. 3 pass as ONE jitted program, cached by (config,
    classes): a ``scan`` (CPU-unroll-capped, same policy as the round
    engines) over pre-stacked ``[T, B, ...]`` batches.  The host-loop
    seed dispatched one ``acc`` per batch with a device round-trip per
    call — this runs the loop engine's exact pass dispatch-free.  The
    per-batch body is the same ``proto_accumulate`` op the per-batch
    path runs (bit-identical accumulation), and it increments the same
    ``PROTO_ACC_TRACES`` counter: the scan body traces once per
    (config, classes, batch shape), never per round x node."""
    key = (cfg.name, n_classes)

    def run(params, stacked):
        sums0 = jnp.zeros((n_classes, cfg.proto_dim), jnp.float32)
        counts0 = jnp.zeros((n_classes,), jnp.float32)

        def body(carry, batch):
            PROTO_ACC_TRACES[key] = PROTO_ACC_TRACES.get(key, 0) + 1
            sums, counts = carry
            out = forward(cfg, params, batch, remat=False)
            labels_p = proto_labels(cfg, batch)
            s_add, c_add = proto_accumulate(out.f1, labels_p, n_classes)
            return (sums + s_add, counts + c_add), ()

        length = len(next(iter(stacked.values())))
        (sums, counts), _ = scan(body, (sums0, counts0), stacked, length)
        return sums, counts

    return jax.jit(run)


def compute_local_prototypes(cfg: ModelConfig, params, batches,
                             n_classes: int, *, raw: bool = False):
    """Stream local data once, accumulate Eq. 3 sums/counts.

    Uniform-shape batch streams (the common drop-remainder case) stack
    into one ``[T, B, ...]`` program: a single jitted scan instead of a
    host loop with a dispatch + device round-trip per batch.  Ragged
    streams keep the cached per-batch accumulator.

    ``raw=True`` returns the un-normalized ``(sums, counts)``
    accumulators — the EMA prototype carry blends raw accumulators
    across rounds before the shared ``normalize_protos`` division."""
    params = as_tree(params)        # plane-backed students forward as views
    batch_list = [dict(b) for b in batches]
    if not batch_list:
        sums = jnp.zeros((n_classes, cfg.proto_dim), jnp.float32)
        counts = jnp.zeros((n_classes,), jnp.float32)
        if raw:
            return sums, counts
        return normalize_protos(sums, counts), counts
    shapes = {tuple(sorted((k, np.shape(v)) for k, v in b.items()))
              for b in batch_list}
    if len(shapes) == 1:
        stacked = {k: jnp.asarray(np.stack([np.asarray(b[k])
                                            for b in batch_list]))
                   for k in batch_list[0]}
        sums, counts = _proto_scan_fn(cfg, n_classes)(params, stacked)
    else:
        sums = jnp.zeros((n_classes, cfg.proto_dim), jnp.float32)
        counts = jnp.zeros((n_classes,), jnp.float32)
        acc = _proto_acc_step(cfg, n_classes)
        for batch in batch_list:
            sums, counts = acc(params, sums, counts, batch)
    if raw:
        return sums, counts
    return normalize_protos(sums, counts), counts
