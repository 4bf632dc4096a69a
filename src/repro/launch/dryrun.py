"""Multi-pod dry-run: lower + compile every (arch x input-shape) on the
production meshes and extract the roofline terms.

    PYTHONPATH=src python -m repro.launch.dryrun \
        --arch yi-6b --shape train_4k --mesh pod1 [--json out.json]

mesh pod1 = (16,16) ("data","model") — 256 chips, one federation node.
mesh pod2 = (2,16,16) ("pod","data","model") — 512 chips, 2 nodes:
  * the training/serving program is vmapped over the node dim (proves the
    pod axis shards with NO cross-pod collectives during local training),
  * plus the ProFe gossip round (federate) lowers the int16 student
    exchange across pods (and a FedAvg fp32 round for comparison).

Outputs memory_analysis + cost_analysis + a collective-bytes breakdown
parsed from the compiled HLO (see launch/roofline.py).

**Topology axis** (physical sparse gossip):

    PYTHONPATH=src python -m repro.launch.dryrun \
        --arch yi-6b --topology ring --pods 8

compiles the gossip round per exchange mode (per-leaf gather / packed
single-buffer gather / ppermute neighbor collectives) on an
(N, 1, 1) federation mesh and ASSERTS the measured HLO collective bytes
match ``ScheduleCommAccountant``'s per-round prediction (within 10%)
and, for sparse regular graphs, stay under 0.5x the full-graph
all-gather exchange — the logical topology IS the physical wire.

``--pods RxC`` (e.g. ``8x2``) builds a multi-axis pod mesh — R nodes of
C devices each — where ppermute lowers the ROW-SHARDED permute: each
device moves only its row shard of the packed wire buffer, so pod-axis
bytes stay spec-exact instead of widening to the container.  The gate
then also asserts pod-axis collective-permute bytes/node ==
``predicted_node_bytes(..., "packed", inner=C)`` EXACTLY.

The dry-run compiles for 512 virtual CPU devices and never touches an
accelerator: ``main`` pins the CPU platform and the host device count
before the first jax call, so importing this module has no side effect
on the importing process.
"""
import argparse
import os
import json
import sys
import traceback
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.config import (FederationConfig, TrainConfig, get_config,
                          get_shape)
from repro.launch.mesh import dp_axes, make_production_mesh
from repro.launch import programs as PR
from repro.launch.roofline import (collective_bytes_from_hlo, roofline_report)
from repro.models import derive_student, init_cache
from repro.sharding import (batch_specs, cache_specs, opt_state_specs,
                            param_specs, set_activation_sharding, to_named)


def _eval_params_struct(cfg):
    from repro.models import init_params
    return jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))


def _spec_tree_for_state(state_struct, teacher_cfg, student_cfg, train_cfg,
                         mesh, data_axis="data", model_axis="model"):
    sp_student = param_specs(student_cfg, state_struct.student, mesh,
                             data_axis=data_axis, model_axis=model_axis)
    sp_teacher = param_specs(teacher_cfg, state_struct.teacher, mesh,
                             data_axis=data_axis, model_axis=model_axis)
    from repro.core.profe import NodeState
    return NodeState(
        student=sp_student,
        teacher=sp_teacher,
        opt_s=opt_state_specs(train_cfg.optimizer, sp_student),
        opt_t=opt_state_specs(train_cfg.optimizer, sp_teacher),
        global_protos=P(None, None),
        proto_mask=P(None),
        round_idx=P(),
    )


def _add_node_dim(spec_tree):
    return jax.tree_util.tree_map(
        lambda s: P("pod", *s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))


def _stack_struct(struct, n):
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct((n,) + tuple(s.shape), s.dtype), struct)


def lower_combo(arch: str, shape_name: str, mesh_kind: str,
                *, include_federate: bool = True,
                fsdp: bool = True, microbatches: int = 0,
                layout: str = "auto") -> Dict[str, Any]:
    # layout="tp":   FSDP(data) x TP(model) (paper-faithful baseline).
    # layout="fsdp": pure 256/512-way ZeRO-3, no tensor parallelism — the
    #   right mapping for <=20B-class TRAIN steps where TP activation
    #   all-reduces dominate (7x collective cut on yi-6b; EXPERIMENTS §Perf).
    # "auto" picks fsdp for small-arch training, tp otherwise (decode
    #   stays TP: per-token weight gathers would kill latency).
    multi = mesh_kind == "pod2"
    mesh = make_production_mesh(multi_pod=multi)
    n_pods = mesh.shape.get("pod", 1) if hasattr(mesh.shape, "get") else \
        (2 if multi else 1)
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    student_cfg = derive_student(cfg)
    if layout == "auto":
        from repro.launch.roofline import approx_params
        # pure-FSDP (iteration 15) wins for small-model training, but at
        # batch-over-all-chips each device holds 1 row and the [1, S, V]
        # loss temps replicate -> affordable only for vocab <= 100k
        # (chunked fused-linear-CE would lift this; EXPERIMENTS Perf-16)
        layout = "fsdp" if (shape.kind == "train"
                            and approx_params(cfg) < 1e10
                            and cfg.vocab_size <= 100_000) else "tp"
    if layout == "fsdp" and not microbatches:
        microbatches = 1   # the full batch shards over all chips
    fed = FederationConfig()
    m = microbatches or (16 if shape.kind == "train" else 1)
    train_cfg = TrainConfig(optimizer=cfg.optimizer, remat=True,
                            microbatches=m if shape.kind == "train" else 1)
    report: Dict[str, Any] = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "n_devices": mesh.devices.size,
        "layout": layout,
        "microbatches": m if shape.kind == "train" else 1,
    }

    if layout == "fsdp":
        act_dp = ("data", "model")   # batch over ALL chips (m=1)
    else:
        act_dp = (("data",) if shape.kind == "train" else
                  (("pod", "data") if multi else ("data",)))
    set_activation_sharding(mesh, dp_axes=act_dp,
                            model_axis=None if layout == "fsdp" else "model")
    with mesh:
        if shape.kind == "train":
            step, _ = PR.make_profe_train_fn(cfg, student_cfg, fed, train_cfg)
            state_struct = PR.node_state_struct(cfg, student_cfg, train_cfg,
                                                cfg.n_proto_classes)
            batch_struct = PR.batch_struct(cfg, shape)
            state_specs = _spec_tree_for_state(
                state_struct, cfg, student_cfg, train_cfg, mesh,
                data_axis=(("data", "model") if layout == "fsdp"
                           else ("data" if fsdp else None)),
                model_axis=None if layout == "fsdp" else "model")
            b_specs = batch_specs(batch_struct, mesh, dp_axes=act_dp)
            if multi:
                # nodes = pods: stack everything on a leading node dim
                step = jax.vmap(step, spmd_axis_name="pod")
                state_struct = _stack_struct(state_struct, n_pods)
                batch_struct = _stack_struct(batch_struct, n_pods)
                state_specs = _add_node_dim(state_specs)
                b_specs = _add_node_dim(b_specs)
            jitted = jax.jit(
                step,
                in_shardings=(to_named(state_specs, mesh),
                              to_named(b_specs, mesh)),
                out_shardings=(to_named(state_specs, mesh), None),
                donate_argnums=(0,),
            )
            lowered = jitted.lower(state_struct, batch_struct)

        elif shape.kind == "prefill":
            fn = PR.make_prefill_fn(cfg)
            params_struct = _eval_params_struct(cfg)
            p_specs = param_specs(cfg, params_struct, mesh)
            batch_struct = PR.batch_struct(cfg, shape)
            dpa = ("pod", "data") if multi else ("data",)
            b_specs = batch_specs(batch_struct, mesh, dp_axes=dpa)
            cache_struct = jax.eval_shape(
                lambda: init_cache(cfg, shape.global_batch, shape.seq_len,
                                   jnp.bfloat16))
            c_specs = cache_specs(cache_struct, mesh, data_axis=dpa)
            logits_spec = P(dpa, None)
            jitted = jax.jit(
                fn,
                in_shardings=(to_named(p_specs, mesh),
                              to_named(b_specs, mesh)),
                out_shardings=(NamedSharding(mesh, logits_spec),
                               to_named(c_specs, mesh)),
            )
            lowered = jitted.lower(params_struct, batch_struct)

        else:  # decode
            fn = PR.make_serve_fn(cfg, shape)
            params_struct = _eval_params_struct(cfg)
            p_specs = param_specs(cfg, params_struct, mesh)
            d = PR.decode_struct(cfg, shape)
            dpa = ("pod", "data") if multi else ("data",)
            c_specs = cache_specs(d["cache"], mesh, data_axis=dpa)
            tok_spec = batch_specs({"token": d["token"]}, mesh,
                                   dp_axes=dpa)["token"]
            mem_spec = None
            args = [params_struct, d["token"], d["index"], d["cache"]]
            in_sh = [to_named(p_specs, mesh),
                     NamedSharding(mesh, tok_spec),
                     NamedSharding(mesh, P()),
                     to_named(c_specs, mesh)]
            if "memory" in d:
                args.append(d["memory"])
                mem_spec = batch_specs({"m": d["memory"]}, mesh,
                                       dp_axes=dpa)["m"]
                in_sh.append(NamedSharding(mesh, mem_spec))
            logits_spec = NamedSharding(mesh, P(tok_spec[0], None))
            jitted = jax.jit(
                fn,
                in_shardings=tuple(in_sh),
                out_shardings=(logits_spec, to_named(c_specs, mesh)),
                donate_argnums=(3,),
            )
            lowered = jitted.lower(*args)

        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()
        coll = collective_bytes_from_hlo(hlo)
        report.update(roofline_report(cfg, shape, mesh, mem, cost, coll,
                                      hlo_text=hlo))

        # federation gossip round (multi-pod only): ProFe vs FedAvg wire
        if multi and include_federate and shape.kind == "train":
            report["federate"] = lower_federate(cfg, student_cfg, mesh,
                                                n_pods)
    return report


def lower_federate(cfg, student_cfg, mesh, n_pods: int) -> Dict[str, Any]:
    from repro.core.mesh_federation import make_fedavg_round, make_profe_round
    out: Dict[str, Any] = {}

    student_struct = _eval_params_struct(student_cfg)
    teacher_struct = _eval_params_struct(cfg)
    s_specs = param_specs(student_cfg, student_struct, mesh)
    t_specs = param_specs(cfg, teacher_struct, mesh)
    C, Pdim = cfg.n_proto_classes, student_cfg.proto_dim

    students = _stack_struct(student_struct, n_pods)
    teachers = _stack_struct(teacher_struct, n_pods)
    protos = jax.ShapeDtypeStruct((n_pods, C, Pdim), jnp.float32)
    counts = jax.ShapeDtypeStruct((n_pods, C), jnp.float32)
    sizes = jax.ShapeDtypeStruct((n_pods,), jnp.float32)

    from repro.launch.hlo_analysis import analyze_hlo

    def lower_profe(exchange):
        profe_round = make_profe_round(mesh, s_specs, bits=16,
                                       exchange=exchange)
        jit_p = jax.jit(
            profe_round,
            in_shardings=(to_named(_add_node_dim(s_specs), mesh),
                          NamedSharding(mesh, P("pod", None, None)),
                          NamedSharding(mesh, P("pod", None)),
                          NamedSharding(mesh, P(None))),
        )
        an = analyze_hlo(jit_p.lower(students, protos, counts,
                                     sizes).compile().as_text())
        return {"total": an.coll_total, "by_kind": an.coll}

    # the real exchange (packed single buffer) + the per-leaf reference;
    # on multi-axis pods the packed path trades intra-pod resharding for
    # one pod-axis launch — the clean pod-wire numbers come from the
    # (N, 1, 1) federation mesh of the --topology mode
    out["profe_collective_bytes"] = lower_profe("auto")
    out["profe_collective_bytes_gather"] = lower_profe("gather")

    fedavg_round = make_fedavg_round(mesh, t_specs)
    jit_f = jax.jit(
        fedavg_round,
        in_shardings=(to_named(_add_node_dim(t_specs), mesh),
                      NamedSharding(mesh, P(None))),
    )
    lf = jit_f.lower(teachers, sizes)
    cf = lf.compile()
    an_f = analyze_hlo(cf.as_text())
    out["fedavg_collective_bytes"] = {"total": an_f.coll_total,
                                      "by_kind": an_f.coll}

    pb = out["profe_collective_bytes"]["total"]
    fb = out["fedavg_collective_bytes"]["total"]
    out["wire_reduction_vs_fedavg"] = 1.0 - pb / fb if fb else None
    return out


def topology_report(arch: str, topology: str, pods,
                    bits="16", ef: bool = False,
                    adapters: int = 0, adapter_grams: bool = False,
                    adapter_frac: Optional[float] = None
                    ) -> Dict[str, Any]:
    """The --topology axis: physical wire bytes per exchange mode on a
    federation mesh, asserted against the accountant.

    ``pods`` is an int or an ``"R"``/``"RxC"`` string: R federation
    nodes, C inner (data-axis) devices per node.  At C > 1 the ppermute
    exchange lowers the row-sharded permute (each device moves its own
    row shard of the packed wire buffer) and the gate tightens: the
    pod-axis collective-permute bytes per node must equal the
    accountant's ``packed`` prediction (``inner=C``) EXACTLY.

    ``bits`` is a wire-spec string (``"16"``/``"8"``/``"4"`` uniform,
    ``"4/16"`` = int4 student + int16 prototypes; a ``+ef`` suffix or
    ``ef=True`` enables the stateful error-feedback codec).  For
    sub-int16 specs the int16 round is compiled too and the physical
    code-buffer bytes must shrink by the spec's exact ratio (int4 ring
    ≤ 0.25x the int16 ring buffer bytes).  With error feedback the
    stateless twin is ALSO compiled and the exchange bytes must match
    it exactly — the residual state costs zero wire bytes.

    ``adapters=r > 0`` compiles the adapter-rank wire (matrix leaves
    gossip rank-``r`` delta factors; ``adapter_grams`` adds the RegMean
    gram group) and the gate tightens on both ends: per-node HLO
    collective-permute bytes must equal the accountant's packed
    prediction EXACTLY (the factor payload packs spec-exact rows, one
    device per node, so no tolerance is owed), AND the dense
    full-parameter round at the same spec is compiled as the reference
    — the adapter exchange must move < ``adapter_frac`` (default
    0.15x) of its physical bytes.
    """
    import dataclasses

    from repro.core import topology as T
    from repro.launch.wire import (check_adapter_reduction,
                                   check_bits_reduction,
                                   check_ef_zero_overhead,
                                   check_topology_bytes,
                                   measure_exchange_bytes, parse_pods)
    from repro.wirespec import WireSpec, resolve_spec
    pods, inner = parse_pods(pods)
    if adapters and inner > 1:
        raise ValueError("--adapters does not support multi-axis pods "
                         "('RxC') — the adapter wire has no row-sharded "
                         "permute lowering; use --pods R")
    spec = WireSpec.parse(bits) if isinstance(bits, str) \
        else resolve_spec(bits)
    if ef and not spec.error_feedback:
        spec = dataclasses.replace(spec, error_feedback=True)
    report = measure_exchange_bytes(arch, pods, topology, bits=spec,
                                    inner=inner, adapter_rank=adapters,
                                    adapter_grams=adapter_grams)
    adj = T.make_schedule(pods, topology, rounds=1, seed=0).adjacency_at(0)
    deg = int(adj.sum(axis=1).max())
    # The degree x payload prediction only holds for regular graphs,
    # where the permutation lowering is exactly `degree` full steps; an
    # irregular graph can need more (partial) steps than its max degree
    # and SPMD charges every step to every device, so asserting there
    # would fail a correct program.
    if spec.error_feedback:
        # error feedback must be wire-free on EVERY graph: the compiled
        # stateless twin moves byte-identical collectives.  The packed
        # gather compiles for any topology; ppermute is checked too when
        # the graph is regular (the mode the ring acceptance relies on).
        exs = ("packed", "ppermute") if T.is_regular(adj) else ("packed",)
        report_sl = measure_exchange_bytes(arch, pods, topology,
                                           bits=spec.stateless(),
                                           exchanges=exs, inner=inner,
                                           adapter_rank=adapters,
                                           adapter_grams=adapter_grams)
        report["stateless_reference"] = {
            "bits": report_sl["bits"],
            "exchanges": report_sl["exchanges"],
        }
        for ex in exs:
            check_ef_zero_overhead(report, report_sl, exchange=ex)
    if T.is_regular(adj):
        # a regular graph MUST lower to ppermute and pass the byte
        # assertion — a compile failure would otherwise make the gate
        # pass vacuously (check_topology_bytes raises on recorded errors)
        # sparse graphs must also beat the dense exchange by the margin
        # the degree implies (ring at N=8: 2/8 = 0.25x, bound 0.5x).
        # On the adapter wire the full-gather reference does not exist
        # (merge is neighborhood-wise) and the byte gate is EXACT.
        frac = None if adapters else (0.5 if 2 * deg <= pods else None)
        check_topology_bytes(report, exchange="ppermute", rel_tol=0.10,
                             gather_frac=frac,
                             exact=bool(adapters) or inner > 1)
        if adapters:
            # the headline adapter gate: the dense full-parameter round
            # at the SAME spec, same graph — factors must move
            # < adapter_frac of its physical permute bytes
            report_dense = measure_exchange_bytes(
                arch, pods, topology, bits=spec.stateless(),
                exchanges=("ppermute",), inner=inner)
            report["dense_reference"] = {
                "bits": report_dense["bits"],
                "packed_pred_bytes_per_node":
                    report_dense["packed_pred_bytes_per_node"],
                "exchanges": report_dense["exchanges"],
            }
            # the gram group rides the wire at full [*, k, k] per leaf,
            # so gram mode legitimately costs more — unless the caller
            # pins a fraction, record the ratio without gating it
            check_adapter_reduction(
                report, report_dense, exchange="ppermute",
                frac=(adapter_frac if adapter_frac is not None
                      else (None if adapter_grams else 0.15)))
        if spec.stateless() != WireSpec.from_bits(16):
            # the headline knob: the same graph at int16, and the
            # physical buffer bytes must scale by exactly spec/int16
            # (only the ppermute mode is consumed — skip the other
            # reference compiles)
            report16 = measure_exchange_bytes(arch, pods, topology, bits=16,
                                              exchanges=("ppermute",),
                                              inner=inner,
                                              adapter_rank=adapters,
                                              adapter_grams=adapter_grams)
            report["int16_reference"] = {
                "packed_pred_bytes_per_node":
                    report16["packed_pred_bytes_per_node"],
                "exchanges": report16["exchanges"],
            }
            check_bits_reduction(report, report16, exchange="ppermute")
    return report


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="pod1", choices=["pod1", "pod2"])
    ap.add_argument("--json", default=None, help="write report JSON here")
    ap.add_argument("--no-federate", action="store_true")
    ap.add_argument("--microbatches", type=int, default=0)
    ap.add_argument("--layout", default="auto",
                    choices=["auto", "tp", "fsdp"])
    ap.add_argument("--no-fsdp", action="store_true",
                    help="replicate params over the data axis (weight "
                         "gathers removed; for <=15B-class archs)")
    ap.add_argument("--topology", default=None,
                    help="gossip graph spec: compile the federation round "
                         "per exchange mode on an (N,1,1) mesh and assert "
                         "physical == logical wire bytes")
    ap.add_argument("--pods", default="8",
                    help="federation nodes for --topology mode: 'R' or "
                         "'RxC' (R nodes x C inner devices per node; "
                         "C > 1 compiles the row-sharded permute on a "
                         "multi-axis pod mesh and the byte gate becomes "
                         "exact on the pod-axis permute)")
    ap.add_argument("--bits", default="16",
                    help="wire spec for --topology mode: 16 | 8 | 4 "
                         "(uniform) or <student>/<protos> (mixed, e.g. "
                         "4/16 = int4 student + int16 prototypes); "
                         "append +ef (or pass --ef) for the stateful "
                         "error-feedback codec")
    ap.add_argument("--ef", action="store_true",
                    help="error-feedback wire codec for --topology mode: "
                         "compiles the stateful round AND its stateless "
                         "twin, asserting byte-identical collectives "
                         "(EF must cost zero wire bytes)")
    ap.add_argument("--adapters", type=int, default=0, metavar="RANK",
                    help="adapter-rank wire for --topology mode: matrix "
                         "leaves gossip rank-r delta factors; the gate "
                         "asserts permute bytes == accountant prediction "
                         "EXACTLY and < --adapter-frac x the dense "
                         "full-parameter exchange")
    ap.add_argument("--adapter-grams", action="store_true",
                    help="ship RegMean gram statistics as their own "
                         "payload group (with --adapters)")
    ap.add_argument("--adapter-frac", type=float, default=None,
                    help="required adapter-vs-dense physical byte "
                         "fraction (default 0.15; with --adapter-grams "
                         "the ratio is recorded but not gated unless "
                         "this is set)")
    args = ap.parse_args()
    # before the first jax call: jax fixes its platform and the host
    # device count when its backend initializes
    jax.config.update("jax_platforms", "cpu")
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

    if args.topology is not None:
        try:
            report = topology_report(args.arch, args.topology, args.pods,
                                     bits=args.bits, ef=args.ef,
                                     adapters=args.adapters,
                                     adapter_grams=args.adapter_grams,
                                     adapter_frac=args.adapter_frac)
            report["status"] = "ok"
        except Exception as e:
            report = {"arch": args.arch, "topology": args.topology,
                      "status": "error", "error": f"{type(e).__name__}: {e}",
                      "traceback": traceback.format_exc()}
        print(json.dumps(report, indent=2, default=str))
        if args.json:
            with open(args.json, "w") as f:
                json.dump(report, f, indent=2, default=str)
        sys.exit(0 if report["status"] == "ok" else 1)

    if args.shape is None:
        ap.error("--shape is required (unless --topology is given)")
    try:
        report = lower_combo(args.arch, args.shape, args.mesh,
                             include_federate=not args.no_federate,
                             fsdp=not args.no_fsdp,
                             microbatches=args.microbatches,
                             layout=args.layout)
        report["status"] = "ok"
    except Exception as e:
        report = {"arch": args.arch, "shape": args.shape, "mesh": args.mesh,
                  "status": "error", "error": f"{type(e).__name__}: {e}",
                  "traceback": traceback.format_exc()}
    print(json.dumps(report, indent=2, default=str))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=2, default=str)
    sys.exit(0 if report["status"] == "ok" else 1)


if __name__ == "__main__":
    main()
