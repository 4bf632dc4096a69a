#!/usr/bin/env python3
"""On-chip smoke test of ProFe's federation round.

    python3 chip_smoke.py             # one chip
    python3 chip_smoke.py --chips 4   # four chips: the mesh round only

One chip: ``repro.core.federation.run_federation`` (the stacked engine,
``algorithm="profe"``) trains the cifar10-resnet18 teacher at its
published width (ResNet18, width 64, 32x32x3 inputs, 10 classes) next
to its derived student on every node: the paper's 20 nodes, 3 rounds
of one local epoch, 4 batches of 32 per node.  The
images are synthetic, made from ``SEED`` and split iid into equal node
shares of whole batches, so the stacked engine never falls back to the
per-node loop.  Two runs: the default int16 wire on the full graph, and
the int4 error-feedback wire on a ring.  Then, on the round's own
shapes, the chip's codec and optimizer kernels must equal their jnp
references exactly, its Eq. 3 accumulate must match at the tests'
tolerance, and the round's gossip mix and Eq. 4 aggregate must match a
float64 host reference at the tests' tolerance.

Four chips (``--chips 4``): ``make_profe_round`` on ``fed_mesh(4)``, one
federation node per chip, with the cifar10-resnet18 student stacked as
a ``Plane``: the ring round over ``exchange="ppermute"`` against the
same round over ``exchange="gather"``; the compiled program's
collective bytes per node against ``ScheduleCommAccountant``; and each
node's output shard on its own chip.

Lines before the last are facts of the run.  The last line is one JSON
object, ``{"ok": true, "device": {...}}``.  The script exits non-zero
and prints no result when JAX finds no TPU, when the repo's sources are
not next to it, or when any check fails.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

ARCH = "cifar10-resnet18"
NODES = 20
ROUNDS = 3
BATCHES = 4          # local batches per node and epoch
BATCH = 32
SEED = 0
RUNS = (
    ("int16/full", dict(quantize_bits=16, topology="full")),
    ("int4+ef/ring", dict(quantize_bits=4, error_feedback=True,
                          topology="ring")),
)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class SmokeFailure(Exception):
    """A check of the smoke run did not hold."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# one chip: run_federation through the stacked engine
# ---------------------------------------------------------------------------

def make_data(n_test: int = 512):
    from repro.config import get_config
    from repro.data import make_image_dataset, partition
    cfg = get_config(ARCH)
    data = make_image_dataset(SEED, NODES * BATCHES * BATCH + n_test,
                              cfg.input_hw, cfg.num_classes)
    test = {k: v[:n_test] for k, v in data.items()}
    train = {k: v[n_test:] for k, v in data.items()}
    parts = partition(train["label"], NODES, "iid", SEED)
    return cfg, [{k: v[i] for k, v in train.items()} for i in parts], test


def custom_calls(ir_dir: str, fn_name: str) -> int:
    """``tpu_custom_call`` sites in the module JAX handed the compiler
    for the jitted ``fn_name`` (dumped before any compile-cache lookup,
    so a warm cache still shows the program)."""
    files = glob.glob(os.path.join(ir_dir, f"*jit_{fn_name}_compile.mlir"))
    check(bool(files), f"no compiled module of {fn_name} was dumped")
    return sum(Path(f).read_text().count("@tpu_custom_call") for f in files)


def run_profe(cfg, node_data, test, name, kw, *, compile_s):
    import jax
    import numpy as np
    from repro.config import FederationConfig, TrainConfig
    from repro.core.federation import run_federation

    fed = FederationConfig(num_nodes=len(node_data), rounds=ROUNDS,
                           local_epochs=1, algorithm="profe", seed=SEED, **kw)
    compile_s.clear()
    dump_was = jax.config.values.get("jax_dump_ir_to")
    with tempfile.TemporaryDirectory() as ir_dir:
        jax.config.update("jax_dump_ir_to", ir_dir)
        try:
            res = run_federation(cfg, fed, TrainConfig(batch_size=BATCH),
                                 node_data, test)
        finally:
            jax.config.update("jax_dump_ir_to", dump_was)
        n_calls = custom_calls(ir_dir, "round_fn")
    dev = jax.devices()[0]
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    x = res.extras
    log(f"[{name}] engine={x['engine']} nodes={fed.num_nodes} "
        f"rounds={ROUNDS} compile_s={sum(compile_s):.1f} "
        f"round_s={[round(t, 3) for t in x['round_times_s']]} "
        f"f1={[round(f, 4) for f in res.f1_per_round]} "
        f"loss={[round(v, 4) for v in x['loss_per_round']]} "
        f"avg_sent_gb={x['avg_sent_gb']:.6f} "
        f"avg_sent_packed_gb={x['avg_sent_packed_gb']:.6f} "
        f"peak_bytes_in_use={peak} tpu_custom_call={n_calls}")
    check(x["engine"] == "stacked",
          f"{name}: run_federation fell back to the per-node loop")
    check(n_calls > 0, f"{name}: the round program holds no Pallas kernel")
    check(bool(np.all(np.isfinite(res.f1_per_round))),
          f"{name}: non-finite F1 {res.f1_per_round}")
    check(bool(np.all(np.isfinite(x["loss_per_round"]))),
          f"{name}: non-finite loss {x['loss_per_round']}")


def _max_abs(a, b) -> float:
    import numpy as np
    return float(np.max(np.abs(np.asarray(a, np.float64)
                               - np.asarray(b, np.float64))))


def _equal(name: str, got, want) -> None:
    """Bitwise agreement, as the kernels' tests assert it."""
    import numpy as np
    got, want = np.asarray(got), np.asarray(want)
    n_diff = int(np.sum(got != want))
    log(f"[check] {name}: max|diff|={_max_abs(got, want):.3g}, "
        f"{n_diff} of {got.size} differ")
    check(n_diff == 0, f"{name}: differs from its reference")


def _close(name: str, got, want, *, rtol: float, atol: float) -> None:
    import numpy as np
    log(f"[check] {name}: max|diff|={_max_abs(got, want):.3g}")
    np.testing.assert_allclose(np.asarray(got, np.float64),
                               np.asarray(want, np.float64),
                               rtol=rtol, atol=atol, err_msg=name)


def check_against_references(cfg) -> None:
    """On the round's shapes: the plane payload codec (int16, and int4
    with error feedback, residual included) and the fused adamw sweep
    equal their jnp references bitwise; the Eq. 3 accumulate matches at
    ``tests/test_kernels.py``'s tolerance; the gossip mix and the Eq. 4
    neighborhood aggregate of both runs' graphs match a float64 host
    reference at ``tests/test_round_engine.py``'s tolerance."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import round_ops as R
    from repro.core import topology as T
    from repro.kernels.opt_update.ops import fused_adamw_update
    from repro.kernels.proto_accum.ops import proto_accumulate_nodes
    from repro.kernels.quantize import ops as Q
    from repro.models import derive_student, init_params
    from repro.optim.plane import Plane, plane_from_tree
    from repro.wirespec import WireSpec

    check(not Q._interpret(), "the Pallas kernels would run interpreted")
    scfg = derive_student(cfg)
    k = jax.random.split(jax.random.PRNGKey(SEED), 9)
    plane = jax.jit(jax.vmap(lambda key: plane_from_tree(
        init_params(scfg, key))))(jax.random.split(k[0], NODES))
    ncls, pdim = cfg.num_classes, scfg.proto_dim
    protos = jax.random.normal(k[1], (NODES, ncls, pdim), jnp.float32)
    residual = {
        "protos": 0.01 * jax.random.normal(k[2], protos.shape, jnp.float32),
        "student": Plane(0.01 * jax.random.normal(k[3], plane.buf.shape,
                                                  jnp.float32),
                         plane.raw, plane.meta)}
    payload = {"protos": protos, "student": plane}

    received = None
    for spec in (WireSpec.from_bits(16),
                 WireSpec(student_bits=4, error_feedback=True)):
        res = residual if spec.error_feedback else None

        def codec(use_kernels, spec=spec, res=res):
            return jax.jit(lambda p, r: Q.quantize_dequantize_plane_payload(
                p, spec=spec, use_kernels=use_kernels, residual=r))(
                    payload, res)
        got, want = codec(True), codec(False)
        if res is not None:
            (got, got_res), (want, want_res) = got, want
            _equal(f"codec {spec.describe()} student residual",
                   got_res["student"].buf, want_res["student"].buf)
            _equal(f"codec {spec.describe()} protos residual",
                   got_res["protos"], want_res["protos"])
        else:
            received = got
        _equal(f"codec {spec.describe()} student",
               got["student"].buf, want["student"].buf)
        _equal(f"codec {spec.describe()} protos",
               got["protos"], want["protos"])

    shape = plane.buf.shape
    g, mu = (0.01 * jax.random.normal(kk, shape, jnp.float32)
             for kk in (k[4], k[5]))
    nu = jnp.abs(1e-4 * jax.random.normal(k[6], shape, jnp.float32))

    # The kernel reads lr/scale/bc1/bc2 at run time either way.  The
    # reference gets them as constants: with traced scalars XLA:TPU
    # rounds the update's scalar mul-add differently from the Pallas
    # compiler (one ulp, ~7% of params), with folded ones it rounds
    # exactly as the kernel does.
    def adamw(use_kernels):
        return jax.jit(lambda *a: fused_adamw_update(
            *a, 1e-3, 0.5, 1 - 0.9, 1 - 0.999, b1=0.9, b2=0.999, eps=1e-8,
            weight_decay=0.01, use_kernels=use_kernels))(g, plane.buf, mu, nu)
    for name, a, b in zip(("params", "mu", "nu"), adamw(True), adamw(False)):
        _equal(f"adamw {name}", a, b)

    f1 = jax.random.normal(k[7], (NODES, BATCH, pdim), jnp.float32)
    labels = jax.random.randint(k[7], (NODES, BATCH), 0, ncls)
    (s_k, c_k), (s_r, c_r) = (
        proto_accumulate_nodes(f1, labels, ncls, use_kernels=uk)
        for uk in (True, False))
    _equal("proto_accum counts", c_k, c_r)
    _close("proto_accum sums", s_k, s_r, rtol=1e-5, atol=1e-5)

    # the round's gossip mix (own copy unquantized, neighbors' int16
    # wire view) and Eq. 4, per graph, against float64 on the host
    own = np.asarray(plane.buf, np.float64)
    recv = np.asarray(received["student"].buf, np.float64)
    p_rx = np.asarray(received["protos"], np.float64)
    counts = jax.random.randint(k[8], (NODES, ncls), 0, 5).astype(jnp.float32)
    cnt = np.asarray(counts, np.float64)
    for _, kw in RUNS:
        topo = kw["topology"]
        w_self, w_neigh, include = (
            x[0] for x in T.make_schedule(NODES, topo, seed=SEED).lower(
                np.ones(NODES, np.float32)))
        mixed = jax.jit(R.mix_node_trees)(w_self, w_neigh, plane,
                                          received["student"])
        ws, wn = (np.asarray(x, np.float64) for x in (w_self, w_neigh))
        _close(f"gossip mix ({topo})", mixed.buf,
               ws[:, None, None] * own + np.einsum("mn,nrc->mrc", wn, recv),
               rtol=1e-5, atol=1e-6)
        gp, mask = jax.jit(R.neighborhood_prototype_aggregate)(
            include, received["protos"], counts)
        eff = np.asarray(include, np.float64)[:, :, None] * cnt[None]
        n_j = eff.sum(axis=1)
        want_gp = np.einsum("ijc,jcp->icp",
                            eff / np.maximum(n_j, 1.0)[:, None, :], p_rx)
        _close(f"Eq. 4 aggregate ({topo})", gp, want_gp,
               rtol=1e-5, atol=1e-6)
        _equal(f"Eq. 4 mask ({topo})", mask, (n_j > 0).astype(np.float32))


def one_chip() -> None:
    import jax
    compile_s = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compile_s.append(secs)
        if event == COMPILE_EVENT else None)
    cfg, node_data, test = make_data()
    for name, kw in RUNS:
        run_profe(cfg, node_data, test, name, kw, compile_s=compile_s)
    check_against_references(cfg)


# ---------------------------------------------------------------------------
# four chips: the mesh engine, one node per chip
# ---------------------------------------------------------------------------

def mesh_round_inputs(mesh):
    """The 4-node ring round's operands on ``mesh``: the cifar10-resnet18
    student stacked as a ``Plane`` (one node per pod-axis device),
    prototypes, class counts and node sizes — plus the param specs and
    the accountant's per-copy payload skeleton."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.config import get_config
    from repro.launch.wire import accountant_payload
    from repro.models import derive_student, init_params
    from repro.optim.plane import plane_from_tree
    from repro.sharding import param_specs

    n = mesh.shape["pod"]
    cfg = get_config(ARCH)
    scfg = derive_student(cfg)
    struct = jax.eval_shape(lambda: init_params(scfg, jax.random.PRNGKey(0)))
    specs = param_specs(scfg, struct, mesh)
    node = NamedSharding(mesh, P("pod"))
    k = jax.random.split(jax.random.PRNGKey(SEED), 3)
    students = jax.jit(jax.vmap(lambda key: plane_from_tree(
        init_params(scfg, key))), out_shardings=node)(jax.random.split(k[0], n))
    protos = jax.device_put(jax.random.normal(
        k[1], (n, cfg.num_classes, scfg.proto_dim), jnp.float32), node)
    counts = jax.device_put(jax.random.randint(
        k[2], (n, cfg.num_classes), 0, 5).astype(jnp.float32), node)
    sizes = jax.device_put(jnp.arange(1.0, n + 1.0, dtype=jnp.float32),
                           NamedSharding(mesh, P()))
    payload = accountant_payload(struct, cfg.num_classes, scfg.proto_dim)
    return specs, (students, protos, counts, sizes), payload


def mesh_chips() -> None:
    import jax
    import numpy as np
    from repro.core import topology as T
    from repro.core.comm import ScheduleCommAccountant
    from repro.core.mesh_federation import make_profe_round
    from repro.launch.hlo_analysis import analyze_hlo
    from repro.launch.wire import fed_mesh
    from repro.wirespec import WireSpec

    n = 4
    check(len(jax.devices()) >= n, f"--chips 4 needs 4 chips, JAX finds "
          f"{len(jax.devices())}")
    mesh = fed_mesh(n)
    specs, inputs, payload = mesh_round_inputs(mesh)
    sched = T.make_schedule(n, "ring", rounds=1, seed=SEED)
    adj = sched.adjacency_at(0)
    outs, texts = {}, {}
    for ex in ("ppermute", "gather"):
        fn = make_profe_round(mesh, specs, adjacency=adj, exchange=ex)
        with mesh:
            t0 = time.perf_counter()
            compiled = jax.jit(fn).lower(*inputs).compile()
            t_c = time.perf_counter() - t0
            outs[ex] = jax.block_until_ready(compiled(*inputs))
            t0 = time.perf_counter()
            jax.block_until_ready(compiled(*inputs))
            t_r = time.perf_counter() - t0
        texts[ex] = compiled.as_text()
        log(f"[mesh {ex}] compile_s={t_c:.2f} round_s={t_r:.4f}")

    # ppermute == gather at tests/test_round_engine.py's tolerance
    worst = 0.0
    for got, want in zip(jax.tree_util.tree_leaves(outs["ppermute"]),
                         jax.tree_util.tree_leaves(outs["gather"])):
        worst = max(worst, _max_abs(got, want))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
    log(f"[mesh] ppermute vs gather: max|diff|={worst:.3g}")

    # compiled collective bytes per node == the accountant's prediction
    phys = float(analyze_hlo(texts["ppermute"]).coll_total)
    pred = int(ScheduleCommAccountant(sched).predicted_node_bytes(
        payload, 0, WireSpec.from_bits(16), wire="packed").max())
    log(f"[mesh] ppermute collective bytes/node: hlo={phys:.0f} "
        f"accountant={pred}")
    check(phys == pred, f"HLO moves {phys} bytes/node, accountant "
          f"predicts {pred}")

    # one node per chip: each output row block lives on its own device
    buf = outs["ppermute"][0].buf
    shards = buf.addressable_shards
    rows = sorted(s.index[0].start for s in shards)
    devices = {s.device.id for s in shards}
    log(f"[mesh] student shards: rows={rows} devices={sorted(devices)}")
    check(len(devices) == n and rows == list(range(n))
          and all(s.data.shape[0] == 1 for s in shards),
          "node shards are not one per chip")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repo's sources are not next to this "
              f"script ({e})", file=sys.stderr)
        return 2
    cache = enable_compile_cache()
    import jax
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX finds no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    log(f"device: {dev.device_kind} x{len(devs)}, jax {jax.__version__}, "
        f"compile cache {cache}")
    try:
        if args.chips == 4:
            mesh_chips()
        else:
            one_chip()
    except (SmokeFailure, AssertionError) as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
