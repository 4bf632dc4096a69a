"""The plane kernels' byte counts against the program's Pallas calls, and
the roofline reader on synthetic trace events."""
from types import SimpleNamespace

import jax
import numpy as np
import pytest
from jax.extend.core import ClosedJaxpr, Jaxpr

from bench import harness
from bench import roofline
from bench import scopes as S
from bench import trace as T
from bench.counts import plane as counts
from bench.entries import stacked
from repro.models import derive_student, init_params
from repro.optim.plane import Plane, make_plane_optimizer, plane_from_tree

CONFIGS = harness.configs("resnet")
NODES = 2


def _cell(name):
    cfg = harness.load_json(harness.BENCH / "configs" / f"{name}.json")
    return {"config": dict(cfg, nodes=NODES),
            "traffic": harness.load_json(harness.BENCH / "traffic"
                                         / "kd.json")}


def _pallas_bytes(jaxpr):
    """Per ``pallas_call`` of a jaxpr, sub-jaxprs included: the shape and
    bytes of each of its operands and results."""
    calls = []

    def nbytes(v):
        return int(np.prod(v.aval.shape)) * v.aval.dtype.itemsize

    def walk(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name == "pallas_call":
                avals = list(eqn.invars) + list(eqn.outvars)
                calls.append([(tuple(v.aval.shape), nbytes(v))
                              for v in avals])
                continue
            for p in eqn.params.values():
                for sub in (p if isinstance(p, (list, tuple)) else [p]):
                    if isinstance(sub, ClosedJaxpr):
                        walk(sub.jaxpr)
                    elif isinstance(sub, Jaxpr):
                        walk(sub)
    walk(jaxpr.jaxpr)
    return calls


def _student_plane(cfg):
    student = derive_student(stacked.model_configs(cfg))
    one = jax.eval_shape(lambda k: plane_from_tree(init_params(student, k)),
                         jax.random.PRNGKey(0))
    buf = jax.ShapeDtypeStruct((NODES,) + one.buf.shape, one.buf.dtype)
    return Plane(buf, one.raw, one.meta)


@pytest.mark.parametrize("name", CONFIGS)
def test_opt_sweep_bytes_equal_the_kernel_operands(name):
    """One step's clip+AdamW update over every node's plane, traced as
    the round vmaps it: one ``pallas_call`` whose plane operands and
    results are the count; its scalars are a few bytes a node."""
    cell = _cell(name)
    cfg = cell["config"]
    plane = _student_plane(cfg)
    assert plane.buf.shape == (NODES, counts.plane_rows(cfg), counts.COLS)
    t = cfg["train"]
    opt = make_plane_optimizer("adamw", t["learning_rate"],
                               weight_decay=t["weight_decay"],
                               grad_clip=t["grad_clip"], use_kernels=True)
    state = jax.eval_shape(jax.vmap(opt.init), plane)
    jaxpr = jax.make_jaxpr(jax.vmap(opt.update))(plane, state, plane)
    calls = _pallas_bytes(jaxpr)
    assert len(calls) == 1
    big = [b for shape, b in calls[0] if shape == plane.buf.shape]
    rest = [b for shape, b in calls[0] if shape != plane.buf.shape]
    assert len(big) == 7
    assert sum(big) == counts.opt_sweep_bytes(cell)
    assert sum(rest) <= 16 * NODES


def _op(start, end, name):
    return S.Op(start, end, name, "jit_round_fn(3)")


def _ctx(window=(0.0, 1e6)):
    return SimpleNamespace(
        cell=_cell("cifar100-resnet32"), out={"traced_rounds": 2},
        trace=T.Trace({}, [], window),
        peaks={"hbm_bytes_per_s": 1e12})


def test_roofline_counts_the_scope_pallas_calls_inside_the_window(
        monkeypatch):
    sweep = "jit(round_fn)/while/body/vmap(round.student)/pallas_call:"
    codec = "jit(round_fn)/round.codec/pallas_call:"
    ops = [_op(0.0, 100.0, sweep), _op(150.0, 250.0, sweep),
           _op(260.0, 300.0, "jit(round_fn)/vmap(round.student)/add:"),
           _op(300.0, 310.0, codec), _op(310.0, 340.0, codec),
           _op(400.0, 450.0, "jit(round_fn)/round.codec/pallas_calls:"),
           _op(2e6, 2e6 + 100.0, sweep)]            # outside the window
    monkeypatch.setattr(S, "load_ops", lambda _d: {"/device:TPU:0": ops})
    ctx = _ctx()
    # two traced rounds' bytes over 200 ns and 40 ns at 1e12 bytes/s
    assert harness.read_metric("opt_sweep_roofline", ctx) == pytest.approx(
        100.0 * 2 * counts.opt_sweep_round_bytes(ctx.cell)
        / (200e-9 * 1e12))
    assert roofline.hbm_share(ctx, "round.mix", 1) is None


def test_roofline_reads_nothing_without_pallas_calls(monkeypatch):
    ops = [_op(0.0, 10.0, "jit(round_fn)/vmap(round.student)/fusion:")]
    monkeypatch.setattr(S, "load_ops", lambda _d: {"/device:TPU:0": ops})
    assert harness.read_metric("opt_sweep_roofline", _ctx()) is None
