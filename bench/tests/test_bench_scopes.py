"""The reduction by the program's scopes and spans, on synthetic events
and on the span table of a CPU run."""
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

from bench import harness
from bench import scopes as S
from bench import trace as T

DEV = "/device:TPU:0"


def _metric(name, ctx):
    return harness.read_metric(name, ctx)


def _ctx(ops=(), host=(), window=(0.0, 100.0), rounds=2):
    tr = T.Trace({DEV: sorted(ops)} if ops else {},
                 sorted(host, key=lambda e: e[1]), window)
    busy_s = T.mean_busy_s(tr)
    return SimpleNamespace(trace=tr, busy_s=busy_s,
                           window_s=(window[1] - window[0]) / 1e9,
                           out={"traced_rounds": rounds})


def test_idle_parts_add_up_to_the_idle_share():
    ops = [("a", 0.0, 10.0), ("b", 30.0, 50.0), ("c", 45.0, 60.0),
           ("d", 90.0, 120.0)]
    host = [("fed.round", 0.0, 100.0),
            ("fed.stage", 5.0, 25.0),        # 15 idle of it
            ("fed.eval", 55.0, 80.0),        # 20 idle of it
            ("fed.stage", 85.0, 95.0),       # 5 idle of it
            ("other span", 60.0, 70.0)]
    ctx = _ctx(ops, host)
    parts = {p: _metric(f"idle.{p}", ctx) for p in ("stage", "eval", "other")}
    assert parts["stage"] == pytest.approx(20.0)
    assert parts["eval"] == pytest.approx(20.0)
    idle = _metric("idle_share.round", ctx)
    assert idle == pytest.approx(100.0 - 10 - 30 - 10)
    assert sum(parts.values()) == pytest.approx(idle)
    assert parts["other"] == pytest.approx(10.0)


def test_idle_split_takes_overlapping_spans_once():
    ops = [("a", 0.0, 10.0)]
    host = [("fed.stage", 20.0, 60.0), ("fed.eval", 40.0, 80.0)]
    split = S.idle_split(_ctx(ops, host).trace, S.IDLE_SPANS)
    assert split["fed.stage"] == pytest.approx(40.0)
    assert split["fed.eval"] == pytest.approx(20.0)
    assert split["other"] == pytest.approx(30.0)


def test_scope_matching_under_transforms():
    name = "jit(round_fn)/while/body/closed_call/vmap(round.teacher)/" \
           "transpose(jvp(round.teacher))/conv_general_dilated"
    assert S.has_scope(name, "round.teacher")
    assert S.has_scope("jit(round_fn)/transpose(jvp(round.student))",
                       "round.student")
    assert S.has_scope("round.mix/dot_general", "round.mix")
    assert not S.has_scope(name, "round.student")
    assert not S.has_scope("jit(f)/round.teachers/mul", "round.teacher")
    assert not S.has_scope("jit(f)/my_round.teacher/mul", "round.teacher")


def _op(start, end, name, module="jit_round_fn(3)"):
    return S.Op(start, end, name, module)


def test_overlapping_ops_in_one_scope_count_once_and_unscoped_in_none():
    ops = [_op(0.0, 10.0, "jit(round_fn)/round.teacher/conv"),
           _op(5.0, 20.0, "jit(round_fn)/jvp(round.teacher)/mul"),
           _op(20.0, 30.0, "jit(round_fn)/round.student/dot"),
           _op(30.0, 40.0, "jit(round_fn)/round.codec/convert"),
           _op(35.0, 45.0, "jit(round_fn)/round.mix/dot_general"),
           _op(50.0, 70.0, "jit(run)/conv_general_dilated", "jit_run(9)"),
           _op(70.0, 80.0, "jit(round_fn)/while/body/copy")]
    w = (0.0, 100.0)
    assert S.scoped_ns(ops, ["round.teacher"], w) == 20.0
    assert S.scoped_ns(ops, ["round.student"], w) == 10.0
    assert S.scoped_ns(ops, ["round.codec", "round.mix"], w) == 15.0
    assert S.scoped_ns(ops, S.ROUND_SCOPES, w) == 45.0
    # the eager evaluation's ops and the unscoped copy are in no scope
    assert S.coverage({DEV: ops}, w, S.ROUND_SCOPES) == \
        pytest.approx(100.0 * 45.0 / 55.0)


def test_device_metrics_per_traced_round(monkeypatch):
    ops = [_op(0.0, 10.0, "jit(round_fn)/round.teacher/conv"),
           _op(5.0, 20.0, "jit(round_fn)/transpose(jvp(round.teacher))/c"),
           _op(20.0, 26.0, "jit(round_fn)/round.protos/dot"),
           _op(26.0, 30.0, "jit(round_fn)/round.mix/dot"),
           _op(30.0, 90.0, "jit(_eval)/conv_general_dilated", "jit_run")]
    monkeypatch.setattr(S, "load_ops", lambda _d: {DEV: ops})
    ctx = _ctx([("x", o.start, o.end) for o in ops], rounds=2)
    got = {m: _metric(f"device_s.{m}", ctx)
           for m in ("teacher", "student", "protos", "exchange")}
    assert got == {"teacher": pytest.approx(10e-9),
                   "student": 0.0, "protos": pytest.approx(3e-9),
                   "exchange": pytest.approx(2e-9)}
    assert sum(got.values()) * 2 <= ctx.busy_s


def test_a_trace_without_scopes_or_spans_reads_nothing(monkeypatch):
    ops = [_op(0.0, 10.0, "jit(round_fn)/while/body/conv")]
    monkeypatch.setattr(S, "load_ops", lambda _d: {DEV: ops})
    ctx = _ctx([("x", 0.0, 10.0)], host=[("bench.window", 0.0, 100.0)])
    for m in ("device_s.teacher", "device_s.exchange", "idle.stage",
              "idle.eval", "idle.other"):
        assert _metric(m, ctx) is None, m


def test_set_up_metrics_read_the_span_table(monkeypatch):
    from repro import spans
    monkeypatch.setattr(spans, "_table", {})
    assert _metric("setup.init_s", None) is None
    assert _metric("setup.compile_s", None) is None
    with spans.span("fed.run"):
        with spans.span("fed.init"):
            jax.jit(lambda x: jnp.tanh(x) + 7.0)(jnp.ones(5)) \
                .block_until_ready()
    c = spans.counters()
    init = _metric("setup.init_s", None)
    assert init == pytest.approx(c["fed.init.s"] - c["fed.init.compile_s"])
    assert 0.0 <= init < c["fed.init.s"]
    assert _metric("setup.compile_s", None) == c["fed.run.compile_s"] > 0


def _varint(n):
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _msg(*fields):
    """A protobuf message from ``(field number, int | str | bytes)``."""
    out = b""
    for num, v in fields:
        if isinstance(v, int):
            out += _varint(num << 3) + _varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _varint(num << 3 | 2) + _varint(len(v)) + v
    return out


def test_load_ops_reads_op_names_and_modules_from_the_trace_file(tmp_path):
    stat_meta = [_msg((1, k), (2, _msg((1, k), (2, name)))) for k, name in
                 ((1, "tf_op"), (2, "flops"),
                  (3, "jit(round_fn)/transpose(jvp(round.student))/dot"))]
    fusion = "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop"
    loop = "%while.3 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %t)"
    metas = {
        10: _msg((1, 10), (2, fusion),
                 (5, _msg((1, 1), (5, "jit(round_fn)/round.teacher/mul"))),
                 (5, _msg((1, 2), (4, 99)))),
        11: _msg((1, 11), (2, loop)),
        12: _msg((1, 12), (2, "jit_round_fn(7)")),
        13: _msg((1, 13), (2, "%dot.4 = f32[8]{0} dot(%a, %b)"),
                 (5, _msg((1, 1), (7, 3)))),       # an interned string
    }
    event_meta = [_msg((1, k), (2, v)) for k, v in metas.items()]

    def line(name, ts, events):
        return _msg((1, 1), (2, name), (3, ts),
                    *[(4, _msg((1, m), (2, off), (3, dur)))
                      for m, off, dur in events])

    plane = _msg((1, 0), (2, "/device:TPU:0"),
                 (3, line("XLA Modules", 1000, [(12, 0, 100_000)])),
                 (3, line("XLA Ops", 1000, [(10, 0, 20_000),
                                            (11, 0, 50_000),
                                            (13, 30_000, 10_000),
                                            (10, 200_000, 5_000)])),
                 *[(4, e) for e in event_meta], *[(5, s) for s in stat_meta])
    host = _msg((1, 1), (2, "/host:CPU"))
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "h.xplane.pb").write_bytes(_msg((1, plane), (1, host)))
    ops = S.load_ops(str(tmp_path))
    assert list(ops) == ["/device:TPU:0"]
    assert ops["/device:TPU:0"] == [
        S.Op(1000.0, 1020.0, "jit(round_fn)/round.teacher/mul",
             "jit_round_fn(7)"),
        S.Op(1030.0, 1040.0,
             "jit(round_fn)/transpose(jvp(round.student))/dot",
             "jit_round_fn(7)"),
        S.Op(1200.0, 1205.0, "jit(round_fn)/round.teacher/mul", "")]
    assert S.coverage(ops, (0.0, 2000.0), S.ROUND_SCOPES) == 100.0
