"""The program's spans and scopes (``repro.spans``, ``jax.named_scope``).

* every convolution and dot of the stacked round program carries
  exactly one ``round.*`` scope in its ``op_name``;
* the span table nests, adds seconds and counts per name, and credits
  a compile to the spans open at the time and to none when none is;
* a run of ``run_federation`` compiles its round program under
  ``fed.dispatch`` in round 0 only, and times its rounds by ``fed.round``.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import round_program as RP
from repro import spans
from repro.config import FederationConfig, get_config
from repro.core import federation as F


@pytest.mark.parametrize("arch,proto_pass,bits", [
    ("resnet", "exact", 16),
    ("cnn", "fused", 8),
])
def test_round_program_contractions_carry_one_scope(monkeypatch, arch,
                                                     proto_pass, bits):
    cfg = RP.config(arch)
    built, call = RP.capture_round(monkeypatch, cfg,
                                   RP.federation(proto_pass, bits))
    ops = RP.contractions(RP.lower(built, call).compile().as_text())
    assert ops
    found = set()
    for _, op_name in ops:
        if op_name is None:
            # XLA:CPU's convolution rewrites make new instructions without
            # metadata; tests/test_tpu_compile.py checks the program as
            # the chip's compiler leaves it, with no exception
            continue
        got = RP.scopes(op_name)
        assert len(got) == 1 and got <= set(RP.ROUND_SCOPES), op_name
        found |= got
    assert {"round.teacher", "round.student", "round.protos"} <= found


def test_spans_nest_and_credit_compiles_to_open_spans():
    spans.reset()
    with spans.span("a") as a:
        time.sleep(0.01)
        with spans.span("b"):
            jax.jit(lambda x: jnp.sin(x) * 3.0)(jnp.ones(7)).block_until_ready()
        with spans.span("b"):
            pass
    c = spans.counters()
    assert c["a.n"] == 1 and c["b.n"] == 2
    assert c["a.s"] == pytest.approx(a.seconds) and c["a.s"] >= 0.01
    assert c["a.s"] >= c["b.s"] > 0
    assert c["b.compiles"] >= 1 and c["a.compiles"] == c["b.compiles"]
    assert c["b.traces"] >= 1
    assert 0 < c["b.compile_s"] <= c["b.s"]
    assert c["a.compile_s"] == pytest.approx(c["b.compile_s"])

    spans.reset()
    jax.jit(lambda x: jnp.cos(x) * 5.0)(jnp.ones(9)).block_until_ready()
    spans.count("x", 2)
    assert spans.counters() == {"x": 2}


def test_nested_compile_events_count_each_second_once(monkeypatch):
    spans.reset()
    now = time.time()
    with spans.span("a"):
        # a trace inside an outer trace, then a cache load inside a
        # backend compile; each event arrives as it ends
        for event, secs, end in ((spans.TRACE_EVENT, 0.2, now - 1.0),
                                 (spans.TRACE_EVENT, 0.5, now - 0.9),
                                 (spans.CACHE_EVENT, 0.1, now - 0.15),
                                 (spans.COMPILE_EVENT, 0.3, now)):
            monkeypatch.setattr(spans.time, "time", lambda end=end: end)
            spans._on_duration(event, secs)
    c = spans.counters()
    assert c["a.compile_s"] == pytest.approx(0.5 + 0.3)
    assert c["a.traces"] == 2 and c["a.compiles"] == 1


def test_round_program_compiles_in_round_zero_only(monkeypatch):
    cfg = get_config("mnist-cnn")
    node_data, test_d = RP.node_data(cfg, 300)
    # alpha_limit 0: the teacher trains every round, one program variant
    fed = FederationConfig(num_nodes=RP.N_NODES, rounds=3, local_epochs=1,
                           algorithm="profe", alpha_limit=0.0)
    per_round = []
    eval_nodes = F._eval_nodes

    def eval_and_read(*a, **kw):
        per_round.append(spans.counters().get("fed.dispatch.compiles", 0))
        return eval_nodes(*a, **kw)

    monkeypatch.setattr(F, "_eval_nodes", eval_and_read)
    spans.reset()
    res = F.run_federation(cfg, fed, RP.TRAIN, node_data, test_d)
    c = spans.counters()
    assert per_round[0] >= 1 and per_round == [per_round[0]] * 3
    assert c["fed.run.n"] == 1 and c["fed.init.n"] == 1
    assert c["fed.round.n"] == 3 and c["fed.dispatch.n"] == 3
    for name in ("fed.meter", "fed.eval", "fed.sync"):
        assert c[name + ".n"] == 3
    assert c["fed.stage.n"] == 1 + 3          # the probe, then proto streams
    times = res.extras["round_times_s"]
    assert len(times) == 3
    assert sum(times) == pytest.approx(c["fed.round.s"])
    assert c["fed.run.compile_s"] >= c["fed.dispatch.compile_s"] > 0
    assert np.isfinite(res.extras["loss_per_round"]).all()
