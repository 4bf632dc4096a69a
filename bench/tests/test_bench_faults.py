"""A run whose timed path is broken underneath comes out not correct:
the harness runs the tiny cell with each fault planted in the program."""
import time

import pytest

from bench import harness
from bench.tests.tiny import CPU, tiny_cell


def _unchanged(orig):
    def make(*a, **kw):
        step = orig(*a, **kw)

        def broken(state, batch, teacher_on):
            _, metrics = step(state, batch, teacher_on)
            return state, metrics
        return broken
    return make


def _half_batch(orig):
    def make(*a, **kw):
        step = orig(*a, **kw)

        def broken(state, batch, teacher_on):
            half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
            return step(state, half, teacher_on)
        return broken
    return make


FAULTS = {
    "state_unchanged": ("repro.core.federation", "make_profe_step",
                        _unchanged),
    "half_batch": ("repro.core.federation", "make_profe_step", _half_batch),
    "exchange_left_out": ("repro.core.round_ops", "mix_node_trees",
                          lambda orig: lambda w_self, w_neigh, own, recv: own),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_is_not_correct(fault, monkeypatch):
    import importlib
    module, name, plant = FAULTS[fault]
    mod = importlib.import_module(module)
    monkeypatch.setattr(mod, name, plant(getattr(mod, name)))
    res = harness.run_cell(tiny_cell(dtype="float32"), 2 ** 31 + 11, 1,
                           False, CPU, time.time())
    assert not res["correct"], res["checks"]
