"""The per-round evaluation as one compiled program (``_eval_fn``).

* the program's predictions, F1 and accuracy equal an eager ``forward``
  + ``argmax`` over the same test batches, for a node of a stacked
  plane student and for a plain pytree student;
* a stacked ``run_federation`` calls it once per test batch a round
  and traces and compiles it in round 0 only.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import round_program as RP
from repro import spans
from repro.config import FederationConfig
from repro.core import federation as F
from repro.core.metrics import accuracy, macro_f1
from repro.data import make_image_dataset
from repro.models import derive_student, forward, init_params
from repro.optim.plane import plane_from_tree

BATCH = 256
N_TEST = 300            # two test batches: 256 and a ragged 44


def _test_set(cfg, n=N_TEST):
    return make_image_dataset(1, n, cfg.input_hw, cfg.num_classes)


def _eager_preds(cfg, params, test_d):
    preds = []
    for i in range(0, N_TEST, BATCH):
        batch = {k: jnp.asarray(v[i:i + BATCH]) for k, v in test_d.items()}
        out = forward(cfg, params, batch, remat=False)
        preds.append(np.asarray(jnp.argmax(out.logits, -1)))
    return np.concatenate(preds)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("student,node", [("plane", 0), ("plane", 2),
                                          ("tree", None)])
def test_compiled_eval_matches_eager_forward(dtype, student, node):
    cfg = derive_student(RP.tiny_resnet()).replace(dtype=dtype)
    trees = [init_params(cfg, jax.random.PRNGKey(i)) for i in range(3)]
    test_d = _test_set(cfg)
    want_tree = trees[node or 0]
    if student == "plane":
        held = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                      *[plane_from_tree(t) for t in trees])
    else:
        held = want_tree

    fn = F._eval_fn(cfg)
    got = np.concatenate([
        np.asarray(fn(held, None if node is None else np.int32(node),
                      {k: v[i:i + BATCH] for k, v in test_d.items()}))
        for i in range(0, N_TEST, BATCH)])
    want = _eager_preds(cfg, want_tree, test_d)
    assert got.shape == want.shape == (N_TEST,)
    assert len(np.unique(want)) > 1      # not a constant classifier

    f1, acc = F._eval_params(cfg, held, test_d, node=node)
    y = test_d["label"]
    want_f1 = macro_f1(y, want, cfg.num_classes)
    want_acc = accuracy(y, want)
    if dtype == "float32":
        np.testing.assert_array_equal(got, want)
        assert (f1, acc) == (want_f1, want_acc)
    else:
        assert abs(f1 - want_f1) < 0.02 and abs(acc - want_acc) < 0.02


@pytest.mark.parametrize("eval_all_nodes", [False, True])
def test_stacked_run_compiles_eval_in_round_zero_only(monkeypatch,
                                                      eval_all_nodes):
    cfg = RP.config("cnn")
    node_data, _ = RP.node_data(cfg, 300)
    test_d = _test_set(cfg)
    rounds = 3
    fed = FederationConfig(num_nodes=RP.N_NODES, rounds=rounds,
                           local_epochs=1, algorithm="profe",
                           alpha_limit=0.0)
    after = []
    eval_nodes = F._eval_nodes

    def eval_and_read(*a, **kw):
        out = eval_nodes(*a, **kw)
        c = spans.counters()
        after.append((c.get("fed.eval.programs", 0),
                      c.get("fed.eval.traces", 0),
                      c.get("fed.eval.compiles", 0)))
        return out

    monkeypatch.setattr(F, "_eval_nodes", eval_and_read)
    spans.reset()
    res = F.run_federation(cfg, fed, RP.TRAIN, node_data, test_d,
                           eval_all_nodes=eval_all_nodes)
    assert res.extras["engine"] == "stacked"
    batches = math.ceil(N_TEST / BATCH)
    assert [a[0] for a in after] == [batches * (r + 1) for r in range(rounds)]
    _, traces0, compiles0 = after[0]
    assert traces0 >= batches and compiles0 >= batches
    assert [a[1:] for a in after[1:]] == [(traces0, compiles0)] * (rounds - 1)
    assert len(res.f1_per_round) == rounds
    if eval_all_nodes:
        assert len(res.extras["f1_per_round_nodes"][0]) == RP.N_NODES
