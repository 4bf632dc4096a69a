"""The cells added beside ``c10r18.kd``, at a size the CPU holds: the
``student`` traffic leaves the teacher alone in the program and in the
reference, and each cell's limits refuse the ``fp8`` control and every
fault planted in the program's timed path."""
import time

import jax
import numpy as np
import pytest

from bench import compare, harness
from bench import reference as ref
from bench.entries import stacked
from bench.tests.test_bench_faults import FAULTS
from bench.tests.tiny import CPU, tiny_cell

TRAFFIC = {"c100r32.kd": "kd", "c10r18.student": "student"}
MAN = harness.manifest()
PHASE_READERS = {"device_s.teacher", "device_s.student", "device_s.protos",
                 "device_s.exchange", "idle.stage", "idle.eval",
                 "idle.other", "setup.init_s", "setup.compile_s"}


def _tiny(name):
    """The tiny cell with ``name``'s traffic, configuration keys and
    limits; the CIFAR100 cell keeps 100 classes with a ResNet8 student
    at width 16 (its own student is 11.3M parameters)."""
    cell = tiny_cell(traffic=TRAFFIC[name], limits_of=name)
    if name == "c100r32.kd":
        cell["config"].update(program_config="cifar10-resnet18",
                              student={"resnet_blocks": [1, 1, 1],
                                       "resnet_width": 16})
    return cell


@pytest.mark.parametrize("name", sorted(TRAFFIC))
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_is_not_correct(name, fault, monkeypatch):
    import importlib
    module, attr, plant = FAULTS[fault]
    mod = importlib.import_module(module)
    monkeypatch.setattr(mod, attr, plant(getattr(mod, attr)))
    res = harness.run_cell(_tiny(name), 2 ** 31 + 41, 1, False, CPU,
                           time.time())
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("name", sorted(TRAFFIC))
def test_control_in_lower_precision_fails_the_limits(name):
    cell = _tiny(name)
    seed = 2 ** 31 + 43
    data, _ = stacked.make_federation_data(seed, cell["config"],
                                           cell["traffic"])
    fed = stacked.reference_federation(cell["config"], cell["traffic"], seed)
    want = stacked.reference_readings(fed, data, 2, block_nodes=2)
    ctrl = stacked.reference_readings(fed, data, 2, block_nodes=2,
                                      rnd=ref.float8_round())
    checks = stacked.checks(stacked.readings(ctrl, want),
                            cell["workload"]["limits"])
    assert not compare.judge(checks), checks


@pytest.mark.parametrize(
    "metric", [m for m in MAN["end_to_end"] + MAN["per_layer"]
               if "workloads" in m], ids=lambda m: m["name"])
def test_metric_lists_name_cells_that_exist(metric):
    assert set(metric["workloads"]) <= {w["name"] for w in MAN["workloads"]}


@pytest.mark.parametrize("name", sorted(TRAFFIC))
def test_new_cells_report_the_phase_driver_and_setup_readers(name):
    reported = {m["name"] for m in harness.load_cell(name)["per_layer"]}
    assert PHASE_READERS <= reported


def test_student_traffic_is_kd_with_the_teacher_off():
    kd = harness.load_json(harness.BENCH / "traffic" / "kd.json")
    student = harness.load_json(harness.BENCH / "traffic" / "student.json")
    moved = {"phase", "why", "alpha_s", "alpha_limit"}
    assert {k: v for k, v in kd.items() if k not in moved} == \
        {k: v for k, v in student.items() if k not in moved}
    assert student["alpha_s"] < student["alpha_limit"]


def test_student_traffic_leaves_the_program_teacher_untouched(monkeypatch):
    """Through the harness and the program: every teacher leaf and both
    teacher moments read the same before round 0 and after round 1,
    the student moves, and no teacher number is compared."""
    taps = []

    class Tap(stacked.RoundTap):
        def __init__(self, reads, *a, **kw):
            super().__init__({r: ("params", "moments") for r in reads},
                             *a, **kw)
            taps.append(self)

    monkeypatch.setattr(stacked, "RoundTap", Tap)
    cell = _tiny("c10r18.student")
    res = harness.run_cell(cell, 2 ** 31 + 23, 1, False, CPU, time.time())
    assert res["correct"], res["checks"]
    assert not any("teacher" in k for k in res["checks"])
    first, last = taps[0].states[0], taps[0].states[stacked.COMPARED_ROUNDS]
    for key in ("teacher", "mu_t", "nu_t"):
        a, b = (jax.tree_util.tree_leaves(s[key]) for s in (first, last))
        assert a and len(a) == len(b)
        for x, y in zip(a, b):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    moved = [not np.array_equal(np.asarray(x), np.asarray(y)) for x, y in
             zip(*(jax.tree_util.tree_leaves(s["student"])
                   for s in (first, last)))]
    assert all(moved)


def test_student_traffic_leaves_the_reference_teacher_untouched():
    """The reference never builds a teacher under the ``student``
    traffic, and its local round with the teacher off returns a given
    teacher and its moments bit for bit."""
    cell = _tiny("c10r18.student")
    config, traffic = cell["config"], cell["traffic"]
    seed = 2 ** 31 + 29
    data, _ = stacked.make_federation_data(seed, config, traffic)
    fed = stacked.reference_federation(config, traffic, seed)
    seen = []
    ref.run_rounds(fed, data, 3, block_nodes=2,
                   on_round=lambda r, st, loss, gp: seen.append(
                       jax.tree_util.tree_leaves(
                           (st["teacher"], st["opt_t"]["mu"],
                            st["opt_t"]["nu"]))))
    assert len(seen) == 4 and not any(seen)

    n, b = config["nodes"], config["batch_size"]
    st = ref.init_nodes(fed, n, teacher=True)
    steps = config["images_per_node"] // b
    imgs = np.stack([d["image"][:steps * b].reshape(steps, b,
                                                    *config["input_hw"])
                     for d in data])
    labs = np.stack([d["label"][:steps * b].reshape(steps, b)
                     for d in data])
    classes, pdim = config["num_classes"], config["proto_dim"]
    run = ref._local_round(fed.hyper, False, ref.identity, classes)
    out = run(st, np.zeros((n, classes, pdim), np.float32),
              np.zeros((n, classes), np.float32), np.float32(0.0),
              imgs, labs, imgs, labs)[0]
    for part in ("teacher", "opt_t"):
        for x, y in zip(jax.tree_util.tree_leaves(st[part]),
                        jax.tree_util.tree_leaves(out[part])):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert not np.array_equal(
        np.asarray(jax.tree_util.tree_leaves(st["student"])[0]),
        np.asarray(jax.tree_util.tree_leaves(out["student"])[0]))
