"""The reference against the program: the same initial weights, the same
rounds when the program computes in float32, and a control in a lower
precision that the comparison refuses."""
import time

import jax
import numpy as np
import pytest

from bench import compare, harness
from bench import reference as ref
from bench.entries import stacked
from bench.tests.tiny import CPU, tiny_cell


def _leaves(tree):
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(p): np.asarray(x) for p, x in flat}


@pytest.mark.parametrize("name", [c["name"] for c in
                                  harness.manifest()["configs"]])
def test_initial_weights_equal_the_program(name):
    from repro.models import init_params
    cfg_file = harness.load_json(harness.BENCH / "configs" / f"{name}.json")
    teacher = stacked.model_configs(cfg_file)
    from repro.models import derive_student
    fed = stacked.reference_federation(
        cfg_file, harness.load_json(harness.BENCH / "traffic" / "kd.json"), 5)
    k1, k2 = jax.random.split(jax.random.PRNGKey(fed.seed * 1000 + 3))
    for cfg, arch, key in ((teacher, fed.teacher, k1),
                           (derive_student(teacher), fed.student, k2)):
        a = _leaves(init_params(cfg, key))
        b = _leaves(ref.init_resnet(arch, key))
        assert a.keys() == b.keys()
        for k in a:
            np.testing.assert_allclose(a[k], b[k], rtol=1e-6, atol=1e-7,
                                       err_msg=k)


def test_float8_round_keeps_three_mantissa_bits_and_the_range():
    import jax.numpy as jnp
    f8 = ref.float8_round()
    x = jnp.asarray([1.2, 1.05, 1000.0, 2.0 ** -12])
    np.testing.assert_array_equal(np.asarray(f8(x)), [1.25, 1.0, 448.0, 0.0])
    g = jax.grad(lambda v: jnp.sum(f8(v) * 1e-4))(x)
    assert float(g[0]) == 0.0         # the cotangent underflows too


def test_control_in_lower_precision_fails_the_limits():
    """The reference rounded to float8 e4m3 where the program computes in
    bfloat16, put in the program's place, is refused by the cell's
    limits."""
    cell = tiny_cell()
    seed = 2 ** 31 + 77
    data, _ = stacked.make_federation_data(seed, cell["config"],
                                           cell["traffic"])
    fed = stacked.reference_federation(cell["config"], cell["traffic"], seed)
    want = stacked.reference_readings(fed, data, 3, block_nodes=2)
    ctrl = stacked.reference_readings(fed, data, 3, block_nodes=2,
                                      rnd=ref.float8_round())
    checks = stacked.checks(stacked.readings(ctrl, want),
                            cell["workload"]["limits"])
    assert not compare.judge(checks), checks


def test_program_in_float32_equals_the_reference():
    """With float32 compute the program's rounds are the reference's to
    rounding, through the same entry and harness as a chip run."""
    seconds = 1.5
    res = harness.run_cell(tiny_cell(dtype="float32"), 2 ** 31 + 5, seconds,
                           False, CPU, time.time())
    assert res["correct"], res["checks"]
    assert all(c["value"] < 1e-4 for c in res["checks"].values()), \
        res["checks"]
    # the window holds whole rounds and lasts at least ``seconds``
    n, round_s = res["attempted"], res["metrics"]["round_s"]["value"]
    assert n >= 1 and res["failed"] == 0
    assert seconds <= n * round_s < seconds + 2 * round_s
    assert set(res["metrics"]) == {"round_s", "peak_hbm_gb", "setup_s"}
