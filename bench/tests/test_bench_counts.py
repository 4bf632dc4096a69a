"""Analytic FLOPs and layouts against the compiler and the program."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench import reference as ref
from bench.counts import resnet

CONFIGS = harness.configs("resnet")


def _config(name):
    return harness.load_json(harness.BENCH / "configs" / f"{name}.json")


def _arch(cfg, part):
    return ref.Arch(tuple(cfg[part]["resnet_blocks"]),
                    cfg[part]["resnet_width"], cfg["input_hw"][2],
                    cfg["proto_dim"], cfg["num_classes"])


@pytest.mark.parametrize("name", CONFIGS)
@pytest.mark.parametrize("part", ["teacher", "student"])
def test_forward_flops_match_the_compiler(name, part):
    """XLA counts every operation, the analytic count only convolutions
    and dense layers; GroupNorm, ReLU, pooling and the residual adds are
    a few per cent on top, never less."""
    cfg = _config(name)
    arch = _arch(cfg, part)
    params = jax.eval_shape(lambda k: ref.init_resnet(arch, k),
                            jax.random.PRNGKey(0))
    img = jax.ShapeDtypeStruct((1, *cfg["input_hw"]), jnp.float32)
    cost = jax.jit(ref.forward).lower(params, img).compile().cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    xla = cost["flops"]
    ours = resnet.forward_flops(cfg[part]["resnet_blocks"],
                                cfg[part]["resnet_width"], cfg["input_hw"],
                                cfg["proto_dim"], cfg["num_classes"])
    assert ours <= xla <= 1.06 * ours, (ours, xla)


@pytest.mark.parametrize("name", CONFIGS)
@pytest.mark.parametrize("part", ["teacher", "student"])
def test_param_sizes_match_the_program(name, part):
    from repro.models import derive_student, init_params
    from bench.entries.stacked import model_configs
    cfg = _config(name)
    params = ref.init_resnet(_arch(cfg, part), jax.random.PRNGKey(0))
    leaves = jax.tree_util.tree_leaves(params)
    assert sorted(resnet.param_sizes(cfg, part)) == \
        sorted(int(np.prod(x.shape)) for x in leaves)
    teacher = model_configs(cfg)
    prog = init_params(teacher if part == "teacher"
                       else derive_student(teacher), jax.random.PRNGKey(0))
    assert sorted(resnet.param_sizes(cfg, part)) == \
        sorted(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(prog))
