"""Compile rehearsals of the main path's Pallas kernels for a TPU v5e.

Each test compiles one kernel at the widths ``chip_smoke.py`` runs
(cifar10-resnet18's student plane, 20 nodes, the 4-node mesh) for a
described ``v5e:2x2`` topology — no chip attached — and asserts the
compiled program holds the kernel (``tpu_custom_call``).  The chip's
compiler refuses what interpret mode accepts: blocks that break the
(8,128) tiling rule, scalar stores to VMEM, blocks larger than VMEM.

The topology is described inside a module-scoped fixture (never at
import): only the worker that runs this file loads the TPU compiler,
and where it cannot be loaded every test here skips from the fixture.
The persistent compilation cache is off around these compiles (a
described chip can write entries but never read them back).
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.config import get_config
from repro.kernels.lowrank_apply.lowrank_apply import lowrank_apply_pallas
from repro.kernels.opt_update.opt_update import (adafactor_apply_pallas,
                                                 adamw_update_pallas,
                                                 sgd_update_pallas)
from repro.kernels.proto_accum.proto_accum import proto_accum_pallas
from repro.kernels.quantize.ops import packed_wire_rows
from repro.kernels.quantize.quantize import (dequantize_rows_pallas,
                                             fused_quantize_pallas,
                                             mix_packed_pallas,
                                             quantize_rows_ef_pallas,
                                             quantize_rows_mixed_pallas,
                                             quantize_rows_pallas,
                                             rowabs_pallas,
                                             rowabs_sum_pallas)
from repro.models import derive_student, init_params

N_NODES = 20          # chip_smoke.py's stacked-engine federation
MESH_NODES = 4        # chip_smoke.py --chips 4: one node per chip
BATCH = 32
C = 512


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    old_log = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler here: nothing to rehearse
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    cc.reset_cache()
    if old_log is None:
        os.environ.pop("TPU_LOG_DIR", None)


@pytest.fixture(scope="module")
def rows():
    """Rows of one node's wire buffer: the ResNet18 student plane plus
    the prototype rows (10 classes x 256 dims), 8-aligned."""
    cfg = get_config("cifar10-resnet18")
    scfg = derive_student(cfg)
    student = jax.eval_shape(lambda: init_params(scfg, jax.random.PRNGKey(0)))
    protos = jax.ShapeDtypeStruct((cfg.num_classes, scfg.proto_dim),
                                  jnp.float32)
    r, _ = packed_wire_rows({"protos": protos, "student": student},
                            node_axis=False)
    return r


def _compile(sharding, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


F32, I32 = jnp.float32, jnp.int32


def test_rowabs_compiles(one_chip, rows):
    _compile(one_chip, rowabs_pallas, ((N_NODES * rows, C), F32))


def test_rowabs_sum_compiles(one_chip, rows):
    _compile(one_chip, lambda x, r: rowabs_sum_pallas(x, r, decay=1.0),
             ((N_NODES * rows, C), F32), ((N_NODES * rows, C), F32))


def test_quantize_rows_compiles(one_chip, rows):
    _compile(one_chip, lambda x, d: quantize_rows_pallas(x, d, bits=16),
             ((N_NODES * rows, C), F32), ((N_NODES * rows, 1), F32))


def test_quantize_rows_mixed_compiles(one_chip, rows):
    _compile(one_chip, quantize_rows_mixed_pallas,
             ((N_NODES * rows, C), F32), ((N_NODES * rows, 1), F32),
             ((N_NODES * rows, 1), F32))


def test_quantize_rows_ef_compiles(one_chip, rows):
    _compile(one_chip,
             lambda x, r, d, q: quantize_rows_ef_pallas(x, r, d, q,
                                                        decay=1.0),
             ((N_NODES * rows, C), F32), ((N_NODES * rows, C), F32),
             ((N_NODES * rows, 1), F32), ((N_NODES * rows, 1), F32))


def test_dequantize_rows_compiles(one_chip, rows):
    _compile(one_chip, dequantize_rows_pallas,
             ((N_NODES * rows, C), I32), ((N_NODES * rows, 1), F32))


@pytest.mark.parametrize("m,n", [(1, 2), (N_NODES, N_NODES)],
                         ids=["mesh-ring", "all-nodes"])
def test_mix_packed_compiles(one_chip, rows, m, n):
    """(1, 2): one node per chip receiving a ring's two permute steps;
    (20, 20): every receiver and sender in one launch."""
    _compile(one_chip, mix_packed_pallas,
             ((m, rows, C), F32), ((n, rows, C), I32), ((n, rows), F32),
             ((m,), F32), ((m, n), F32))


def test_adamw_update_compiles(one_chip, rows):
    """vmapped over nodes like the engine's step: the buffers and the
    (1, 1) runtime scalars both carry the node axis."""
    kern = jax.vmap(lambda g, p, mu, nu, lr, s, b1, b2: adamw_update_pallas(
        g, p, mu, nu, lr, s, b1, b2, b1=0.9, b2=0.999, eps=1e-8,
        weight_decay=0.01))
    _compile(one_chip, kern, *[((N_NODES, rows, C), F32)] * 4,
             *[((N_NODES, 1, 1), F32)] * 4)


def test_sgd_update_compiles(one_chip, rows):
    kern = jax.vmap(lambda g, p, mu, lr, s: sgd_update_pallas(
        g, p, mu, lr, s, momentum=0.9, weight_decay=0.01))
    _compile(one_chip, kern, *[((N_NODES, rows, C), F32)] * 3,
             *[((N_NODES, 1, 1), F32)] * 2)


def test_adafactor_apply_compiles(one_chip, rows):
    kern = jax.vmap(lambda u, p, lr: adafactor_apply_pallas(
        u, p, lr, weight_decay=0.0))
    _compile(one_chip, kern, *[((N_NODES, rows, C), F32)] * 2,
             ((N_NODES, 1, 1), F32))


def test_proto_accum_compiles(one_chip):
    cfg = get_config("cifar10-resnet18")
    p_dim = derive_student(cfg).proto_dim
    kern = jax.vmap(lambda f, l: proto_accum_pallas(
        f, l, cfg.num_classes, block_b=BATCH, block_c=cfg.num_classes))
    _compile(one_chip, kern, ((N_NODES, BATCH, p_dim), F32),
             ((N_NODES, BATCH, 1), I32))


def test_lowrank_apply_compiles(one_chip):
    """A 4096 x 4096 matrix leaf at rank 8, four receivers and senders,
    naive and RegMean (per-receiver factors) variants."""
    n, d, r = MESH_NODES, 4096, 8
    _compile(one_chip, lowrank_apply_pallas, ((n, d, d), F32),
             ((n, n), F32), ((n, d, r), F32), ((n, r, d), F32))
    _compile(one_chip, lowrank_apply_pallas, ((n, d, d), F32),
             ((n, n), F32), ((n, d, r), F32), ((n, n, r, d), F32))


def test_fused_quantize_compiles(one_chip, rows):
    _compile(one_chip,
             lambda x, q: fused_quantize_pallas(x, q, bits=4),
             ((rows, C), F32), ((1, 1), F32))
