"""The cell's node count and FLOP count follow its files: the number of
nodes from the traffic over the configuration, a sample's FLOPs from the
configuration's family, the FLOP share over all the cell's chips; a
configuration of another family on four chips takes only new files."""
import json
import os
import subprocess
import sys
import time
from types import SimpleNamespace

import jax
import pytest

from bench import harness
from bench.counts import plane, resnet
from bench.entries import stacked
from bench.tests.test_bench_manifest import _copy_bench, undeclared_cuts
from bench.tests.tiny import CPU, tiny_cell

PINNED = ["c10r18.kd", "c100r32.kd", "c10r18.student"]
# one round's model FLOPs of each cell, as the count read before the
# round was composed by family
ROUND_FLOPS = {"c10r18.kd": 30610507431936, "c100r32.kd": 45325595443200,
               "c10r18.student": 997174542336}
RESNET_ONLY = ["bench/tests/test_bench_counts.py",
               "bench/tests/test_bench_plane.py",
               "bench/tests/test_bench_reference.py"]


def _ctx(cell, window_s=4.9121, rounds=2):
    return SimpleNamespace(cell=cell, out={"traced_rounds": rounds},
                           window_s=window_s,
                           peaks=harness.peaks_for("TPU v5 lite"))


def _resnet_round_flops(config, teacher_on, eval_images):
    """One round's FLOPs as ``counts/resnet.round_flops`` wrote them out
    before the round was composed in ``mfu.round``."""
    def fwd(part):
        return resnet.forward_flops(
            config[part]["resnet_blocks"], config[part]["resnet_width"],
            config["input_hw"], config["proto_dim"], config["num_classes"])
    images = config["nodes"] * config["images_per_node"]
    total = images * (3 * fwd("student") + fwd("student"))
    if teacher_on:
        total += images * 3 * fwd("teacher")
    return total + eval_images * fwd("student")


@pytest.mark.parametrize("name", PINNED)
def test_mfu_round_reading_is_pinned(name):
    cell = harness.load_cell(name)
    cfg, traffic = cell["config"], cell["traffic"]
    ctx = _ctx(cell)
    flops = _resnet_round_flops(
        cfg, traffic["alpha_s"] >= traffic["alpha_limit"], cfg["test_images"])
    assert flops == ROUND_FLOPS[name]
    want = 100.0 * 2 * flops / (ctx.window_s * ctx.peaks["bf16_flops"])
    assert cell["chips"] == 1
    assert harness.read_metric("mfu.round", ctx) == want
    four = _ctx(dict(cell, chips=4))
    assert harness.read_metric("mfu.round", four) == want / 4


def test_mfu_round_reads_nothing_without_traced_rounds():
    cell = harness.load_cell(PINNED[0])
    assert harness.read_metric("mfu.round", _ctx(cell, rounds=0)) is None
    assert harness.read_metric("mfu.round", _ctx(cell, window_s=0.0)) is None


@pytest.mark.parametrize("name", PINNED)
def test_nodes_come_from_the_configuration_where_the_traffic_sets_none(
        name):
    cell = harness.load_cell(name)
    assert "nodes" not in cell["traffic"]
    assert harness.nodes(cell) == cell["config"]["nodes"] == 20


def test_nodes_come_from_the_traffic_where_it_sets_them():
    cell = harness.load_cell("c100r32.kd")
    cell["traffic"] = dict(cell["traffic"], nodes=4)
    assert harness.nodes(cell) == 4


def test_a_node_count_cut_is_named_in_the_traffic():
    """Four nodes of the paper's twenty is a cut of the deployment: the
    traffic names it with the published count, or the manifest's test
    refuses it."""
    cell = harness.load_cell("c100r32.kd")
    kd = cell["traffic"]
    cell["traffic"] = dict(kd, nodes=4)
    assert undeclared_cuts(cell) == ["nodes"]
    cell["traffic"] = dict(kd, nodes=4, reduced=["nodes"],
                           published={"nodes": 8})
    assert undeclared_cuts(cell) == ["nodes"]
    cell["traffic"] = dict(kd, nodes=4, reduced=["nodes"],
                           published={"nodes": 20})
    assert undeclared_cuts(cell) == []
    cell["traffic"] = dict(kd, nodes=20)
    assert undeclared_cuts(cell) == []


def test_plane_bytes_follow_the_traffic_nodes():
    """As today without the key; with the traffic's ``nodes`` the
    sweep's bytes scale with it."""
    cell = harness.load_cell("c100r32.kd")
    assert plane.opt_sweep_round_bytes(cell) == \
        7 * 20 * 22144 * plane.COLS * plane.F32 * 16
    four = dict(cell, traffic=dict(cell["traffic"], nodes=4))
    assert 5 * plane.opt_sweep_round_bytes(four) == \
        plane.opt_sweep_round_bytes(cell)
    assert 5 * plane.opt_sweep_bytes(four) == plane.opt_sweep_bytes(cell)


def test_traffic_node_count_runs_that_many_nodes(monkeypatch):
    """The tiny cell (3 nodes in its configuration) through the stacked
    entry, the harness and the reference: a traffic that sets ``nodes``
    runs that many, and the plane bytes scale with it."""
    taps = []

    class Tap(stacked.RoundTap):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            taps.append(self)

    monkeypatch.setattr(stacked, "RoundTap", Tap)
    cell = tiny_cell()
    base = plane.opt_sweep_round_bytes(cell)
    cell["traffic"] = dict(cell["traffic"], nodes=4)
    res = harness.run_cell(cell, 2 ** 31 + 61, 1, False, CPU, time.time())
    assert res["correct"], res["checks"]
    for leaf in jax.tree_util.tree_leaves(taps[0].states[0]["student"]):
        assert leaf.shape[0] == 4
    assert 3 * plane.opt_sweep_round_bytes(cell) == 4 * base


def test_without_the_key_the_configuration_sets_the_nodes():
    """The tiny cell's data, reference and plane bytes as the entry
    builds them, with no ``nodes`` in its traffic."""
    cell = tiny_cell()
    data, test = stacked.make_federation_data(2 ** 31 + 61, cell)
    assert len(data) == 3 and len(test["label"]) == 8
    assert all(len(d["label"]) == 8 for d in data)
    assert stacked.reference_federation(cell, 5).adjacency.shape == (3, 3)
    steps = 8 // 4
    assert plane.opt_sweep_round_bytes(cell) == \
        steps * 7 * 3 * plane.plane_rows(cell["config"]) * plane.COLS * 4


COUNT = '''def sample_flops(config, part):
    return 2 * config[part]["params"]
'''


def _add_made_up_family(root):
    """A configuration of a family the benchmark has never seen, its
    count, a traffic that sets ``nodes`` and a four-chip cell: new files
    and new entries in ``BENCHMARK.json``, nothing else."""
    bench = root / "bench"
    (bench / "configs" / "toy-moe.json").write_text(json.dumps(
        {"name": "toy-moe", "family": "toymoe",
         "teacher": {"params": 425_000_000}, "student": {"params": 50_000_000},
         "nodes": 20, "images_per_node": 64, "batch_size": 8,
         "test_images": 64}))
    (bench / "counts" / "toymoe.py").write_text(COUNT)
    kd = json.loads((bench / "traffic" / "kd.json").read_text())
    (bench / "traffic" / "mesh4.json").write_text(json.dumps(
        dict(kd, nodes=4, reduced=["nodes"], published={"nodes": 20})))
    (bench / "workloads" / "toy-moe.mesh4.json").write_text(
        (bench / "workloads" / "c10r18.kd.json").read_text())
    man = json.loads((root / "BENCHMARK.json").read_text())
    man["configs"].append({"name": "toy-moe",
                           "source": "https://example.org/toy-moe",
                           "file": "bench/configs/toy-moe.json",
                           "reduced": [], "why": "a made-up family"})
    man["workloads"].append({"name": "toy-moe.mesh4", "config": "toy-moe",
                             "traffic": "mesh4", "chips": 4,
                             "why": "one node a chip over four chips"})
    (root / "BENCHMARK.json").write_text(json.dumps(man))


def _pytest(root, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(harness.REPO / "src"))
    return subprocess.run([sys.executable, "-m", "pytest", "-p",
                           "no:cacheprovider", *args], cwd=root, env=env,
                          capture_output=True, text=True, timeout=600)


def test_made_up_family_on_four_chips_takes_only_new_files(tmp_path):
    root = _copy_bench(tmp_path)
    _add_made_up_family(root)
    cell = harness.load_cell("toy-moe.mesh4", repo=root)
    assert cell["chips"] == 4 and cell["config"]["family"] == "toymoe"
    assert harness.nodes(cell) == 4
    assert "mfu.round" in {m["name"] for m in cell["per_layer"]}
    ctx = SimpleNamespace(cell=cell, out={"traced_rounds": 2}, window_s=2.0,
                          peaks={"bf16_flops": 1e15})
    # four nodes of 64 samples: student training and Eq. 3, teacher
    # training (the kd traffic trains it), one node's eval of 64 samples
    student, teacher = 2 * 50_000_000, 2 * 425_000_000
    count = 4 * 64 * (4 * student + 3 * teacher) + 64 * student
    assert harness.read_metric("mfu.round", ctx, bench=root / "bench") == \
        100.0 * 2 * count / (2.0 * 1e15 * 4)
    assert harness.configs("resnet", repo=root) == harness.configs("resnet")

    # the manifest's tests, the family's count among them, hold in the copy
    p = _pytest(root, "-v", "bench/tests/test_bench_manifest.py",
                "-k", "not run_without")
    assert p.returncode == 0, p.stdout[-4000:] + p.stderr[-2000:]
    assert "test_family_names_a_count[toy-moe] PASSED" in p.stdout
    assert "test_traffic_names_its_cuts[toy-moe.mesh4] PASSED" in p.stdout
    # the ResNet-only modules collect the ResNet configurations alone
    p = _pytest(root, "--collect-only", "-q", *RESNET_ONLY)
    assert p.returncode == 0, p.stdout[-4000:] + p.stderr[-2000:]
    assert "toy-moe" not in p.stdout
    for name in harness.configs("resnet"):
        assert name in p.stdout
