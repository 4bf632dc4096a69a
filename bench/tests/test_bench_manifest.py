"""BENCHMARK.json against the contract's rules, and the files it names."""
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
MAN = harness.manifest()
ALL_METRICS = MAN["end_to_end"] + MAN["per_layer"]


def _cells_reporting(metric):
    return [w["name"] for w in MAN["workloads"]
            if harness.applies(metric, w["name"])]


def test_top_level_keys():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert 1 <= MAN["run_seconds"] <= 51
    for p in MAN["paths"]:
        assert (harness.REPO / p).is_dir()


@pytest.mark.parametrize("name", [c["name"] for c in MAN["configs"]]
                         + [w["name"] for w in MAN["workloads"]]
                         + [m["name"] for m in ALL_METRICS])
def test_names_use_allowed_characters(name):
    assert NAME.match(name), name


@pytest.mark.parametrize("metric", ALL_METRICS, ids=lambda m: m["name"])
def test_units_and_sources(metric):
    assert UNIT.match(metric["unit"]), metric["unit"]
    assert metric["better"] in ("lower", "higher")
    if metric in MAN["end_to_end"]:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.25


@pytest.mark.parametrize("metric", MAN["per_layer"], ids=lambda m: m["name"])
def test_per_layer_cells_report_what_they_move(metric):
    moves = [m for m in MAN["end_to_end"] if m["name"] == metric["moves"]]
    assert moves, metric["moves"]
    cells = _cells_reporting(metric)
    assert cells
    for c in cells:
        assert harness.applies(moves[0], c), (metric["name"], c)
    assert (harness.BENCH / "metrics" / f"{metric['name']}.py").is_file()


def test_every_config_has_a_cell_and_every_cell_its_files():
    used = {w["config"] for w in MAN["workloads"]}
    assert used == {c["name"] for c in MAN["configs"]}
    for w in MAN["workloads"]:
        cell = harness.load_cell(w["name"])
        assert (harness.BENCH / "entries"
                / f"{cell['workload']['entry']}.py").is_file()
        assert len(w["why"]) <= 200
        reported = {m["name"] for m in cell["end_to_end"]}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell["per_layer"]


@pytest.mark.parametrize("name", harness.configs())
def test_family_names_a_count(name):
    """Each configuration names its family, whose count
    ``bench/counts/<family>.py`` gives the FLOPs of one sample's forward
    through the teacher and the student, from which ``mfu.round``
    composes a round."""
    entry = next(c for c in MAN["configs"] if c["name"] == name)
    config = harness.load_json(harness.REPO / entry["file"])
    family = config["family"]
    assert NAME.match(family), family
    assert (harness.BENCH / "counts" / f"{family}.py").is_file()
    count = harness.family_count(family)
    for part in ("teacher", "student"):
        flops = count.sample_flops(config, part)
        assert isinstance(flops, int) and flops > 0, (part, flops)


def undeclared_cuts(cell) -> list:
    """The keys a cell's traffic sets at another value than its
    configuration's without naming the cut: such a key has to be in the
    traffic's ``reduced``, with the deployment's published value (the
    configuration's ``published`` one, else its own) in the traffic's
    ``published``."""
    config, traffic = cell["config"], cell["traffic"]
    published = dict(config, **config.get("published", {}))
    named, given = traffic.get("reduced", []), traffic.get("published", {})
    return sorted(k for k in set(traffic) & set(config) - {"reduced",
                                                          "published"}
                  if traffic[k] != config[k]
                  and (k not in named or given.get(k) != published[k]))


@pytest.mark.parametrize("name", [w["name"] for w in MAN["workloads"]])
def test_traffic_names_its_cuts(name):
    """A traffic that overrides a configuration key (``nodes``, which
    ``harness.nodes`` reads) cuts the deployment, and says so."""
    assert not undeclared_cuts(harness.load_cell(name))


def test_four_chip_cells_are_few():
    four = [w for w in MAN["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(MAN["workloads"]) // 2)
    assert all(w["chips"] in (1, 4) for w in MAN["workloads"])


def _copy_bench(tmp_path: Path) -> Path:
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(harness.REPO / "BENCHMARK.json", tmp_path)
    return tmp_path


def test_new_cell_and_metric_are_found_from_new_files(tmp_path):
    root = _copy_bench(tmp_path)
    man = json.loads((root / "BENCHMARK.json").read_text())
    base = MAN["workloads"][0]
    man["workloads"].append(dict(base, name="added.cell"))
    man["per_layer"].append({"name": "added.metric", "unit": "%",
                             "better": "higher", "source": "device_trace",
                             "layer": "device", "moves": "round_s",
                             "workloads": ["added.cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    shutil.copy(root / "bench" / "workloads" / f"{base['name']}.json",
                root / "bench" / "workloads" / "added.cell.json")
    (root / "bench" / "metrics" / "added.metric.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    cell = harness.load_cell("added.cell", repo=root)
    assert [m["name"] for m in cell["per_layer"]][-1] == "added.metric"
    assert harness.read_metric("added.metric", None,
                               bench=root / "bench") == 42.0


def _run(cwd: Path, env_extra):
    env = dict(os.environ, **env_extra)
    return subprocess.run([sys.executable, "bench/run.py", "--workload",
                           MAN["workloads"][0]["name"], "--seed", "1",
                           "--seconds", "1", "--trace", "0"],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def test_run_without_a_chip_prints_no_result():
    p = _run(harness.REPO, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert "{" not in p.stdout


def test_run_without_the_program_prints_no_result(tmp_path):
    root = _copy_bench(tmp_path)
    p = _run(root, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert "{" not in p.stdout
