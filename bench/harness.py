"""The benchmark harness: finds a cell's files by name, runs its entry,
reads its metrics and prints the result line.

Everything that belongs to one cell, configuration, traffic mix, entry
or per-layer metric sits in a file of its own, found by the names in
``BENCHMARK.json``:

* ``bench/configs/<config>.json``  — the model configuration as run;
  its ``family`` names its FLOP count,
* ``bench/counts/<family>.py``     — ``sample_flops(config, part)``,
  the model FLOPs of one sample's forward through the teacher or the
  student,
* ``bench/traffic/<traffic>.json`` — the federation job's parameters;
  it may set ``nodes`` (read through ``nodes``), the configuration
  gives the rest,
* ``bench/workloads/<cell>.json``  — the entry kind, the reference's
  block size and the limits of the comparison with the reference,
* ``bench/entries/<kind>.py``      — ``run(cell, seed, seconds, trace,
  trace_dir)``; a traced run leaves two traces, ``<trace_dir>/plain``
  and ``<trace_dir>/named``, each window inside a host span
  ``WINDOW_LABEL``,
* ``bench/metrics/<metric>.py``    — ``read(ctx)`` of a per-layer metric,
  returning None where the trace holds nothing to read.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from pathlib import Path
from types import SimpleNamespace
from typing import Any, Dict, List, Optional

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent
TRACE_DIR = REPO / ".bench_trace"
# the host span an entry opens around each traced window
WINDOW_LABEL = "bench.window"


class NoDevice(RuntimeError):
    """The chips the cell asks for are not there."""


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def manifest(repo: Path = REPO) -> dict:
    return load_json(repo / "BENCHMARK.json")


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def configs(family: Optional[str] = None, repo: Path = REPO) -> List[str]:
    """Names of the configurations ``BENCHMARK.json`` lists, of
    ``family`` only where it is given."""
    return [c["name"] for c in manifest(repo)["configs"]
            if family is None
            or load_json(repo / c["file"]).get("family") == family]


def nodes(cell: dict) -> int:
    """The cell's number of federation nodes: the traffic's ``nodes``
    where it sets it, else the configuration's.  A traffic that sets
    another number than the configuration's cuts the deployment, and
    names the cut in its own ``reduced`` and ``published``."""
    return cell["traffic"].get("nodes", cell["config"]["nodes"])


def load_cell(name: str, repo: Path = REPO) -> dict:
    """Everything one cell needs, from the files ``BENCHMARK.json`` names."""
    man = manifest(repo)
    cells = {w["name"]: w for w in man["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    configs = {c["name"]: c for c in man["configs"]}
    bench = repo / "bench"
    return {"name": name, "chips": w["chips"],
            "config": load_json(repo / configs[w["config"]]["file"]),
            "traffic": load_json(bench / "traffic" / f"{w['traffic']}.json"),
            "workload": load_json(bench / "workloads" / f"{name}.json"),
            "end_to_end": [m for m in man["end_to_end"] if applies(m, name)],
            "per_layer": [m for m in man["per_layer"] if applies(m, name)]}


def device_info(chips: int) -> Dict[str, Any]:
    """The accelerator JAX finds; raises :class:`NoDevice` where it finds
    none, or fewer chips than the cell asks for."""
    import jax
    devs = jax.devices()
    if devs[0].platform == "cpu":
        raise NoDevice("JAX finds no accelerator")
    if len(devs) < chips:
        raise NoDevice(f"the cell asks for {chips} chips, JAX finds "
                       f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peaks_for(kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in "
                       f"bench/peaks.json")
    return table[kind]


def load_module(path: Path, name: str):
    """The Python file at ``path`` as a module named ``name``."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def family_count(family: str, bench: Path = BENCH):
    """The FLOP count of a configuration family,
    ``bench/counts/<family>.py``."""
    return load_module(bench / "counts" / f"{family}.py",
                       f"bench_count_{family.replace('-', '_')}")


def read_metric(name: str, ctx, bench: Path = BENCH) -> Optional[float]:
    return load_module(bench / "metrics" / f"{name}.py",
                       f"bench_metric_{name.replace('.', '_')}").read(ctx)


def per_layer(cell: dict, out: dict, device: dict, trace_dir: Path):
    """Per-layer metrics, busy and window seconds, and the breakdown
    from the traces of the run: ``plain`` (Python tracer off) for every
    number, ``named`` (Python tracer on) only to name the idle gaps."""
    from bench import trace as T
    tr = T.load(str(trace_dir / "plain"), label=WINDOW_LABEL)
    named = T.load(str(trace_dir / "named"), label=WINDOW_LABEL)
    busy_s = T.mean_busy_s(tr)
    window_s = (tr.window[1] - tr.window[0]) / 1e9
    ctx = SimpleNamespace(cell=cell, out=out, trace=tr, busy_s=busy_s,
                          window_s=window_s,
                          peaks=peaks_for(device["kind"]))
    metrics = {}
    for m in cell["per_layer"]:
        v = read_metric(m["name"], ctx)
        if v is not None and math.isfinite(v):
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    sources = sorted({f.name for f in (REPO / "src").rglob("*.py")})
    breakdown = {"device_ops": T.top_ops(tr),
                 "idle_gaps": T.idle_gaps(named, sources=sources)}
    return metrics, busy_s, window_s, breakdown


def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             device: dict, t0: float) -> dict:
    """Run the cell's entry once and assemble the result line."""
    from bench import compare
    entry = importlib.import_module(f"bench.entries.{cell['workload']['entry']}")
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    print(f"bench: entry starts {time.time() - t0:.1f} s after start",
          file=sys.stderr, flush=True)
    out = entry.run(cell, seed, seconds, trace, str(TRACE_DIR))
    checks = out["checks"]
    device = dict(device, memory_peak_bytes=out["memory_peak_bytes"])
    result: Dict[str, Any] = {"correct": compare.judge(checks),
                              "attempted": out["attempted"],
                              "failed": out["failed"]}
    if trace:
        try:
            metrics, busy_s, window_s, breakdown = per_layer(
                cell, out, device, TRACE_DIR)
        finally:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
        device.update(busy_s=busy_s, window_s=window_s)
        result.update(metrics=metrics, device=device, breakdown=breakdown)
    else:
        values = dict(out["e2e"], setup_s=out["window_start"] - t0)
        result.update(metrics={m["name"]: {"value": values[m["name"]],
                                           "unit": m["unit"]}
                               for m in cell["end_to_end"]},
                      device=device)
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, v, lim in checks}
    return result


def report(result: dict) -> None:
    from bench import compare
    checks = [(k, v["value"], v["limit"]) for k, v in result["checks"].items()]
    sys.stdout.flush()
    for line in compare.format_checks(checks):
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
