"""Run the full dry-run sweep: 10 archs x 4 shapes x {pod1, pod2}.

Each combo runs in its own subprocess (fresh XLA state, isolated
failures); reports land in reports/dryrun/<arch>_<shape>_<mesh>.json and
completed combos are skipped on re-run.

    PYTHONPATH=src python -m benchmarks.dryrun_all [--mesh pod1 pod2] \
        [--arch ...] [--shape ...] [--force]

``--topo`` runs the federation-topology byte-gate suite instead
(exchange modes vs the accountant, incl. the yi-6b ring-8 adapter-rank
acceptance row) into reports/dryrun/topology_*.json.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

ARCHS = [
    "mamba2-130m", "whisper-small", "yi-6b", "recurrentgemma-9b",
    "qwen3-14b", "starcoder2-15b", "llama4-scout-17b-a16e",
    "llama-3.2-vision-90b", "qwen1.5-110b", "grok-1-314b",
]
SHAPES = ["train_4k", "prefill_32k", "decode_32k", "long_500k"]
OUT_DIR = "reports/dryrun"


def run_one(arch: str, shape: str, mesh: str, force: bool) -> dict:
    os.makedirs(OUT_DIR, exist_ok=True)
    path = os.path.join(OUT_DIR, f"{arch}_{shape}_{mesh}.json")
    if not force and os.path.exists(path):
        with open(path) as f:
            rep = json.load(f)
        if rep.get("status") == "ok":
            return rep
    t0 = time.time()
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
         "--shape", shape, "--mesh", mesh, "--json", path],
        capture_output=True, text=True, env=env, timeout=3000)
    try:
        with open(path) as f:
            rep = json.load(f)
    except Exception:
        rep = {"arch": arch, "shape": shape, "mesh": mesh, "status": "error",
               "error": (proc.stderr or "")[-2000:]}
        with open(path, "w") as f:
            json.dump(rep, f, indent=2)
    rep["compile_wall_s"] = time.time() - t0
    return rep


# --topo suite: federation-mesh byte gates (exchange modes vs the
# accountant) — (arch, topology, pods, extra dryrun args, report tag)
TOPO_SUITE = [
    # ring-8: at 4 pods a ring is exactly half the full gather, and the
    # dryrun sparsity check requires strictly less
    ("mnist-cnn", "ring", "8", [], "mnist-cnn_ring8"),
    ("mnist-cnn", "ring", "8", ["--bits", "4", "--ef"],
     "mnist-cnn_ring8_int4ef"),
    ("yi-6b", "ring", "8", ["--bits", "4", "--adapters", "8"],
     "yi-6b_ring8_int4_adapters8"),
    ("yi-6b", "ring", "8",
     ["--bits", "4", "--adapters", "8", "--adapter-grams"],
     "yi-6b_ring8_int4_adapters8_grams"),
]


def run_topo(arch: str, topology: str, pods: str, extra, tag: str,
             force: bool) -> dict:
    os.makedirs(OUT_DIR, exist_ok=True)
    path = os.path.join(OUT_DIR, f"topology_{tag}.json")
    if not force and os.path.exists(path):
        with open(path) as f:
            rep = json.load(f)
        if rep.get("status") == "ok":
            return rep
    t0 = time.time()
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
         "--topology", topology, "--pods", pods] + list(extra),
        capture_output=True, text=True, env=env, timeout=3000)
    try:
        rep = json.loads(proc.stdout)
    except Exception:
        rep = {"arch": arch, "topology": topology, "status": "error",
               "error": (proc.stderr or "")[-2000:]}
    rep["compile_wall_s"] = time.time() - t0
    with open(path, "w") as f:
        json.dump(rep, f, indent=2)
    return rep


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", nargs="+", default=["pod1", "pod2"])
    ap.add_argument("--arch", nargs="+", default=ARCHS)
    ap.add_argument("--shape", nargs="+", default=SHAPES)
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--topo", action="store_true",
                    help="run the federation-topology byte-gate suite "
                         "instead of the arch x shape sweep (writes "
                         "reports/dryrun/topology_*.json; includes the "
                         "yi-6b ring-8 adapter-rank acceptance row)")
    args = ap.parse_args()

    failures = []
    if args.topo:
        for arch, topology, pods, extra, tag in TOPO_SUITE:
            rep = run_topo(arch, topology, pods, extra, tag, args.force)
            ok = rep.get("status") == "ok"
            checks = rep.get("checks", [])
            print(f"[{'OK' if ok else 'FAIL'}] topology {tag:36s} "
                  f"{len(checks)} checks "
                  f"({rep.get('compile_wall_s', 0):.0f}s)", flush=True)
            if not ok:
                failures.append((arch, topology, pods,
                                 rep.get("error", "")[:200]))
        print(f"\n{len(failures)} failures")
        for f in failures:
            print("  FAIL:", f)
        sys.exit(1 if failures else 0)
    for mesh in args.mesh:
        for arch in args.arch:
            for shape in args.shape:
                rep = run_one(arch, shape, mesh, args.force)
                ok = rep.get("status") == "ok"
                dom = rep.get("dominant", "?")
                fit = rep.get("memory_analysis", {}).get("fits_16gb_hbm")
                print(f"[{'OK' if ok else 'FAIL'}] {arch:24s} {shape:12s} "
                      f"{mesh}  dom={dom} fits={fit} "
                      f"({rep.get('compile_wall_s', 0):.0f}s)", flush=True)
                if not ok:
                    failures.append((arch, shape, mesh,
                                     rep.get("error", "")[:200]))
    print(f"\n{len(failures)} failures")
    for f in failures:
        print("  FAIL:", f)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
