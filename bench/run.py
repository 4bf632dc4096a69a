#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload c10r18.kd --seed 7 --seconds 30 --trace 0

With ``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of a
short steady window in a run of its own.  The last line of standard
output is one JSON object; the numbers compared with the reference, each
beside its limit, are the last lines of standard error and the last key
of that object.  Where JAX finds no accelerator, or fewer chips than the
cell asks for, the run exits with code 2 and prints no result.
"""
import time

T0 = time.time()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

REPO = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(REPO), str(REPO / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness
    cell = harness.load_cell(args.workload)
    try:
        device = harness.device_info(cell["chips"])
        harness.peaks_for(device["kind"])
    except (harness.NoDevice, KeyError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), device, T0)
    harness.report(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
