#!/usr/bin/env python3
"""Upper readings of a one-chip cell's comparison, at the cell's own
size: the reference, put in the program's place with a fault planted,
against the reference as it is.

    python3 bench/control.py --workload c10r18.kd --seeds 11 12 13

Variants: ``fp8`` (the control: activations, weights and cotangents
rounded to float8 e4m3 where the program computes in bfloat16),
``half_batch`` (each training step sees the first half of its batch and
takes the mean over it) and ``no_exchange`` (the gossip mix keeps every
node's own student).  Prints one JSON line per seed and variant with
every number the cell compares.  The benchmark's own runs never run
this.
"""
import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(REPO), str(REPO / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--variants", nargs="+",
                    default=["fp8", "half_batch", "no_exchange"])
    args = ap.parse_args(argv)

    from bench import compare, harness
    from bench import reference as ref
    from bench.entries import stacked
    cell = harness.load_cell(args.workload)
    try:
        harness.device_info(cell["chips"])
    except harness.NoDevice as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    config, traffic, workload = cell["config"], cell["traffic"], \
        cell["workload"]
    kw = dict(block_nodes=workload["reference_block_nodes"])
    for seed in args.seeds:
        data, _ = stacked.make_federation_data(seed, config, traffic)
        fed = stacked.reference_federation(config, traffic, seed)
        want = stacked.reference_readings(fed, data, stacked.COMPARED_ROUNDS,
                                          **kw)
        for variant in args.variants:
            t0 = time.time()
            with planted(variant, ref) as rnd:
                got = stacked.reference_readings(
                    fed, data, stacked.COMPARED_ROUNDS, rnd=rnd, **kw)
            values = stacked.readings(got, want)
            checks = stacked.checks(values, workload["limits"])
            print(json.dumps({"seed": seed, "variant": variant,
                              "fails": not compare.judge(checks),
                              "seconds": time.time() - t0,
                              "readings": values}), flush=True)
    return 0


@contextlib.contextmanager
def planted(variant: str, ref):
    """Yields the rounding function for the variant, with its fault
    patched into the reference for the length of the block."""
    if variant == "fp8":
        yield ref.float8_round()
        return
    saved = ref._local_round, ref._mix_leaf
    if variant == "half_batch":
        def local_round(*a):
            fn = saved[0](*a)

            def run(st, gp, mask, alpha, imgs, labs, pimgs, plabs):
                half = imgs.shape[2] // 2
                return fn(st, gp, mask, alpha, imgs[:, :, :half],
                          labs[:, :, :half], pimgs, plabs)
            return run
        ref._local_round = local_round
    elif variant == "no_exchange":
        ref._mix_leaf = lambda w_self, w_neigh, own, recv: own
    else:
        raise ValueError(f"unknown variant {variant!r}")
    try:
        yield ref.identity
    finally:
        ref._local_round, ref._mix_leaf = saved


if __name__ == "__main__":
    sys.exit(main())
