"""Round-step + wire-exchange perf regression gates against the
committed baselines.

Re-runs ``benchmarks/round_step.py``'s jitted-round measurement for the
node counts recorded in ``BENCH_round_step.json`` and fails (exit 1)
when the fresh per-round time exceeds the committed one by more than
``--threshold`` (default 1.3x — wide enough to absorb container noise,
tight enough to catch a dispatch-path regression).

When ``BENCH_wire_exchange.json`` exists, the wire-exchange microbench
is also re-run (in a subprocess — it forces one host device per
federation node) and gated: per-node collective bytes must match the
baseline EXACTLY (the packed codec and permutation lowering are
deterministic — any drift is a wire-format change that needs a
deliberate baseline refresh), and the jitted packed-codec round-trip ms
must stay within the same threshold.

When the committed baseline carries per-phase rows
(``nodes[n]["phases"]``, written by ``round_step.py --phases``), the
phase gate also runs: the exact Eq. 3 proto phase is re-measured fresh
at the LARGEST committed node count (one node count bounds the extra
compile time; the whole-round gate above already covers every N) and
must stay within ``--threshold`` x the committed ``proto_exact_ms``;
and the committed rows themselves must keep the single-pass win —
``round_fused_ms < round_exact_ms`` at the largest N (and at worst
break-even, <= 1.05x, on the smaller rows, where the saved pass is
inside timer noise), at every committed N the flat-parameter-plane
fused clip+update sweep must beat the per-leaf reference
(``update_fused_ms < update_per_leaf_ms``), at every committed N the plane-resident grad and gossip-mix paths
must beat their references (``grad_plane_ms < grad_repack_ms``,
``mix_plane_ms < mix_tree_ms``), at every committed N the fused
low-rank adapter merge must beat the materialized merge + plane
rebuild (``apply_fused_ms < apply_dense_ms``), and at the largest N the
fused in-scan proto marginal must cost at most HALF the exact second
pass (``proto_fused_ms <= 0.5 * proto_exact_ms``).  A failure
of the committed invariants means the committed file was refreshed
from a run where the fusion stopped paying — that needs investigation,
not a baseline bump.

Tier-1-adjacent invocation (see ROADMAP):

    PYTHONPATH=src python benchmarks/check_regression.py

Refresh the baselines after an intentional perf change with:

    PYTHONPATH=src python benchmarks/round_step.py --nodes 2 4 8 --phases
    PYTHONPATH=src python benchmarks/round_step.py --wire

(the first command is the deliberate-refresh flow for both the
whole-round and the per-phase rows: re-run, eyeball the printed
breakdown, commit the regenerated BENCH_round_step.json).
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile

from round_step import measure, measure_phases
from repro.launch.compile_cache import enable_compile_cache


def check_wire(baseline_path: str, threshold: float) -> bool:
    """Wire-exchange gate, per committed wire spec (bits row).  Returns
    True on failure.  For every bits entry in the baseline: the jitted
    packed-codec round-trip must stay within ``threshold``x, and the
    per-node collective bytes of every exchange mode must match EXACTLY
    — the codec, the byte encoding, and the permutation lowering are all
    deterministic, so any drift is a wire-format change that needs a
    deliberate baseline refresh."""
    with open(baseline_path) as f:
        base = json.load(f)
    cfg = base["config"]
    # adapter rows are labeled "<bits>+adapters<rank>" — they re-run
    # through --wire-adapters/--wire-adapter-bits, not --wire-bits
    labels = list(base["per_bits"].keys())
    bits_list = [b for b in labels if "+adapters" not in b]
    ad_ranks = [str(r) for r in cfg.get("adapter_ranks", [])] or \
        sorted({b.split("+adapters")[1] for b in labels
                if "+adapters" in b})
    ad_bits = [str(b) for b in cfg.get("adapter_bits", [])] or \
        sorted({b.split("+adapters")[0] for b in labels
                if "+adapters" in b})
    if any("+adapters" in b for b in labels):
        adapter_args = ["--wire-adapters", *ad_ranks,
                        "--wire-adapter-bits", *ad_bits]
    else:
        adapter_args = ["--wire-adapters", "0"]   # rank 0 = no extra rows
    # pod-shaped baselines ("RxC" rows: multi-axis mesh, row-sharded
    # permute) ride the same file under "per_pods"; a pre-pods baseline
    # has only the flat "per_bits" view
    base_pods = base.get("per_pods", {cfg.get("nodes", 8): base["per_bits"]})
    pods_args = [str(p) for p in cfg["pods"]] if "pods" in cfg \
        else [str(cfg["nodes"])]
    with tempfile.NamedTemporaryFile(suffix=".json", delete=False) as tf:
        out = tf.name
    script = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "round_step.py")
    # the child measures bytes on virtual host devices: it must never
    # reach for the accelerator this process may already hold
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    try:
        r = subprocess.run(
            [sys.executable, script, "--wire",
             "--wire-nodes", str(cfg["nodes"]),
             "--wire-topology", cfg["topology"],
             "--wire-bits", *bits_list, *adapter_args,
             "--pods", *pods_args, "--out", out],
            capture_output=True, text=True, env=env)
        if r.returncode != 0:
            print(f"wire bench failed to run:\n{r.stdout}\n{r.stderr}")
            return True
        with open(out) as f:
            fresh = json.load(f)
    finally:
        if os.path.exists(out):
            os.unlink(out)
    fresh_pods = fresh.get("per_pods",
                           {cfg.get("nodes", 8): fresh["per_bits"]})

    failed = False
    for pods, base_bits in base_pods.items():
        fresh_bits = fresh_pods.get(str(pods), {})
        for bits, brow in base_bits.items():
            tag = f"[pods={pods} bits={bits}]"
            frow = fresh_bits.get(bits, {})
            b_ms = brow["codec"]["packed_ms"]
            f_ms = frow.get("codec", {}).get("packed_ms")
            if f_ms is None:
                print(f"{tag} missing from fresh run  REGRESSION")
                failed = True
                continue
            ratio = f_ms / b_ms
            verdict = "OK" if ratio <= threshold else "REGRESSION"
            failed |= verdict == "REGRESSION"
            print(f"{tag} wire codec: packed qdq {f_ms:7.2f} ms vs "
                  f"committed {b_ms:7.2f} ms  ({ratio:.2f}x)  {verdict}")
            # every byte field of the exchange report must match EXACTLY
            # — not just the headline collective bytes: the accountant
            # predictions (packed_pred/packed_copy/sidecar) and the
            # per-kind / per-mesh-axis HLO attributions are all
            # deterministic integers
            bex, fex = brow["exchange"], frow.get("exchange", {})
            for key, bv in bex.items():
                if key == "exchanges" or "bytes" not in key:
                    continue
                fv = fex.get(key)
                ok = fv == bv
                failed |= not ok
                print(f"{tag} {key}: {fv} vs committed {bv}  "
                      f"{'OK' if ok else 'WIRE-FORMAT DRIFT'}")
            for ex, rep in bex["exchanges"].items():
                if "error" in rep:
                    # visible, so an error'd baseline mode can't hide
                    # forever — regenerate the baseline to bring it under
                    # the gate
                    print(f"{tag} wire bytes [{ex}]: UNCHECKED "
                          f"(baseline recorded {rep['error']!r} — refresh "
                          f"BENCH_wire_exchange.json)")
                    continue
                fr = fex.get("exchanges", {}).get(ex, {})
                fb = rep["collective_bytes_per_node"]
                ff = fr.get("collective_bytes_per_node")
                ok = ff == fb
                failed |= not ok
                print(f"{tag} wire bytes [{ex}]: {ff} vs committed "
                      f"{fb}  {'OK' if ok else 'WIRE-FORMAT DRIFT'}")
                for key in ("by_kind", "by_axis", "pod_by_kind_per_node"):
                    if key in rep and fr.get(key) != rep[key]:
                        failed = True
                        print(f"{tag} wire bytes [{ex}].{key}: "
                              f"{fr.get(key)} vs committed {rep[key]}  "
                              f"WIRE-FORMAT DRIFT")
    return failed


def check_phases(baseline: dict, threshold: float, rounds: int) -> bool:
    """Per-phase gate (see module docstring).  Returns True on failure.
    No-op when the committed baseline has no ``phases`` rows (pre-phase
    baseline files stay checkable)."""
    cfg = baseline["config"]
    phased = {n: row["phases"] for n, row in baseline["nodes"].items()
              if "phases" in row}
    if not phased:
        return False
    failed = False
    n_big = max(phased, key=int)

    # committed invariants: the single-pass round must win where the
    # round is big enough for the saved pass to clear the noise floor
    # (the largest committed N), and must never do worse than
    # break-even (<= 1.05x) on any row — at tiny N the exact pass
    # costs about what the in-scan accumulators add, so strict
    # per-row "cheaper" would gate on timer noise
    for n, ph in sorted(phased.items(), key=lambda kv: int(kv[0])):
        if n == n_big:
            ok = ph["round_fused_ms"] < ph["round_exact_ms"]
            tag = "FUSED-NOT-CHEAPER"
        else:
            ok = ph["round_fused_ms"] <= 1.05 * ph["round_exact_ms"]
            tag = "FUSED-REGRESSED"
        failed |= not ok
        print(f"N={n}: committed round fused {ph['round_fused_ms']:7.1f} ms"
              f" vs exact {ph['round_exact_ms']:7.1f} ms  "
              f"{'OK' if ok else tag}")
    # flat-parameter-plane invariant: the fused clip+update sweep over
    # the packed buffer must beat the per-leaf reference at every
    # committed N (rows without the update sub-phase predate the plane
    # and stay checkable)
    for n, ph in sorted(phased.items(), key=lambda kv: int(kv[0])):
        if "update_fused_ms" not in ph:
            continue
        ok = ph["update_fused_ms"] < ph["update_per_leaf_ms"]
        failed |= not ok
        print(f"N={n}: committed update fused {ph['update_fused_ms']:6.2f} "
              f"ms vs per-leaf {ph['update_per_leaf_ms']:6.2f} ms  "
              f"{'OK' if ok else 'FUSED-UPDATE-NOT-CHEAPER'}")
    # plane-resident round invariants: the custom-vjp grad backward must
    # beat the autodiff-through-views repack, and the buffer-native
    # gossip mix must beat the tree mix + plane rebuild, at every
    # committed N (rows without the sub-phases predate the
    # plane-resident round and stay checkable)
    for n, ph in sorted(phased.items(), key=lambda kv: int(kv[0])):
        if "grad_plane_ms" not in ph:
            continue
        ok = ph["grad_plane_ms"] < ph["grad_repack_ms"]
        failed |= not ok
        print(f"N={n}: committed grad plane {ph['grad_plane_ms']:6.2f} ms "
              f"vs repack {ph['grad_repack_ms']:6.2f} ms  "
              f"{'OK' if ok else 'PLANE-GRAD-NOT-CHEAPER'}")
    for n, ph in sorted(phased.items(), key=lambda kv: int(kv[0])):
        if "mix_plane_ms" not in ph:
            continue
        ok = ph["mix_plane_ms"] < ph["mix_tree_ms"]
        failed |= not ok
        print(f"N={n}: committed mix plane {ph['mix_plane_ms']:6.2f} ms "
              f"vs tree {ph['mix_tree_ms']:6.2f} ms  "
              f"{'OK' if ok else 'PLANE-MIX-NOT-CHEAPER'}")
    # adapter-wire invariant: the fused low-rank plane sweep must beat
    # the materialized merge + plane rebuild at every committed N (rows
    # without the apply sub-phase predate the adapter wire and stay
    # checkable)
    for n, ph in sorted(phased.items(), key=lambda kv: int(kv[0])):
        if "apply_fused_ms" not in ph:
            continue
        ok = ph["apply_fused_ms"] < ph["apply_dense_ms"]
        failed |= not ok
        print(f"N={n}: committed apply fused {ph['apply_fused_ms']:6.2f} "
              f"ms vs dense {ph['apply_dense_ms']:6.2f} ms  "
              f"{'OK' if ok else 'FUSED-APPLY-NOT-CHEAPER'}")

    big = phased[n_big]
    ok = big["proto_fused_ms"] <= 0.5 * big["proto_exact_ms"]
    failed |= not ok
    print(f"N={n_big}: committed proto fused marginal "
          f"{big['proto_fused_ms']:6.1f} ms vs 0.5 x exact "
          f"{big['proto_exact_ms']:6.1f} ms  "
          f"{'OK' if ok else 'FUSED-MARGINAL-TOO-HIGH'}")

    # fresh exact proto phase at the largest committed N
    fresh = measure_phases(int(n_big),
                           samples_per_node=cfg["samples_per_node"],
                           batch_size=cfg["batch_size"], rounds=rounds)
    ratio = fresh["proto_exact_ms"] / big["proto_exact_ms"]
    verdict = "OK" if ratio <= threshold else "REGRESSION"
    failed |= verdict == "REGRESSION"
    print(f"N={n_big}: proto phase {fresh['proto_exact_ms']:7.1f} ms vs "
          f"committed {big['proto_exact_ms']:7.1f} ms  ({ratio:.2f}x)  "
          f"{verdict}")
    return failed


def main() -> int:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--baseline", default="BENCH_round_step.json")
    ap.add_argument("--wire-baseline", default="BENCH_wire_exchange.json")
    ap.add_argument("--threshold", type=float, default=1.3,
                    help="fail when fresh jitted ms/round > threshold x "
                         "committed")
    ap.add_argument("--nodes", nargs="+", type=int, default=None,
                    help="subset of baseline node counts to check "
                         "(default: all)")
    ap.add_argument("--rounds", type=int, default=5,
                    help="timed rounds per node count (median) — 5 keeps "
                         "the median outside this container's timer noise")
    ap.add_argument("--skip-wire", action="store_true")
    args = ap.parse_args()

    with open(args.baseline) as f:
        baseline = json.load(f)
    cfg = baseline["config"]
    node_counts = [str(n) for n in args.nodes] if args.nodes \
        else sorted(baseline["nodes"], key=int)

    failed = False
    for n in node_counts:
        if n not in baseline["nodes"]:
            print(f"N={n}: not in baseline, skipping")
            continue
        committed = baseline["nodes"][n]["jitted_ms"]
        fresh = measure(int(n),
                        samples_per_node=cfg["samples_per_node"],
                        batch_size=cfg["batch_size"],
                        rounds=args.rounds,
                        jitted_only=True)["jitted_ms"]
        ratio = fresh / committed
        verdict = "OK" if ratio <= args.threshold else "REGRESSION"
        if verdict == "REGRESSION":
            failed = True
        print(f"N={n}: jitted {fresh:8.1f} ms/round vs committed "
              f"{committed:8.1f} ms  ({ratio:.2f}x)  {verdict}")

    failed |= check_phases(baseline, args.threshold, args.rounds)

    if not args.skip_wire and os.path.exists(args.wire_baseline):
        failed |= check_wire(args.wire_baseline, args.threshold)

    if failed:
        print(f"\nFAIL: regression vs the committed baselines "
              f"({args.baseline}, {args.wire_baseline})")
        return 1
    print(f"\nall measurements within {args.threshold:.1f}x of the "
          f"committed baselines")
    return 0


if __name__ == "__main__":
    sys.exit(main())
