"""Table III reproduction: elapsed wall time per algorithm (% vs FedAvg).

Wall time on this CPU container is only meaningful *relatively* (the
paper used 2x RTX 3080); the claim under test is the ORDERING and the
ProFe overhead band (~+18-20% on CIFAR-scale, ~0% on MNIST-scale) vs the
FedProto floor (~-65%).

``--full`` runs the paper's N=20 protocol on the stacked round engine
(one jitted program per round, dispatch O(1) in N).  ``--topologies``
sweeps gossip graphs (full/ring/star/random-k/...; see
``core/topology.make_schedule``) and the JSON output carries the
per-round timings for each topology.  ``--overlap`` records the
pipelined-round modes next to the sequential reference, and
``--stale-floor F`` appends just the ``overlap="rounds"`` +
self-weight-floor row (the fix for the dense-graph stale collapse)
without re-running the whole sweep.

    PYTHONPATH=src python benchmarks/table3_time.py [--full] \\
        [--topologies full ring star]
"""
from __future__ import annotations

import argparse
import json
import os
import statistics

from repro.config import FederationConfig, TrainConfig, get_config
from repro.core.federation import run_federation, run_federation_loop
from repro.data import make_image_dataset, partition, train_test_split
from repro.launch.compile_cache import enable_compile_cache

ALGOS = ["fedavg", "fedgpd", "fml", "fedproto", "profe"]


def measure(dataset: str, *, nodes: int, rounds: int, n_samples: int,
            seed: int = 0, engine: str = "stacked", topology: str = "full"):
    cfg = get_config(dataset)
    data = make_image_dataset(seed, n_samples, cfg.input_hw, cfg.num_classes)
    train_d, test_d = train_test_split(data, 0.1, seed)
    parts = partition(train_d["label"], nodes, "iid", seed)
    node_data = [{k: v[i] for k, v in train_d.items()} for i in parts]
    train = TrainConfig(batch_size=64, learning_rate=1e-3, optimizer="adamw",
                        remat=False)
    run = run_federation if engine == "stacked" else run_federation_loop
    rows = {}
    for algo in ALGOS:
        fed = FederationConfig(num_nodes=nodes, rounds=rounds, local_epochs=1,
                               algorithm=algo, seed=seed, topology=topology)
        res = run(cfg, fed, train, node_data, test_d)
        times = res.extras.get("round_times_s", [])
        rows[algo] = {
            "elapsed_s": res.elapsed_s,
            "round_times_s": [round(t, 4) for t in times],
            "median_round_s": round(statistics.median(times), 4)
            if times else None,
        }
    base = rows["fedavg"]["elapsed_s"]
    for algo in ALGOS:
        rows[algo]["pct_vs_fedavg"] = 100.0 * (rows[algo]["elapsed_s"] / base - 1)
    return rows


def measure_overlap(dataset: str, *, nodes: int, rounds: int, n_samples: int,
                    seed: int = 0, topology: str = "full"):
    """Sequential vs pipelined ProFe round engine on the same protocol:
    ``overlap=None`` (one jitted program per round), ``"none"`` (phase-
    split train/share/mix programs — bit-identical outputs, next round's
    batches staged behind the dispatched device work), and ``"rounds"``
    (stale-by-one gossip: round t's exchange mixes while round t+1
    trains).  Records the measured per-round critical path and the
    per-round F1 next to the sequential reference.  The recorded
    ``f1_final_abs_diff`` is the fidelity observable: the stale
    pipeline tracks the sequential fixed point on sparse graphs
    (ring), while the dense full graph's uniform 1/N stale average
    can collapse — both land in the report as measured."""
    cfg = get_config(dataset)
    data = make_image_dataset(seed, n_samples, cfg.input_hw, cfg.num_classes)
    train_d, test_d = train_test_split(data, 0.1, seed)
    parts = partition(train_d["label"], nodes, "iid", seed)
    node_data = [{k: v[i] for k, v in train_d.items()} for i in parts]
    train = TrainConfig(batch_size=64, learning_rate=1e-3, optimizer="adamw",
                        remat=False)
    out = {}
    for mode in (None, "none", "rounds"):
        fed = FederationConfig(num_nodes=nodes, rounds=rounds,
                               local_epochs=1, algorithm="profe", seed=seed,
                               topology=topology)
        res = run_federation(cfg, fed, train, node_data, test_d,
                             overlap=mode)
        times = res.extras.get("round_times_s", [])
        out["sequential" if mode is None else mode] = {
            "elapsed_s": res.elapsed_s,
            "median_round_s": round(statistics.median(times), 4)
            if times else None,
            "round_times_s": [round(t, 4) for t in times],
            "f1_per_round": [round(f, 4) for f in res.f1_per_round],
        }
    seq = out["sequential"]
    for mode in ("none", "rounds"):
        if seq["median_round_s"] and out[mode]["median_round_s"]:
            out[mode]["round_speedup_vs_sequential"] = round(
                seq["median_round_s"] / out[mode]["median_round_s"], 4)
        out[mode]["f1_final_abs_diff"] = round(
            abs(out[mode]["f1_per_round"][-1] - seq["f1_per_round"][-1]), 4)
    return out


def measure_floor(dataset: str, *, nodes: int, rounds: int, n_samples: int,
                  floor: float, seq_ref: dict | None, seed: int = 0,
                  topology: str = "full"):
    """The stale-mixing self-weight floor row, alone.  The full
    ``--overlap`` sweep already records that ``overlap="rounds"`` on the
    dense full graph can collapse (uniform 1/N stale averaging erases
    local progress before it compounds); this re-runs ONLY the pipelined
    mode with ``stale_self_floor=floor`` and scores it against the
    committed sequential reference row, so the ~15-minute three-mode
    sweep does not have to repeat to record the fix."""
    cfg = get_config(dataset)
    data = make_image_dataset(seed, n_samples, cfg.input_hw, cfg.num_classes)
    train_d, test_d = train_test_split(data, 0.1, seed)
    parts = partition(train_d["label"], nodes, "iid", seed)
    node_data = [{k: v[i] for k, v in train_d.items()} for i in parts]
    train = TrainConfig(batch_size=64, learning_rate=1e-3, optimizer="adamw",
                        remat=False)
    fed = FederationConfig(num_nodes=nodes, rounds=rounds, local_epochs=1,
                           algorithm="profe", seed=seed, topology=topology)
    res = run_federation(cfg, fed, train, node_data, test_d,
                         overlap="rounds", stale_self_floor=floor)
    times = res.extras.get("round_times_s", [])
    row = {
        "elapsed_s": res.elapsed_s,
        "median_round_s": round(statistics.median(times), 4)
        if times else None,
        "round_times_s": [round(t, 4) for t in times],
        "f1_per_round": [round(f, 4) for f in res.f1_per_round],
        "stale_self_floor": floor,
    }
    if seq_ref is not None:
        if seq_ref.get("median_round_s") and row["median_round_s"]:
            row["round_speedup_vs_sequential"] = round(
                seq_ref["median_round_s"] / row["median_round_s"], 4)
        row["f1_final_abs_diff"] = round(
            abs(row["f1_per_round"][-1] - seq_ref["f1_per_round"][-1]), 4)
    return row


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="the paper's N=20 protocol on the stacked engine")
    ap.add_argument("--datasets", nargs="+", default=["mnist-cnn"])
    ap.add_argument("--topologies", nargs="+", default=["full"],
                    help="gossip graphs to sweep (any "
                         "core/topology.make_schedule spec)")
    ap.add_argument("--engine", choices=["stacked", "loop"],
                    default="stacked",
                    help="round engine: jitted stacked rounds (default) or "
                         "the per-node reference loop")
    ap.add_argument("--overlap", action="store_true",
                    help="pipelined-round comparison instead of the "
                         "algorithm table: sequential vs overlap='none' "
                         "(bit-identical phase split) vs 'rounds' "
                         "(stale-by-one gossip), per-round critical path "
                         "+ F1 (merged into the same JSON under "
                         "'overlap')")
    ap.add_argument("--stale-floor", type=float, default=None,
                    metavar="F",
                    help="run ONLY overlap='rounds' with "
                         "stale_self_floor=F and merge it as the "
                         "'rounds+floor' row under 'overlap', scored "
                         "against the already-committed sequential "
                         "reference (no 3-mode re-sweep)")
    ap.add_argument("--out", default="reports/table3_time.json")
    args = ap.parse_args()

    results = {}
    if os.path.exists(args.out):
        # --overlap and the algorithm table share the report file —
        # merge per (dataset, topology) instead of clobbering
        with open(args.out) as f:
            results = json.load(f)
    for ds in args.datasets:
        nodes, rounds, n = (20, 10, 20000) if args.full else (3, 2, 900)
        results.setdefault(ds, {})
        for topo in args.topologies:
            print(f"== {ds} ({nodes} nodes, topology={topo}) ==")
            results[ds].setdefault(topo, {})
            if args.stale_floor is not None:
                seq_ref = results[ds][topo].get("overlap", {}) \
                    .get("sequential")
                row = measure_floor(ds, nodes=nodes, rounds=rounds,
                                    n_samples=n, topology=topo,
                                    floor=args.stale_floor, seq_ref=seq_ref)
                results[ds][topo].setdefault("overlap", {})
                results[ds][topo]["overlap"]["rounds+floor"] = row
                extra = ""
                if "f1_final_abs_diff" in row:
                    extra = (f"  |dF1| {row['f1_final_abs_diff']} vs "
                             f"committed sequential")
                print(f"  rounds+floor({args.stale_floor}) median "
                      f"{row['median_round_s']}s/round  final f1 "
                      f"{row['f1_per_round'][-1]}{extra}")
                continue
            if args.overlap:
                rows = measure_overlap(ds, nodes=nodes, rounds=rounds,
                                       n_samples=n, topology=topo)
                results[ds][topo]["overlap"] = rows
                for mode, r in rows.items():
                    extra = ""
                    if "round_speedup_vs_sequential" in r:
                        extra = (f"  {r['round_speedup_vs_sequential']:.2f}x"
                                 f" round vs sequential, |dF1| "
                                 f"{r['f1_final_abs_diff']}")
                    print(f"  {mode:10s} median "
                          f"{r['median_round_s']}s/round{extra}")
                continue
            rows = measure(ds, nodes=nodes, rounds=rounds, n_samples=n,
                           engine=args.engine, topology=topo)
            results[ds][topo].update(rows)
            for algo, r in rows.items():
                print(f"  {algo:9s} {r['elapsed_s']:8.1f}s "
                      f"({r['pct_vs_fedavg']:+.0f}% vs FedAvg, "
                      f"median {r['median_round_s']}s/round)")
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(results, f, indent=2)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
