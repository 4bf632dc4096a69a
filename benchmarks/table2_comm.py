"""Table II reproduction: network bytes sent/received per node (GB) and
% vs FedAvg, per algorithm — logical (accountant) next to physical
(dry-run HLO) wire bytes per topology.

Byte counts are *analytic serialized payload sizes* (exact), so this
table does not need long training — one round with the real models gives
the exact per-round payload; total = payload x rounds x neighbours.
``--full`` uses the paper's 20-node/10-20-80-round protocol numbers.
``--topology`` accepts any ``core/topology.make_schedule`` spec: the
numbers come from the schedule-derived vectorized accounting
(``ScheduleCommAccountant``), byte-identical to the seed per-edge meter.

``--physical`` additionally compiles the mesh gossip round on an
(N, 1, 1) federation mesh and prints the HLO collective bytes per
exchange mode next to the accountant's prediction — the gap the packed
ppermute exchange closes is *measured*, not asserted.
"""
from __future__ import annotations

import argparse
import json
import os

import numpy as np

from repro.config import FederationConfig, TrainConfig, get_config
from repro.core.federation import run_federation
from repro.data import make_image_dataset, partition, train_test_split
from repro.launch.compile_cache import enable_compile_cache

ALGOS = ["fedavg", "fedgpd", "fml", "fedproto", "profe"]
PAPER_ROUNDS = {"mnist-cnn": 10, "cifar10-resnet18": 20,
                "cifar100-resnet32": 80}


def measure(dataset: str, *, nodes: int, rounds: int,
            n_samples: int = 1200, seed: int = 0, topology: str = "full"):
    cfg = get_config(dataset)
    data = make_image_dataset(seed, n_samples, cfg.input_hw, cfg.num_classes)
    train_d, test_d = train_test_split(data, 0.1, seed)
    parts = partition(train_d["label"], nodes, "iid", seed)
    node_data = [{k: v[i] for k, v in train_d.items()} for i in parts]
    train = TrainConfig(batch_size=64, learning_rate=1e-3, optimizer="adamw",
                        remat=False)
    rows = {}
    for algo in ALGOS:
        fed = FederationConfig(num_nodes=nodes, rounds=rounds,
                               local_epochs=1, algorithm=algo, seed=seed,
                               topology=topology)
        res = run_federation(cfg, fed, train, node_data, test_d)
        rows[algo] = {
            "sent_gb": res.extras["avg_sent_gb"],
            "received_gb": res.extras["avg_received_gb"],
        }
    base = rows["fedavg"]["sent_gb"]
    for algo in ALGOS:
        rows[algo]["pct_vs_fedavg"] = 100.0 * (rows[algo]["sent_gb"] / base - 1)
    return rows


def physical_wire(dataset: str, nodes: int, topology: str, bits="16",
                  adapter_rank: int = 0, adapter_grams: bool = False):
    """Compile the mesh ProFe round per exchange mode on an (N, 1, 1)
    federation mesh; per-node HLO collective bytes vs the accountant."""
    from repro.launch.wire import measure_exchange_bytes
    return measure_exchange_bytes(dataset, nodes, topology, bits=bits,
                                  adapter_rank=adapter_rank,
                                  adapter_grams=adapter_grams)


def logical_wire(dataset: str, nodes: int, topology: str, bits="16",
                 adapter_rank: int = 0, adapter_grams: bool = False):
    """Accountant-only per-bits wire bytes (no compilation): logical
    (Table II) and packed-codec predictions for one gossip round.  The
    payload comes from the SAME ``accountant_payload`` builder the
    dry-run byte gate asserts against, so this table and the compiled
    HLO can never disagree about what rides the wire (including the
    rank-r "adapters"/"grams" groups when ``adapter_rank`` is set)."""
    from repro.core import topology as T
    from repro.core.comm import ScheduleCommAccountant
    from repro.launch.wire import _student_setup, accountant_payload
    from repro.wirespec import WireSpec
    spec = WireSpec.parse(bits)
    sched = T.make_schedule(nodes, topology, rounds=1, seed=0)
    cfg, student_cfg, struct, C = _student_setup(dataset)
    payload = accountant_payload(struct, C, student_cfg.proto_dim,
                                 adapter_rank=adapter_rank,
                                 adapter_grams=adapter_grams)
    acct = ScheduleCommAccountant(sched)
    return {
        "bits": spec.describe(),
        "logical_bytes_per_node": int(acct.predicted_node_bytes(
            payload, 0, spec, wire="dense").max()),
        "packed_pred_bytes_per_node": int(acct.predicted_node_bytes(
            payload, 0, spec, wire="packed").max()),
    }


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--datasets", nargs="+", default=["mnist-cnn"])
    ap.add_argument("--topology", default="full",
                    help="gossip graph spec (core/topology.make_schedule)")
    ap.add_argument("--physical", action="store_true",
                    help="also compile the mesh round and print physical "
                         "HLO collective bytes per exchange mode")
    ap.add_argument("--bits", default="16",
                    help="comma list of wire specs for the per-bits wire "
                         "column, e.g. 16,8,4 or 16,4/16 (the first is "
                         "the headline row)")
    ap.add_argument("--adapters", type=int, default=0, metavar="RANK",
                    help="adapter-rank wire for the wire columns: matrix "
                         "leaves ride as rank-r delta factors "
                         "('adapters' payload group) instead of dense "
                         "parameters")
    ap.add_argument("--adapter-grams", action="store_true",
                    help="with --adapters: add the RegMean gram "
                         "statistics payload group")
    ap.add_argument("--out", default="reports/table2_comm.json")
    args = ap.parse_args()

    nodes = 20 if args.full else 4
    bits_list = [b.strip() for b in args.bits.split(",") if b.strip()]
    if args.physical:
        # one host device per federation node, BEFORE first jax use
        from repro.launch.wire import ensure_host_device_flag
        ensure_host_device_flag(nodes)

    results = {}
    for ds in args.datasets:
        rounds = PAPER_ROUNDS.get(ds, 10) if args.full else 2
        print(f"== {ds} ({nodes} nodes, {rounds} rounds, "
              f"topology={args.topology}) ==")
        rows = measure(ds, nodes=nodes, rounds=rounds,
                       n_samples=20000 if args.full else 1200,
                       topology=args.topology)
        results[ds] = rows
        print(f"  {'algo':9s} {'sent GB':>10s} {'recv GB':>10s} {'% vs FedAvg':>12s}")
        for algo, r in rows.items():
            print(f"  {algo:9s} {r['sent_gb']:10.4f} {r['received_gb']:10.4f} "
                  f"{r['pct_vs_fedavg']:+11.1f}%")
        # per-bits wire column: the paper's quantization knob swept
        # end-to-end — accountant always, compiled HLO with --physical
        rows["wire_bits"] = {}
        for b in bits_list:
            if args.physical:
                wire = physical_wire(ds, nodes, args.topology, bits=b,
                                     adapter_rank=args.adapters,
                                     adapter_grams=args.adapter_grams)
            else:
                wire = logical_wire(ds, nodes, args.topology, bits=b,
                                    adapter_rank=args.adapters,
                                    adapter_grams=args.adapter_grams)
            if args.adapters:
                wire["adapter_rank"] = args.adapters
                wire["adapter_grams"] = args.adapter_grams
            rows["wire_bits"][b] = wire
            print(f"  profe wire @ bits={b}, per round per node "
                  f"(topology={args.topology}):")
            print(f"    logical (accountant)  "
                  f"{wire['logical_bytes_per_node']/1e6:9.3f} MB   "
                  f"packed codec {wire['packed_pred_bytes_per_node']/1e6:9.3f} MB")
            for ex, rep in wire.get("exchanges", {}).items():
                if "error" in rep:
                    print(f"    physical [{ex:8s}]  {rep['error']}")
                    continue
                print(f"    physical [{ex:8s}]  "
                      f"{rep['collective_bytes_per_node']/1e6:9.3f} MB "
                      f"({', '.join(f'{k}:{int(v)}' for k, v in rep['counts'].items())} launches)")
        rows["wire"] = rows["wire_bits"][bits_list[0]]   # headline row
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(results, f, indent=2)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
