"""ProFe ablations (beyond the paper's tables): which of the three
ingredients buys what?

* wire precision: 32 (off) / 16 (paper) / 8 bit
* professor-importance decay: paper schedule vs alpha fixed vs alpha=0
  (no distillation at all)
* prototypes: on vs off (beta_s = beta_t = 0)

Each cell reports final F1, bytes/node, and wall time on the scaled-down
MNIST-style protocol.

    PYTHONPATH=src python -m benchmarks.ablations
"""
from __future__ import annotations

import argparse
import json
import os

from repro.config import FederationConfig, TrainConfig, get_config
from repro.core.federation import run_federation
from repro.data import make_image_dataset, partition, train_test_split
from repro.launch.compile_cache import enable_compile_cache


def setting(n_nodes=4, n=2400, split="iid", seed=0):
    cfg = get_config("mnist-cnn")
    data = make_image_dataset(seed, n, cfg.input_hw, cfg.num_classes)
    train_d, test_d = train_test_split(data, 0.1, seed)
    parts = partition(train_d["label"], n_nodes, split, seed)
    node_data = [{k: v[i] for k, v in train_d.items()} for i in parts]
    return cfg, node_data, test_d


ABLATIONS = {
    "paper (16-bit, decay, protos)": dict(),
    "32-bit wire": dict(quantize_bits=32),
    "8-bit wire": dict(quantize_bits=8),
    "no decay (alpha fixed)": dict(alpha_limit=0.0),
    "no distillation (alpha=0)": dict(alpha_s=0.0, alpha_limit=1.0),
    "no prototypes (beta=0)": dict(beta_s=0.0, beta_t=0.0),
}


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--split", default="iid")
    ap.add_argument("--out", default="reports/ablations.json")
    args = ap.parse_args()

    cfg, node_data, test_d = setting(split=args.split)
    train = TrainConfig(batch_size=64, learning_rate=1e-3,
                        optimizer="adamw", remat=False)
    results = {}
    print(f"{'ablation':34s} {'final F1':>9s} {'MB/node':>9s} {'time s':>7s}")
    for name, overrides in ABLATIONS.items():
        fed = FederationConfig(num_nodes=len(node_data), rounds=args.rounds,
                               algorithm="profe", split=args.split,
                               **overrides)
        res = run_federation(cfg, fed, train, node_data, test_d)
        row = {
            "f1": res.f1_per_round[-1],
            "f1_curve": res.f1_per_round,
            "mb_per_node": res.extras["avg_sent_gb"] * 1e3,
            "elapsed_s": res.elapsed_s,
        }
        results[name] = row
        print(f"{name:34s} {row['f1']:9.3f} {row['mb_per_node']:9.2f} "
              f"{row['elapsed_s']:7.1f}", flush=True)

    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(results, f, indent=2)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
