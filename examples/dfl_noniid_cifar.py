"""End-to-end driver: ProFe vs the literature on the CIFAR10-style task
(ResNet18 teacher -> ResNet8 student) under a pathological non-IID split —
the regime where the paper reports ProFe's largest wins.

    PYTHONPATH=src python examples/dfl_noniid_cifar.py [--rounds 3]
"""
import argparse

from repro.config import FederationConfig, TrainConfig, get_config
from repro.core.federation import run_federation
from repro.data import make_image_dataset, partition, train_test_split
from repro.models import derive_student
from repro.launch.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=2)
    ap.add_argument("--nodes", type=int, default=3)
    ap.add_argument("--samples", type=int, default=1200)
    ap.add_argument("--split", default="noniid40",
                    choices=["iid", "noniid60", "noniid40", "noniid20",
                             "dirichlet"])
    args = ap.parse_args()

    cfg = get_config("cifar10-resnet18")
    stu = derive_student(cfg)
    print(f"teacher {cfg.name}: blocks={cfg.resnet_blocks} w={cfg.resnet_width}")
    print(f"student {stu.name}: blocks={stu.resnet_blocks} w={stu.resnet_width}")

    data = make_image_dataset(0, args.samples, cfg.input_hw, cfg.num_classes)
    train_d, test_d = train_test_split(data, 0.1, 0)
    parts = partition(train_d["label"], args.nodes, args.split, 0)
    node_data = [{k: v[i] for k, v in train_d.items()} for i in parts]
    for i, p in enumerate(parts):
        import numpy as np
        print(f"  node {i}: {len(p)} samples, "
              f"classes {sorted(set(train_d['label'][p].tolist()))}")

    train = TrainConfig(batch_size=32, learning_rate=1e-3,
                        optimizer="adamw", remat=False)
    for algo in ("profe", "fedproto", "fedavg"):
        fed = FederationConfig(num_nodes=args.nodes, rounds=args.rounds,
                               local_epochs=1, algorithm=algo,
                               split=args.split)
        res = run_federation(cfg, fed, train, node_data, test_d, verbose=True)
        print(f"[{algo}] final F1 {res.f1_per_round[-1]:.3f} | "
              f"{res.extras['avg_sent_gb']*1e3:.1f} MB/node | "
              f"{res.elapsed_s:.0f}s\n")


if __name__ == "__main__":
    main()
