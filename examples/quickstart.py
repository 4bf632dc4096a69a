"""Quickstart: ProFe on a 4-node federation in ~2 minutes on CPU.

    PYTHONPATH=src python examples/quickstart.py

Trains the paper's MNIST-style setup (2-layer CNN teacher, half-channel
student) with ProFe and FedAvg, then prints the F1 curves and the
communication saving — the paper's two headline numbers.
"""
from repro.config import FederationConfig, TrainConfig, get_config
from repro.core.federation import run_federation
from repro.data import make_image_dataset, partition, train_test_split
from repro.launch.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    cfg = get_config("mnist-cnn")
    print(f"teacher: {cfg.name}  channels={cfg.cnn_channels}")

    data = make_image_dataset(0, 2400, cfg.input_hw, cfg.num_classes)
    train_d, test_d = train_test_split(data, 0.1, 0)
    parts = partition(train_d["label"], 4, "iid", 0)
    node_data = [{k: v[i] for k, v in train_d.items()} for i in parts]
    train = TrainConfig(batch_size=64, learning_rate=1e-3,
                        optimizer="adamw", remat=False)

    results = {}
    for algo in ("profe", "fedavg"):
        fed = FederationConfig(num_nodes=4, rounds=3, algorithm=algo)
        print(f"\n=== {algo} ===")
        results[algo] = run_federation(cfg, fed, train, node_data, test_d,
                                       verbose=True)

    p, f = results["profe"], results["fedavg"]
    print("\n----- summary -----")
    print(f"F1 (ProFe)  : {p.f1_per_round[-1]:.3f}")
    print(f"F1 (FedAvg) : {f.f1_per_round[-1]:.3f}")
    red = 1 - p.extras["avg_sent_gb"] / f.extras["avg_sent_gb"]
    print(f"bytes/node  : {p.extras['avg_sent_gb']*1e3:.2f} MB vs "
          f"{f.extras['avg_sent_gb']*1e3:.2f} MB  (-{red:.0%})")
    print(f"wall time   : {p.elapsed_s:.0f}s vs {f.elapsed_s:.0f}s "
          f"({p.elapsed_s / f.elapsed_s - 1:+.0%})")


if __name__ == "__main__":
    main()
