"""Topology sweep: how the gossip graph trades communication for
convergence on CIFAR-style synthetic data — logical accountant bytes
printed NEXT TO the physical HLO collective bytes of the mesh exchange.

Runs the same ProFe federation (stacked round engine) over a
fully-connected graph, a ring, and a time-varying ring/star schedule —
the ``TopologySchedule`` lowers each to per-round gossip matrices, so
every variant is the *same* jitted round program fed different traced
operands.  Comm bytes come from the schedule-derived vectorized
accounting (Table II math); the physical bytes come from compiling the
mesh gossip round per topology on an (N, 1, 1) federation mesh — on a
ring the ppermute exchange moves O(degree), not O(N), per node.

    PYTHONPATH=src python examples/topology_sweep.py [--rounds 3]
"""
import argparse

from repro.config import FederationConfig, TrainConfig, get_config
from repro.core import topology as T
from repro.core.federation import run_federation
from repro.data import make_image_dataset, partition, train_test_split
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.wire import ensure_host_device_flag

_N_DEFAULT = 4


def main():
    # one host device per federation node for the physical-bytes
    # lowering (before the first jax use; --nodes above 8 needs a
    # manual flag)
    ensure_host_device_flag(8)
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=2)
    ap.add_argument("--nodes", type=int, default=_N_DEFAULT)
    ap.add_argument("--samples", type=int, default=1200)
    ap.add_argument("--topologies", nargs="+",
                    default=["full", "ring", "dynamic:ring,star",
                             "random-k2"])
    ap.add_argument("--bits", nargs="+", default=["16"],
                    help="wire specs to sweep per topology (16 | 8 | 4 "
                         "| <student>/<protos>, e.g. 4/16; +ef suffix "
                         "= stateful error-feedback codec): quantifies "
                         "the F1 cost of the comm-reduction knob")
    ap.add_argument("--no-physical", action="store_true",
                    help="skip the per-topology mesh-round compilation")
    args = ap.parse_args()

    cfg = get_config("cifar10-resnet18")
    data = make_image_dataset(0, args.samples, cfg.input_hw, cfg.num_classes)
    train_d, test_d = train_test_split(data, 0.1, 0)
    parts = partition(train_d["label"], args.nodes, "iid", 0)
    node_data = [{k: v[i] for k, v in train_d.items()} for i in parts]
    train = TrainConfig(batch_size=32, learning_rate=1e-3,
                        optimizer="adamw", remat=False)

    from repro.wirespec import WireSpec
    for topo in args.topologies:
        sched = T.make_schedule(args.nodes, topo, rounds=args.rounds, seed=0)
        edges = sched.directed_edge_counts()
        print(f"== {topo}: {sched.num_phases} phase(s), "
              f"{edges.tolist()} directed edges/round ==")
        for bits in args.bits:
            spec = WireSpec.parse(bits)
            tag = f"{topo} @ {spec.describe()}"
            fed = FederationConfig(num_nodes=args.nodes, rounds=args.rounds,
                                   local_epochs=1, algorithm="profe",
                                   topology=topo,
                                   quantize_bits=spec.student_bits,
                                   proto_quantize_bits=spec.proto_bits,
                                   error_feedback=spec.error_feedback)
            res = run_federation(cfg, fed, train, node_data, test_d,
                                 verbose=True)
            print(f"[{tag}] final F1 {res.f1_per_round[-1]:.3f} | "
                  f"{res.extras['avg_sent_gb'] * 1e3:.1f} MB sent/node "
                  f"(logical) | {res.elapsed_s:.0f}s")
            if not args.no_physical and sched.num_phases == 1:
                from repro.launch.wire import measure_exchange_bytes
                try:
                    wire = measure_exchange_bytes("cifar10-resnet18",
                                                  args.nodes, topo,
                                                  bits=spec)
                except RuntimeError as e:
                    print(f"[{tag}] physical bytes skipped: {e}\n")
                    continue
                print(f"[{tag}] wire per round/node: "
                      f"logical {wire['logical_bytes_per_node']/1e6:.2f} MB"
                      f" | " + " | ".join(
                          f"physical {ex} "
                          f"{rep['collective_bytes_per_node']/1e6:.2f} MB"
                          for ex, rep in wire["exchanges"].items()
                          if "error" not in rep))
        print()


if __name__ == "__main__":
    main()
