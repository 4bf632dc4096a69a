"""Integration: the DFL simulator end-to-end (paper Sec. IV protocol) —
every algorithm trains, communicates the right payloads, and ProFe's
byte count sits where the paper says (between FedProto and FedAvg,
~quantization+student factor below FedAvg)."""
import numpy as np
import pytest

from repro.config import FederationConfig, TrainConfig, get_config
from repro.core.federation import run_federation
from repro.data import make_image_dataset, partition, train_test_split

N_NODES = 3


@pytest.fixture(scope="module")
def mnist_like():
    cfg = get_config("mnist-cnn")
    data = make_image_dataset(0, 1500, cfg.input_hw, cfg.num_classes)
    train_d, test_d = train_test_split(data, 0.1, 0)
    parts = partition(train_d["label"], N_NODES, "iid", 0)
    node_data = [{k: v[i] for k, v in train_d.items()} for i in parts]
    return cfg, node_data, test_d


TRAIN = TrainConfig(batch_size=64, learning_rate=1e-3, optimizer="adamw",
                    remat=False)


def _run(cfg, node_data, test_d, algo, rounds=2, **kw):
    fed = FederationConfig(num_nodes=N_NODES, rounds=rounds, local_epochs=1,
                           algorithm=algo, **kw)
    return run_federation(cfg, fed, TRAIN, node_data, test_d)


def test_profe_learns_and_reduces_comm(mnist_like):
    cfg, node_data, test_d = mnist_like
    profe = _run(cfg, node_data, test_d, "profe", rounds=3)
    fedavg = _run(cfg, node_data, test_d, "fedavg", rounds=3)
    assert profe.f1_per_round[-1] > 0.5           # learns
    assert fedavg.f1_per_round[-1] > 0.5
    red = 1 - profe.extras["avg_sent_gb"] / fedavg.extras["avg_sent_gb"]
    # student(1/2 channels) + 16-bit wire => well beyond the paper's 40%
    assert red > 0.40, f"comm reduction only {red:.1%}"


def test_payload_ordering_matches_table2(mnist_like):
    """FedProto << ProFe < FedAvg <= FedGPD (bytes/node)."""
    cfg, node_data, test_d = mnist_like
    sizes = {}
    for algo in ["fedproto", "profe", "fedavg", "fedgpd"]:
        r = _run(cfg, node_data, test_d, algo, rounds=1)
        sizes[algo] = r.extras["avg_sent_gb"]
    assert sizes["fedproto"] < sizes["profe"] < sizes["fedavg"]
    assert sizes["fedavg"] <= sizes["fedgpd"]


def test_fml_runs_and_ships_meme_model(mnist_like):
    cfg, node_data, test_d = mnist_like
    r = _run(cfg, node_data, test_d, "fml", rounds=1)
    assert len(r.f1_per_round) == 1
    assert r.extras["avg_sent_gb"] > 0


def test_noniid_split_profe_still_learns(mnist_like):
    cfg, node_data, test_d = mnist_like
    # re-partition pathologically (40% of classes per node)
    labels = np.concatenate([d["label"] for d in node_data])
    imgs = np.concatenate([d["image"] for d in node_data])
    parts = partition(labels, N_NODES, "noniid40", 1)
    nd = [{"image": imgs[p], "label": labels[p]} for p in parts]
    r = _run(cfg, nd, test_d, "profe", rounds=4)
    # pathological splits converge slower; 4 rounds on 3 nodes is a smoke
    # bar (the full Fig. 2 protocol runs 10+ rounds on 20 nodes).  The
    # trajectory on this split crosses the bar between rounds 3 and 4
    # (~0.12 -> ~0.36), so 3 rounds sat exactly on the knife edge.
    assert r.f1_per_round[-1] > 0.15


def test_ring_topology(mnist_like):
    cfg, node_data, test_d = mnist_like
    fed = FederationConfig(num_nodes=N_NODES, rounds=1, algorithm="profe",
                           topology="ring")
    r = run_federation(cfg, fed, TRAIN, node_data, test_d)
    assert len(r.f1_per_round) == 1


def test_teacher_decay_freezes_teacher(mnist_like):
    """alpha_limit high enough that the teacher switches off mid-run."""
    cfg, node_data, test_d = mnist_like
    r = _run(cfg, node_data, test_d, "profe", rounds=3, alpha_s=0.2,
             alpha_limit=0.15)  # round 0: 0.2 on; round 1: 0.1 -> off
    assert len(r.f1_per_round) == 3


# ---------------------------------------------------------------------------
# pipelined round engine (overlap=)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kw", [{}, {"quantize_bits": 4,
                                     "error_feedback": True}],
                         ids=["fp32", "int4+ef"])
def test_overlap_none_bit_identical_to_sequential(mnist_like, kw):
    """The phase-split pipeline (overlap='none') runs the exact same
    jitted math as the single-program round — per-round F1 must match
    BIT for bit, error-feedback state included."""
    cfg, node_data, test_d = mnist_like
    fed = FederationConfig(num_nodes=N_NODES, rounds=2, local_epochs=1,
                           algorithm="profe", topology="ring", **kw)
    seq = run_federation(cfg, fed, TRAIN, node_data, test_d)
    piped = run_federation(cfg, fed, TRAIN, node_data, test_d,
                           overlap="none")
    assert piped.f1_per_round == seq.f1_per_round
    assert piped.extras["avg_sent_gb"] == seq.extras["avg_sent_gb"]


def test_overlap_rounds_stale_gossip_runs_and_learns(mnist_like):
    """overlap='rounds' (round t's gossip mixed during round t+1's local
    epochs) is stale-by-one, not bit-identical — but it must track the
    sequential run: same round count, same wire bytes, and visible
    learning.  Stale mixing lags the sequential curve early on; on
    sparse graphs it lands on the sequential fixed point (the N=20
    ring row in ``reports/table3_time.json``), while the dense full
    graph's uniform 1/N stale average can collapse (same report,
    recorded honestly).  3 rounds on a ring is the cheap smoke bar —
    the stale run must be learning, not tracking yet."""
    cfg, node_data, test_d = mnist_like
    fed = FederationConfig(num_nodes=N_NODES, rounds=3, local_epochs=1,
                           algorithm="profe", topology="ring")
    seq = run_federation(cfg, fed, TRAIN, node_data, test_d)
    stale = run_federation(cfg, fed, TRAIN, node_data, test_d,
                           overlap="rounds")
    assert len(stale.f1_per_round) == len(seq.f1_per_round)
    assert stale.extras["avg_sent_gb"] == seq.extras["avg_sent_gb"]
    assert stale.f1_per_round[-1] > 0.25
    # staleness is real: the curve diverges from the sequential one
    # (round 0 is the same sequential round in both, later rounds differ)
    assert stale.f1_per_round != seq.f1_per_round


def test_overlap_rejects_unknown_mode(mnist_like):
    cfg, node_data, test_d = mnist_like
    fed = FederationConfig(num_nodes=N_NODES, rounds=1, algorithm="profe")
    with pytest.raises(ValueError, match="overlap"):
        run_federation(cfg, fed, TRAIN, node_data, test_d,
                       overlap="stale")
