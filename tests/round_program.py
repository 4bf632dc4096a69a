"""The stacked engine's round program as ``run_federation`` builds it,
for tests that read its compiled text."""
import re

import jax

from repro.config import FederationConfig, TrainConfig, get_config
from repro.core import federation as F
from repro.data import make_image_dataset, partition, train_test_split

N_NODES = 3
TRAIN = TrainConfig(batch_size=16, learning_rate=1e-3, optimizer="adamw",
                    remat=False)
ROUND_SCOPES = ("round.teacher", "round.student", "round.protos",
                "round.codec", "round.mix")
_SCOPE = re.compile(r"(?:^|[/(])(round\.[a-z]+)(?=[/)]|$)")


def node_data(cfg, n_images):
    data = make_image_dataset(0, n_images, cfg.input_hw, cfg.num_classes)
    train_d, test_d = train_test_split(data, 0.1, 0)
    parts = partition(train_d["label"], N_NODES, "iid", 0)
    return [{k: v[i] for k, v in train_d.items()} for i in parts], test_d


def tiny_resnet():
    return get_config("cifar10-resnet18").replace(
        resnet_blocks=(1, 1, 1, 1), resnet_width=8, input_hw=(8, 8, 3),
        proto_dim=16)


def config(arch: str):
    return tiny_resnet() if arch == "resnet" else get_config("mnist-cnn")


def federation(proto_pass: str, bits: int, rounds: int = 1):
    return FederationConfig(num_nodes=N_NODES, rounds=rounds,
                            local_epochs=1, algorithm="profe",
                            proto_pass=proto_pass, quantize_bits=bits)


def capture_round(monkeypatch, cfg, fed, n_images=120):
    """Run ``run_federation`` once; return the arguments it built its
    round program from and those of the program's first call."""
    calls = []
    make = F._make_round_fn

    def capture(*a, **kw):
        fn = make(*a, **kw)

        def round_fn(*args, **kwargs):
            if not calls:
                calls.append(((a, kw), (args, kwargs)))
            return fn(*args, **kwargs)
        return round_fn

    monkeypatch.setattr(F, "_make_round_fn", capture)
    data, test_d = node_data(cfg, n_images)
    F.run_federation(cfg, fed, TRAIN, data, test_d)
    monkeypatch.setattr(F, "_make_round_fn", make)
    return calls[0]


def lower(built, call, sharding=None):
    """The round program lowered for this process's backend, or for
    ``sharding``'s device with the TPU code paths traced."""
    (a, kw), (args, kwargs) = built, call
    if sharding is None:
        return F._make_round_fn(*a, **kw).lower(*args, **kwargs)
    real = jax.default_backend
    jax.default_backend = lambda: "tpu"
    try:
        fn = F._make_round_fn(*a, **kw)
        shapes = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=sharding), args)
        return fn.lower(*shapes, **kwargs)
    finally:
        jax.default_backend = real


def contractions(text: str):
    """``(instruction, op_name or None)`` of every convolution and dot,
    fused or not."""
    out = []
    for ln in text.splitlines():
        if re.search(r" (convolution|dot)\(", ln):
            m = re.search(r'op_name="([^"]*)"', ln)
            out.append((ln.strip(), m.group(1) if m else None))
    return out


def scopes(op_name: str):
    """The distinct ``round.*`` scopes in an op's name path, under
    transforms such as ``transpose(jvp(...))`` too."""
    return set(_SCOPE.findall(op_name))
