"""Model FLOPs of the CIFAR ResNet, counted from its layer shapes.

A convolution costs two FLOPs per multiply-add over the taps that land
inside the image (``SAME`` padding's zeros are not work the model
needs): ``2 * C_in * C_out`` times the valid taps; a dense layer costs
``2 * d_in * d_out``.  Normalisation, activations, pooling and the loss
are elementwise and left out.  A training step is
three forward passes (the forward, and the two products of the backward
for activations and weights).  Recomputation is not counted.
"""
from __future__ import annotations


def _taps(size: int, k: int, stride: int) -> int:
    """Valid kernel taps summed over the output positions of one axis
    under ``SAME`` padding."""
    out = -(-size // stride)
    lo = max((out - 1) * stride + k - size, 0) // 2
    return sum(1 for o in range(out) for j in range(k)
               if 0 <= o * stride - lo + j < size)


def _conv(h: int, w: int, k: int, stride: int, cin: int, cout: int) -> int:
    return 2 * cin * cout * _taps(h, k, stride) * _taps(w, k, stride)


def forward_flops(blocks, width: int, hw, proto_dim: int,
                  classes: int) -> int:
    """FLOPs of one image's forward pass."""
    h, w, cin = hw
    total = _conv(h, w, 3, 1, cin, width)                  # stem
    c = width
    for si, n in enumerate(blocks):
        cout = width * 2 ** si
        for bi in range(n):
            s = 2 if (si > 0 and bi == 0) else 1
            ho, wo = -(-h // s), -(-w // s)
            total += _conv(h, w, 3, s, c, cout)             # conv1
            total += _conv(ho, wo, 3, 1, cout, cout)        # conv2
            if c != cout:
                total += _conv(h, w, 1, s, c, cout)         # 1x1 projection
            h, w, c = ho, wo, cout
    total += 2 * c * proto_dim + 2 * proto_dim * classes    # f1 and head
    return total


def sample_flops(config: dict, part: str) -> int:
    """FLOPs of one image's forward pass through ``part`` (``"teacher"``
    or ``"student"``): the family's count that ``mfu.round`` composes
    into a round."""
    p = config[part]
    return forward_flops(p["resnet_blocks"], p["resnet_width"],
                         config["input_hw"], config["proto_dim"],
                         config["num_classes"])


def param_sizes(config: dict, part: str) -> list:
    """Element counts of the parameter tensors of ``part``."""
    p = config[part]
    width, cin = p["resnet_width"], config["input_hw"][2]
    pd, k = config["proto_dim"], config["num_classes"]
    sizes = [9 * cin * width, width, width]             # stem, gn0
    c = width
    for si, n in enumerate(p["resnet_blocks"]):
        cout = width * 2 ** si
        for _ in range(n):
            sizes += [9 * c * cout, cout, cout, 9 * cout * cout, cout, cout]
            if c != cout:
                sizes.append(c * cout)
            c = cout
    return sizes + [c * pd, pd, pd * k, k]
