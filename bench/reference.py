"""Plain float32 reference of the first rounds of a ProFe federation.

Written from the paper (Sec. III, Eq. 3, 4, 8, 9) and the configuration
files under ``bench/configs``; it imports nothing of the program under
test.  Everything is straightforward ``jax.numpy`` at float32 with every
contraction at ``Precision.HIGHEST``: the CIFAR ResNet (GroupNorm in
place of BatchNorm), the student and teacher losses, global-norm clip
and AdamW, the exact Eq. 3 pass, the per-node per-tensor integer wire
codec, the size-weighted gossip mix and the Eq. 4 prototype aggregate.

``rnd`` rounds activations, weights and their cotangents at the points
where a mixed-precision program casts to its compute type.  The
reference itself uses the identity; the control of the comparison
rounds to float8 e4m3 (:func:`float8_round`).

Weights are drawn from the seed the way the configuration describes
them: per node ``PRNGKey(seed * 1000 + node)``, split into teacher and
student keys; He-normal convolutions and LeCun-normal dense kernels,
both truncated at two standard deviations; GroupNorm scale 1, biases 0.
"""
from __future__ import annotations

import functools
import math
from typing import Callable, Dict, List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HI = lax.Precision.HIGHEST


def identity(x):
    return x


def float8_round() -> Callable:
    """Round float32 values, and their cotangents, to float8 e4m3fn:
    three mantissa bits, subnormals below 2**-6, saturating at 448."""
    def rnd(x):
        y = jnp.clip(x.astype(jnp.float32), -448.0, 448.0)
        return y.astype(jnp.float8_e4m3fn).astype(jnp.float32)

    @jax.custom_vjp
    def f(x):
        return rnd(x)

    f.defvjp(lambda x: (rnd(x), None), lambda _, g: (rnd(g),))
    return f


# ---------------------------------------------------------------------------
# the CIFAR ResNet
# ---------------------------------------------------------------------------

class Arch(NamedTuple):
    blocks: tuple
    width: int
    in_ch: int
    proto_dim: int
    classes: int


def _trunc(key, shape, std):
    return jax.random.truncated_normal(key, -2.0, 2.0, shape,
                                       jnp.float32) * std


def _gn_init(c):
    return {"scale": jnp.ones((c,), jnp.float32),
            "bias": jnp.zeros((c,), jnp.float32)}


def init_resnet(arch: Arch, key):
    ks = jax.random.split(key, 2 + sum(arch.blocks) + 2)
    w = arch.width
    p = {"stem": {"kernel": _trunc(ks[0], (3, 3, arch.in_ch, w),
                                   math.sqrt(2.0 / (9 * arch.in_ch)))},
         "gn0": _gn_init(w), "stages": []}
    k = 1
    c = w
    for si, n in enumerate(arch.blocks):
        cout = w * 2 ** si
        stage = []
        for _ in range(n):
            bk = jax.random.split(ks[k], 3)
            k += 1
            blk = {"conv1": {"kernel": _trunc(bk[0], (3, 3, c, cout),
                                              math.sqrt(2.0 / (9 * c)))},
                   "gn1": _gn_init(cout),
                   "conv2": {"kernel": _trunc(bk[1], (3, 3, cout, cout),
                                              math.sqrt(2.0 / (9 * cout)))},
                   "gn2": _gn_init(cout)}
            if c != cout:
                blk["proj"] = {"kernel": _trunc(bk[2], (1, 1, c, cout),
                                                math.sqrt(2.0 / c))}
            stage.append(blk)
            c = cout
        p["stages"].append(stage)
    p["proto_proj"] = {"kernel": _trunc(ks[k], (c, arch.proto_dim),
                                        1.0 / math.sqrt(c)),
                       "bias": jnp.zeros((arch.proto_dim,), jnp.float32)}
    p["fc"] = {"kernel": _trunc(ks[k + 1], (arch.proto_dim, arch.classes),
                                1.0 / math.sqrt(arch.proto_dim)),
               "bias": jnp.zeros((arch.classes,), jnp.float32)}
    return p


def _conv(x, kernel, stride, rnd):
    y = lax.conv_general_dilated(
        x, rnd(kernel), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HI)
    return rnd(y)


def _groupnorm(p, x, rnd, groups=8, eps=1e-5):
    b, h, w, c = x.shape
    g = min(groups, c)
    while c % g:
        g -= 1
    xr = x.reshape(b, h, w, g, c // g)
    mu = jnp.mean(xr, axis=(1, 2, 4), keepdims=True)
    var = jnp.mean(jnp.square(xr - mu), axis=(1, 2, 4), keepdims=True)
    y = ((xr - mu) / jnp.sqrt(var + eps)).reshape(b, h, w, c)
    return rnd(y * p["scale"] + p["bias"])


def _dense(p, x, rnd):
    return rnd(jnp.matmul(x, rnd(p["kernel"]), precision=HI)
               + rnd(p["bias"]))


def forward(params, image, rnd=identity):
    """image [B, H, W, C] -> (logits [B, K], f1 [B, proto_dim])."""
    x = rnd(image)
    x = jax.nn.relu(_groupnorm(params["gn0"],
                               _conv(x, params["stem"]["kernel"], 1, rnd),
                               rnd))
    for si, stage in enumerate(params["stages"]):
        for bi, blk in enumerate(stage):
            s = 2 if (si > 0 and bi == 0) else 1
            h = jax.nn.relu(_groupnorm(
                blk["gn1"], _conv(x, blk["conv1"]["kernel"], s, rnd), rnd))
            h = _groupnorm(blk["gn2"],
                           _conv(h, blk["conv2"]["kernel"], 1, rnd), rnd)
            if "proj" in blk:
                sc = _conv(x, blk["proj"]["kernel"], s, rnd)
            else:
                sc = x[:, ::s, ::s, :] if s != 1 else x
            x = jax.nn.relu(rnd(h + sc))
    pooled = rnd(jnp.mean(x, axis=(1, 2)))
    f1 = jax.nn.relu(_dense(params["proto_proj"], pooled, rnd))
    logits = _dense(params["fc"], f1, rnd)
    return logits, f1


# ---------------------------------------------------------------------------
# losses (Eq. 1, 6, 8, 9) and the optimizer
# ---------------------------------------------------------------------------

def ce_loss(logits, labels):
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    true = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - true)


def proto_mse(f1, gp, labels, mask):
    valid = mask[labels]
    per = jnp.mean(jnp.square(f1 - gp[labels]), axis=-1) * valid
    return jnp.sum(per) / jnp.maximum(jnp.sum(valid), 1.0)


def kd_loss(ls, lt, temp):
    log_ps = jax.nn.log_softmax(ls / temp, axis=-1)
    log_pt = jax.nn.log_softmax(lt / temp, axis=-1)
    return jnp.mean(jnp.sum(jnp.exp(log_pt) * (log_pt - log_ps), -1)) \
        * temp ** 2


class Hyper(NamedTuple):
    alpha_s: float
    alpha_limit: float
    beta_s: float
    beta_t: float
    temperature: float
    lr: float
    weight_decay: float
    grad_clip: float
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8


def _clip(grads, max_norm):
    leaves = jax.tree_util.tree_leaves(grads)
    gn = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in leaves))
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(gn, 1e-9))
    return jax.tree_util.tree_map(lambda g: g * scale, grads)


def adam_init(params):
    z = jax.tree_util.tree_map(jnp.zeros_like, params)
    return {"mu": z, "nu": z, "step": jnp.zeros((), jnp.int32)}


def _adamw(hp: Hyper, grads, state, params):
    step = state["step"] + 1
    t = step.astype(jnp.float32)
    bc1 = 1.0 - hp.b1 ** t
    bc2 = 1.0 - hp.b2 ** t
    mu = jax.tree_util.tree_map(lambda m, g: hp.b1 * m + (1 - hp.b1) * g,
                                state["mu"], grads)
    nu = jax.tree_util.tree_map(
        lambda v, g: hp.b2 * v + (1 - hp.b2) * jnp.square(g),
        state["nu"], grads)
    new = jax.tree_util.tree_map(
        lambda p, m, v: p - hp.lr * ((m / bc1) / (jnp.sqrt(v / bc2) + hp.eps)
                                     + hp.weight_decay * p),
        params, mu, nu)
    return new, {"mu": mu, "nu": nu, "step": step}


def alpha_at(hp: Hyper, rnd_idx: int) -> float:
    a = hp.alpha_s * 0.5 ** rnd_idx
    return 0.0 if a < hp.alpha_limit else a


# ---------------------------------------------------------------------------
# one node's local round (Eq. 8/9 steps, then the exact Eq. 3 pass)
# ---------------------------------------------------------------------------

def _local_round(hp: Hyper, teacher_on: bool, rnd: Callable, classes: int):
    def step(carry, batch):
        st, gp, mask, alpha = carry
        img, lab = batch
        tout = None
        teacher, opt_t = st["teacher"], st["opt_t"]
        if teacher_on:
            def tl(tp):
                lg, f1 = forward(tp, img, rnd)
                return (ce_loss(lg, lab) + hp.beta_t
                        * proto_mse(f1, gp, lab, mask)), (lg, f1)
            (_, tout), gt = jax.value_and_grad(tl, has_aux=True)(teacher)
            teacher, opt_t = _adamw(hp, _clip(gt, hp.grad_clip), opt_t,
                                    teacher)
            tout = jax.tree_util.tree_map(lax.stop_gradient, tout)

        def sl(sp):
            lg, f1 = forward(sp, img, rnd)
            loss = ce_loss(lg, lab) + hp.beta_s * proto_mse(f1, gp, lab, mask)
            if tout is not None:
                rep = jnp.mean(jnp.square(f1 - tout[1]))
                loss = loss + alpha * (kd_loss(lg, tout[0], hp.temperature)
                                       + rep)
            return loss

        ls, gs = jax.value_and_grad(sl)(st["student"])
        student, opt_s = _adamw(hp, _clip(gs, hp.grad_clip), st["opt_s"],
                                st["student"])
        st = dict(st, student=student, opt_s=opt_s, teacher=teacher,
                  opt_t=opt_t)
        return (st, gp, mask, alpha), ls

    def run(st, gp, mask, alpha, imgs, labs, pimgs, plabs):
        (st, _, _, _), losses = lax.scan(step, (st, gp, mask, alpha),
                                         (imgs, labs))

        def pstep(acc, batch):
            img, lab = batch
            _, f1 = forward(st["student"], img, rnd)
            onehot = jax.nn.one_hot(lab, classes, dtype=jnp.float32)
            return (acc[0] + jnp.einsum("bc,bp->cp", onehot, f1,
                                        precision=HI),
                    acc[1] + jnp.sum(onehot, 0)), ()

        p_dim = gp.shape[-1]
        acc0 = (jnp.zeros((classes, p_dim), jnp.float32),
                jnp.zeros((classes,), jnp.float32))
        (sums, counts), _ = lax.scan(pstep, acc0, (pimgs, plabs))
        return st, losses, sums, counts

    return jax.jit(jax.vmap(run, in_axes=(0, 0, 0, None, 0, 0, 0, 0)))


# ---------------------------------------------------------------------------
# wire codec, gossip mix, Eq. 4
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=1)
def codec_round_trip(x, bits: int):
    """Per-node, per-tensor symmetric integer codec: x [N, ...] fp32."""
    qm = float((1 << (bits - 1)) - 1)
    axes = tuple(range(1, x.ndim))
    amax = jnp.max(jnp.abs(x), axis=axes, keepdims=True)
    delta = jnp.maximum(amax / qm, jnp.finfo(jnp.float32).tiny)
    codes = jnp.clip(jnp.floor(x / delta + 0.5), -qm - 1, qm)
    return codes * delta


def gossip_weights(adj: np.ndarray, sizes: Sequence[int]):
    """Size-weighted neighbourhood mean: node i weighs itself by its
    data size and each neighbour j by j's."""
    a = np.asarray(adj, np.float64)
    s = np.asarray(sizes, np.float64)
    w = a * s[None, :]
    denom = w.sum(1) + s
    return (jnp.asarray(s / denom, jnp.float32),
            jnp.asarray(w / denom[:, None], jnp.float32))


@jax.jit
def _mix_leaf(w_self, w_neigh, own, recv):
    mixed = jnp.tensordot(w_neigh, recv, axes=1, precision=HI)
    return mixed + w_self.reshape((-1,) + (1,) * (own.ndim - 1)) * own


@jax.jit
def eq4(include, protos, counts):
    eff = include[:, :, None] * counts[None, :, :]
    n_j = jnp.sum(eff, axis=1)
    w = eff / jnp.maximum(n_j, 1.0)[:, None, :]
    glob = jnp.einsum("ijc,jcp->icp", w, protos, precision=HI)
    return glob, (n_j > 0).astype(jnp.float32)


# ---------------------------------------------------------------------------
# the federation
# ---------------------------------------------------------------------------

def batch_order(n: int, batch: int, seed: int) -> List[np.ndarray]:
    """A node's batches for one epoch: a seeded permutation cut into
    whole batches (the remainder dropped)."""
    perm = np.random.default_rng(seed).permutation(n)
    return [perm[i:i + batch] for i in range(0, (n // batch) * batch, batch)]


class Federation(NamedTuple):
    teacher: Arch
    student: Arch
    hyper: Hyper
    bits: int
    adjacency: np.ndarray
    seed: int
    batch: int


def init_nodes(fed: Federation, n_nodes: int, *, teacher: bool):
    """Node states stacked over a leading node axis, on the device."""
    @jax.jit
    def one(key):
        k1, k2 = jax.random.split(key)
        st = {"student": init_resnet(fed.student, k2),
              "teacher": init_resnet(fed.teacher, k1) if teacher else {}}
        st["opt_s"] = adam_init(st["student"])
        st["opt_t"] = adam_init(st["teacher"])
        return st

    nodes = [one(jax.random.PRNGKey(fed.seed * 1000 + i))
             for i in range(n_nodes)]
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *nodes)


def _take(tree, lo, hi):
    return jax.tree_util.tree_map(lambda x: x[lo:hi], tree)


def run_rounds(fed: Federation, node_data: List[Dict[str, np.ndarray]],
               rounds: int, *, rnd: Callable = identity,
               block_nodes: int = 4,
               on_round: Optional[Callable] = None):
    """Run ``rounds`` rounds of the federation from the seed.

    ``on_round(r, state, loss, global_protos)`` sees the state after
    each round, and with ``r = -1`` the initial state (device arrays
    stacked over nodes).  Nodes train in blocks
    of ``block_nodes`` so that the float32 activations fit.  The teacher
    trains in round r while alpha_s * 0.5**r >= alpha_limit."""
    n = len(node_data)
    hp = fed.hyper
    teacher_on = [hp.alpha_s * 0.5 ** r >= hp.alpha_limit
                  for r in range(rounds)]
    st = init_nodes(fed, n, teacher=any(teacher_on))
    classes = fed.student.classes
    gp = jnp.zeros((n, classes, fed.student.proto_dim), jnp.float32)
    mask = jnp.zeros((n, classes), jnp.float32)
    sizes = [len(d["label"]) for d in node_data]
    w_self, w_neigh = gossip_weights(fed.adjacency, sizes)
    include = jnp.asarray(np.minimum(
        np.asarray(fed.adjacency, np.float64) + np.eye(n), 1.0), jnp.float32)
    if on_round is not None:
        on_round(-1, st, None, gp)
    fns = {}
    for r in range(rounds):
        t_on = teacher_on[r]
        if t_on not in fns:
            fns[t_on] = _local_round(hp, t_on, rnd, classes)
        order = [batch_order(sizes[i], fed.batch, fed.seed + r * 997 + i)
                 for i in range(n)]
        porder = [batch_order(sizes[i], fed.batch, fed.seed + r)
                  for i in range(n)]
        parts, losses, sums, counts = [], [], [], []
        for lo in range(0, n, block_nodes):
            hi = min(lo + block_nodes, n)
            ids = range(lo, hi)
            imgs = np.stack([np.stack([node_data[i]["image"][b]
                                       for b in order[i]]) for i in ids])
            labs = np.stack([np.stack([node_data[i]["label"][b]
                                       for b in order[i]]) for i in ids])
            pimgs = np.stack([np.stack([node_data[i]["image"][b]
                                        for b in porder[i]]) for i in ids])
            plabs = np.stack([np.stack([node_data[i]["label"][b]
                                        for b in porder[i]]) for i in ids])
            out = fns[t_on](_take(st, lo, hi), gp[lo:hi], mask[lo:hi],
                            jnp.float32(alpha_at(hp, r)), imgs, labs,
                            pimgs, plabs)
            parts.append(out[0])
            losses.append(out[1])
            sums.append(out[2])
            counts.append(out[3])
        del st
        st = jax.tree_util.tree_map(lambda *xs: jnp.concatenate(xs), *parts)
        del parts
        loss = float(jnp.mean(jnp.concatenate(losses)))
        counts = jnp.concatenate(counts)
        protos = jnp.concatenate(sums) / jnp.maximum(counts, 1.0)[..., None]
        # the wire: every node's student and prototypes through the
        # codec; a node mixes its own student unquantized
        protos_rx = codec_round_trip(protos, fed.bits)
        st["student"] = jax.tree_util.tree_map(
            lambda x: _mix_leaf(w_self, w_neigh, x,
                                codec_round_trip(x, fed.bits)),
            st["student"])
        gp, mask = eq4(include, protos_rx, counts)
        if on_round is not None:
            on_round(r, st, loss, gp)
    return st
