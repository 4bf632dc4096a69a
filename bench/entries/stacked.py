"""One-chip cells: ProFe rounds through ``repro.core.federation.run_federation``
(the stacked engine: every node's state stacked on one chip).

One ``run_federation`` call runs the whole cell.  Its first
``SETUP_ROUNDS`` rounds are set-up: round 0 traces and loads (or
compiles) the round program, and the state that rounds 0-2 carry is
read at their boundaries for the comparison with the reference.  The
rounds after them are the window.

The benchmark keeps its own clock: the wrapper stamps the host time at
which each round's jitted program is called.  From one stamp to the
next is one whole round of ``run_federation``: its program, metering,
evaluation and sync, and the host staging of the next round's batches.
The window opens at stamp ``SETUP_ROUNDS`` and closes at the first stamp
``seconds`` or more after it, so it holds whole rounds and its length
follows ``--seconds``; the call is then ended by raising
:class:`WindowClosed` from the wrapper, before that round starts.

In a traced run the profiler, with the Python tracer off, covers the
``TRACE_ROUNDS`` rounds after set-up (busy time, idle share,
utilisation); a second trace with the Python tracer on covers
``NAMED_ROUNDS`` more, only to name what the host does in each device
gap.  A ``TraceAnnotation`` marks each traced window.

The state is read by wrapping the program's round factory
``federation._make_round_fn``: the wrapper copies the state a round is
given to the host before calling the program's own jitted round.  The
run's ``FederationResult`` is caught as it is made, since the call ends
by an exception.
"""
from __future__ import annotations

import gc
import sys
import time
from typing import Any, Dict, List

import numpy as np

from bench import compare
from bench import reference as ref
from bench.generate import make_federation_data
from bench.harness import WINDOW_LABEL

COMPARED_ROUNDS = 2
SETUP_ROUNDS = COMPARED_ROUNDS + 1    # states before rounds 0, 1 and 2 are read
TRACE_ROUNDS = 2
NAMED_ROUNDS = 1
# rounds handed to the program; the window closes long before
MAX_ROUNDS = 10_000


class WindowClosed(Exception):
    """Raised by the round wrapper to end ``run_federation``."""


def program_seed(seed: int) -> int:
    """The federation seed handed to the program.  It keys node k's
    weights as ``seed * 1000 + k``, which has to stay within 31 bits."""
    return seed % 2_000_000


def model_configs(config: dict):
    from repro.config import get_config
    from repro.models import derive_student
    t = config["teacher"]
    cfg = get_config(config["program_config"]).replace(
        resnet_blocks=tuple(t["resnet_blocks"]),
        resnet_width=t["resnet_width"], input_hw=tuple(config["input_hw"]),
        num_classes=config["num_classes"], proto_dim=config["proto_dim"],
        dtype=config["dtype"], param_dtype=config["param_dtype"])
    student = derive_student(cfg)
    want = (tuple(config["student"]["resnet_blocks"]),
            config["student"]["resnet_width"])
    got = (tuple(student.resnet_blocks), student.resnet_width)
    if got != want:
        raise ValueError(f"the program derives the student {got}, the "
                         f"configuration states {want}")
    return cfg


def _to_host_tree(x, plane_meta=None):
    """A student plane (or a buffer laid out like it) as a host tree."""
    import jax
    from repro.optim.plane import Plane
    if isinstance(x, Plane):
        plane_meta, raw, x = x.meta, x.raw, x.buf
    elif plane_meta is None:
        return jax.device_get(x)
    else:
        raw = ()
    buf = np.asarray(jax.device_get(x))
    leaves = []
    for item in plane_meta.recipe:
        if item[0] == "raw":
            leaves.append(np.asarray(raw[item[1]]))
            continue
        _, shape, _dt, row, r_leaf = item
        per = int(np.prod(shape))
        v = buf[:, row:row + r_leaf, :].reshape(buf.shape[0], -1)[:, :per]
        leaves.append(v.reshape((buf.shape[0],) + tuple(shape)))
    return jax.tree_util.tree_unflatten(plane_meta.treedef, leaves)


def _read_state(state, fields) -> Dict[str, Any]:
    import jax
    from repro.optim.plane import Plane
    meta = state.student.meta if isinstance(state.student, Plane) else None
    out = {}
    if "params" in fields:
        out["student"] = _to_host_tree(state.student)
        out["teacher"] = jax.device_get(state.teacher)
    if "moments" in fields:
        for m in ("mu", "nu"):
            out[f"{m}_s"] = _to_host_tree(state.opt_s[m], meta)
            out[f"{m}_t"] = jax.device_get(state.opt_t[m])
        out["gp"] = np.asarray(jax.device_get(state.global_protos))
    return out


class RoundTap:
    """Wraps the program's round factory for the length of a
    ``with`` block: stamps the time each round's program is called,
    keeps each round's loss, reads the state before the rounds in
    ``reads``, runs the profiler in a traced run and ends the call when
    the window closes."""

    def __init__(self, reads: Dict[int, tuple], seconds: float,
                 trace_dir: str = ""):
        self.reads = reads
        self.seconds = seconds
        self.trace_dir = trace_dir
        self.calls: List[float] = []
        self.losses: List[Any] = []
        self.states: Dict[int, Dict[str, Any]] = {}
        self.result = None
        self.closed_at = None
        self._ann = None

    def _closes(self, i: int) -> bool:
        if self.trace_dir:
            return i == SETUP_ROUNDS + TRACE_ROUNDS + NAMED_ROUNDS
        return i > SETUP_ROUNDS and \
            self.calls[i] - self.calls[SETUP_ROUNDS] >= self.seconds

    def __enter__(self):
        from repro.core import federation
        self._fed = federation
        self._orig = federation._make_round_fn
        self._orig_result = federation.FederationResult
        tap = self

        class Result(self._orig_result):
            def __init__(self, *a, **kw):
                super().__init__(*a, **kw)
                tap.result = self

        def make(*a, **kw):
            fn = self._orig(*a, **kw)

            def round_fn(state, *args, **kwargs):
                i = len(self.calls)
                self.calls.append(time.time())
                if self.trace_dir and i == SETUP_ROUNDS + TRACE_ROUNDS:
                    self._stop_trace()
                if self._closes(i):
                    self._stop_trace()
                    self.closed_at = i
                    raise WindowClosed(i)
                if i in self.reads:
                    self.states[i] = _read_state(state, self.reads[i])
                if self.trace_dir and i == SETUP_ROUNDS:
                    self._start_trace("plain", python=False)
                if self.trace_dir and i == SETUP_ROUNDS + TRACE_ROUNDS:
                    self._start_trace("named", python=True)
                state, loss = fn(state, *args, **kwargs)
                self.losses.append(loss)
                return state, loss
            return round_fn

        federation._make_round_fn = make
        federation.FederationResult = Result
        return self

    def _start_trace(self, sub: str, python: bool):
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = int(python)
        opts.host_tracer_level = 1
        jax.profiler.start_trace(f"{self.trace_dir}/{sub}",
                                 profiler_options=opts)
        self._ann = jax.profiler.TraceAnnotation(WINDOW_LABEL)
        self._ann.__enter__()

    def _stop_trace(self):
        import jax
        if self._ann is None:
            return
        self._ann.__exit__(None, None, None)
        self._ann = None
        jax.profiler.stop_trace()

    def __exit__(self, *exc):
        self._fed._make_round_fn = self._orig
        self._fed.FederationResult = self._orig_result
        self._stop_trace()
        return exc[0] is WindowClosed


def _hyper(config: dict, traffic: dict) -> ref.Hyper:
    p, t = config["profe"], config["train"]
    return ref.Hyper(alpha_s=traffic["alpha_s"],
                     alpha_limit=traffic["alpha_limit"], beta_s=p["beta_s"],
                     beta_t=p["beta_t"], temperature=p["kd_temperature"],
                     lr=t["learning_rate"], weight_decay=t["weight_decay"],
                     grad_clip=t["grad_clip"])


def reference_federation(config: dict, traffic: dict, seed: int
                         ) -> ref.Federation:
    def arch(part):
        return ref.Arch(tuple(config[part]["resnet_blocks"]),
                        config[part]["resnet_width"], config["input_hw"][2],
                        config["proto_dim"], config["num_classes"])
    if traffic["topology"] != "full":
        raise ValueError(f"unsupported topology {traffic['topology']!r}")
    n = config["nodes"]
    return ref.Federation(
        teacher=arch("teacher"), student=arch("student"),
        hyper=_hyper(config, traffic), bits=traffic["quantize_bits"],
        adjacency=np.ones((n, n)) - np.eye(n), seed=program_seed(seed),
        batch=config["batch_size"])


def reference_readings(fed: ref.Federation, node_data, rounds: int, *,
                       rnd=ref.identity, block_nodes: int = 4):
    """What the comparison reads from a run of the reference (or of the
    control, with ``rnd``): per-round losses, the moments after round 0,
    the global prototypes after round 0 and the parameters' change over
    ``rounds`` rounds."""
    got: Dict[str, Any] = {"loss": []}

    def on_round(r, st, loss, gp):
        if r == -1:
            got["p0"] = {"student": st["student"], "teacher": st["teacher"]}
            return
        got["loss"].append(loss)
        if r == 0:
            got["grad.student"] = compare.norms(st["opt_s"]["mu"])
            got["grad.teacher"] = compare.norms(st["opt_t"]["mu"])
            got["var.student"] = compare.norms(st["opt_s"]["nu"])
            got["var.teacher"] = compare.norms(st["opt_t"]["nu"])
            got["gp0"] = np.asarray(gp)
        if r == rounds - 1:
            for part in ("student", "teacher"):
                got[f"step.{part}"] = compare.delta_norms(got["p0"][part],
                                                          st[part])

    ref.run_rounds(fed, node_data, rounds, rnd=rnd, block_nodes=block_nodes,
                   on_round=on_round)
    del got["p0"]
    return got


def program_readings(tap: RoundTap, losses) -> Dict[str, Any]:
    s0, s1 = tap.states[0], tap.states[1]
    last = tap.states[COMPARED_ROUNDS]
    out = {"loss": list(losses[:COMPARED_ROUNDS]), "gp0": s1["gp"],
           "grad.student": compare.norms(s1["mu_s"]),
           "grad.teacher": compare.norms(s1["mu_t"]),
           "var.student": compare.norms(s1["nu_s"]),
           "var.teacher": compare.norms(s1["nu_t"])}
    for part in ("student", "teacher"):
        out[f"step.{part}"] = compare.delta_norms(s0[part], last[part])
    return out


def readings(prog: Dict[str, Any], want: Dict[str, Any]) -> Dict[str, float]:
    """Every number the comparison can make of the program's (or a
    variant's) first rounds against the reference's."""
    out = {f"loss.r{r}": compare.rel(a, b)
           for r, (a, b) in enumerate(zip(prog["loss"], want["loss"]))}
    gp_p, gp_r = np.asarray(prog["gp0"], np.float64), \
        np.asarray(want["gp0"], np.float64)
    out["protos.r0"] = float(np.linalg.norm(gp_p - gp_r)
                             / max(np.linalg.norm(gp_r), 1e-30))
    for part in ("student", "teacher"):
        g_ref = want[f"grad.{part}"]
        if not g_ref or max(g_ref.values()) == 0.0:
            continue                      # the phase never trains it
        out[f"grad.{part}"] = compare.worst_leaf_gap(prog[f"grad.{part}"],
                                                     g_ref)
        out[f"var.{part}"] = compare.worst_leaf_gap(prog[f"var.{part}"],
                                                    want[f"var.{part}"])
        out[f"step.{part}"] = compare.worst_leaf_gap(
            prog[f"step.{part}"], want[f"step.{part}"],
            compare.moved_leaves(g_ref))
    return out


def checks(values: Dict[str, float],
           limits: Dict[str, float]) -> List[compare.Check]:
    """The compared numbers: those the cell's workload file gives a limit."""
    return [(name, values[name], lim) for name, lim in limits.items()]


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def run(cell: dict, seed: int, seconds: float, trace: bool,
        trace_dir: str) -> Dict[str, Any]:
    """Run the cell once; returns what the harness reports."""
    import jax
    from repro.config import FederationConfig, TrainConfig
    from repro.core.federation import run_federation

    config, traffic, workload = cell["config"], cell["traffic"], \
        cell["workload"]
    cfg = model_configs(config)
    t_data = time.time()
    node_data, test = make_federation_data(seed, config, traffic)
    log(f"data {time.time() - t_data:.2f} s")
    t = config["train"]
    fed = FederationConfig(
        num_nodes=config["nodes"], topology=traffic["topology"],
        rounds=MAX_ROUNDS, local_epochs=traffic["local_epochs"],
        algorithm="profe", kd_temperature=config["profe"]["kd_temperature"],
        alpha_s=traffic["alpha_s"], alpha_limit=traffic["alpha_limit"],
        beta_s=config["profe"]["beta_s"], beta_t=config["profe"]["beta_t"],
        quantize_bits=traffic["quantize_bits"],
        proto_pass=traffic["proto_pass"], param_plane=traffic["param_plane"],
        split=traffic["split"], seed=program_seed(seed))
    train = TrainConfig(batch_size=config["batch_size"],
                        learning_rate=t["learning_rate"],
                        optimizer=t["optimizer"],
                        weight_decay=t["weight_decay"],
                        grad_clip=t["grad_clip"])
    reads = {0: ("params",), 1: ("moments",), COMPARED_ROUNDS: ("params",)}
    tap = RoundTap(reads, seconds, trace_dir if trace else "")
    t_call = time.time()
    with tap:
        run_federation(cfg, fed, train, node_data, test)
    if tap.closed_at is None:
        raise RuntimeError(f"the window did not close in {MAX_ROUNDS} rounds")
    res = tap.result
    if res is None or res.extras.get("engine") != "stacked":
        raise RuntimeError("run_federation left the stacked engine")
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.local_devices())
    stamps = tap.calls
    losses = [float(x) for x in tap.losses]
    window = tap.closed_at - SETUP_ROUNDS
    start, stop = stamps[SETUP_ROUNDS], stamps[tap.closed_at]
    out = {
        "window_start": start,
        "attempted": window,
        "failed": int(sum(not np.isfinite(v) for v in losses[SETUP_ROUNDS:])),
        "e2e": {"round_s": (stop - start) / window,
                "peak_hbm_gb": peak / 1e9},
        "wire_mb_per_node_round":
            res.extras["avg_sent_packed_gb"] * 1e3 / fed.rounds,
        "memory_peak_bytes": int(peak),
        "traced_rounds": TRACE_ROUNDS if trace else 0,
    }
    log(f"before round 0 {stamps[0] - t_call:.1f} s, rounds "
        f"{[round(b - a, 4) for a, b in zip(stamps, stamps[1:])]}"
        f" s, losses {[round(x, 5) for x in losses]}")
    prog = program_readings(tap, losses)
    del res, tap
    gc.collect()                 # the program's state, before the reference
    t_ref = time.time()
    want = reference_readings(
        reference_federation(config, traffic, seed), node_data,
        COMPARED_ROUNDS, block_nodes=workload["reference_block_nodes"])
    log(f"reference {time.time() - t_ref:.1f} s")
    values = readings(prog, want)
    log(f"readings {values}")
    out["checks"] = checks(values, workload["limits"])
    return out
