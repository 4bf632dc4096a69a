"""The trace reduction on synthetic events and on a trace recorded on
the CPU."""
import time

import jax
import jax.numpy as jnp
import pytest

from bench import trace as T


def _trace(ops, host=(), window=(0.0, 100.0)):
    return T.Trace({"/device:TPU:0": sorted(ops)}, sorted(host, key=lambda e: e[1]),
                   window)


def test_union_merges_overlaps():
    assert T.union([(0, 10), (5, 20), (30, 40), (40, 45)]) == \
        [(0, 20), (30, 45)]


def test_busy_counts_overlapping_ops_once_and_clips_to_window():
    ops = [("a", -5.0, 10.0), ("b", 5.0, 20.0), ("c", 90.0, 130.0)]
    assert T.busy_ns(ops, (0.0, 100.0)) == 30.0
    tr = _trace(ops)
    assert T.mean_busy_s(tr) == pytest.approx(30e-9)


def test_kernel_time_by_name_pattern():
    ops = [("_adamw_kernel.1", 0.0, 4.0), ("fusion.3", 4.0, 9.0),
           ("_adamw_kernel.2", 10.0, 13.0)]
    assert T.kernel_ns(ops, r"adamw") == (7.0, 2)
    assert T.kernel_ns(ops, r"nothing") == (0.0, 0)


def test_idle_gaps_named_by_the_narrowest_host_span():
    ops = [("op", 0.0, 10.0), ("op", 60.0, 100.0)]
    host = [("$loop.py:1 run", 0.0, 100.0),
            ("$federation.py:311 _stack_round_batches", 12.0, 58.0)]
    tr = _trace(ops, host)
    gaps = T.idle_gaps(tr)
    assert gaps[0] == ["$federation.py:311 _stack_round_batches",
                       pytest.approx(50e-9)]
    host.append(("$dispatch.py:84 apply_primitive", 20.0, 50.0))
    tr = _trace(ops, host)
    assert T.idle_gaps(tr)[0][0] == "$dispatch.py:84 apply_primitive"
    assert T.idle_gaps(tr, sources=["federation.py"])[0][0] == \
        "$federation.py:311 _stack_round_batches > " \
        "$dispatch.py:84 apply_primitive"
    top = T.top_ops(tr)
    assert top == [["op", pytest.approx(50e-9)]]


def test_op_names_and_containers():
    fusion = "%fusion.12 = bf16[2,3]{1,0} fusion(f32[2,3]{1,0} %p), kind=kLoop"
    assert T.op_name(fusion) == "fusion.12 fusion"
    cc = ('%custom-call.4 = f32[8,128]{1,0} custom-call(f32[8,128]{1,0} %x), '
          'custom_call_target="tpu_custom_call"')
    assert T.op_name(cc) == "custom-call.4 custom-call tpu_custom_call"
    loop = "%while.7 = (s32[], f32[4]{0}) while((s32[], f32[4]{0}) %t), body=%b"
    assert T._is_container(loop) and not T._is_container(fusion)


def _bench_probe_stage(f, x):
    time.sleep(0.02)
    return f(x).block_until_ready()


def test_load_reads_python_spans_of_a_cpu_trace(tmp_path):
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for _ in range(2):
            _bench_probe_stage(f, x)
    finally:
        jax.profiler.stop_trace()
    tr = T.load(str(tmp_path))
    names = [e[0] for e in tr.host]
    start = next(e for e in tr.host if e[0].endswith(" start_trace"))
    assert tr.window[0] == start[2]
    assert any("_bench_probe_stage" in n for n in names)
    assert tr.window[1] > tr.window[0]
    probe = next(e for e in tr.host if "_bench_probe_stage" in e[0])
    assert "sleep" in T._host_label(tr.host, probe[1] + 1e5, probe[1] + 1e7)


def test_load_takes_the_labelled_window_with_the_python_tracer_off(tmp_path):
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        time.sleep(0.05)                  # outside the window
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(2):
                _bench_probe_stage(f, x)
        inside = time.perf_counter() - t0
        time.sleep(0.05)
    finally:
        jax.profiler.stop_trace()
    tr = T.load(str(tmp_path), label="bench.window")
    assert not any(e[0].startswith("$") for e in tr.host)
    window_s = (tr.window[1] - tr.window[0]) / 1e9
    assert 0.04 <= window_s <= inside     # the sleeps around it left out
