"""The trace reduction (bench/devtrace.py): busy time, idle gaps, chunk gaps
and the breakdown, on a hand-made trace in the shape ``devtrace.events``
reads from an xplane file."""
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import devtrace  # noqa: E402


def _hand_made():
    dev = "/device:TPU:0"
    ops = [("fusion.1", 1.0, 2.0), ("custom_kernel", 1.5, 2.5),
           ("fusion.2", 3.0, 4.0), ("custom_kernel", 5.0, 5.5)]
    mods = [("jit_chunk", 1.0, 2.5), ("jit_small", 2.6, 2.7),
            ("jit_chunk", 3.0, 4.0), ("jit_chunk", 5.0, 5.5)]
    host = [("bench/chunk", 0.5, 2.8), ("bench/chunk", 2.8, 6.0),
            ("fetch", 2.5, 3.0), ("build", 4.0, 5.0), ("tiny", 4.2, 4.3)]
    return {"ops": {dev: ops}, "modules": {dev: mods}, "host": host}


def test_hand_made_trace():
    s = devtrace.summarize_events(_hand_made())
    assert s.window_s == pytest.approx(5.5)                 # 0.5 .. 6.0
    assert s.busy_s == pytest.approx(1.5 + 1.0 + 0.5)
    # the dominant program runs 1.0-2.5, 3.0-4.0, 5.0-5.5
    assert s.chunk_gaps_s == pytest.approx([0.5, 1.0])
    # idle: 0.5-1.0, 2.5-3.0, 4.0-5.0, 5.5-6.0; the longest is named by
    # the host event covering most of it
    assert s.idle[0] == ("build", pytest.approx(1.0))
    assert ("fetch", pytest.approx(0.5)) in s.idle
    b = s.breakdown()
    assert b["device_ops"][0] == ["custom_kernel", pytest.approx(1.5)]
    assert len(b["idle_gaps"]) == 4


def test_union_merges_overlaps():
    assert devtrace.union([(3, 4), (1, 2), (1.5, 2.5), (4, 5)]) == \
        [[1, 2.5], [3, 5]]


def test_events_reads_a_profiler_file(tmp_path):
    """``events`` on an xplane file the JAX profiler wrote here (the CPU
    has no device plane, so only host events come back)."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    for _ in range(2):
        with jax.profiler.TraceAnnotation(devtrace.CHUNK_ANNOTATION):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    ev = devtrace.events(devtrace.find_xplane(str(tmp_path)))
    chunks = sorted((a, b) for n, a, b in ev["host"]
                    if n == devtrace.CHUNK_ANNOTATION)
    assert len(chunks) == 2
    assert chunks[0][0] < chunks[0][1] <= chunks[1][0] < chunks[1][1]
    assert ev["ops"] == {}
