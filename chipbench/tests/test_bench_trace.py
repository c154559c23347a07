"""The reduction from a profiler trace to busy time, idle share and summed
kernel time."""
import glob
import os
import re
import time

import jax
import jax.numpy as jnp
import pytest

from chipbench import trace as bt


def test_union_and_gaps():
    busy = bt.union([(5, 8), (0, 2), (1, 3), (7, 9), (20, 30)], 0, 25)
    assert busy == [(0, 3), (5, 9), (20, 25)]
    assert bt.gaps(busy, 0, 25) == [(3, 5), (9, 20)]
    assert bt.gaps([], 0, 4) == [(0, 4)]


def test_op_name_strips_numbering():
    name = ("%sinkhorn_iteration_pallas.18 = (f32[1,4096]{1,0:T(1,128)}) "
            "custom-call(%x)")
    assert bt.op_name(name) == "sinkhorn_iteration_pallas"
    assert bt.op_name("%while.3 = (s32[]) while(%t)") == "while"
    assert bt.op_name("fusion") == "fusion"


def test_reduce_nested_ops_and_named_gaps():
    P, L = "/device:TPU:0", "XLA Ops"
    ev = [bt.Event(P, L, "%while.1 = x", 100, 400),
          bt.Event(P, L, "%sinkhorn_iteration_pallas.1 = y", 150, 200),
          bt.Event(P, L, "%sinkhorn_iteration_pallas.2 = y", 250, 300),
          bt.Event(P, L, "%fusion.4 = z", 700, 800),
          bt.Event(P, "XLA Modules", "jit_f", 100, 800),
          bt.Event("/host:CPU", "python3", "other", 0, 1000)]
    spans = [("engine.round", 0.0, 1000e-9, {}),
             ("policy.extract", 450e-9, 650e-9, {})]
    r = bt.reduce(ev, 0, 1000, spans=spans)
    assert r.window_s == pytest.approx(1000e-9)
    assert r.busy_s == pytest.approx(400e-9)
    assert r.idle_share == pytest.approx(0.6)
    assert r.kernel_s["sinkhorn_iteration_pallas"] == pytest.approx(100e-9)
    assert r.top_ops[0] == ("while", pytest.approx(300e-9))
    assert r.idle_gaps == [("policy.extract", pytest.approx(300e-9)),
                           ("engine.round", pytest.approx(200e-9)),
                           ("engine.round", pytest.approx(100e-9))]
    with pytest.raises(ValueError):
        bt.reduce([e for e in ev if e.plane != P], 0, 1000)


def test_reduce_averages_the_chips_and_clips_to_the_window():
    L = "XLA Ops"
    ev = [bt.Event("/device:TPU:0", L, "%a.1 = x", 100, 300),
          bt.Event("/device:TPU:1", L, "%a.2 = x", 600, 1200),
          bt.Event("/device:TPU:1", L, "%b.1 = x", -50, 50)]
    r = bt.reduce(ev, 0, 1000)
    assert r.window_s == pytest.approx(1000e-9)
    assert r.busy_s == pytest.approx((200e-9 + 400e-9 + 50e-9) / 2)
    assert r.kernel_s["a"] == pytest.approx((200e-9 + 400e-9) / 2)
    assert r.kernel_s["b"] == pytest.approx(50e-9 / 2)
    assert sorted(g for _, g in r.idle_gaps) == pytest.approx(
        [100e-9, 550e-9, 700e-9])


def test_reduce_reads_the_cells_chips_only():
    """A one-chip cell on a host of two chips reads its own chip alone."""
    L = "XLA Ops"
    ev = [bt.Event("/device:TPU:0", L, "%a.1 = x", 100, 300),
          bt.Event("/device:TPU:1", L, "%b.1 = x", 0, 1000)]
    one = bt.reduce(ev, 0, 1000, chips=1)
    assert one.busy_s == pytest.approx(200e-9)
    assert one.idle_share == pytest.approx(0.8)
    assert set(one.kernel_s) == {"a"}
    alone = bt.reduce(ev[:1], 0, 1000)
    assert one.busy_s == alone.busy_s and one.idle_gaps == alone.idle_gaps
    both = bt.reduce(ev, 0, 1000, chips=2)
    assert both.busy_s == pytest.approx((200e-9 + 1000e-9) / 2)
    assert bt.reduce(ev, 0, 1000).busy_s == both.busy_s
    with pytest.raises(ValueError):
        bt.reduce(ev[1:], 0, 1000, chips=1)


def test_reduce_a_recorded_trace(tmp_path):
    """A trace recorded here on the CPU, whose XLA client thread stands in
    for the device plane."""
    f = jax.jit(lambda x: jnp.exp(jnp.sin(x) @ x.T).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        anchor = time.perf_counter()
        with jax.profiler.TraceAnnotation(bt.ANCHOR):
            pass
        for _ in range(5):
            f(x).block_until_ready()
            time.sleep(0.002)
        end = time.perf_counter()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                     recursive=True)[0]
    events = bt.load_xplane(path)
    offset = bt.anchor_offset_ns(events, anchor)
    assert offset is not None
    lo, hi = anchor * 1e9 + offset, end * 1e9 + offset
    host_line = next(e.line for e in events if e.plane == "/host:CPU"
                     and e.line.startswith("tf_XLAPjRtCpuClient"))
    r = bt.reduce(events, lo, hi, plane=re.compile("^/host:CPU$"),
                  line=lambda name: name == host_line)
    assert 0 < r.busy_s <= r.window_s
    assert r.idle_share == pytest.approx(1 - r.busy_s / r.window_s)
    assert r.window_s == pytest.approx(end - anchor, rel=1e-6)
    ops = [e for e in events if e.line == host_line]
    name, seconds = r.top_ops[0]
    want = sum(min(e.end_ns, hi) - max(e.start_ns, lo) for e in ops
               if bt.op_name(e.name) == name and e.end_ns > lo
               and e.start_ns < hi)
    assert seconds == pytest.approx(want * 1e-9)
    assert r.idle_gaps and all(g[0] == "host" for g in r.idle_gaps)
