"""The program's spans in a recorded profiler trace: with ``repro.obs``
enabled, each span also enters a profiler annotation, so the trace holds
the spans as host events on the profiler's own clock."""
import glob
import os
import time

import jax
import jax.numpy as jnp

from chipbench import trace as bt


def test_program_spans_lie_in_the_profiler_trace(tmp_path):
    """With obs enabled, each program span also enters a profiler
    annotation: the recorded trace holds the spans as host events, whose
    starts, mapped through the anchor, agree with the JSONL's."""
    import repro.obs as obs
    f = jax.jit(lambda x: jnp.exp(jnp.sin(x) @ x.T).sum())
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    path = os.path.join(tmp_path, "obs.jsonl")
    obs.enable(trace_path=path)
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            anchor = time.perf_counter()
            with jax.profiler.TraceAnnotation(bt.ANCHOR):
                pass
            for _ in range(3):
                with obs.span("serve.round"):
                    with obs.span("solver.device"):
                        f(x).block_until_ready()
                    time.sleep(0.002)
        finally:
            jax.profiler.stop_trace()
        t0 = obs.tracer()._t0
    finally:
        obs.disable()
    spans = sorted(bt.load_spans(path, t0), key=lambda sp: sp[1])
    events = bt.load_xplane(glob.glob(os.path.join(
        tmp_path, "**", "*.xplane.pb"), recursive=True)[0])
    offset = bt.anchor_offset_ns(events, anchor)
    host = sorted((e for e in events if e.plane == "/host:CPU"
                   and e.name in ("serve.round", "solver.device")),
                  key=lambda e: e.start_ns)
    assert [sp[0] for sp in spans] == [e.name for e in host]
    assert len(spans) == 6
    for (_, start, end, _), e in zip(spans, host):
        assert abs(e.start_ns - (start * 1e9 + offset)) < 1e6
        assert abs(e.end_ns - (end * 1e9 + offset)) < 1e6
