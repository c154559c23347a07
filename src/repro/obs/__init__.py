"""``repro.obs`` — zero-overhead-when-disabled observability.

Three pillars:

* a process-global :class:`~repro.obs.metrics.MetricsRegistry`
  (counters / gauges / exact-quantile latency histograms) with
  snapshot + associative merge, so sharded-executor workers ship their
  metrics back to the driver;
* structured **span tracing** with nesting, exported as
  Chrome-trace-event JSONL (Perfetto / ``chrome://tracing``-loadable)
  via :class:`~repro.obs.trace.TraceWriter`; every span carries its
  identity (``sid``) and its parent's (``parent``), and while enabled
  each span also enters a ``jax.profiler.TraceAnnotation`` of its name,
  so a running profiler session records the program's spans on its own
  clock beside the device ops. Spans nest per thread (a span's parent is
  the innermost span open on its own thread), and the registry and the
  writer take a lock, so threads may record at once;
* runtime hooks while enabled: generation-2 garbage collections as
  ``host.gc`` spans (every collection's seconds add to ``host.gc_s``),
  and JAX's backend compiles as ``jax.compile`` spans; the ``jit/traces``
  and ``jit/compiles`` counters are always live (:func:`watch_jax`);
* :func:`session`: what the latest enabled session recorded (each
  counter's increase, each span's count and seconds), readable after
  :func:`disable`;
* a reporting CLI (``python -m repro.obs.report``) rendering per-stage
  p50/p99 tables, per-region carbon/water/WUE series, and run diffs.

Disabled (the default) is the fast path: ``span()`` returns a shared
no-op context manager, ``observe``/``gauge`` return immediately, and no
trace I/O happens — pinned in ``tests/test_obs.py`` by checking engine
records are bit-identical with obs on vs off.  Only plain **counters**
are always live (a dict add), because degenerate-path warning counts
and JIT-retrace accounting must be visible in ordinary runs too.

Typical use::

    import repro.obs as obs

    with obs.capture(trace_path="out/run.trace.jsonl"):
        result = engine.run(...)
        snap = obs.snapshot()          # counters/gauges/histograms
    # trace file closed; report with `python -m repro.obs.report`

Instrumentation sites use::

    with obs.span("policy.solve", jobs=M):
        res = solvers.solve(problem)
        obs.annotate(status=res.status)   # add args to the open span

    with obs.timed("cell.run") as t:      # always measures .elapsed_s
        sim.run()
    row["wall_s"] = t.elapsed_s
"""
from __future__ import annotations

import contextlib
import gc
import itertools
import threading
import time
import warnings
from typing import Dict, List, Optional

from repro.obs.metrics import (HIST_BASE, HIST_MAX_SAMPLES, Counter, Gauge,
                               Histogram, MetricsRegistry, merge_snapshots)
from repro.obs.trace import (SIM_PID, TraceWriter, iter_spans, read_trace,
                             validate_events)

__all__ = [
    "enabled", "enable", "disable", "capture", "span", "timed", "annotate",
    "counter", "gauge", "observe", "warn", "snapshot", "merge", "reset",
    "counter_value", "tracer", "registry", "watch_jax", "session",
    "MetricsRegistry", "Histogram", "Counter", "Gauge", "merge_snapshots",
    "TraceWriter", "read_trace", "iter_spans", "validate_events",
    "HIST_BASE", "HIST_MAX_SAMPLES", "SIM_PID",
]

_REGISTRY = MetricsRegistry()
_TRACER: Optional[TraceWriter] = None
_ENABLED = False
# Each thread's open spans, innermost last: a span's ``parent`` and
# :func:`annotate` reach the calling thread's own. ``disable`` empties every
# thread's stack by moving the epoch on.
_LOCAL = threading.local()
_EPOCH = 0
_SIDS = itertools.count(1)          # span identities, unique per process
# ``jax.profiler.TraceAnnotation`` while enabled (imported by ``enable``),
# else None: spans then enter no profiler annotation.
_ANNOTATION = None
_JAX_WATCHED = False
# The latest enabled session's registry totals (:func:`_totals`) at its
# enable, and at its disable (None while it is open).
_SESSION_START: Optional[Dict] = None
_SESSION_END: Optional[Dict] = None


def enabled() -> bool:
    return _ENABLED


def _stack() -> List["_Span"]:
    """The calling thread's open spans, innermost last."""
    if getattr(_LOCAL, "epoch", None) != _EPOCH:
        _LOCAL.epoch, _LOCAL.stack = _EPOCH, []
    return _LOCAL.stack


def _parent_sid() -> Optional[int]:
    st = _stack()
    return st[-1].sid if st else None


def registry() -> MetricsRegistry:
    return _REGISTRY


def tracer() -> Optional[TraceWriter]:
    return _TRACER


def enable(trace_path: Optional[str] = None) -> None:
    """Turn collection on; if ``trace_path`` is given, also write
    Chrome-trace events there until :func:`disable`. Registers the GC
    hook and the JAX compile listener, and makes spans enter profiler
    annotations. Starts a new :func:`session`."""
    global _ENABLED, _TRACER, _ANNOTATION, _SESSION_START, _SESSION_END
    _SESSION_START, _SESSION_END = _totals(), None
    _ENABLED = True
    if trace_path is not None:
        if _TRACER is not None:
            _TRACER.close()
        _TRACER = TraceWriter(trace_path)
    from jax.profiler import TraceAnnotation
    _ANNOTATION = TraceAnnotation
    watch_jax()
    _REGISTRY.counter("host.gc_s", 0.0)     # present, if zero, while enabled
    if _gc_hook not in gc.callbacks:
        gc.callbacks.append(_gc_hook)


def disable() -> None:
    """Stop collection and close any open trace file. The metrics
    registry is kept (read it with :func:`snapshot`; clear with
    :func:`reset`). Closes the open :func:`session`."""
    global _ENABLED, _TRACER, _ANNOTATION, _GC_SPAN, _SESSION_END, _EPOCH
    if _SESSION_START is not None and _SESSION_END is None:
        _SESSION_END = _totals()
    _ENABLED = False
    _ANNOTATION = None
    if _gc_hook in gc.callbacks:
        gc.callbacks.remove(_gc_hook)
    _GC_SPAN = None
    if _TRACER is not None:
        _TRACER.close()
        _TRACER = None
    _EPOCH += 1


@contextlib.contextmanager
def capture(trace_path: Optional[str] = None, fresh: bool = True,
            fold: bool = True):
    """Enable obs for a block, restoring the previous state after.
    Yields the live registry. ``fresh=True`` starts from an empty
    registry so the snapshot covers only this block; ``fold=False``
    discards the block's metrics on exit instead of merging them into
    the outer registry (shard workers ship their snapshot explicitly,
    so the driver must not also receive it by fold)."""
    global _REGISTRY
    prev_enabled, prev_reg = _ENABLED, _REGISTRY
    if fresh:
        _REGISTRY = MetricsRegistry()
    enable(trace_path)
    try:
        yield _REGISTRY
    finally:
        disable()
        if prev_enabled:
            enable()
        if fresh:
            # fold the block's metrics into the outer registry so nested
            # captures don't silently drop observations
            captured = _REGISTRY.snapshot() if fold else None
            _REGISTRY = prev_reg
            if captured is not None:
                _REGISTRY.merge(captured)


def reset() -> None:
    global _REGISTRY
    _REGISTRY = MetricsRegistry()


def _totals() -> Dict:
    with _REGISTRY.lock:
        return {"counters": {k: c.value
                             for k, c in _REGISTRY.counters.items()},
                "spans": {k: (h.count, h.total)
                          for k, h in _REGISTRY.hists.items()}}


def session() -> Optional[Dict]:
    """What the latest enabled session recorded, from its :func:`enable`
    to its :func:`disable` (to now while it is open); None before any.
    ``counters`` holds the increase of every counter present at its end
    (``host.gc_s`` is, from ``enable`` on); ``spans`` holds
    ``(count, seconds)`` of each span name, or :func:`observe` name,
    recorded in it."""
    if _SESSION_START is None:
        return None
    end = _SESSION_END or _totals()
    c0, s0 = _SESSION_START["counters"], _SESSION_START["spans"]
    spans = {}
    for k, (n, total) in end["spans"].items():
        n0, total0 = s0.get(k, (0, 0.0))
        if n > n0:
            spans[k] = (n - n0, total - total0)
    return {"counters": {k: v - c0.get(k, 0.0)
                         for k, v in end["counters"].items()},
            "spans": spans}


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

class _NullSpan:
    """Shared no-op span: the entire disabled-mode cost of ``span()``."""
    __slots__ = ()
    elapsed_s = 0.0

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **args) -> None:
        pass

    def elapsed(self) -> float:
        return 0.0


_NULL_SPAN = _NullSpan()


class _Span:
    __slots__ = ("name", "args", "t0", "elapsed_s", "_measure_only", "sid",
                 "parent", "_ann")

    def __init__(self, name: str, args: Dict, measure_only: bool = False):
        self.name = name
        self.args = args
        self.t0 = 0.0
        self.elapsed_s = 0.0
        self._measure_only = measure_only
        self._ann = None

    def set(self, **args) -> None:
        self.args.update(args)

    def elapsed(self) -> float:
        """Mid-flight wall-clock reading (``elapsed_s`` is only set at
        exit); lets a multi-return function report its wall so far."""
        return time.perf_counter() - self.t0

    def __enter__(self):
        if not self._measure_only:
            stack = _stack()
            self.parent = stack[-1].sid if stack else None
            self.sid = next(_SIDS)
            stack.append(self)
            if _ANNOTATION is not None:
                self._ann = _ANNOTATION(self.name)
                self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self.elapsed_s = t1 - self.t0
        if self._measure_only:
            return False
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        stack = _stack()
        if stack and stack[-1] is self:
            stack.pop()
        _REGISTRY.observe(self.name, self.elapsed_s)
        if _TRACER is not None:
            self.args.update(sid=self.sid, parent=self.parent)
            _TRACER.complete(self.name, (self.t0 - _TRACER._t0) * 1e6,
                             self.elapsed_s * 1e6, args=self.args)
        return False


def span(name: str, **args):
    """Context manager timing a named stage.  No-op singleton when obs
    is disabled; when enabled, records a latency-histogram observation
    and (if tracing) a Chrome-trace ``X`` event with ``args``."""
    if not _ENABLED:
        return _NULL_SPAN
    return _Span(name, args)


def timed(name: str, **args):
    """Like :func:`span`, but **always** measures wall time and exposes
    ``.elapsed_s`` — the drop-in replacement for ad-hoc
    ``time.perf_counter()`` pairs whose result feeds a data field
    (``solve_time_s``, ``wall_s``): the field is populated identically
    whether obs is on or off."""
    if not _ENABLED:
        return _Span(name, args, measure_only=True)
    return _Span(name, args)


def annotate(**args) -> None:
    """Attach args to the calling thread's innermost open (enabled) span,
    if any."""
    if _ENABLED:
        stack = _stack()
        if stack:
            stack[-1].set(**args)


# ---------------------------------------------------------------------------
# runtime hooks: garbage collection and JAX compiles
# ---------------------------------------------------------------------------

_GC_SPAN: Optional[_Span] = None
_GC_T0 = 0.0


def _gc_hook(phase: str, info: Dict) -> None:
    """``gc.callbacks`` hook, registered only while enabled: every
    collection's seconds add to counter ``host.gc_s``; a generation-2
    collection is also span ``host.gc``."""
    global _GC_SPAN, _GC_T0
    if phase == "start":
        if info["generation"] == 2:
            _GC_SPAN = _Span("host.gc", {"generation": 2})
            _GC_SPAN.__enter__()
        _GC_T0 = time.perf_counter()
        return
    _REGISTRY.counter("host.gc_s", time.perf_counter() - _GC_T0)
    if _GC_SPAN is not None:
        sp, _GC_SPAN = _GC_SPAN, None
        sp.set(collected=info["collected"])
        sp.__exit__(None, None, None)


# JAX monitoring events -> the always-live counters they bump.
_JAX_EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "jit/traces",
               "/jax/core/compile/backend_compile_duration": "jit/compiles"}


def _on_jax_event(event: str, duration: float, **kwargs) -> None:
    name = _JAX_EVENTS.get(event)
    if name is None:
        return
    _REGISTRY.counter(name)
    if name != "jit/compiles" or not _ENABLED:
        return
    # The event arrives as the compile ends: a span ending now that lasted
    # ``duration``, child of the innermost open span.
    _REGISTRY.observe("jax.compile", duration)
    if _TRACER is not None:
        t1 = time.perf_counter()
        _TRACER.complete("jax.compile", (t1 - duration - _TRACER._t0) * 1e6,
                         duration * 1e6, args=dict(
                             fun=str(kwargs.get("fun_name", "")),
                             sid=next(_SIDS), parent=_parent_sid()))


def watch_jax() -> None:
    """Count JAX's traces (``jit/traces``) and backend compiles, cache
    loads included (``jit/compiles``), from here on; while enabled and
    tracing, each compile is also span ``jax.compile``. Idempotent; the
    modules that compile the solve path call it as they are imported."""
    global _JAX_WATCHED
    if _JAX_WATCHED:
        return
    import jax.monitoring
    jax.monitoring.register_event_duration_secs_listener(_on_jax_event)
    _JAX_WATCHED = True


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def counter(name: str, n: float = 1) -> None:
    """Increment a counter. Always live (cheap), even when disabled —
    counters carry degenerate-path and JIT-retrace accounting that must
    not vanish in ordinary runs."""
    _REGISTRY.counter(name, n)


def counter_value(name: str) -> float:
    c = _REGISTRY.counters.get(name)
    return 0.0 if c is None else c.value


def gauge(name: str, value: float, weight: float = 1.0) -> None:
    if _ENABLED:
        _REGISTRY.gauge(name, value, weight)


def observe(name: str, value: float) -> None:
    if _ENABLED:
        _REGISTRY.observe(name, value)


def warn(name: str, message: str, n: float = 1) -> None:
    """Degenerate-path signal: bump ``warn/<name>`` (always) and issue a
    ``RuntimeWarning`` (Python's default filter dedups repeats per
    call site, so hot loops don't spam)."""
    _REGISTRY.counter(f"warn/{name}", n)
    warnings.warn(f"[{name}] {message}", RuntimeWarning, stacklevel=3)


def snapshot() -> Dict:
    return _REGISTRY.snapshot()


def merge(snap: Dict) -> None:
    """Fold a worker's snapshot into this process's registry."""
    _REGISTRY.merge(snap)
