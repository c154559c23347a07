"""Reduction of a JAX profiler trace to device busy time, idle gaps and
kernel time.

A traced run records the window with ``jax.profiler`` (an ``.xplane.pb``)
and the program's ``repro.obs`` spans (a Chrome-trace JSONL on the host's
``perf_counter`` clock). One anchor annotation, entered at a known
``perf_counter`` instant, lies in both and aligns the two clocks.

On a TPU the device plane is ``/device:TPU:<n>``; its ``XLA Ops`` line holds
every operation the chip ran (a loop and the kernel calls inside it
overlap, so busy time is the union of the intervals). The Pallas Sinkhorn
kernel shows as ``%sinkhorn_iteration_pallas.<n>`` custom calls.
"""
from __future__ import annotations

import dataclasses
import json
import re
from typing import Callable, Iterable, List, Optional, Sequence, Tuple

ANCHOR = "chipbench.anchor"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    end_ns: float


def load_xplane(path: str) -> List[Event]:
    """Every event of an ``.xplane.pb``, on the trace's own clock."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                out.append(Event(plane.name, line.name, e.name,
                                 float(e.start_ns), float(e.end_ns)))
    return out


def load_spans(path: str, t0_perf_s: float) -> list:
    """Complete spans of a ``repro.obs`` trace file as (name, start, end,
    args), in ``perf_counter`` seconds; ``t0_perf_s`` is the writer's
    epoch."""
    spans = []
    with open(path) as f:
        for line in f:
            line = line.strip().rstrip(",")
            if not line.startswith("{"):
                continue
            ev = json.loads(line)
            if ev.get("ph") == "X" and ev.get("pid") != 2:
                s = t0_perf_s + ev["ts"] * 1e-6
                spans.append((ev["name"], s, s + ev["dur"] * 1e-6,
                              ev.get("args") or {}))
    return spans


def op_name(name: str) -> str:
    """``%sinkhorn_iteration_pallas.18 = (f32[...]) custom-call(...)`` ->
    ``sinkhorn_iteration_pallas``: the HLO instruction without its
    numbering, so a kernel's calls add up under one name."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def union(intervals: Iterable[Tuple[float, float]], lo: float,
          hi: float) -> List[Tuple[float, float]]:
    """Disjoint, sorted union of the intervals, clipped to [lo, hi]."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def gaps(busy: Sequence[Tuple[float, float]], lo: float,
         hi: float) -> List[Tuple[float, float]]:
    """The idle stretches of [lo, hi] between the busy intervals."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float
    kernel_s: dict            # op name -> summed device seconds
    top_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def reduce(events: Sequence[Event], lo_ns: float, hi_ns: float, *,
           spans: Sequence[tuple] = (),
           perf_to_trace_ns: Callable[[float], float] = lambda s: s * 1e9,
           plane: re.Pattern = DEVICE_PLANE, line: Callable[[str], bool]
           = lambda name: name == OPS_LINE, top: int = 10,
           chips: Optional[int] = None) -> Reduction:
    """Busy and idle time of the device planes in [lo_ns, hi_ns], averaged
    over the planes (one per chip), summed device time per operation, and
    the ``top`` longest idle gaps, each named by the innermost host span
    open at its midpoint (``host`` where none was). With ``chips``, only
    the planes ``/device:TPU:0`` to ``chips - 1`` count: the chips the
    cell runs on, whatever else the host holds."""
    planes = sorted({e.plane for e in events if plane.match(e.plane)})
    if chips is not None:
        ours = {f"/device:TPU:{i}" for i in range(chips)}
        planes = [p for p in planes if p in ours]
    if not planes:
        raise ValueError("the trace holds no device plane")
    busy_total, op_s = 0.0, {}
    all_gaps = []
    for p in planes:
        ops = [e for e in events if e.plane == p and line(e.line)]
        busy = union(((e.start_ns, e.end_ns) for e in ops), lo_ns, hi_ns)
        busy_total += sum(e - s for s, e in busy)
        all_gaps += gaps(busy, lo_ns, hi_ns)
        for e in ops:
            d = min(e.end_ns, hi_ns) - max(e.start_ns, lo_ns)
            if d > 0:
                op_s[op_name(e.name)] = op_s.get(op_name(e.name), 0.0) + d
    n = len(planes)
    span_ns = [(sp[0], perf_to_trace_ns(sp[1]), perf_to_trace_ns(sp[2]))
               for sp in spans]
    named = []
    for s, e in sorted(all_gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = 0.5 * (s + e)
        inner = [(ss, nm) for nm, ss, ee in span_ns if ss <= mid <= ee]
        named.append((max(inner)[1] if inner else "host", (e - s) * 1e-9))
    ops_sorted = sorted(op_s.items(), key=lambda kv: -kv[1])
    return Reduction(
        window_s=(hi_ns - lo_ns) * 1e-9,
        busy_s=busy_total / n * 1e-9,
        kernel_s={k: v / n * 1e-9 for k, v in op_s.items()},
        top_ops=[(k, v / n * 1e-9) for k, v in ops_sorted[:top]],
        idle_gaps=named)


def anchor_offset_ns(events: Sequence[Event], anchor_perf_s: float
                     ) -> Optional[float]:
    """Trace time minus ``perf_counter`` time, in ns, from the anchor."""
    starts = [e.start_ns for e in events if e.name == ANCHOR]
    return None if not starts else starts[0] - anchor_perf_s * 1e9
