"""Reduce a JAX profiler trace to the benchmark's device numbers.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with nothing but
``jax.profiler.ProfileData``:

* busy time: the union of the intervals in which an operation ran on a
  device (the ``XLA Ops`` line of each ``/device:TPU:<i>`` plane),
  clipped to the traced window and averaged over the devices;
* device time per operation, by the operation's HLO name without its
  instance number (``eager_accumulate``, ``add``, ``sub``, ...);
* idle gaps: the stretches of the window in which no operation ran on
  the first device, each named by the two innermost host spans open at
  its midpoint on the thread that drives the rounds (the thread whose
  line holds the harness's ``bench.window`` span; with JAX's Python
  tracer on, its spans are the program's own functions), and summed
  by name.

The window is that ``bench.window`` span, which the harness opens and
closes around the measured stretch.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass
from typing import Dict, Iterable, List, Sequence, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
_INSTANCE = re.compile(r"\.\d+$")

Interval = Tuple[float, float]


@dataclass
class TraceData:
    """What the reduction needs, pulled out of the profile once."""

    window: Interval
    device_ops: List[List[Tuple[str, float, float]]]   # per device
    host_names: List[str]          # the driving thread's spans
    host_start: List[float]
    host_end: List[float]


@dataclass
class Reduced:
    window_s: float
    busy_s: float                               # mean over devices
    op_s: Dict[str, float]                      # summed over devices
    op_calls: Dict[str, int]
    idle_gaps: List[Tuple[str, float]]          # by total length

    def kernel(self, name: str) -> Tuple[int, float]:
        """(calls, device seconds) of the operation ``name``."""
        return self.op_calls.get(name, 0), self.op_s.get(name, 0.0)

    def breakdown(self, top: int = 10) -> Dict[str, List[list]]:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in self.idle_gaps[:top]]}


def op_name(hlo: str) -> str:
    """``%eager_accumulate.1 = f32[...] custom-call(...)`` ->
    ``eager_accumulate``."""
    head = hlo.split(" = ", 1)[0].strip().lstrip("%")
    return _INSTANCE.sub("", head)


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str) -> TraceData:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    window = None
    devices: List[List[Tuple[str, float, float]]] = []
    thread: List[Tuple[str, float, float]] = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((op_name(ev.name), ev.start_ns, ev.end_ns)
                               for ev in line.events)
            devices.append(ops)
        elif plane.name.startswith("/host:") and window is None:
            for line in plane.lines:
                evs = [(ev.name, ev.start_ns, ev.end_ns)
                       for ev in line.events]
                hit = next((e for e in evs if e[0] == WINDOW_SPAN), None)
                if hit is not None:
                    window = (hit[1], hit[2])
                    thread = [e for e in evs if e[0] != WINDOW_SPAN]
                    break
    if window is None:
        raise ValueError(f"no {WINDOW_SPAN!r} span in {path}")
    if not devices:
        raise ValueError(f"no {DEVICE_PREFIX}* plane in {path}")
    return TraceData(window=window, device_ops=devices,
                     host_names=[e[0] for e in thread],
                     host_start=[e[1] for e in thread],
                     host_end=[e[2] for e in thread])


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _clip(iv: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def name_gaps(gaps: Sequence[Interval], data: TraceData) -> List[str]:
    """Name each gap ``outer > inner`` by the two innermost host spans
    open at its midpoint (``host idle`` where none is).  One sweep over
    the spans, which nest on one thread: the innermost open span is the
    top of a stack."""
    order = sorted(range(len(data.host_names)),
                   key=lambda i: (data.host_start[i], -data.host_end[i]))
    names: List[str] = []
    stack: List[int] = []
    k = 0
    for a, b in gaps:
        mid = 0.5 * (a + b)
        while k < len(order) and data.host_start[order[k]] <= mid:
            i = order[k]
            while stack and data.host_end[stack[-1]] <= data.host_start[i]:
                stack.pop()
            stack.append(i)
            k += 1
        while stack and data.host_end[stack[-1]] <= mid:
            stack.pop()
        if not stack:
            names.append("host idle")
            continue
        inner = [data.host_names[i].lstrip("$") for i in stack[-2:]]
        names.append(" > ".join(inner))
    return names


def reduce(data: TraceData) -> Reduced:
    lo, hi = data.window
    busy = []
    op_s: Dict[str, float] = defaultdict(float)
    op_calls: Dict[str, int] = defaultdict(int)
    gaps: List[Interval] = []
    for i, ops in enumerate(data.device_ops):
        inside = [(n, a, b) for n, a, b in ops if b > lo and a < hi]
        for n, a, b in inside:
            op_s[n] += (min(b, hi) - max(a, lo)) / 1e9
            op_calls[n] += 1
        u = _clip(union((a, b) for _n, a, b in inside), lo, hi)
        busy.append(sum(b - a for a, b in u) / 1e9)
        if i == 0:
            edges = [lo] + [x for iv in u for x in iv] + [hi]
            gaps = [(edges[j], edges[j + 1]) for j in range(0, len(edges), 2)
                    if edges[j + 1] > edges[j]]
    named: Dict[str, float] = defaultdict(float)
    for g, name in zip(gaps, name_gaps(gaps, data)):
        named[name] += (g[1] - g[0]) / 1e9
    return Reduced(window_s=(hi - lo) / 1e9,
                   busy_s=sum(busy) / len(busy),
                   op_s=dict(op_s), op_calls=dict(op_calls),
                   idle_gaps=sorted(named.items(), key=lambda kv: -kv[1]))
