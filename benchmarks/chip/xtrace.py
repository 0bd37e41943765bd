"""From a profiler trace to the numbers the per-layer metrics read.

The JAX profiler writes ``<dir>/plugins/profile/<run>/<host>.xplane.pb``;
``jax.profiler.ProfileData`` reads it.  Device planes are named
``/device:TPU:<n>``; their ``XLA Ops`` line holds one event per executed
HLO instruction, named by the instruction's text
(``%logicsparse_quant_matmul.3 = bf16[16,512] custom-call(...)``); control
flow (``while``, ``conditional``, ``call``) appears as an event spanning
its body's events.  A Pallas kernel's instruction is named after the
kernel, so a kernel's time is the summed duration of the events of the
instructions named ``<kernel>`` or ``<kernel>.<n>``.  Host
planes hold the benchmark's own spans (``jax.profiler.TraceAnnotation``),
on the same clock.
"""
from __future__ import annotations

import dataclasses
import re
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Tuple

DEVICE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
CONTROL_FLOW = ("while", "conditional", "call")
_INSTR = re.compile(r"^%?([^\s=]+)")

Interval = Tuple[int, int]   # (start_ns, end_ns)


@dataclasses.dataclass
class Op:
    name: str
    start: int
    end: int
    device: str


@dataclasses.dataclass
class Trace:
    ops: List[Op]
    spans: List[Tuple[str, int, int]]   # (name, start_ns, end_ns)
    n_devices: int
    layout: List[Tuple[str, str, int]] = dataclasses.field(
        default_factory=list)            # (plane, line, events)


def _load_profile(pb: Path):
    from jax.profiler import ProfileData
    return ProfileData.from_file(str(pb))


def find_xplane(trace_dir: Path) -> Optional[Path]:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    return found[-1] if found else None


def reduce_profile(prof) -> Trace:
    """Device ops and benchmark spans of a ``ProfileData``."""
    ops: List[Op] = []
    spans: List[Tuple[str, int, int]] = []
    devices = set()
    layout = []
    for plane in prof.planes:
        pname = plane.name
        for line in plane.lines:
            layout.append((pname, line.name, len(list(line.events))))
        if pname.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                devices.add(pname)
                for ev in line.events:
                    s = int(ev.start_ns)
                    ops.append(Op(instruction(ev.name), s,
                                  s + int(ev.duration_ns), pname))
        else:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        s = int(ev.start_ns)
                        spans.append((ev.name, s, s + int(ev.duration_ns)))
    ops.sort(key=lambda o: o.start)
    spans.sort(key=lambda x: x[1])
    return Trace(ops, spans, len(devices), layout)


def instruction(event_name: str) -> str:
    """``%name.3 = type op(...)`` -> ``name.3``."""
    m = _INSTR.match(event_name)
    return m.group(1) if m else event_name


def load(trace_dir: Path) -> Optional[Trace]:
    pb = find_xplane(trace_dir)
    return None if pb is None else reduce_profile(_load_profile(pb))


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals: Iterable[Interval]) -> int:
    return sum(e - s for s, e in intervals)


def intersect(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Intersection of two sorted, disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def busy(trace: Trace, window: Optional[Interval] = None,
         device: Optional[str] = None) -> List[Interval]:
    iv = union((o.start, o.end) for o in trace.ops
               if device is None or o.device == device)
    return iv if window is None else intersect(iv, [window])


def busy_seconds_per_device(trace: Trace, window: Interval) -> float:
    """Busy seconds in ``window``, averaged over the devices traced."""
    devs = sorted({o.device for o in trace.ops})
    if not devs:
        return 0.0
    return sum(total(busy(trace, window, d)) for d in devs) / len(devs) / 1e9


def kernel_seconds(trace: Trace, kernel: str,
                   window: Optional[Interval] = None) -> float:
    """Summed device time of the events of one kernel."""
    pat = re.compile(re.escape(kernel) + r"(\.\d+)?$")
    t = 0
    for o in trace.ops:
        if pat.match(o.name) and (window is None or
                                  (o.start >= window[0]
                                   and o.end <= window[1])):
            t += o.end - o.start
    return t / 1e9


def span_union(trace: Trace, name: str) -> List[Interval]:
    return union((s, e) for n, s, e in trace.spans if n == name)


def op_family(name: str) -> str:
    """An instruction name without its numeric suffix."""
    return re.sub(r"\.\d+$", "", name)


def top_ops(trace: Trace, window: Interval, n: int = 10):
    """Device time by instruction family, control flow left out (its
    events span their bodies' events)."""
    acc: Dict[str, int] = {}
    for o in trace.ops:
        if o.start >= window[0] and o.end <= window[1]:
            k = op_family(o.name)
            if k in CONTROL_FLOW:
                continue
            acc[k] = acc.get(k, 0) + (o.end - o.start)
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in ranked]


SPAN_LABELS = {"bench.step": "engine step", "bench.wait":
               "waiting for an arrival"}


def idle_gaps(trace: Trace, window: Interval, n: int = 10):
    """The longest gaps between device ops in ``window`` (first device),
    each labelled by the benchmark span open at its midpoint."""
    devs = sorted({o.device for o in trace.ops})
    if not devs:
        return []
    iv = busy(trace, window, devs[0])
    edges = [window[0]] + [x for se in iv for x in se] + [window[1]]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:n]:
        mid = (s + e) // 2
        label = "bookkeeping"
        for name, ss, se in trace.spans:
            if ss <= mid < se and name in SPAN_LABELS:
                label = SPAN_LABELS[name]
                break
        out.append([label, (e - s) / 1e9])
    return out
