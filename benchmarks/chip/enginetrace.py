"""The serving engine's own spans and step programs in a traced run.

``xtrace.Trace`` keeps the device ops and the benchmark's ``bench.*``
spans.  This module reads, from the same profile, what the engine adds:
its ``engine.*`` host spans (``jax.profiler.TraceAnnotation``, on the
device trace's clock) and the device planes' ``XLA Modules`` line, one
event per executed program, named after the jitted function
(``jit_engine_decode(<id>)``).

The profile is the newest ``*.xplane.pb`` under ``TRACE_ROOT`` whose
plane and line layout equals the ``Trace``'s own (``Trace.layout``), so
it is the file the harness reduced and no other.  Where none matches, or
the engine records no span and names no program (a program that predates
them), the readers find nothing to read.

The first time a traced run's profile is read, the traced run's output
gains the attribution these spans allow: the summed ``engine.prefill`` /
``engine.decode`` spans beside the summed ``stats()`` times of the same
steps, device time by step program and op family, idle time by the
innermost engine span open in each gap, and the ten longest gaps so
labelled.
"""
from __future__ import annotations

import bisect
import dataclasses
from pathlib import Path
from typing import Dict, List, Optional, Tuple

import work
import xtrace

# where run.py's TraceWindow writes each cell's profile (run.OUT / "trace")
TRACE_ROOT = Path(__file__).resolve().parent / ".out" / "trace"
ENGINE_PREFIX = "engine."
MODULES_LINE = "XLA Modules"

Interval = xtrace.Interval
Span = Tuple[str, int, int]          # (name, start_ns, end_ns)


@dataclasses.dataclass
class EngineTrace:
    spans: List[Span]                # engine.* spans, by start
    modules: List[xtrace.Op]         # executed programs, by start


def _load_profile(pb: Path):
    from jax.profiler import ProfileData
    return ProfileData.from_file(str(pb))


def layout(prof) -> List[Tuple[str, str, int]]:
    """(plane, line, events) of every line, as ``xtrace.Trace.layout``."""
    return [(plane.name, line.name, len(list(line.events)))
            for plane in prof.planes for line in plane.lines]


def reduce_engine(prof) -> EngineTrace:
    """The engine spans and the executed programs of a ``ProfileData``."""
    spans: List[Span] = []
    modules: List[xtrace.Op] = []
    for plane in prof.planes:
        device = plane.name.startswith(xtrace.DEVICE_PREFIX)
        for line in plane.lines:
            if device and line.name != MODULES_LINE:
                continue
            for ev in line.events:
                s = int(ev.start_ns)
                e = s + int(ev.duration_ns)
                if device:
                    modules.append(xtrace.Op(ev.name, s, e, plane.name))
                elif ev.name.startswith(ENGINE_PREFIX):
                    spans.append((ev.name, s, e))
    spans.sort(key=lambda x: x[1])
    modules.sort(key=lambda o: o.start)
    return EngineTrace(spans, modules)


_read: Dict[int, Tuple[xtrace.Trace, Optional[EngineTrace]]] = {}


def of(ctx) -> Optional[EngineTrace]:
    """The engine's spans and programs in the profile that ``ctx.trace``
    was reduced from; None where there is no such profile or it holds
    neither."""
    tr = ctx.trace
    if tr is None:
        return None
    if id(tr) in _read and _read[id(tr)][0] is tr:
        return _read[id(tr)][1]
    eng = None
    found = sorted(TRACE_ROOT.rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime, reverse=True) \
        if TRACE_ROOT.is_dir() else []
    for pb in found:
        prof = _load_profile(pb)
        if layout(prof) == list(tr.layout):
            eng = reduce_engine(prof)
            if not (eng.spans or eng.modules):
                eng = None
            break
    _read[id(tr)] = (tr, eng)
    if eng is not None:
        report(ctx, eng)
    return eng


def spans_in(spans: List[Span], name: str,
             window: Interval) -> List[Interval]:
    """The spans named ``name`` that lie wholly inside ``window``."""
    return [(s, e) for n, s, e in spans
            if n == name and window[0] <= s and e <= window[1]]


def program_name(event_name: str) -> str:
    """``jit_engine_decode(12)`` -> ``jit_engine_decode``."""
    return event_name.split("(", 1)[0]


def program_events(eng: EngineTrace, program: str,
                   window: Interval) -> List[xtrace.Op]:
    """The executions of one step program inside ``window``, on the
    first device traced."""
    devs = sorted({m.device for m in eng.modules})
    if not devs:
        return []
    return [m for m in eng.modules
            if m.device == devs[0] and program_name(m.name) == program
            and window[0] <= m.start and m.end <= window[1]]


def fetch_ms(ctx) -> Optional[float]:
    """Mean over the window's ``bench.step`` spans of the summed
    ``engine.prefill.fetch`` and ``engine.decode.fetch`` spans inside
    them, in ms."""
    eng = of(ctx)
    if eng is None:
        return None
    steps = spans_in(ctx.trace.spans, "bench.step", ctx.window)
    fetch = [iv for name in ("engine.prefill.fetch", "engine.decode.fetch")
             for iv in spans_in(eng.spans, name, ctx.window)]
    inside = [(s, e) for s, e in fetch
              if any(a <= s and e <= b for a, b in steps)]
    if not steps or not inside:
        return None
    return xtrace.total(inside) / len(steps) / 1e6


def program_mfu(ctx, phase: str, program: str) -> Optional[float]:
    """Percent of the bf16 peak that one phase's useful FLOPs reach over
    the device time of its step program's executions in the window; None
    where the trace holds no execution, or not one for each of the
    phase's calls in ``ctx.steps`` (a prefill chunk, or a step with
    decoding rows)."""
    eng = of(ctx)
    if eng is None:
        return None
    events = program_events(eng, program, ctx.window)
    if phase == "prefill":
        calls = sum(len(s.get("prefill", [])) for s in ctx.steps)
    else:
        calls = sum(1 for s in ctx.steps if s.get("decode_live"))
    secs = sum(e.end - e.start for e in events) / 1e9
    flops = sum(work.step_flops(ctx.cfg, s)[phase] for s in ctx.steps)
    if not events or len(events) != calls or secs <= 0 or flops <= 0:
        return None
    return 100.0 * flops / secs / ctx.peak["bf16_flops_per_s"]


# ------------------------------------------------------------ attribution


def top_ops_by_program(trace: xtrace.Trace, eng: EngineTrace,
                       window: Interval, n: int = 10):
    """Device time by step program and instruction family, control flow
    left out; an op runs in the program whose execution on its device
    holds it (``-`` where none does)."""
    by_dev: Dict[str, List[xtrace.Op]] = {}
    for m in eng.modules:
        by_dev.setdefault(m.device, []).append(m)
    starts = {d: [m.start for m in ms] for d, ms in by_dev.items()}
    acc: Dict[Tuple[str, str], int] = {}
    for o in trace.ops:
        if not (o.start >= window[0] and o.end <= window[1]):
            continue
        fam = xtrace.op_family(o.name)
        if fam in xtrace.CONTROL_FLOW:
            continue
        prog = "-"
        i = bisect.bisect_right(starts.get(o.device, []), o.start) - 1
        if i >= 0 and o.end <= by_dev[o.device][i].end:
            prog = program_name(by_dev[o.device][i].name)
        acc[prog, fam] = acc.get((prog, fam), 0) + (o.end - o.start)
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[p, f, v / 1e9] for (p, f), v in ranked]


def innermost(spans: List[Span], times: List[int]) -> List[Optional[str]]:
    """The name of the innermost of the nested ``(name, start, end)``
    spans open at each of the ascending ``times``, or None."""
    spans = sorted(spans, key=lambda x: (x[1], -x[2]))
    out, stack, i = [], [], 0
    for t in times:
        while i < len(spans) and spans[i][1] <= t:
            while stack and stack[-1][2] <= spans[i][1]:
                stack.pop()
            stack.append(spans[i])
            i += 1
        while stack and stack[-1][2] <= t:
            stack.pop()
        out.append(stack[-1][0] if stack else None)
    return out


def gap_labels(trace: xtrace.Trace, eng: EngineTrace,
               times: List[int]) -> List[str]:
    """The label of an idle moment at each of the ascending ``times``: the
    innermost engine span open then, less the ``engine.`` prefix
    (``prefill.fetch``), or where none is open the benchmark span open
    then, as ``xtrace.idle_gaps`` labels it."""
    inner = innermost(eng.spans, times)
    bench = innermost([x for x in trace.spans if x[0] in xtrace.SPAN_LABELS],
                      times)
    return [e[len(ENGINE_PREFIX):] if e else
            xtrace.SPAN_LABELS[b] if b else "bookkeeping"
            for e, b in zip(inner, bench)]


def gaps(trace: xtrace.Trace, window: Interval) -> List[Interval]:
    """The gaps between device ops in ``window`` on the first device."""
    devs = sorted({o.device for o in trace.ops})
    if not devs:
        return []
    iv = xtrace.busy(trace, window, devs[0])
    edges = [window[0]] + [x for se in iv for x in se] + [window[1]]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def idle_gaps(trace: xtrace.Trace, eng: EngineTrace, window: Interval,
              n: int = 10):
    """``xtrace.idle_gaps``, each gap labelled as ``gap_labels`` says."""
    longest = sorted(gaps(trace, window), key=lambda g: g[0] - g[1])[:n]
    mids = [(s + e) // 2 for s, e in longest]
    order = sorted(range(len(mids)), key=mids.__getitem__)
    labels = dict(zip(order, gap_labels(trace, eng,
                                        [mids[i] for i in order])))
    return [[labels[i], (e - s) / 1e9] for i, (s, e) in enumerate(longest)]


def idle_by_label(trace: xtrace.Trace, eng: EngineTrace, window: Interval):
    """Idle seconds and gap counts in ``window`` (first device) by the
    label of each gap's midpoint, most first."""
    gs = gaps(trace, window)
    acc: Dict[str, List[float]] = {}
    for (s, e), label in zip(gs, gap_labels(
            trace, eng, [(s + e) // 2 for s, e in gs])):
        a = acc.setdefault(label, [0.0, 0])
        a[0] += (e - s) / 1e9
        a[1] += 1
    return sorted(([k, v[0], v[1]] for k, v in acc.items()),
                  key=lambda x: -x[1])


def report(ctx, eng: EngineTrace) -> None:
    """Print the attribution of a traced run (see the module docstring)."""
    for ph in ("prefill", "decode"):
        spans = spans_in(eng.spans, f"engine.{ph}", ctx.window)
        print(f"engine.{ph} spans: {len(spans)}, "
              f"{xtrace.total(spans) / 1e6} ms; stats {ph}_ms over the "
              f"traced steps: {sum(s[f'{ph}_ms'] for s in ctx.steps)} ms",
              flush=True)
    print("device time by step program and op family (s): "
          f"{top_ops_by_program(ctx.trace, eng, ctx.window, 16)}",
          flush=True)
    print("idle time by label (s, gaps): "
          f"{idle_by_label(ctx.trace, eng, ctx.window)}", flush=True)
    print("longest idle gaps by engine label (s): "
          f"{idle_gaps(ctx.trace, eng, ctx.window)}", flush=True)
