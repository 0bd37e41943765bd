#!/usr/bin/env python3
"""One run of one cell of the chip benchmark.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine with the chips the cell
asks for.  One process, one cell, once:

1. reads the cell from ``BENCHMARK.json`` and its configuration, traffic
   mix and limits from the files named after them (``cellspec.py``);
2. refuses to run (non-zero exit, no result) without a TPU;
3. sets ``REPRO_DISPATCH_STRICT=1`` and the program's compile cache;
4. makes the weights on the device from ``--seed`` (``weights.py``),
   compresses them with the program's ``compile_model`` under the
   configuration's rules, and builds a ``ServeEngine``;
5. warms up through the engine's own ``submit`` and ``step``: one
   request alone at each power of two that the traffic's cached lengths
   cross, the longest prompt, then one request in every slot; every
   program JAX compiles or loads meanwhile is written out as text, and
   each step program must hold the configuration's kernels;
6. drives the engine for ``--seconds`` with the cell's traffic
   (``loadgen.py``), stamping every token as it appears; a run in which
   a program is compiled or loaded inside the window is refused;
7. after the window, frees the engine and replays a sample of finished
   requests through the plain reference (``references/``), and prints
   the result line.

Everything before the window is ``setup_s``.  With ``--trace 1`` the
first ``TRACE_SECONDS`` of the window are traced with the JAX profiler,
and the result carries the cell's per-layer metrics (``metrics/``).
With ``--control fp8`` the lower-precision control is judged against the
same limits, and the result says whether it passed (``control_correct``).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from contextlib import nullcontext  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
if str(HERE) not in sys.path:
    sys.path.insert(0, str(HERE))

import cellspec  # noqa: E402
import loadgen  # noqa: E402
import xtrace  # noqa: E402

OUT = HERE / ".out"
TRACE_SECONDS = 8.0       # traced part of the window in a --trace 1 run
HF_TO_ARCH = {"hidden_size": "d_model", "intermediate_size": "d_ff",
              "num_attention_heads": "n_heads",
              "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
              "num_hidden_layers": "n_layers", "vocab_size": "vocab",
              "rope_theta": "rope_theta", "qkv_bias": "qkv_bias",
              "tie_word_embeddings": "tie_embeddings", "norm": "norm",
              "torch_dtype": "param_dtype"}
ACTS = {"silu": "swiglu", "gelu_pytorch_tanh": "gelu"}
# fires once for every program compiled or read from the compile cache
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
                "/jax/compilation_cache/cache_misses": "misses"}


class Refused(SystemExit):
    """A run that cannot give a result: non-zero exit, no result line."""

    def __init__(self, msg: str):
        print(f"run: {msg}", file=sys.stderr, flush=True)
        super().__init__(2)


def say(msg: str) -> None:
    print(msg, flush=True)


def percentile(xs, q: float):
    """Linear-interpolated percentile (numpy's default), None if empty."""
    if not xs:
        return None
    s = sorted(xs)
    k = (len(s) - 1) * q / 100.0
    lo = math.floor(k)
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (k - lo)


# ------------------------------------------------------------------ set-up


def import_program(root: Path):
    src = root / "src"
    if not (src / "repro" / "serve" / "engine.py").is_file():
        raise Refused(f"no program under {src}: run from a checkout")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))


def arch_config(cfgj: dict):
    """The program's registered config, checked against the file and cut
    as the file's ``reduced`` says."""
    from repro.configs import get_config
    base = get_config(cfgj["arch"])
    want = {a: cfgj[h] for h, a in HF_TO_ARCH.items()}
    want["act"] = ACTS[cfgj["hidden_act"]]
    cut = {HF_TO_ARCH[k] for k in cfgj["reduced"] if k in HF_TO_ARCH}
    diff = {k: (getattr(base, k), v) for k, v in want.items()
            if k not in cut and getattr(base, k) != v}
    if diff:
        raise Refused(f"the program's {cfgj['arch']} config differs from "
                      f"the benchmark's file (program, file): {diff}")
    return dataclasses.replace(base, **{k: want[k] for k in cut})


class CompileCounter:
    """Programs compiled or loaded, and persistent-cache hits and misses."""

    def __init__(self, jax):
        self.n = 0
        self.cache = {"hits": 0, "misses": 0}
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event, duration, **kw):
        if event == BACKEND_COMPILE:
            self.n += 1

    def _on_event(self, event, **kw):
        if event in CACHE_EVENTS:
            self.cache[CACHE_EVENTS[event]] += 1


def build(cfgj: dict, mix: dict, seed: int):
    """Weights, compressed model and engine; returns (engine, cfg, shapes)."""
    import jax
    from repro.core.compile_sparse import CompileRules, compile_model
    from repro.models.model import init_params
    from repro.serve.engine import ServeEngine

    import weights
    cfg = arch_config(cfgj)
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    rules = dict(cfgj["compile_rules"])
    rules["block"] = tuple(rules.get("block", (128, 128)))
    t0 = time.perf_counter()
    raw = weights.make(seed, shapes, rules["block"],
                       rules.get("block_density", 0.25), cfgj["policy"])
    jax.block_until_ready(raw)
    say(f"phase weights: {time.perf_counter() - t0:.3f} s")
    t0 = time.perf_counter()
    cm = compile_model(raw, cfg, rules=CompileRules(**rules))
    del raw
    say(f"phase compile_model: {time.perf_counter() - t0:.3f} s")
    bad = {r.name: r.policy for r in cm.report if r.policy != cfgj["policy"]}
    if bad:
        raise Refused(f"compile_model chose other policies than the "
                      f"configuration's {cfgj['policy']!r}: {bad}")
    serve = cfgj["serve"]
    engine = ServeEngine(cm, cfg, batch_slots=mix["batch_slots"],
                         max_len=mix["max_len"], kv_cache=serve["kv_cache"],
                         prefill_chunk=serve["prefill_chunk"],
                         dispatch=serve["dispatch"])
    return engine, cfg, shapes


KERNEL_NAME = re.compile(r'kernel_name = "([^"]+)"')


class ProgramDump:
    """While entered, JAX writes every program it compiles or reads from
    the compile cache to ``out`` as StableHLO text; ``new_kernels`` gives
    the Pallas kernel names of each program written since its last call."""

    def __init__(self, jax, out: Path):
        self.jax, self.out, self.seen = jax, out, set()

    def __enter__(self):
        shutil.rmtree(self.out, ignore_errors=True)
        self.out.mkdir(parents=True)
        self.jax.config.update("jax_include_debug_info_in_dumps", False)
        self.jax.config.update("jax_dump_ir_to", str(self.out))
        return self

    def __exit__(self, *exc):
        self.jax.config.update("jax_dump_ir_to", "")
        shutil.rmtree(self.out, ignore_errors=True)

    def new_kernels(self):
        out = []
        for path in sorted(self.out.iterdir()):
            if path.name not in self.seen:
                self.seen.add(path.name)
                out.append(set(KERNEL_NAME.findall(path.read_text())))
        return out


def warm_plan(mix: dict, slots: int, max_len: int):
    """(prompt length, output budget) of the warm-up requests: those sent
    one at a time, then those sent together.

    The engine buckets the cache extent a step reads.  One request alone
    at each power of two that the traffic's cached lengths cross, cached
    from two positions below it to two above, reaches every bucket of a
    power-of-two bucketing; the longest prompt reaches every prefill
    chunk position.  Then one shortest request in each slot."""
    pmin, pmax, omax = loadgen.extents(mix)
    alone = {(pmin, 2), (pmax, 2)}
    b = 1
    while b <= pmax + omax:
        if b > pmin:
            p = min(max(b - 2, pmin), pmax)
            alone.add((p, max(2, min(b + 2 - p, max_len + 1 - p))))
        b *= 2
    return sorted(alone), [(pmin, 2)] * slots


def warm(engine, cfgj: dict, mix: dict, Request, vocab: int, out: Path):
    """Compile or load every step program the traffic can reach, through
    the engine's public ``submit`` and ``step``, and check that each step
    program holds the kernels of the phase that first ran it."""
    import jax
    import numpy as np
    want = cfgj["kernels"]
    alone, together = warm_plan(mix, engine.slots, engine.max_len)
    rng = np.random.default_rng(0)
    seen = {ph: 0 for ph in want}
    missing, uid, n_prog = [], -1, 0
    with ProgramDump(jax, out) as dump:
        for batch in [[r] for r in alone] + [together]:
            for p, n in batch:
                engine.submit(Request(uid=uid, prompt=rng.integers(
                    1, vocab, p, dtype=np.int32), max_new_tokens=n))
                uid -= 1
            while engine.queue or engine.active:
                st0 = engine.stats()
                engine.step()
                st1 = engine.stats()
                ran = [ph for ph in want
                       if st1[f"{ph}_steps"] > st0[f"{ph}_steps"]]
                new = dump.new_kernels()
                n_prog += len(new)
                # eager ops hold no kernel; a step program holds some
                progs = [k for k in new if k]
                if not progs or not ran:
                    continue
                # a step that ran both phases is held to what both need
                need = set.intersection(*(set(want[ph]) for ph in ran))
                phase = "+".join(ran)
                for ks in progs:
                    say(f"kernels in a new {phase} step program (warm-up "
                        f"request of {batch[0][0]} + {batch[0][1]} "
                        f"tokens): {sorted(ks)}")
                    missing += [(phase, k) for k in sorted(need - ks)]
                    if len(ran) == 1:
                        seen[ran[0]] += 1
    missing += [(ph, "no step program holds them") for ph, n in seen.items()
                if want[ph] and not n]
    if missing:
        raise Refused(f"step programs lack kernels: {missing}")
    return n_prog


# ------------------------------------------------------------------ window


@dataclasses.dataclass
class Flight:
    item: loadgen.Item
    req: object
    due: float                # absolute perf_counter time
    submitted: float
    stamps: list = dataclasses.field(default_factory=list)

    @property
    def finished(self) -> bool:
        return len(self.req.out) >= self.item.max_new


class Driver:
    """Sends the traffic, steps the engine, and records every step and
    every token.

    A step's work is read from the engine's own state around the step:
    ``active`` (slot -> request) and ``prompt_pos`` (slot -> prompt tokens
    prefilled).  A request in decode has its whole prompt prefilled; a
    request whose prompt position moved was prefilled by the step."""

    def __init__(self, engine, Request, jax_profiler=None):
        self.engine = engine
        self.Request = Request
        self.prof = jax_profiler
        self.flights = []          # every submitted request, in order
        self.live = []             # submitted, not finished
        self.steps = []
        self.failed = 0
        self.tracing = False

    def span(self, name):
        if self.tracing:
            return self.prof.TraceAnnotation(name)
        return nullcontext()

    def submit(self, item: loadgen.Item, due: float) -> None:
        req = self.Request(uid=item.uid, prompt=item.prompt,
                           max_new_tokens=item.max_new)
        now = time.perf_counter()
        try:
            self.engine.submit(req)
        except ValueError as e:
            self.failed += 1
            say(f"refused request {item.uid}: {e}")
            return
        f = Flight(item, req, due, now)
        self.flights.append(f)
        self.live.append(f)

    def positions(self):
        """Prompt tokens prefilled, by request, as the engine holds them."""
        eng = self.engine
        return {id(r): eng.prompt_pos[s] for s, r in eng.active.items()}

    def step(self):
        """One engine step; returns the flights it finished."""
        eng = self.engine
        st0 = eng.stats()
        pos0 = self.positions()
        decode_live = [len(r.prompt) + len(r.out)
                       for s, r in eng.active.items()
                       if eng.prompt_pos[s] >= len(r.prompt)]
        with self.span("bench.step"):
            t0 = time.perf_counter()
            eng.step()
            t1 = time.perf_counter()
        st1 = eng.stats()
        pos1 = self.positions()
        chunks = []
        for f in self.live:
            r = f.req
            a = pos0.get(id(r), 0)
            b = pos1.get(id(r), len(r.prompt) if f.finished else 0)
            if b > a:
                chunks.append([a, b - a, b >= len(r.prompt)])
        rec = {"t0": t0, "t1": t1, "span_ms": (t1 - t0) * 1e3,
               "prefill_ms": sum(st1["prefill_ms"][len(st0["prefill_ms"]):]),
               "decode_ms": sum(st1["decode_ms"][len(st0["decode_ms"]):]),
               "prefill": chunks, "decode_live": decode_live,
               "prefill_rows": st1["prefill_tokens"] - st0["prefill_tokens"],
               "decode_rows": st1["decode_tokens"] - st0["decode_tokens"]}
        rec["agrees"] = (rec["prefill_rows"] == sum(c[1] for c in chunks)
                         and rec["decode_rows"] == len(decode_live))
        self.steps.append(rec)
        done = []
        for f in self.live:
            n_new = len(f.req.out) - len(f.stamps)
            if n_new > 0:
                f.stamps.extend([t1] * n_new)
            if f.finished:
                done.append(f)
        if done:
            self.live = [f for f in self.live if not f.finished]
        return done

    def wait_until(self, t: float) -> None:
        with self.span("bench.wait"):
            dt = t - time.perf_counter()
            if dt > 0:
                time.sleep(dt)


class TraceWindow:
    """Starts the profiler at the window's open and stops it after
    ``seconds``; the traced part is wrapped in a ``bench.window`` span."""

    def __init__(self, drv: Driver, on: bool, seconds: float, out: Path):
        self.drv, self.on, self.seconds, self.out = drv, on, seconds, out
        self.t_start = self.t_stop = None
        self._ann = None

    def poll(self, now: float) -> None:
        if not self.on:
            return
        if self.t_start is None:
            shutil.rmtree(self.out, ignore_errors=True)
            self.drv.prof.start_trace(str(self.out))
            self._ann = self.drv.prof.TraceAnnotation("bench.window")
            self._ann.__enter__()
            self.drv.tracing = True
            self.t_start = time.perf_counter()
        elif self.t_stop is None and now >= self.t_start + self.seconds:
            self.stop()

    def stop(self) -> None:
        if self.on and self.t_start is not None and self.t_stop is None:
            self.t_stop = time.perf_counter()
            self._ann.__exit__(None, None, None)
            self.drv.tracing = False
            self.drv.prof.stop_trace()


def run_open(drv: Driver, mix: dict, seed: int, seconds: float, vocab: int,
             tw: TraceWindow, rate=None):
    items = loadgen.open_schedule(mix, seed, seconds, vocab, rate=rate)
    ramp = float(mix.get("ramp_s", 0.0))
    t_open = time.perf_counter() + ramp
    end = t_open + seconds
    i, n = 0, len(items)
    eng = drv.engine
    while True:
        now = time.perf_counter()
        if now >= end:
            break
        if now >= t_open:
            tw.poll(now)
        while i < n and t_open + items[i].due <= now:
            drv.submit(items[i], t_open + items[i].due)
            i += 1
        if eng.queue or eng.active:
            drv.step()
        else:
            nxt = t_open + items[i].due if i < n else end
            drv.wait_until(min(nxt, end, t_open) if now < t_open
                           else min(nxt, end))
    tw.stop()
    return t_open, end


def run_closed(drv: Driver, mix: dict, seed: int, seconds: float,
               vocab: int, tw: TraceWindow):
    """Each client sends its next request when its last one finishes.
    The window opens once every client's first request has its first
    token: the start-up prefill backlog is then drained."""
    clients = loadgen.closed_clients(mix, seed, vocab)
    nxt = [0] * len(clients)

    def send(c: int, now: float):
        seq = clients[c]
        drv.submit(seq[nxt[c] % len(seq)], now)
        nxt[c] += 1

    now = time.perf_counter()
    for c in range(len(clients)):
        send(c, now)
    firsts = list(drv.flights)
    t_open = end = None
    while True:
        now = time.perf_counter()
        if t_open is None and all(f.stamps for f in firsts):
            t_open, end = now, now + seconds
        if end is not None and now >= end:
            break
        if t_open is not None:
            tw.poll(now)
        for f in drv.step():
            send(f.item.client, f.stamps[-1])
    tw.stop()
    return t_open, end


# ----------------------------------------------------------------- results


def window_metrics(drv: Driver, t_open: float, end: float, seconds: float):
    toks = 0
    ttft, itl, late = [], [], []
    for f in drv.flights:
        inside = [t for t in f.stamps if t_open <= t < end]
        toks += len(inside)
        itl += [b - a for a, b in zip(inside, inside[1:])]
        if t_open <= f.due < end:
            late.append(f.submitted - f.due)
            first = f.stamps[0] if f.stamps and f.stamps[0] < end else end
            ttft.append(first - f.due)
    return {"tokens": toks, "tokens_per_s": toks / seconds,
            "ttft_s": ttft, "itl_s": itl, "lateness_s": late,
            "attempted": len(late)}


def choose_sample(drv: Driver, mix: dict, seed: int):
    """Finished requests, drawn from the seed, the longest among them;
    as (prompt, served tokens, budget) triples."""
    import numpy as np
    done = [f for f in drv.flights if f.finished]
    if not done:
        return []
    want = mix["sample"]
    longest = max(done, key=lambda f: len(f.item.prompt) + len(f.req.out))
    # each request's place in the draw depends on the seed and its own id
    # only, so one more or one fewer finished request leaves the others'
    # order as it was
    s = int(seed) % (1 << 64)
    order = sorted(done, key=lambda f: np.random.default_rng(
        [s, 7, f.item.uid]).random())
    pick, n_tok = [longest], len(longest.req.out)
    for f in order:
        if n_tok >= want["min_tokens"] or len(pick) >= want["max_requests"]:
            break
        if f is not longest:
            pick.append(f)
            n_tok += len(f.req.out)
    return [(f.item.prompt, list(f.req.out), f.item.max_new) for f in pick]


def judge(sample, readings: dict, lim: dict, tag: str = ""):
    """The numbers compared, each with its limit, and whether all hold: a
    run is also not correct where a sampled request came back short of
    its budget or no request finished."""
    compared = {k: {"value": readings.get(tag + k), "limit": v}
                for k, v in lim.items()}
    short = [i for i, (_, out, budget) in enumerate(sample)
             if len(out) != budget]
    ok = bool(sample) and bool(compared) and not short and all(
        c["value"] is not None and c["value"] <= c["limit"]
        for c in compared.values())
    return compared, ok


def check(sample, cfgj, shapes, seed, control, base: Path):
    """Replay the sample through the reference; returns its readings.
    With ``control``, the control's readings too, under
    ``control_<name>_``."""
    import numpy as np

    import weights
    if not sample:
        return {"served_tokens": 0, "requests": 0}
    ref = cellspec.reference(cfgj["reference"], base)
    rules = cfgj["compile_rules"]
    raw = weights.make(seed, shapes, tuple(rules.get("block", (128, 128))),
                       rules.get("block_density", 0.25), cfgj["policy"])
    seqs, starts, served = [], [], []
    for prompt, out, _ in sample:
        out = np.asarray(out, np.int32)
        seqs.append(np.concatenate([prompt, out[:-1]]))
        starts.append(len(prompt) - 1)
        served.append(out)
    variants = ("ref", control) if control else ("ref",)
    g = ref.gaps(raw, cfgj, seqs, starts, served, variants=variants)
    del raw
    readings = {"served_tokens": int(sum(len(s) for s in served)),
                "requests": len(sample)}
    for v in variants:
        allg = np.concatenate(g[v])
        tag = "" if v == "ref" else f"control_{v}_"
        readings[tag + "max_gap"] = float(allg.max())
        readings[tag + "mean_gap"] = float(allg.mean())
    return readings


def bounded_share(metric: dict, value: float) -> float:
    """A roofline or peak share cannot pass 100%: raise where it does."""
    if metric["unit"] == "%" and value > 100.0 and (
            "roofline" in metric["name"] or "mfu" in metric["name"]):
        raise ValueError(f"{metric['name']} reads {value}% (> 100%): the "
                         "work is counted too high or the time misses work")
    return value


class LayerCtx:
    """What a per-layer metric reader gets."""

    def __init__(self, cfg, peak, steps, trace, window):
        self.cfg, self.peak = cfg, peak
        self.steps, self.trace, self.window = steps, trace, window

    def kernel_seconds(self, kernel: str):
        if self.trace is None:
            return None
        return xtrace.kernel_seconds(self.trace, kernel, self.window)


def traced(drv, tw, cfgj, peak, bench, cell_name, base: Path):
    """Per-layer metrics, device busy/window seconds and the breakdown."""
    tr = xtrace.load(tw.out)
    if tr is None:
        raise Refused(f"no trace written under {tw.out}")
    wins = xtrace.span_union(tr, "bench.window")
    window = wins[0] if wins else (tr.ops[0].start, tr.ops[-1].end)
    steps = [s for s in drv.steps
             if s["t0"] >= tw.t_start and s["t1"] <= tw.t_stop]
    ctx = LayerCtx(cfgj, peak, steps, tr, window)
    metrics = {}
    for m in cellspec.per_layer_metrics(bench, cell_name):
        v = cellspec.metric_reader(m["name"], base)(ctx)
        if v is None:
            say(f"per-layer metric {m['name']}: nothing to read")
            continue
        metrics[m["name"]] = {"value": bounded_share(m, v),
                              "unit": m["unit"]}
    device = {"busy_s": xtrace.busy_seconds_per_device(tr, window),
              "window_s": (window[1] - window[0]) / 1e9}
    breakdown = {"device_ops": xtrace.top_ops(tr, window),
                 "idle_gaps": xtrace.idle_gaps(tr, window)}
    for plane, line, n in tr.layout:
        if n:
            say(f"trace plane {plane!r} line {line!r}: {n} events")
    say(f"traced window: {device['window_s']} s, {len(steps)} steps, "
        f"{len(tr.ops)} device ops, busy {device['busy_s']} s")
    return metrics, device, breakdown


# -------------------------------------------------------------------- main


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=("fp8",), default=None,
                    help="also judge the lower-precision control against "
                    "the same limits (control_correct); the benchmark's "
                    "runs leave it off")
    return ap.parse_args(argv)


def main(argv=None, root: Path = cellspec.ROOT, base: Path = HERE,
         require_tpu: bool = True):
    """``root`` is the checkout, ``base`` the benchmark's directory; tests
    give other ones and ``require_tpu=False``."""
    args = parse(argv)
    bench = cellspec.benchmark(root)
    wl = cellspec.cell(bench, args.workload)
    cfgj = cellspec.config(wl["config"], base)
    mix = cellspec.traffic(wl["traffic"], base)
    lim = cellspec.limits(args.workload, base)
    import_program(root)
    os.environ["REPRO_DISPATCH_STRICT"] = "1"

    import jax
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu"
                        or len(devs) < wl["chips"]):
        raise Refused(f"needs {wl['chips']} TPU chip(s); JAX has "
                      f"{len(devs)} {devs[0].platform} device(s)")
    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs)}
    say(f"device: {json.dumps(device)}")
    peak = cellspec.peak(dev.device_kind) if require_tpu else \
        cellspec.peak("TPU v5 lite")
    from repro.launch.compile_cache import enable_compile_cache
    from repro.serve.engine import Request
    cache_dir = enable_compile_cache()
    # every program, however quick to compile, goes into the cache: a
    # warm run then compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    say(f"compile cache: {cache_dir}")
    cc = CompileCounter(jax)

    engine, cfg, shapes = build(cfgj, mix, args.seed)
    n0 = cc.n
    t0 = time.perf_counter()
    n_dumped = warm(engine, cfgj, mix, Request, cfg.vocab,
                    OUT / "programs" / args.workload)
    say(f"phase warm-up: {time.perf_counter() - t0:.3f} s, {cc.n - n0} "
        f"programs compiled or read from the compile cache ({n_dumped} "
        "written out and searched for kernels)")
    say(f"compile cache over set-up (misses are compiles): {cc.cache}")

    drv = Driver(engine, Request, jax.profiler)
    trace_s = min(args.seconds, TRACE_SECONDS)
    tw = TraceWindow(drv, bool(args.trace), trace_s,
                     OUT / "trace" / args.workload)
    n_before = cc.n
    if mix["loop"] == "open":
        t_open, end = run_open(drv, mix, args.seed, args.seconds, cfg.vocab,
                               tw)
    else:
        t_open, end = run_closed(drv, mix, args.seed, args.seconds,
                                 cfg.vocab, tw)
    compiles_in_window = cc.n - n_before
    setup_s = t_open - T_START
    wm = window_metrics(drv, t_open, end, args.seconds)
    win_steps = [s for s in drv.steps if s["t0"] >= t_open and s["t1"] <= end]
    mem = dev.memory_stats() or {}
    peak_bytes = mem.get("peak_bytes_in_use")
    device["memory_peak_bytes"] = peak_bytes
    mism = sum(1 for s in win_steps if not s["agrees"])
    say(f"window: {args.seconds} s, {len(win_steps)} engine steps, "
        f"{wm['tokens']} tokens, {len(drv.flights)} requests submitted "
        f"(ramp included), {drv.failed} refused")
    say(f"programs compiled or loaded inside the window: "
        f"{compiles_in_window}")
    if compiles_in_window:
        raise Refused(f"{compiles_in_window} programs were compiled or "
                      "loaded inside the window: the warm-up missed a "
                      "shape that the traffic uses")
    say(f"tail samples: ttft {len(wm['ttft_s'])}, itl {len(wm['itl_s'])}")
    if wm["ttft_s"]:
        say(f"ttft mean (ms): {sum(wm['ttft_s']) / len(wm['ttft_s']) * 1e3}")
    for name, xs, qs in (("ttft", wm["ttft_s"], (50, 75, 90)),
                         ("itl", wm["itl_s"], (50, 90, 95, 99))):
        if xs:
            say(f"{name} percentiles (ms): " + ", ".join(
                f"p{q} {percentile(xs, q) * 1e3}" for q in qs))
    late = wm["lateness_s"]
    say(f"generator lateness: max {max(late) if late else 0} s, mean "
        f"{sum(late) / len(late) if late else 0} s over {len(late)} "
        "requests")
    say(f"step records whose prefill or decode rows disagree with the "
        f"engine's counts: {mism}")
    if mism and args.trace:
        raise Refused("the step records disagree with the engine's counts: "
                      "the per-layer work cannot be read")
    pre = [s["prefill_ms"] for s in win_steps if s["prefill_rows"]]
    dec = [s["decode_ms"] for s in win_steps if s["decode_rows"]]
    span = [s["span_ms"] for s in win_steps]
    say(f"engine steps in the window: {len(span)}, mean span "
        f"{sum(span) / max(1, len(span))} ms; {len(pre)} prefill chunks, "
        f"mean {sum(pre) / max(1, len(pre))} ms; {len(dec)} decode steps, "
        f"mean {sum(dec) / max(1, len(dec))} ms, mean rows "
        f"{sum(s['decode_rows'] for s in win_steps) / max(1, len(dec))}")
    say(f"peak_bytes_in_use: {peak_bytes}; bytes_in_use at the window's "
        f"close: {mem.get('bytes_in_use')}")

    metrics, breakdown = {}, None
    if args.trace:
        metrics, dev_extra, breakdown = traced(drv, tw, cfgj, peak, bench,
                                               args.workload, base)
        device.update(dev_extra)
        shutil.rmtree(tw.out, ignore_errors=True)
    else:
        e2e = {"setup_s": setup_s, "tokens_per_s": wm["tokens_per_s"],
               "itl_p90_ms": None if not wm["itl_s"] else
               percentile(wm["itl_s"], 90) * 1e3}
        for m in cellspec.end_to_end_metrics(bench, args.workload):
            # a quantity split by cell is named <quantity>.<part>
            v = e2e.get(m["name"].split(".")[0])
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    say(f"setup_s: {setup_s}")

    sample = choose_sample(drv, mix, args.seed)
    attempted, failed = wm["attempted"], drv.failed
    del engine, drv, tw
    gc.collect()
    t0 = time.perf_counter()
    readings = check(sample, cfgj, shapes, args.seed, args.control, base)
    say(f"phase reference: {time.perf_counter() - t0:.3f} s")
    compared, correct = judge(sample, readings, lim)
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    print(f"readings: {json.dumps(readings)}", file=sys.stderr)
    if args.control:
        ccomp, result["control_correct"] = judge(
            sample, readings, lim, f"control_{args.control}_")
        for k, v in ccomp.items():
            print(f"control {args.control} compared {k}: {v['value']} "
                  f"(limit {v['limit']})", file=sys.stderr)
        print(f"control_correct: {result['control_correct']}",
              file=sys.stderr)
    for k, v in compared.items():
        print(f"compared {k}: {v['value']} (limit {v['limit']})",
              file=sys.stderr, flush=True)
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = compared
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return result


if __name__ == "__main__":
    main()
