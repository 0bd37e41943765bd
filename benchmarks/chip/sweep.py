#!/usr/bin/env python3
"""Find the highest arrival rate an open-loop cell sustains, once.

    python3 benchmarks/chip/sweep.py --workload <cell> --rates 1,2,3 \\
        [--seconds 15] [--seed 1]

One process on the chip: builds and warms the cell's engine as
``run.py`` does, then offers the cell's traffic mix at each rate in turn
(after the mix's ramp), and prints one line per rate: offered requests/s,
tokens/s completed, TTFT p50/p90, and the backlog at the window's end
(requests due in the window that had not started).  A rate is sustained
while its backlog stays near zero and its TTFT does not climb across the
window.  The cell's mix file then takes 0.8 of the highest such rate;
this script never writes it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import cellspec  # noqa: E402
import run  # noqa: E402


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    bench = cellspec.benchmark()
    wl = cellspec.cell(bench, args.workload)
    cfgj = cellspec.config(wl["config"])
    mix = cellspec.traffic(wl["traffic"])
    if mix["loop"] != "open":
        raise SystemExit("sweep.py is for open-loop cells")
    run.import_program(cellspec.ROOT)
    os.environ["REPRO_DISPATCH_STRICT"] = "1"
    import jax
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("sweep.py measures on a TPU only")
    from repro.launch.compile_cache import enable_compile_cache
    from repro.serve.engine import Request
    enable_compile_cache()
    engine, cfg, _ = run.build(cfgj, mix, args.seed)
    run.warm(engine, cfgj, mix, Request, cfg.vocab,
             run.OUT / "programs" / f"sweep.{args.workload}")
    for rate in [float(r) for r in args.rates.split(",")]:
        drv = run.Driver(engine, Request)
        tw = run.TraceWindow(drv, False, 0, Path("."))
        t_open, end = run.run_open(drv, mix, args.seed, args.seconds,
                                   cfg.vocab, tw, rate=rate)
        wm = run.window_metrics(drv, t_open, end, args.seconds)
        due = [f for f in drv.flights if t_open <= f.due < end]
        backlog = sum(1 for f in due if not f.stamps or f.stamps[0] >= end)
        third = max(1, len(due) // 3)
        early = [(f.stamps[0] if f.stamps else end) - f.due
                 for f in due[:third]]
        late = [(f.stamps[0] if f.stamps and f.stamps[0] < end else end)
                - f.due for f in due[-third:]]
        print(json.dumps({
            "rate_per_s": rate, "requests_due": len(due),
            "tokens_per_s": wm["tokens_per_s"],
            "ttft_p50_ms": run.percentile(wm["ttft_s"], 50) * 1e3,
            "ttft_p90_ms": run.percentile(wm["ttft_s"], 90) * 1e3,
            "ttft_p90_first_third_ms": run.percentile(early, 90) * 1e3,
            "ttft_p90_last_third_ms": run.percentile(late, 90) * 1e3,
            "backlog_at_end": backlog,
            "itl_p90_ms": run.percentile(wm["itl_s"], 90) * 1e3}),
            flush=True)
        engine.queue.clear()
        engine.run()


if __name__ == "__main__":
    main()
