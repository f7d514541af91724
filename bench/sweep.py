#!/usr/bin/env python3
"""Find an open-loop cell's knee: one set-up, then a window at each rate.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds 30 \
        --rates 1,2,3,4,5,6

For every rate it prints one JSON line: requests due, time to first
token (p50, p90) from the due time, inter-token gaps (p50, p95), tokens
per second, and the engine's queue length over the first and last third
of the window. A queue that grows over the window means the rate is past
what the engine sustains. Between rates, requests still in flight are
aborted, so each rate starts on an empty engine.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import run  # noqa: E402
from harness import registry  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--rates", required=True)
    a = p.parse_args(argv)
    cell = registry.cell(a.workload)
    conf = registry.config(cell["config"])
    mix = registry.traffic(cell["traffic"])
    dev, n_dev = run.check_device(cell["chips"])
    from repro.serve import SamplingParams
    from harness.cell import Cell
    from harness.traffic import Traffic
    from harness.window import (GRACE_S, Window, percentile, token_gaps,
                                tokens_in)
    c = Cell(cell, conf, mix, a.seed, a.seconds, False, dev, n_dev,
             t_start=time.perf_counter())
    eng = c.build()
    c.prefill_prefixes(eng, Traffic(mix, a.seed, c.model["vocab"]))
    qlen = []
    step = eng.step

    def counted_step():
        qlen.append((time.perf_counter(), len(eng.sched.queue)))
        return step()

    eng.step = counted_step
    for i, rate in enumerate(float(r) for r in a.rates.split(",")):
        spec = dict(mix, rate_per_s=rate)
        win = Window(eng, Traffic(spec, a.seed + i, c.model["vocab"]),
                     lambda n: SamplingParams(temperature=0.0,
                                              max_new_tokens=n))
        qlen.clear()
        t0, t1 = win.run_open(a.seconds, spec["preroll_s"])
        due = win.in_window(t0, t1)
        win.first_tokens(due, GRACE_S)
        flights = list(win.flights.values())
        ttft = [(f.times[0] - f.due) if f.times else float("inf")
                for f in due]
        gaps = token_gaps(flights, t0, t1)
        third = (t1 - t0) / 3
        q_first = [n for t, n in qlen if t0 <= t < t0 + third]
        q_last = [n for t, n in qlen if t1 - third <= t < t1]
        print(json.dumps({
            "rate_per_s": rate, "due": len(due),
            "ttft_p50_ms": 1e3 * percentile(ttft, 50),
            "ttft_p90_ms": 1e3 * percentile(ttft, 90),
            "itl_p50_ms": 1e3 * percentile(gaps, 50) if gaps else None,
            "itl_p95_ms": 1e3 * percentile(gaps, 95) if gaps else None,
            "tok_s": tokens_in(flights, t0, t1) / (t1 - t0),
            "queue_first_third": sum(q_first) / max(len(q_first), 1),
            "queue_last_third": sum(q_last) / max(len(q_last), 1),
            "gen_lag_p95_ms": 1e3 * percentile(
                [f.sent - f.due for f in due], 95)}), flush=True)
        for f in flights:                # start the next rate empty
            if not f.done:
                eng.abort(f.uid)
    return 0


if __name__ == "__main__":
    run.use_checkout_cache()
    raise SystemExit(main())
