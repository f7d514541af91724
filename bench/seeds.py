#!/usr/bin/env python3
"""Run one cell on several seeds in one process, one result line each.

    python3 bench/seeds.py --workload <cell> --seeds 11,12,13 --seconds 10
        [--trace 0|1] [--control]

One process pays JAX's start-up and the compiles once, so the readings
that set a cell's ``correct`` limit (a dozen seeds or more of the
program, three or more of the control) come from one call on the chip.
``--control`` serves with the engine's bfloat16 compute path
(``ServeConfig(compute_dtype="bf16")``), the precision below the float32
the configuration runs at: the comparison has to reject it. Each line is
what ``run.py`` prints, with ``seed`` and ``control`` added.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import run  # noqa: E402
from harness import registry  # noqa: E402

CONTROL = {"compute_dtype": "bf16"}


def main(argv=None, device_check=run.check_device) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--control", action="store_true")
    a = p.parse_args(argv)
    cell = registry.cell(a.workload)
    conf = registry.config(cell["config"])
    mix = registry.traffic(cell["traffic"])
    dev, n_dev = device_check(cell["chips"])
    from harness.cell import Cell
    for seed in [int(s) for s in a.seeds.split(",")]:
        c = Cell(cell, conf, mix, seed, a.seconds, bool(a.trace), dev, n_dev,
                 t_start=time.perf_counter(),
                 engine_overrides=CONTROL if a.control else None)
        res = c.run()
        res = dict(seed=seed, control=a.control, **res)
        print(json.dumps(res), flush=True)
        del c
        gc.collect()
    return 0


if __name__ == "__main__":
    run.use_checkout_cache()
    raise SystemExit(main())
