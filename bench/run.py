#!/usr/bin/env python3
"""Run one benchmark cell once on the chip it is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<mix>.json``). Set-up builds the served ``Q + LR`` model
on the device from the seed, warms the engine's step programs, fills the
prefix cache the mix shares and, for a closed loop, every client's first
request. The window then serves the mix for ``--seconds``, submitting
to the continuous ``Engine`` and stepping it from one thread, as a
server's pump does. Afterwards the engine is freed and a plain
float32 reference, the architecture module the configuration names
(``bench/reference/<module>.py``), recomputes a seeded sample of the
finished requests: ``correct`` says whether the served tokens lie within
the cell's limit of the reference's best logit.

With ``--trace 1`` the window runs under the JAX profiler and the result
line carries the cell's per-layer metrics (``bench/metrics/<name>.py``)
instead of the end-to-end ones.

The last line of standard output is one JSON object; the numbers compared
for ``correct`` come last in it, under ``checks``, and again as the last
lines of standard error. Exits 2, printing no result, unless JAX's first
device is a TPU and there are as many as the cell asks for.
"""
from __future__ import annotations

import time

T_IMPORT = time.perf_counter()

import argparse
import json
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

from harness import registry  # noqa: E402


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def process_age_s() -> float:
    """Seconds since this process started (Linux), else since import."""
    try:
        start = int(Path("/proc/self/stat").read_text().rsplit(")", 1)[1]
                    .split()[19]) / os.sysconf("SC_CLK_TCK")
        uptime = float(Path("/proc/uptime").read_text().split()[0])
        return max(uptime - start, time.perf_counter() - T_IMPORT)
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - T_IMPORT


def parse(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def use_checkout_cache() -> None:
    """Keep JAX's persistent compile cache at one fixed path inside this
    checkout (``.jax_cache``), whatever the environment names: two
    checkouts measured side by side then share no compiled programs, and
    only a checkout's first run of a cell compiles. Called before JAX is
    imported, from the command-line entry points only."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(BENCH.parent / ".jax_cache")


def check_device(chips: int):
    """The first device must be a TPU, and there must be ``chips``."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        log(f"needs {chips} TPU chip(s); JAX sees {len(devs)} "
            f"{devs[0].platform!r} device(s)")
        raise SystemExit(2)
    return devs[0], len(devs)


def main(argv=None, device_check=check_device) -> int:
    age0 = process_age_s()
    args = parse(argv)
    cell = registry.cell(args.workload)
    conf = registry.config(cell["config"])
    mix = registry.traffic(cell["traffic"])
    dev, n_dev = device_check(cell["chips"])
    from harness.cell import Cell
    c = Cell(cell, conf, mix, args.seed, args.seconds, bool(args.trace),
             dev, n_dev, t_start=time.perf_counter() - age0)
    result = c.run()
    checks = result.pop("checks")
    for name, v in checks.items():
        log(f"check {name}: {v['value']} (limit {v['limit']})")
    result["checks"] = checks
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    use_checkout_cache()
    raise SystemExit(main())
