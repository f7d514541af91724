"""Find a cell's parts by the names ``BENCHMARK.json`` gives.

Each configuration, traffic mix and per-layer metric is a file of its own
under the benchmark's directory; a new one is a new file and an entry in
``BENCHMARK.json``, never an edit here:

  * ``configs/<config>.json``   the model, its weights and its deployment;
  * ``traffic/<traffic>.json``  the parameters the generator reads;
  * ``metrics/<metric>.py``     a reader with ``read(run) -> float | None``;
  * ``peaks.json``              chip peaks keyed by ``device_kind``.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Dict

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def benchmark(root: Path = None) -> Dict:
    return json.loads(((root or ROOT) / "BENCHMARK.json").read_text())


def cell(name: str, root: Path = None) -> Dict:
    """The workload entry, with its end-to-end and per-layer metrics."""
    b = benchmark(root)
    for w in b["workloads"]:
        if w["name"] == name:
            break
    else:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def applies(m):
        return name in m.get("workloads", [name])

    return dict(w, end_to_end=[m for m in b["end_to_end"] if applies(m)],
                per_layer=[m for m in b["per_layer"] if applies(m)])


def _json(kind: str, name: str, bench: Path = None) -> Dict:
    path = (bench or BENCH) / kind / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind[:-1]} file {path}")
    return json.loads(path.read_text())


def config(name: str, bench: Path = None) -> Dict:
    return _json("configs", name, bench)


def traffic(name: str, bench: Path = None) -> Dict:
    return _json("traffic", name, bench)


def metric_reader(name: str, bench: Path = None):
    """The ``read`` function of ``metrics/<name>.py``."""
    path = (bench or BENCH) / "metrics" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no metric reader {path}")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks(device_kind: str, bench: Path = None) -> Dict:
    """Peaks of one chip; an unknown ``device_kind`` is an error."""
    table = json.loads(((bench or BENCH) / "peaks.json").read_text())
    if device_kind not in table["chips"]:
        raise KeyError(f"no peaks for device_kind {device_kind!r} in "
                       f"peaks.json (known: {sorted(table['chips'])})")
    return table["chips"][device_kind]


def checks(workload: str, bench: Path = None) -> Dict:
    """The limits of the numbers a cell compares (``checks/<cell>.json``)."""
    return _json("checks", workload, bench)["limits"]
