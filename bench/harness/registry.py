"""Find a cell's parts by the names ``BENCHMARK.json`` gives.

Each configuration, traffic mix and per-layer metric is a file of its own
under the benchmark's directory; a new one is a new file and an entry in
``BENCHMARK.json``, never an edit here:

  * ``configs/<config>.json``   the model, its weights and its deployment;
  * ``reference/<module>.py``   the architecture the configuration names
                                in its ``"reference"`` key;
  * ``traffic/<traffic>.json``  the parameters the generator reads;
  * ``metrics/<metric>.py``     a reader with ``read(run) -> float | None``;
  * ``peaks.json``              chip peaks keyed by ``device_kind``;
  * ``checks/<cell>.json``      the limits of the numbers ``correct``
                                compares.

An architecture module holds everything the harness knows of a block
family, and exports:

  * ``MODEL_KEYS``: ``{configuration key: program ModelConfig attribute}``;
    set-up refuses to serve unless each pair agrees;
  * ``BRANCH_OUT``: the parent names of the matrices that end a residual
    branch, which the seeded weights shrink with depth
    (:func:`harness.weights.make_params` takes it);
  * ``leaf_specs(model, weights)``: ``{path: (shape, dtype)}`` of the
    served tree, from the configuration alone; set-up refuses to serve
    unless the program's tree is the same;
  * ``Reference(model, weights)``, whose ``served_logits(seed, seqs,
    batch, groups)`` the check calls through
    :func:`reference.common.served_gaps`;
  * ``kv_bytes_per_token(model, kv_dtype)`` and ``attn_macs_per_ctx(model)``
    for :func:`harness.counts.step_work`;
  * optionally ``step_work(params, model, kv_dtype) -> StepWork``, used in
    place of the generic count (say, to count only the experts a decode
    step can read).

A module writes its block from :mod:`reference.common`.
"""
from __future__ import annotations

import importlib.util
import json
import re
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


def _load(path: Path, prefix: str):
    spec = importlib.util.spec_from_file_location(
        prefix + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, bench: Path = None):
    """The ``read`` function of ``metrics/<name>.py``."""
    path = (bench or BENCH) / "metrics" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no metric reader {path}")
    return _load(path, "bench_metric_").read


ARCH_EXPORTS = ("MODEL_KEYS", "BRANCH_OUT", "leaf_specs", "Reference",
                "kv_bytes_per_token", "attn_macs_per_ctx")


def reference(conf: Dict, bench: Path = None):
    """The architecture module ``reference/<conf["reference"]>.py`` (see
    the module docstring for what it exports)."""
    name = conf.get("reference")
    if name is None:
        raise KeyError(f"configuration of {conf.get('arch')!r} has no "
                       f"\"reference\" key naming its architecture module "
                       f"{(bench or BENCH) / 'reference'}/<module>.py")
    if not re.fullmatch(r"[A-Za-z_][A-Za-z0-9_]*", str(name)):
        raise ValueError(f"\"reference\": {name!r} is not a module name")
    path = (bench or BENCH) / "reference" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no architecture module {path} (named by "
                                f"the configuration of "
                                f"{conf.get('arch')!r})")
    mod = _load(path, "bench_reference_")
    missing = [k for k in ARCH_EXPORTS if not hasattr(mod, k)]
    if missing:
        raise AttributeError(f"architecture module {path} lacks "
                             f"{', '.join(missing)}")
    return mod


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
