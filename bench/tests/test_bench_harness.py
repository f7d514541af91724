"""The harness's own parts on the CPU: discovery by name, the traffic
generator, latency from the due time, and the refusal without a chip."""
import json
import os
import re
import subprocess
import sys
import time
import types
from pathlib import Path

import numpy as np
import pytest

from benchfix import cpu_device, tiny, tiny_model  # noqa: F401  (fixtures;
# importing benchfix puts the harness on the path)
from harness import registry
from harness.traffic import Traffic

BENCH = Path(__file__).resolve().parents[1]


def test_new_files_are_found_by_name(tiny):
    """A configuration, a mix and a metric added as new files are found
    from BENCHMARK.json's names, with no other file edited."""
    (tiny / "configs" / "brand-new.json").write_text(json.dumps(
        {"arch": "x", "model": {}, "weights": {}, "serve": {}}))
    (tiny / "traffic" / "brand_new_mix.json").write_text(json.dumps(
        {"loop": "open"}))
    (tiny / "metrics" / "brand_new.metric.py").write_text(
        "def read(run):\n    return 42.0\n")
    root = tiny.parent
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["workloads"].append({"name": "brand-new.mix", "config": "brand-new",
                           "traffic": "brand_new_mix", "chips": 1,
                           "why": "test"})
    b["per_layer"].append({"name": "brand_new.metric", "unit": "%",
                           "better": "higher", "source": "device_trace",
                           "layer": "kernels", "moves": "tok_s",
                           "workloads": ["brand-new.mix"]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    cell = registry.cell("brand-new.mix")
    assert registry.config(cell["config"])["arch"] == "x"
    assert registry.traffic(cell["traffic"])["loop"] == "open"
    names = [m["name"] for m in cell["per_layer"]]
    assert names == ["idle_share.decode", "prefix_hit_rate.chat",
                     "brand_new.metric"]
    assert registry.metric_reader("brand_new.metric")(None) == 42.0
    # a metric listing other cells stays out of this one
    assert "brand_new.metric" not in [
        m["name"] for m in registry.cell("tiny-mha.open")["per_layer"]]


TOY = '''"""A test architecture: the dense block, counted its own way."""
from reference.dense import (BRANCH_OUT, MODEL_KEYS as DENSE_KEYS, Reference,
                             attn_macs_per_ctx, leaf_specs)

MODEL_KEYS = dict(DENSE_KEYS, toy_width="d_model")


def kv_bytes_per_token(model, kv_dtype):
    return 12345
'''


def _snapshot(root):
    return {p: p.read_bytes() for p in root.rglob("*")
            if p.is_file() and "__pycache__" not in p.parts}


def _add_toy_cell(tiny, **model):
    """A configuration naming ``reference/toy.py`` and its cell, as new
    files and a workload entry."""
    (tiny / "reference" / "toy.py").write_text(TOY)
    conf = json.loads((tiny / "configs" / "tiny-mha.json").read_text())
    conf["reference"] = "toy"
    conf["model"].update(model)
    (tiny / "configs" / "tiny-toy.json").write_text(json.dumps(conf))
    (tiny / "checks" / "tiny-toy.closed.json").write_text(json.dumps(
        {"limits": {"mean_gap": 1e-4}}))
    root = tiny.parent
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["workloads"].append({"name": "tiny-toy.closed", "config": "tiny-toy",
                           "traffic": "tiny_closed", "chips": 1,
                           "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    return conf


def _cell(name, cpu_device, seconds=1.0):
    import time
    from harness.cell import Cell
    cell = registry.cell(name)
    dev, n = cpu_device(1)
    return Cell(cell, registry.config(cell["config"]),
                registry.traffic(cell["traffic"]), 9, seconds, False, dev, n,
                t_start=time.perf_counter())


def test_a_new_architecture_arrives_as_new_files(tiny, cpu_device):
    """A configuration naming a new architecture module is loaded, checked
    against the program's tree and counted from the module's numbers,
    with every file the benchmark had left as it was."""
    from reference import dense
    before = _snapshot(tiny.parent)
    conf = _add_toy_cell(tiny, toy_width=tiny_model("tiny-mha")["d_model"])
    toy = registry.reference(conf)
    assert toy.kv_bytes_per_token(conf["model"], "int4") == 12345
    assert toy.MODEL_KEYS["toy_width"] == "d_model"
    c = _cell("tiny-toy.closed", cpu_device)
    res = c.run()
    assert res["correct"], res["checks"]
    assert c.work.kv_bytes_per_token == 12345
    assert c.work.attn_macs_per_ctx == dense.attn_macs_per_ctx(conf["model"])
    after = _snapshot(tiny.parent)
    changed = [p for p in before if after.get(p) != before[p]]
    assert changed == [tiny.parent / "BENCHMARK.json"]
    for d in ("reference", "metrics"):
        for p in (BENCH / d).glob("*.py"):
            assert (tiny / d / p.name).read_bytes() == p.read_bytes()


@pytest.mark.parametrize("fault", ["no key", "no module", "key differs"])
def test_a_configuration_without_its_architecture_fails_before_weights(
        tiny, cpu_device, monkeypatch, fault):
    import harness.cell
    path = tiny / "configs" / "tiny-mha.json"
    conf = json.loads(path.read_text())
    if fault == "no key":
        del conf["reference"]
        path.write_text(json.dumps(conf))
        err, says = KeyError, str(tiny / "reference")
    elif fault == "no module":
        conf["reference"] = "absent"
        path.write_text(json.dumps(conf))
        err, says = FileNotFoundError, str(tiny / "reference" / "absent.py")
    else:
        _add_toy_cell(tiny, toy_width=1)
        err, says = ValueError, "toy_width=1"

    def no_weights(*a, **k):
        raise AssertionError("weights were made")
    monkeypatch.setattr(harness.cell, "make_params", no_weights)
    name = "tiny-toy.closed" if fault == "key differs" else "tiny-mha.closed"
    with pytest.raises(err, match=re.escape(says)):
        _cell(name, cpu_device).build()


def test_every_benchmark_name_has_its_file():
    b = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    for w in b["workloads"]:
        registry.config(w["config"])
        registry.traffic(w["traffic"])
        registry.checks(w["name"])
    for m in b["per_layer"]:
        assert callable(registry.metric_reader(m["name"]))
    for c in b["configs"]:
        conf = json.loads((BENCH.parent / c["file"]).read_text())
        assert conf["source"] == c["source"]
        assert conf["reduced"] == c["reduced"]
        registry.reference(conf)


def _mix(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", ["docqa", "chat"])
def test_traffic_is_seeded_and_keeps_its_sizes(name):
    spec = _mix(name)
    a, b = Traffic(spec, 2**31 + 9, 1000), Traffic(spec, 2**31 + 9, 1000)
    c = Traffic(spec, 12, 1000)
    for x, y in zip(a.requests, b.requests):
        assert np.array_equal(x.prompt, y.prompt)
        assert (x.out_len, x.due) == (y.out_len, y.due)
    # another seed: other tokens, the same sizes in another order
    sizes = lambda t: sorted((len(r.prompt), r.out_len) for r in
                             t.requests[spec.get("clients", 0):])
    assert sizes(a) == sizes(c)
    assert any(not np.array_equal(x.prompt, y.prompt)
               for x, y in zip(a.requests, c.requests))
    for r in a.requests:
        assert np.array_equal(r.prompt[:spec["prefixes"]["tokens"]],
                              a.prefixes[r.prefix])


def test_a_closed_loop_that_outruns_its_pool_goes_round_it():
    from harness.window import Window
    spec = dict(_mix("docqa"), clients=3, pool=5)
    t = Traffic(spec, 2**31 + 3, 1000)
    win = Window.__new__(Window)
    win.traffic, win.next_req = t, 0
    taken = [win.take() for _ in range(3 + 2 * 5 + 1)]
    again = t.requests + t.requests[3:] + t.requests[3:4]
    assert all(a is b for a, b in zip(taken, again, strict=True))


def test_the_closed_window_closes_at_the_end_of_the_step_in_flight():
    """The step under way when the window's seconds run out counts, with
    its tokens and all its time, so the rate does not move in whole
    steps."""
    from harness.window import Flight, Window, tokens_in
    step_s, lanes = 0.05, 4

    class Engine:
        _prefill_jobs = {}

        def _admit_paged(self, budget):
            return None

        def step(self):
            time.sleep(step_s)
            for uid in range(1, lanes + 1):
                self.on_token(uid, 7)

    spec = dict(_mix("docqa"), clients=lanes, pool=8)
    win = Window(Engine(), Traffic(spec, 5, 1000), None)
    for uid in range(1, lanes + 1):
        win.flights[uid] = Flight(req=types.SimpleNamespace(out_len=10**6),
                                  uid=uid, due=0.0, sent=0.0)
    t0, t1 = win.run_closed(2.5 * step_s)
    stamps = [t for f in win.flights.values() for t in f.times]
    assert t1 - t0 >= 3 * step_s and max(stamps) < t1
    assert tokens_in(list(win.flights.values()), t0, t1) == 3 * lanes


def test_docqa_draws_its_stated_distributions():
    spec = _mix("docqa")
    t = Traffic(spec, 7, 32064)
    body = t.requests[spec["clients"]:]
    q = np.array([len(r.prompt) - 3072 for r in body])
    out = np.array([r.out_len for r in body])
    assert q.min() >= 32 and q.max() <= 128 and abs(q.mean() - 80) < 4
    assert out.min() >= 256 and out.max() <= 512 and abs(out.mean() - 384) < 12
    first = [r.out_len for r in t.requests[:spec["clients"]]]
    assert min(first) >= 16 and max(first) <= 512
    counts = np.bincount([r.prefix for r in t.requests], minlength=4)
    assert counts.min() > 0.2 * len(t.requests)
    assert all(r.due is None for r in t.requests)


def test_chat_draws_its_stated_distributions():
    spec = dict(_mix("chat"), pool=4096)     # enough draws to see them
    t = Traffic(spec, 7, 32064)
    user = np.array([len(r.prompt) - 512 for r in t.requests])
    out = np.array([r.out_len for r in t.requests])
    assert user.min() >= 16 and user.max() <= 2048
    assert abs(np.median(user) - 256) < 30
    assert out.min() >= 16 and out.max() <= 512
    assert abs(np.median(out) - 128) < 12
    # Zipf(1) over 8 prompts: the first is chosen most, about 37 %
    share = np.bincount([r.prefix for r in t.requests], minlength=8) \
        / len(t.requests)
    assert share.argmax() == 0 and abs(share[0] - 1 / sum(
        1 / k for k in range(1, 9))) < 0.04
    # Poisson arrivals at the stated rate, the first preroll_s early
    due = np.array([r.due for r in t.requests])
    assert np.all(np.diff(due) > 0) and due[0] > -spec["preroll_s"]
    rate = (len(due) - 1) / (due[-1] - due[0])
    assert abs(rate / spec["rate_per_s"] - 1) < 0.1


def test_open_loop_latency_counts_from_the_due_time():
    from harness.cell import Cell
    from harness.window import Flight
    req = types.SimpleNamespace(out_len=3)
    t0 = 100.0
    # due at t0 + 1, sent half a second late, first token 0.1 s after that
    f = Flight(req=req, uid=1, due=t0 + 1, sent=t0 + 1.5, matched=0,
               times=[t0 + 1.6, t0 + 1.7, t0 + 1.9], tokens=[1, 2, 3])
    cell = types.SimpleNamespace(cell={"end_to_end": [
        {"name": "ttft_p90_ms", "unit": "ms"},
        {"name": "itl_p95_ms", "unit": "ms"}]})
    m = Cell.end_to_end(cell, [f], [f], t0, t0 + 10, 1.0)
    assert m["ttft_p90_ms"]["value"] == pytest.approx(600.0)
    assert m["itl_p95_ms"]["value"] == pytest.approx(
        1e3 * np.percentile([0.1, 0.2], 95))


def test_run_refuses_without_a_tpu_before_building_anything():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    t = time.perf_counter()
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "phi3-mini-3.8b.docqa", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=120, cwd=BENCH.parent)
    assert p.returncode == 2
    assert p.stdout == ""
    assert "needs 1 TPU" in p.stderr and "setup" not in p.stderr
    assert time.perf_counter() - t < 60
