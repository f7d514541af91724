"""Shared set-up for the benchmark's CPU tests, imported first by each
of them: the harness and the program on the path, and fixtures for a
tiny benchmark tree (a two-layer model at the repo's reduced widths,
one closed and one open mix) under a temporary directory. Not a
``conftest.py``: the repo's own ``tests/conftest.py`` is imported by
that module name, and a second one would shadow it."""
import json
import shutil
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
for p in (BENCH, BENCH.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

TINY_ARCHS = {"tiny-mha": "phi3-mini-3.8b", "tiny-gqa": "chatglm3-6b"}


def tiny_model(arch: str) -> dict:
    from repro.configs import get_config
    c = get_config(TINY_ARCHS[arch]).reduced()
    return {"n_layers": c.n_layers, "d_model": c.d_model,
            "n_heads": c.n_heads, "n_kv_heads": c.n_kv_heads,
            "head_dim": c.head_dim_, "d_ff": c.d_ff, "vocab": c.vocab,
            "qkv_bias": c.qkv_bias, "rope": c.rope_kind,
            "rope_theta": c.rope_theta}


def tiny_mix(loop: str) -> dict:
    mix = {"loop": loop,
           "prefixes": {"count": 2, "tokens": 64, "choice": "uniform",
                        "prefill_at_setup": True},
           "suffix_tokens": {"dist": "uniform", "min": 8, "max": 40},
           "output_tokens": {"dist": "uniform", "min": 4, "max": 12},
           "shape_seed": 5, "pool": 256, "check_requests": 3}
    if loop == "closed":
        mix.update(clients=4,
                   first_output_tokens={"dist": "uniform", "min": 2,
                                        "max": 12})
    else:
        mix.update(rate_per_s=20.0, preroll_s=0.2)
    return mix


@pytest.fixture
def tiny(tmp_path, monkeypatch):
    """A benchmark tree with cells ``tiny-<arch>.<loop>`` over the repo's
    reduced configs, registered under the tiny arch names."""
    import repro.configs as rc
    from harness import registry
    root = tmp_path / "repo"
    bench = root / "bench"
    for d in ("configs", "traffic", "checks"):
        (bench / d).mkdir(parents=True)
    for d in ("metrics", "reference"):
        shutil.copytree(BENCH / d, bench / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    (bench / "peaks.json").write_text(json.dumps({"source": "test", "chips": {
        "cpu": {"bf16_flops": 1e12, "int8_ops": 2e12,
                "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10}}}))
    workloads = []
    for arch, base in TINY_ARCHS.items():
        monkeypatch.setitem(rc.ARCHS, arch, rc.get_config(base).reduced())
        (bench / "configs" / f"{arch}.json").write_text(json.dumps({
            "arch": arch, "reference": "dense", "model": tiny_model(arch),
            "weights": {"bits": 3, "block": 32, "rank": 16,
                        "container": "int8"},
            "serve": {"decode_batch": 4, "max_len": 256, "kv_dtype": "int4",
                      "page_size": 16, "prefill_len": 32}}))
        for loop in ("closed", "open"):
            name = f"{arch}.{loop}"
            workloads.append({"name": name, "config": arch,
                              "traffic": f"tiny_{loop}", "chips": 1,
                              "why": "test"})
            (bench / "checks" / f"{name}.json").write_text(json.dumps(
                {"limits": {"mean_gap": 1e-4}}))
    for loop in ("closed", "open"):
        (bench / "traffic" / f"tiny_{loop}.json").write_text(
            json.dumps(tiny_mix(loop)))
    e2e = [{"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.25,
            "source": "host_clock"},
           {"name": "tok_s", "unit": "tokens/s", "better": "higher",
            "bound": 0.1, "source": "host_clock"},
           {"name": "itl_p95_ms", "unit": "ms", "better": "lower",
            "bound": 0.1, "source": "host_clock"},
           {"name": "ttft_p90_ms", "unit": "ms", "better": "lower",
            "bound": 0.1, "source": "host_clock",
            "workloads": [w["name"] for w in workloads
                          if w["name"].endswith(".open")]}]
    per_layer = [{"name": "idle_share.decode", "unit": "%", "better": "lower",
                  "source": "device_trace", "layer": "device",
                  "moves": "tok_s"},
                 {"name": "prefix_hit_rate.chat", "unit": "%",
                  "better": "higher", "source": "program_counter",
                  "layer": "scheduler", "moves": "ttft_p90_ms"}]
    (root / "BENCHMARK.json").write_text(json.dumps({
        "command": ["python3", "bench/run.py"], "paths": ["bench"],
        "run_seconds": 3, "configs": [], "workloads": workloads,
        "end_to_end": e2e, "per_layer": per_layer}))
    monkeypatch.setattr(registry, "ROOT", root)
    monkeypatch.setattr(registry, "BENCH", bench)
    # the harness points JAX's compile cache at the checkout unless this
    # is set; a test keeps its compiles to itself
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    import jax
    import reference.common as common
    monkeypatch.setattr(common, "PAD_TO", 256)
    keep = {k: getattr(jax.config, k) for k in (
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    yield bench
    for k, v in keep.items():
        jax.config.update(k, v)


@pytest.fixture
def cpu_device():
    """Stands in for the harness's look for a chip."""
    import jax

    def check(chips):
        return jax.devices()[0], len(jax.devices())
    return check
