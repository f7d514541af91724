"""Infrastructure: sharding rules, data determinism, optim, checkpointing,
compile-cache placement."""
import os
import tempfile
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.data import DataConfig, data_config_for, host_batch
from repro.launch.mesh import make_host_mesh
from repro.models import init_lm
from repro.optim import (
    AdamW,
    apply_updates,
    clip_by_global_norm,
    cosine_schedule,
    dequantize_int8,
    quantize_int8,
)
from repro.sharding import (
    batch_axes,
    batch_spec,
    spec_for_cache,
    tree_param_specs,
)
from repro.train import CheckpointManager


def _mesh16():
    return jax.sharding.Mesh(
        np.array(jax.devices() * 256).reshape(16, 16)[:16, :16]
        if jax.device_count() == 1 else None, ("data", "model")) \
        if False else None


@pytest.fixture(scope="module")
def mesh():
    # an abstract 16×16 mesh built from repeated CPU devices is invalid;
    # use AbstractMesh for pure spec logic
    from jax.sharding import AbstractMesh
    return AbstractMesh((16, 16), ("data", "model"),
                        axis_types=(AxisType.Auto,) * 2)


# ---------------------------------------------------------------------------
# Sharding rules
# ---------------------------------------------------------------------------
def test_param_specs_cover_and_divide(mesh):
    for arch in ("qwen1.5-32b", "deepseek-moe-16b", "whisper-large-v3"):
        cfg = get_config(arch)
        absp = jax.eval_shape(
            lambda k: init_lm(k, cfg, dtype=jnp.bfloat16),
            jax.random.PRNGKey(0))
        specs = tree_param_specs(absp, mesh)
        flat_s = jax.tree_util.tree_flatten_with_path(specs)[0]
        flat_p = jax.tree_util.tree_flatten_with_path(absp)[0]
        assert len(flat_s) == len(flat_p)
        for (path, spec), (_, arr) in zip(flat_s, flat_p):
            assert len(spec) <= len(arr.shape)
            for dim, ax in zip(arr.shape, spec):
                if ax is None:
                    continue
                axes = ax if isinstance(ax, tuple) else (ax,)
                n = int(np.prod([dict(mesh.shape)[a] for a in axes]))
                assert dim % n == 0, (jax.tree_util.keystr(path), arr.shape,
                                      spec)


def test_expert_parallelism_claims_model_axis(mesh):
    cfg = get_config("deepseek-moe-16b")
    absp = jax.eval_shape(lambda k: init_lm(k, cfg, dtype=jnp.bfloat16),
                          jax.random.PRNGKey(0))
    specs = tree_param_specs(absp, mesh)
    flat = jax.tree_util.tree_flatten_with_path(specs)[0]
    seen = False
    for path, spec in flat:
        key = jax.tree_util.keystr(path)
        if "experts" in key and key.endswith("['w']"):
            assert "model" in str(spec), (key, spec)
            # within-expert dims must not reuse the model axis
            assert str(spec).count("model") == 1
            seen = True
    assert seen


def test_batch_spec_adapts_to_small_batches(mesh):
    assert batch_axes(mesh, 256) == ("data",)
    assert batch_axes(mesh, 1) == ()
    assert batch_spec(mesh, 1, 1) == P(None, None)


def test_cache_spec_heads_else_sequence(mesh):
    """Divisible KV heads take the model axis; otherwise the SEQUENCE dim
    does (flash-decode: softmax-stat psums only — sharding head_dim would
    all-reduce full score rows; see EXPERIMENTS.md §Perf It-3). Slot K/V
    pages are head-major (B, KV, S, hd); cross-attention memories stay
    sequence-major (B, S, KV, hd)."""
    spec2 = spec_for_cache(
        (jax.tree_util.DictKey("k"),), (128, 32, 32768, 128), mesh, 128)
    assert spec2[1] == "model" and spec2[2] is None   # heads preferred
    spec = spec_for_cache(
        (jax.tree_util.DictKey("k"),), (128, 40, 32768, 128), mesh, 128)
    assert spec[2] == "model"                         # S fallback (40 ∤ 16)
    assert spec[1] is None and spec[3] is None
    xspec = spec_for_cache(
        (jax.tree_util.DictKey("cross_k"),), (128, 1500, 32, 128), mesh, 128)
    assert xspec[2] == "model" and xspec[1] is None   # seq-major memories


# ---------------------------------------------------------------------------
# Data determinism
# ---------------------------------------------------------------------------
def test_data_deterministic_per_step():
    cfg = DataConfig(vocab=100, seq_len=16, global_batch=4)
    a = host_batch(cfg, step=3)
    b = host_batch(cfg, step=3)
    np.testing.assert_array_equal(np.asarray(a["tokens"]),
                                  np.asarray(b["tokens"]))
    c = host_batch(cfg, step=4)
    assert not np.array_equal(np.asarray(a["tokens"]),
                              np.asarray(c["tokens"]))


def test_data_host_sharding_partitions_global_batch():
    cfg = DataConfig(vocab=100, seq_len=8, global_batch=8)
    full = host_batch(cfg, 0, host_index=0, host_count=1)
    h0 = host_batch(cfg, 0, host_index=0, host_count=2)
    h1 = host_batch(cfg, 0, host_index=1, host_count=2)
    stacked = np.concatenate([np.asarray(h0["tokens"]),
                              np.asarray(h1["tokens"])])
    np.testing.assert_array_equal(stacked, np.asarray(full["tokens"]))


def test_labels_shift_tokens():
    cfg = DataConfig(vocab=50, seq_len=12, global_batch=2)
    b = host_batch(cfg, 0)
    np.testing.assert_array_equal(np.asarray(b["tokens"][:, 1:]),
                                  np.asarray(b["labels"][:, :-1]))


# ---------------------------------------------------------------------------
# Optimizer
# ---------------------------------------------------------------------------
def test_adamw_converges_quadratic():
    opt = AdamW(learning_rate=0.1)
    params = {"w": jnp.ones((8,)) * 5.0}
    st = opt.init(params)
    for _ in range(200):
        g = jax.grad(lambda p: jnp.sum(p["w"] ** 2))(params)
        u, st = opt.update(g, st, params)
        params = apply_updates(params, u)
    assert float(jnp.max(jnp.abs(params["w"]))) < 1e-2


def test_weight_decay_excludes_norms():
    opt = AdamW(learning_rate=0.0, weight_decay=1.0)
    params = {"w": jnp.ones((4, 4)), "norm": {"g": jnp.ones((4,))}}
    st = opt.init(params)
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    u, _ = opt.update(zeros, st, params)
    assert float(jnp.max(jnp.abs(u["norm"]["g"]))) == 0.0


def test_cosine_schedule_shape():
    lr = cosine_schedule(1.0, 10, 100, floor=0.1)
    assert float(lr(jnp.asarray(0))) == 0.0
    assert float(lr(jnp.asarray(10))) == pytest.approx(1.0)
    assert float(lr(jnp.asarray(100))) == pytest.approx(0.1, abs=1e-6)


def test_clip_by_global_norm():
    g = {"a": jnp.ones((100,)) * 10}
    clipped, norm = clip_by_global_norm(g, 1.0)
    assert float(norm) == pytest.approx(100.0)
    assert float(jnp.linalg.norm(clipped["a"])) == pytest.approx(1.0, rel=1e-4)


def test_int8_grad_quantization_roundtrip():
    x = jax.random.normal(jax.random.PRNGKey(0), (1000,))
    codes, scale = quantize_int8(x)
    err = jnp.abs(dequantize_int8(codes, scale) - x)
    assert float(jnp.max(err)) <= float(scale) * 0.5 + 1e-7


# ---------------------------------------------------------------------------
# Checkpointing (atomicity, retention, resume)
# ---------------------------------------------------------------------------
def _state(v):
    return {"params": {"w": jnp.full((4, 4), float(v))},
            "step": jnp.asarray(v, jnp.int32)}


def test_checkpoint_roundtrip_and_latest():
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d, keep=2)
        for v in (1, 2, 3):
            mgr.save(v * 10, _state(v))
        assert mgr.latest_step() == 30
        restored, manifest = mgr.restore(_state(0))
        assert manifest["step"] == 30
        assert float(restored["params"]["w"][0, 0]) == 3.0
        # retention pruned the oldest
        steps = sorted(n for n in os.listdir(d) if n.startswith("step_"))
        assert steps == ["step_00000020", "step_00000030"]


def test_checkpoint_ignores_torn_tmp():
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d, keep=3)
        mgr.save(10, _state(1))
        # simulate a crash mid-write: orphan tmp dir + torn step dir
        os.makedirs(os.path.join(d, ".tmp.99.1234"))
        os.makedirs(os.path.join(d, "step_00000099"))  # no manifest inside
        assert mgr.latest_step() == 10
        restored, _ = mgr.restore(_state(0))
        assert float(restored["params"]["w"][0, 0]) == 1.0


def test_checkpoint_shape_mismatch_raises():
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d)
        mgr.save(1, _state(1))
        bad = {"params": {"w": jnp.zeros((2, 2))}, "step": jnp.asarray(0)}
        with pytest.raises(ValueError):
            mgr.restore(bad)


# ---------------------------------------------------------------------------
# Persistent compilation cache placement
# ---------------------------------------------------------------------------
@pytest.fixture
def keep_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_compile_cache_leaves_env_var_to_jax(monkeypatch, keep_cache_dir,
                                             tmp_path):
    from repro.launch.compile_cache import enable_compile_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_falls_back_to_fixed_checkout_path(monkeypatch,
                                                        keep_cache_dir):
    from repro.launch.compile_cache import enable_compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = Path(__file__).resolve().parents[1] / ".jax_cache"
    assert enable_compile_cache() == want
    assert enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == str(want)
