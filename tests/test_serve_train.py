"""End-to-end: serving engine behaviour + trainer resume + launch CLIs."""
import subprocess
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.data import batches, data_config_for
from repro.models import init_lm
from repro.optim import AdamW, cosine_schedule
from repro.serve import Engine, Request, ServeConfig
from repro.train import (
    CheckpointManager,
    StepConfig,
    Trainer,
    init_train_state,
    make_train_step,
)


@pytest.fixture(scope="module")
def tiny():
    cfg = get_config("phi3-mini-3.8b").reduced()
    return cfg, init_lm(jax.random.PRNGKey(0), cfg)


def test_engine_generates_and_orders_results(tiny):
    cfg, params = tiny
    eng = Engine(params, cfg, ServeConfig(max_len=64, decode_batch=4,
                                          max_new_tokens=6))
    rng = np.random.default_rng(0)
    reqs = [Request(uid=i, prompt=rng.integers(
        0, cfg.vocab, size=5 + 3 * (i % 2)).astype(np.int32))
        for i in range(7)]
    res = eng.generate(reqs)
    assert [r.uid for r in res] == list(range(7))
    assert all(len(r.tokens) == 6 for r in res)


def test_engine_greedy_deterministic(tiny):
    cfg, params = tiny
    eng = Engine(params, cfg, ServeConfig(max_len=64, decode_batch=2,
                                          max_new_tokens=5))
    req = [Request(uid=0, prompt=np.arange(6, dtype=np.int32))]
    a = eng.generate(req)[0].tokens
    b = eng.generate(req)[0].tokens
    np.testing.assert_array_equal(a, b)


def test_engine_respects_eos(tiny):
    cfg, params = tiny
    eng = Engine(params, cfg, ServeConfig(max_len=64, decode_batch=2,
                                          max_new_tokens=32, eos_id=-1))
    req = [Request(uid=0, prompt=np.arange(6, dtype=np.int32))]
    out = eng.generate(req)[0]
    # find what the 3rd token is, then rerun with it as EOS
    eos = int(out.tokens[2])
    eng2 = Engine(params, cfg, ServeConfig(max_len=64, decode_batch=2,
                                           max_new_tokens=32, eos_id=eos))
    out2 = eng2.generate(req)[0]
    assert len(out2.tokens) <= 3 or int(out2.tokens[-1]) == eos


def test_trainer_kill_and_resume_bitexact(tiny):
    """Fault-tolerance contract: 10 straight steps ≡ 5 steps + restart + 5
    (deterministic data + checkpoint restore)."""
    cfg, params = tiny
    opt = AdamW(learning_rate=cosine_schedule(1e-3, 2, 10))
    dcfg = data_config_for(cfg, seq_len=16, global_batch=4)
    step = jax.jit(make_train_step(cfg, opt,
                                   StepConfig(compute_dtype=jnp.float32)))

    def fresh():
        return init_train_state(init_lm(jax.random.PRNGKey(0), cfg), opt)

    straight, _ = Trainer(step, lambda s: batches(dcfg, s),
                          log_fn=lambda *_: None).run(fresh(), 10)
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d)
        Trainer(step, lambda s: batches(dcfg, s), ckpt=mgr, ckpt_every=5,
                log_fn=lambda *_: None).run(fresh(), 5)
        resumed, _ = Trainer(step, lambda s: batches(dcfg, s), ckpt=mgr,
                             ckpt_every=5, log_fn=lambda *_: None
                             ).run(fresh(), 10)
    a = np.asarray(jax.tree_util.tree_leaves(straight.params)[0])
    b = np.asarray(jax.tree_util.tree_leaves(resumed.params)[0])
    np.testing.assert_allclose(a, b, atol=1e-6)


def test_train_cli_full_and_qpeft():
    for mode in ("full", "qpeft"):
        r = subprocess.run(
            [sys.executable, "-m", "repro.launch.train", "--mode", mode,
             "--arch", "xlstm-125m", "--steps", "12", "--batch", "4",
             "--seq", "32", "--rank", "8"],
            capture_output=True, text=True, timeout=560,
            env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                 "JAX_PLATFORMS": "cpu",
                 "HOME": "/root"}, cwd="/root/repo")
        assert r.returncode == 0, r.stderr[-2000:]
        assert "final loss" in r.stdout


def test_serve_cli_srr():
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch",
         "minitron-4b", "--method", "srr", "--rank", "8",
         "--requests", "4", "--new-tokens", "4", "--kv", "int8"],
        capture_output=True, text=True, timeout=560,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "JAX_PLATFORMS": "cpu", "HOME": "/root"},
        cwd="/root/repo")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "quantized" in r.stdout and "requests" in r.stdout


def test_build_quantized_model_cuts_depth_not_width():
    import argparse
    import dataclasses
    from repro.launch.serve import build_quantized_model
    args = argparse.Namespace(arch="phi3-mini-3.8b", seed=0, method="none",
                              layers=1)
    _, cfg = build_quantized_model(args, tag="test")
    assert cfg == dataclasses.replace(
        get_config("phi3-mini-3.8b").reduced(), n_layers=1)


def test_compressed_psum_subprocess():
    """int8 EF all-reduce over a 'pod' axis (needs >1 device ⇒ subprocess
    with forced host device count)."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.optim import ef_compressed_psum, init_error_feedback
from repro.launch.mesh import make_mesh
mesh = make_mesh((4,), ("pod",))
g = jnp.stack([jnp.full((8,), float(i + 1)) for i in range(4)])  # per-pod
ef = jnp.zeros((4, 8))
def inner(gi, ei):
    s, e2 = ef_compressed_psum(gi[0], ei[0], axis="pod")
    return s[None], e2[None]
sync = jax.shard_map(inner, mesh=mesh, in_specs=(P("pod"), P("pod")),
                     out_specs=(P("pod"), P("pod")), check_vma=False)
s, e2 = sync(g, ef)
# every pod sees the mean (= 2.5); EF residual bounded by one int8 step
np.testing.assert_allclose(np.asarray(s), 2.5, rtol=0.05)
# error feedback: second round with same grads drives residual down
s2, e3 = sync(g, e2)
assert float(jnp.mean(jnp.abs(np.asarray(s) + np.asarray(s2) - 5.0))) < 0.02
print("EF-PSUM-OK")
"""
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "JAX_PLATFORMS": "cpu",
                            "HOME": "/root"}, cwd="/root/repo")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "EF-PSUM-OK" in r.stdout


def test_dryrun_cli_smallest_cell():
    """The dry-run driver end-to-end on the cheapest (arch × shape)."""
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "xlstm-125m",
         "--shape", "decode_32k", "--mesh", "single", "--out",
         tempfile.mkdtemp()],
        capture_output=True, text=True, timeout=560,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "JAX_PLATFORMS": "cpu", "HOME": "/root"},
        cwd="/root/repo")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "1 ok, 0 skip, 0 FAIL" in r.stdout
