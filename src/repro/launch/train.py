"""Training driver: ``python -m repro.launch.train --arch <id> [...]``.

Runs on whatever devices exist (laptop CPU → host mesh; a TPU slice →
the same code with a bigger mesh). ``--reduced`` (default) trains the
family-preserving tiny config; full-size configs are for real hardware.

Modes:
  full   — ordinary LM pretraining (bf16/f32, AdamW, cosine)
  qpeft  — the paper's §4.4 flow: calibrate → SRR-quantize → freeze the
           backbone → train rank-r adapters with γ-scaled gradients
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core.api import PTQConfig
from repro.data import batches, capture_calibration, data_config_for
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import init_lm, lm_loss
from repro.models.quantize import quantize_model_params, split_qpeft
from repro.optim import AdamW, cosine_schedule
from repro.quant.base import QuantizerConfig
from repro.train import (
    CheckpointManager,
    StepConfig,
    Trainer,
    init_qpeft_state,
    init_train_state,
    make_qpeft_step,
    make_train_step,
)


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="phi3-mini-3.8b")
    p.add_argument("--mode", default="full", choices=["full", "qpeft"])
    p.add_argument("--steps", type=int, default=200)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=64)
    p.add_argument("--lr", type=float, default=3e-3)
    p.add_argument("--rank", type=int, default=16)
    p.add_argument("--bits", type=int, default=3)
    p.add_argument("--gamma", type=float, default=0.1)
    p.add_argument("--microbatch", type=int, default=0)
    p.add_argument("--remat", default="none", choices=["none", "full"])
    p.add_argument("--ckpt-dir", default=None)
    p.add_argument("--ckpt-every", type=int, default=50)
    p.add_argument("--full-size", action="store_true",
                   help="train the full config (needs real hardware)")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if not args.full_size:
        cfg = cfg.reduced()
    mesh = make_host_mesh()
    print(f"[train] arch={args.arch} mode={args.mode} "
          f"devices={jax.device_count()} params≈{cfg.n_params() / 1e6:.1f}M")

    dcfg = data_config_for(cfg, seq_len=args.seq, global_batch=args.batch,
                           seed=args.seed)
    opt = AdamW(learning_rate=cosine_schedule(args.lr, 10, args.steps),
                weight_decay=0.01)
    dtype = jnp.float32 if jax.default_backend() == "cpu" else jnp.bfloat16
    sc = StepConfig(remat=args.remat, microbatch=args.microbatch,
                    compute_dtype=dtype,
                    mesh=mesh if jax.device_count() > 1 else None)

    params = init_lm(jax.random.PRNGKey(args.seed), cfg)
    if args.mode == "qpeft":
        print("[train] calibrating + quantizing (SRR)…")
        stats = capture_calibration(
            params, cfg, dcfg, lambda c, pp, b, cc: lm_loss(c, pp, b, cc),
            n_batches=2)
        ptq = PTQConfig(method="srr", scaling="qera-exact", rank=args.rank,
                        quantizer=QuantizerConfig(kind="mxint",
                                                  bits=args.bits,
                                                  block_size=32),
                        seed=args.seed)
        qparams, reports = quantize_model_params(params, stats, ptq)
        mean_k = sum(r.k_star for r in reports) / max(len(reports), 1)
        print(f"[train] quantized {len(reports)} matrices, mean k*={mean_k:.1f}")
        trainable, frozen = split_qpeft(qparams)
        state = init_qpeft_state(trainable, frozen, opt)
        step = jax.jit(make_qpeft_step(cfg, opt, sc))
    else:
        state = init_train_state(params, opt)
        step = jax.jit(make_train_step(cfg, opt, sc))

    ckpt = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    trainer = Trainer(step, lambda s: batches(dcfg, s), ckpt=ckpt,
                      ckpt_every=args.ckpt_every, log_every=10,
                      meta={"arch": args.arch, "mode": args.mode})
    state, history = trainer.run(state, args.steps)
    if history:
        print(f"[train] final loss {history[-1]['loss']:.4f}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
