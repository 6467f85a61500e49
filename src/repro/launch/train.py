"""Training driver.

Runs any registered architecture (full or smoke config) through the resilient
training loop: deterministic pipeline (+ MoLe provider stage), AdamW, periodic
async checkpoints, auto-resume.  On this CPU container it is exercised with
smoke-scale configs (tests, examples/train_lm_mole.py); on a fleet the same
driver runs under the production mesh (--mesh single|multi).

    PYTHONPATH=src python -m repro.launch.train --arch deepseek_7b --smoke \
        --steps 50 --mole token
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.configs import ARCHS, get_config, get_smoke_config
from repro.data.pipeline import DataConfig, Pipeline
from repro.launch.steps import TrainHParams, make_train_step
from repro.models.api import Model
from repro.models.base import MoLeCfg
from repro.optim import adamw
from repro.runtime.resilience import FailureInjector, ResilientLoop


def build(args):
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if args.mole != "off":
        cfg = dataclasses.replace(
            cfg, mole=MoLeCfg(enabled=True, mode=args.mole, kappa=args.kappa,
                              seed=args.mole_seed)
        )
    model = Model(cfg)
    hp = TrainHParams(
        optimizer=adamw.AdamWConfig(lr=args.lr, warmup_steps=args.warmup,
                                    decay_steps=max(args.steps, 2)),
        microbatch=args.microbatch,
        remat=not args.no_remat,
    )
    step_fn = jax.jit(make_train_step(model, hp), donate_argnums=(0, 1))
    dcfg = DataConfig(vocab=cfg.vocab, seq_len=args.seq_len,
                      global_batch=args.batch, seed=args.data_seed)
    pipeline = Pipeline(dcfg, model_cfg=cfg)
    return cfg, model, step_fn, pipeline


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=ARCHS)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--microbatch", type=int, default=None)
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--mole", default="off", choices=["off", "token", "embedding"])
    ap.add_argument("--kappa", type=int, default=1)
    ap.add_argument("--mole-seed", type=int, default=0)
    ap.add_argument("--data-seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="artifacts/ckpt")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--inject-failures", default="", help="comma steps")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    cfg, model, step_fn, pipeline = build(args)
    params = model.init(jax.random.key(0))
    opt = adamw.init_state(params)
    print(f"arch={cfg.name} params={model.param_count()/1e6:.2f}M "
          f"mole={cfg.mole.mode if cfg.mole.enabled else 'off'}")

    ckpt = CheckpointManager(Path(args.ckpt_dir) / cfg.name, keep=3)
    start = 0
    state = {"params": params, "opt": opt}
    if args.resume and ckpt.latest_step() is not None:
        start = ckpt.latest_step()
        state, extra = ckpt.restore(start, like=state)
        pipeline.seek(extra["data"]["index"])
        print(f"resumed from step {start}")

    injector = None
    if args.inject_failures:
        injector = FailureInjector(
            at_steps={int(s) for s in args.inject_failures.split(",")}
        )

    def loop_step(state, batch):
        b = {k: jnp.asarray(v) for k, v in batch.items()}
        p, o, metrics = step_fn(state["params"], state["opt"], b)
        return {"params": p, "opt": o}, metrics

    loop = ResilientLoop(loop_step, ckpt, pipeline,
                         ckpt_every=args.ckpt_every, injector=injector)
    t0 = time.time()
    state, history = loop.run(state, args.steps, start_step=start)
    dt = time.time() - t0

    losses = [h["loss"] for h in history if "loss" in h]
    for h in history:
        if "event" in h:
            print(f"  [FT] step {h['step']}: {h['event']}")
        elif h["step"] % args.log_every == 0:
            print(f"  step {h['step']:5d} loss {float(h['loss']):.4f} "
                  f"gnorm {float(h['grad_norm']):.3f} {h['wall_s']*1e3:.0f}ms")
    if losses:
        print(f"done: steps={len(losses)} first_loss={float(losses[0]):.4f} "
              f"last_loss={float(losses[-1]):.4f} wall={dt:.1f}s "
              f"restarts={loop.restarts} stragglers={len(loop.straggler.slow_steps)}")
    return state, history


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    main()
