"""Training launcher: mesh + sharding + checkpoint/restart + monitoring.

    PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b \
        --preset tiny --steps 20 --batch 8 --seq 128

Production posture: restart manifests + deterministic data skiping make
``--resume`` exact; StepMonitor flags stragglers; checkpoints are async.
On a real TPU slice run under `jax.distributed.initialize()` with
--data/--model sized to the slice; on CPU it runs the same code on a 1x1
mesh.
"""
from __future__ import annotations

import argparse
from dataclasses import replace

import jax
import jax.numpy as jnp

from ..configs import get_config, smoke_config
from .. import models
from ..checkpoint import Checkpointer
from ..data import DataConfig, TokenPipeline
from ..distributed import sharding as shd
from ..distributed.fault_tolerance import RestartManifest, StepMonitor
from ..training import AdamW, cosine_schedule, init_state, make_train_step
from . import compile_cache
from .mesh import make_host_mesh


def preset_config(cfg, preset: str):
    if preset == "full":
        return cfg
    if preset == "m100":      # ~100M-param config of the same family
        return replace(cfg, name=cfg.name + "-m100", n_layers=12,
                       d_model=768, n_heads=12 if cfg.n_heads else 0,
                       n_kv_heads=4 if cfg.n_kv_heads else 0,
                       d_head=64 if cfg.n_heads else 0, d_ff=2048,
                       vocab_size=32000,
                       n_experts=min(cfg.n_experts, 8),
                       top_k=min(cfg.top_k, 2))
    return smoke_config(cfg)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--preset", choices=["tiny", "m100", "full"],
                    default="tiny")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--data", type=int, default=1)
    ap.add_argument("--model", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="checkpoints")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--token-file", default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    compile_cache.enable()

    cfg = preset_config(get_config(args.arch), args.preset)
    mesh = make_host_mesh(data=args.data, model=args.model)
    shd.set_model_config(cfg)
    opt = AdamW(lr=cosine_schedule(args.lr, warmup=max(args.steps // 20, 1),
                                   total=args.steps))
    pipe = TokenPipeline(DataConfig(cfg.vocab_size, args.seq, args.batch,
                                    seed=args.seed,
                                    token_file=args.token_file))
    ck = Checkpointer(args.ckpt_dir)
    man_path = f"{args.ckpt_dir}/manifest.json"
    mon = StepMonitor(on_straggler=lambda s, dt: print(
        f"[straggler] step {s} took {dt:.2f}s"))

    with jax.sharding.set_mesh(mesh):
        state = init_state(cfg, opt, jax.random.PRNGKey(args.seed))
        start = 0
        if args.resume and ck.latest_step() is not None:
            man = RestartManifest.load(man_path)
            state, _ = ck.restore(state)
            start = man.step + 1
            print(f"resumed from step {man.step}")
        step_fn = jax.jit(make_train_step(
            cfg, opt, microbatches=args.microbatches,
            has_frontend=models.needs_frontend(cfg)))

        n_params = models.param_count(state.params)
        print(f"training {cfg.name}: {n_params / 1e6:.1f}M params, "
              f"mesh={dict(mesh.shape)}, batch={args.batch}x{args.seq}")
        for s in range(start, args.steps):
            mon.start()
            raw = pipe.batch_at(s)
            batch = {k: jnp.asarray(v) for k, v in raw.items()}
            if models.needs_frontend(cfg):
                batch["frontend"] = jnp.zeros(
                    (args.batch, max(cfg.n_frontend_tokens, 1), cfg.d_model),
                    jnp.bfloat16)
            state, metrics = step_fn(state, batch)
            dt = mon.stop(s)
            if s % max(args.steps // 20, 1) == 0 or s == args.steps - 1:
                print(f"step {s:5d}  loss={float(metrics['loss']):.4f}  "
                      f"gnorm={float(metrics['grad_norm']):.3f}  "
                      f"{args.batch * args.seq / dt:.0f} tok/s")
            if s % args.ckpt_every == 0 or s == args.steps - 1:
                ck.save(s, state, extra={"data_step": s}, async_=True)
                RestartManifest(step=s, data_step=s,
                                mesh_shape=dict(mesh.shape),
                                rng_seed=args.seed).save(man_path)
        ck.wait()
    print("done")


if __name__ == "__main__":
    main()
