"""Serving launcher: LLMCompass-planned parallelism + continuous-batching
engine.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b \
        --preset tiny --requests 8 --max-new 16

The planner (the paper's performance model) is consulted first: it prints
the predicted-latency-optimal (tp, pp, dp) plan and predicted throughput
for the target system before the engine starts — Sec. IV of the paper used
as a deployment tool. Those are predictions for the described v5e pod, not
for the device the engine runs on.

`main(argv)` returns the Engine and its served requests, so a caller
(chip_smoke.py) drives this same entry point and inspects the result.
"""
from __future__ import annotations

import argparse
import time

import jax

from ..configs import get_config
from .. import models
from ..core import hardware as hw
from ..core import planner
from ..serving import Engine, Request, SamplingParams
from . import compile_cache
from .train import preset_config


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--preset", choices=["tiny", "m100", "full"],
                    default="tiny")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16,
                    help="tokens per request; odd-numbered requests ask for "
                         "half, so slots free at different steps and are "
                         "refilled while others decode")
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--plan-chips", type=int, default=16,
                    help="v5e chips for the planning report")
    args = ap.parse_args(argv)
    compile_cache.enable()

    full_cfg = get_config(args.arch)
    # 1) plan on the real config with the paper's model
    node = hw.tpu_v5e_pod(args.plan_chips)
    try:
        best = planner.best_plan(node, full_cfg, batch=args.batch,
                                 in_len=512, out_len=args.max_new)
        p = best.plan
        print(f"[planner] {full_cfg.name} on {args.plan_chips}x v5e: "
              f"tp={p.tp} pp={p.pp} dp={p.dp} ep={p.ep}  "
              f"pred latency={best.latency * 1e3:.1f}ms  "
              f"pred throughput={best.throughput:.0f} tok/s  "
              f"mem/chip={best.memory_per_device / 2 ** 30:.2f}GiB")
    except planner.NoFittingPlan as e:
        print(f"[planner] {e}")

    # 2) serve the (preset) model locally
    cfg = preset_config(full_cfg, args.preset)
    params = models.init_params(cfg, jax.random.PRNGKey(0))
    eng = Engine(cfg, params, batch_size=args.batch, max_len=args.max_len)
    sampling = SamplingParams(temperature=args.temperature, top_k=40)
    reqs = [Request(uid=i, prompt=[(7 * i + j) % cfg.vocab_size
                                   for j in range(5 + i % 7)],
                    max_new_tokens=max(1, args.max_new >> (i % 2)),
                    sampling=sampling)
            for i in range(args.requests)]
    t0 = time.perf_counter()
    done = eng.run(reqs)
    dt = time.perf_counter() - t0
    for r in done[: min(4, len(done))]:
        print(f"req {r.uid}: prompt={r.prompt} -> {r.output}")
    print(f"served {len(done)} requests, {eng.stats['tokens_out']} tokens "
          f"in {dt:.2f}s wall, {eng.stats['tokens_out'] / dt:.1f} tok/s")
    return eng, done


if __name__ == "__main__":
    main()
