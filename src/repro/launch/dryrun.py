import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"
"""Multi-pod dry-run: lower + compile every (architecture x input shape)
cell on the production meshes and record memory / FLOPs / collective bytes.

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-1.7b \
        --shape decode_32k --mesh single            # one cell
    PYTHONPATH=src python -m repro.launch.dryrun --all [--mesh both]

Results append to experiments/dryrun/<arch>__<shape>__<mesh>.json; the
roofline table (EXPERIMENTS.md §Roofline) is generated from these files by
launch/roofline_report.py. Cells already on disk are skipped unless
--force.

The FIRST THREE LINES of this file must stay first: jax locks the device
count at first init, and the dry-run (and only the dry-run) needs 512
placeholder CPU devices. It pins the CPU backend so that, on a machine
with an accelerator, it never takes the chip.
"""
import argparse
import json
import time
import traceback

import jax

from ..configs import ARCHS, SHAPES, get_config, shape_applicable
from ..configs.base import ModelConfig, ShapeConfig
from .. import models
from ..distributed import sharding as shd
from ..training import AdamW, constant_schedule
from ..training.train_step import TrainState
from . import analysis
from .mesh import make_production_mesh

OUT_DIR = "experiments/dryrun"


def _decode_max_len(shape: ShapeConfig) -> int:
    return shape.seq_len


def build_lowerable(cfg: ModelConfig, shape: ShapeConfig, mesh,
                    microbatches: int = 8, kv_mode: str = "channel"):
    """Returns (jitted fn, example args as ShapeDtypeStructs).

    Training uses microbatched gradient accumulation (microbatches=8 ->
    32-sequence microbatches at global batch 256): activation memory scales
    with the microbatch, gradients accumulate in fp32 at parameter
    sharding. §Perf iteration 3."""
    specs = models.input_specs(cfg, shape)
    shd.set_model_config(cfg)
    params_abs = models.abstract_params(cfg)
    p_shard = shd.param_shardings(mesh, params_abs)
    d_shard = shd.data_shardings(mesh, specs)

    if shape.kind == "train":
        opt = AdamW(lr=constant_schedule(1e-4))
        opt_abs = jax.eval_shape(opt.init, params_abs)
        o_shard = shd.opt_state_shardings(mesh, opt_abs)
        from ..training.train_step import make_train_step
        step = make_train_step(cfg, opt, microbatches=microbatches,
                               has_frontend=models.needs_frontend(cfg))
        state_abs = TrainState(params_abs, opt_abs)
        state_shard = TrainState(p_shard, o_shard)
        fn = jax.jit(step,
                     in_shardings=(state_shard, d_shard),
                     donate_argnums=(0,))
        return fn, (state_abs, specs)

    cache_len = _decode_max_len(shape) if shape.kind == "decode" \
        else shape.seq_len + 128
    cache_abs = models.abstract_cache(cfg, shape.global_batch, cache_len)
    c_shard = shd.cache_shardings(mesh, cache_abs, shape.global_batch,
                                  kv_mode=kv_mode)

    if shape.kind == "prefill":
        def prefill_step(params, batch, cache):
            return models.prefill(cfg, params, batch["tokens"], cache,
                                  frontend=batch.get("frontend"))
        fn = jax.jit(prefill_step,
                     in_shardings=(p_shard, d_shard, c_shard),
                     donate_argnums=(2,))
        return fn, (params_abs, specs, cache_abs)

    # decode: one new token against a seq_len-deep cache
    def serve_step(params, batch, cache):
        return models.decode_step(cfg, params, batch["token"], cache)

    fn = jax.jit(serve_step,
                 in_shardings=(p_shard, d_shard, c_shard),
                 donate_argnums=(2,))
    return fn, (params_abs, specs, cache_abs)


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             out_dir: str = OUT_DIR, force: bool = False,
             verbose: bool = True, microbatches: int = 8,
             kv_mode: str = "channel") -> dict:
    import os as _os
    _os.makedirs(out_dir, exist_ok=True)
    suffix = "" if kv_mode == "channel" else f"__kv-{kv_mode}"
    path = _os.path.join(out_dir,
                         f"{arch}__{shape_name}__{mesh_kind}{suffix}.json")
    if _os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    if not shape_applicable(cfg, shape):
        rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
               "skipped": True,
               "reason": "long_500k needs sub-quadratic attention "
                         "(DESIGN.md Sec. 5)"}
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
        return rec

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_dev = mesh.devices.size
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
           "devices": int(n_dev), "kind": shape.kind}
    rec["microbatches"] = microbatches if shape.kind == "train" else 1
    rec["kv_mode"] = kv_mode
    try:
        # NOTE: the legacy `with mesh:` context is deliberate. Under
        # set_mesh the in-model with_sharding_constraint helpers activate,
        # and measured cells REGRESSED (granite prefill: 22.8 -> 102.6 GiB,
        # collectives 682 -> 2187 GiB): GSPMD's own propagation from the
        # parameter/input shardings beats our hand constraints. Recorded as
        # a refuted hypothesis in EXPERIMENTS.md §Perf.
        with mesh:
            fn, args = build_lowerable(cfg, shape, mesh,
                                       microbatches=microbatches,
                                       kv_mode=kv_mode)
            lowered = fn.lower(*args)
            t_lower = time.time()
            compiled = lowered.compile()
            t_compile = time.time()
            mem = analysis.memory_summary(compiled)
            cost = analysis.cost_summary(compiled)
            hlo_text = compiled.as_text()
            coll = analysis.collective_bytes(hlo_text)
            hcost = analysis.hlo_costs(hlo_text)
            # keep the HLO for later re-analysis (gzip, ~100KB each)
            import gzip
            _os.makedirs(_os.path.join(out_dir, "hlo"), exist_ok=True)
            with gzip.open(_os.path.join(
                    out_dir, "hlo",
                    f"{arch}__{shape_name}__{mesh_kind}.txt.gz"), "wt") as zf:
                zf.write(hlo_text)
        rec.update({
            "ok": True,
            "lower_s": round(t_lower - t0, 2),
            "compile_s": round(t_compile - t_lower, 2),
            "memory": mem,
            "cost": cost,
            "hlo_cost": hcost,          # trip-count-aware flops/bytes
            "collectives": {"bytes": coll.total_bytes,
                            "count": coll.count,
                            "by_kind": coll.by_kind},
            "bytes_per_device": mem["total_bytes"],
            "params": cfg.param_count(),
            "active_params": cfg.active_param_count(),
            "tokens": shape.tokens if shape.kind != "decode"
            else shape.global_batch,
        })
        if verbose:
            print(f"[{arch} | {shape_name} | {mesh_kind}] OK  "
                  f"compile={rec['compile_s']}s  "
                  f"mem/dev={mem['total_bytes']/2**30:.2f}GiB  "
                  f"flops={cost['flops']:.3e}  "
                  f"coll={coll.total_bytes/2**20:.1f}MiB")
    except Exception as e:  # noqa: BLE001 — record the failure, keep going
        rec.update({"ok": False, "error": f"{type(e).__name__}: {e}",
                    "traceback": traceback.format_exc()[-2000:]})
        if verbose:
            print(f"[{arch} | {shape_name} | {mesh_kind}] FAIL {e}")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), default=None)
    ap.add_argument("--shape", choices=sorted(SHAPES), default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--microbatches", type=int, default=8)
    ap.add_argument("--kv-shard", choices=["channel", "sequence", "auto"],
                    default="channel")
    ap.add_argument("--out", default=OUT_DIR)
    args = ap.parse_args()

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    cells = []
    if args.all:
        for arch in ARCHS:
            for shape in SHAPES:
                for m in meshes:
                    cells.append((arch, shape, m))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        for m in meshes:
            cells.append((args.arch, args.shape, m))

    n_ok = n_fail = n_skip = 0
    for arch, shape, m in cells:
        rec = run_cell(arch, shape, m, out_dir=args.out, force=args.force,
                       microbatches=args.microbatches,
                       kv_mode=args.kv_shard)
        if rec.get("skipped"):
            n_skip += 1
        elif rec.get("ok"):
            n_ok += 1
        else:
            n_fail += 1
    print(f"dry-run: {n_ok} ok, {n_fail} failed, {n_skip} skipped "
          f"(inapplicable cells)")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
