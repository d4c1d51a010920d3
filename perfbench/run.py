#!/usr/bin/env python3
"""Run one benchmark cell once.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

from the root of a checkout that holds `src/` (the program under test) and
BENCHMARK.json. With --trace 0 the result carries the cell's end-to-end
metrics; with --trace 1 a separate run traces up to five seconds of its
window with the JAX profiler and carries the per-layer metrics, the
device's busy and traced seconds, and a breakdown of device operations and
idle gaps. Both check the served tokens against the plain reference.

Earlier lines say what the run did; the last lines on standard error give
each number compared beside its limit; the last line on standard output is
one JSON object (keys correct, attempted, failed, metrics, device, and
with --trace 1 breakdown; `check` comes last). Exits non-zero, printing no
result, where JAX finds no accelerator or fewer chips than the cell needs.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))
os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no logs outside the checkout

import check  # noqa: E402
import harness  # noqa: E402
import serving_stats  # noqa: E402
import reduce_trace as trace_red  # noqa: E402
import work  # noqa: E402


def read_metrics(run, metrics) -> dict:
    out = {}
    for m in metrics:
        reader = harness.load_module(harness.ROOT / "metrics"
                                     / f"{m['name']}.py",
                                     f"pb_metric_{m['name']}")
        v = reader.read(run)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def report(cell: harness.Cell, out: harness.Outcome, trace: bool,
           err=sys.stderr) -> dict:
    run = out.run
    say = print
    setup = run.setup
    say(f"perfbench: {cell.name}: window {run.w1 - run.w0:.3f}s; "
        f"compiles in window {out.compiles_in_window}")
    say(f"perfbench: setup_s {setup['setup_s']:.4f} = init "
        f"{setup['init_s']:.4f} + warm-up {setup['warmup_s']:.4f} + ramp "
        f"{setup['ramp_s']:.4f} (ramp seconds {setup['ramp_s']:.4f})")
    say(f"perfbench: samples: tpot {len(serving_stats.tpot_samples(run))}, "
        f"ttft {len(serving_stats.ttft_samples(run)) if run.open_loop else 0}"
        f"; window tokens {serving_stats.window_tokens(run)}")
    for kind in ("wave", "refill", "decode"):
        calls = run.calls_in_window(kind)
        if calls:
            f = sum(c.flops for c in calls)
            b = sum(c.bytes for c in calls)
            say(f"perfbench: {kind}: {len(calls)} calls, {f:.4e} flops, "
                f"{b:.4e} bytes needed; "
                f"{work.bound(f, b, run.peaks)} bound")
    calls = run.calls_in_window()
    if calls:
        slow = max(calls, key=lambda c: c.t1 - c.t0)
        gap = max((b.t0 - a.t1 for a, b in zip(calls, calls[1:])),
                  default=0.0)
        say(f"perfbench: slowest call in window {slow.kind}#{slow.index} "
            f"{(slow.t1 - slow.t0) * 1e3:.3f} ms at "
            f"{slow.t0 - run.w0:.3f}s; longest gap between calls "
            f"{gap * 1e3:.3f} ms")
    say(f"perfbench: peak device bytes {out.memory_peak_bytes}")
    say(f"perfbench: check: {out.check['requests']} requests, "
        f"{out.check['tokens']} served tokens compared")

    metrics = read_metrics(run, cell.per_layer if trace else cell.end_to_end)
    att = serving_stats.attempted(run)
    failed = sum(1 for r in att if r.failed)
    limit = float(cell.params["logit_gap_limit"])
    gap = out.check["logit_gap"]
    correct = check.verdict(out.check, failed, limit)
    dev = out.devices[0]
    import jax
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": out.memory_peak_bytes}
    result = {"correct": correct, "attempted": len(att), "failed": failed,
              "metrics": metrics, "device": device}
    if trace:
        if not run.has_device_trace():
            raise SystemExit("perfbench: the trace holds no device operations")
        lo, hi = run.trace.window()
        device["busy_s"] = run.device_seconds(lo, hi)
        device["window_s"] = (hi - lo) / 1e9
        busy0 = run.busy()[0]
        result["breakdown"] = {
            "device_ops": [[n, s / 1e9] for n, s in trace_red.top_ops(
                run.trace.device_ops[0], lo, hi)],
            "idle_gaps": [[n, s / 1e9] for n, s in sorted(
                trace_red.idle_by_span(busy0, run.trace.calls(), lo,
                                       hi).items(),
                key=lambda kv: -kv[1])[:10]]}
    result["check"] = {"logit_gap": {"value": gap, "limit": limit},
                       "failed_requests": {"value": failed, "limit": 0}}
    sys.stdout.flush()
    print(f"check: logit_gap {gap!r} limit {limit!r}", file=err)
    print(f"check: failed_requests {failed} limit 0", file=err)
    err.flush()
    print(json.dumps(result))
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           t_start=T_START)
    report(cell, out, bool(args.trace))
    return 0


if __name__ == "__main__":
    sys.exit(main())
