#!/usr/bin/env python3
"""Readings that set a cell's limits and rate; not part of a benchmark run.

    python3 perfbench/calibrate.py --workload <name> --seeds 1,2,3 \
        --seconds 8 [--controls int8,fp8,bf16] [--fault <name>] \
        [--assume attention_score_std=4] [--rates 10,20,30]

For each seed, in one process: a run of the cell with a short window, then
the widest logit gap of the program's served tokens (the number `correct`
compares) and, for each control, the widest gap of the token that the
reference at that lower precision puts first, on the same sample. Each
gap goes through the verdict that decides `correct` (check.verdict), at
the cell's limit. The lower reading of a limit is the largest program gap
over a dozen seeds or more; the upper reading is the smallest control gap.

--fault plants one of perfbench/faults.py's faults in the timed path
before the runs, so its reading is taken at the cell's own size.
--assume overrides one of the configuration's `assumed` numbers (the
attention scores' spread), for readings of what that assumption does.
`bf16` among the controls is a witness, not a control: the reference
rounded as a bfloat16 program rounds.

With --rates (open-loop cells), the first seed is run at each arrival rate
instead, and the queue's length at the window's start and end shows the
highest rate at which it does not grow: the knee.

Prints one JSON line per run.
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
import faults  # noqa: E402
import harness  # noqa: E402
import run as bench_run  # noqa: E402
import serving_stats  # noqa: E402


def summarize(cell: harness.Cell, out: harness.Outcome, **extra) -> dict:
    run = out.run
    failed = sum(1 for r in serving_stats.attempted(run) if r.failed)
    limit = float(cell.params["logit_gap_limit"])

    def judged(gap, failed):
        return {"logit_gap": gap, "limit": limit,
                "correct": check.verdict(dict(out.check, logit_gap=gap),
                                         failed, limit)}

    row = dict(extra)
    row.update(
        program=judged(out.check["logit_gap"], failed),
        controls={q: judged(g, 0) for q, g in out.check["controls"].items()},
        tokens_compared=out.check["tokens"],
        requests_compared=out.check["requests"], failed=failed,
        compiles_in_window=out.compiles_in_window,
        metrics={k: v["value"] for k, v in
                 bench_run.read_metrics(run, cell.end_to_end).items()},
        setup=run.setup, memory_peak_bytes=out.memory_peak_bytes)
    if run.open_loop:
        due = serving_stats.due_in_window(run)
        row["queue_at_start"] = sum(
            1 for r in run.requests
            if r.due <= run.w0 and (r.admit is None or r.admit > run.w0))
        row["queue_at_end"] = sum(
            1 for r in run.requests
            if r.due <= run.w1 and (r.admit is None or r.admit > run.w1))
        row["due_in_window"] = len(due)
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--controls", default="")
    ap.add_argument("--fault", choices=sorted(faults.FAULTS))
    ap.add_argument("--assume", action="append", default=[])
    ap.add_argument("--rates", default="")
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    for item in args.assume:
        key, value = item.split("=")
        cell.conf.setdefault("assumed", {})[key] = float(value)
    if args.fault:
        if str(harness.SRC) not in sys.path:
            sys.path.insert(0, str(harness.SRC))
        faults.FAULTS[args.fault](setattr)
    seeds = [int(s) for s in args.seeds.split(",")]
    controls = tuple(c for c in args.controls.split(",") if c)
    if args.rates:
        runs = [(seeds[0], float(r)) for r in args.rates.split(",")]
    else:
        runs = [(s, None) for s in seeds]
    t_start = T_START
    for seed, rate in runs:
        if rate is not None:
            cell.params["rate"] = rate
        out = harness.run_cell(cell, seed, args.seconds, False,
                               t_start=t_start, controls=controls)
        print(json.dumps(summarize(cell, out, seed=seed, rate=rate,
                                   fault=args.fault, assume=args.assume)),
              flush=True)
        t_start = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
