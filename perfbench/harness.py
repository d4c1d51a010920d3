"""The benchmark harness: one cell, one seed, one run.

A cell (an entry of BENCHMARK.json's `workloads`) is found by name, and
everything it needs comes from files named after it:

  configs/<config>.json       Hugging Face style sizes, plus which
                              reference (references/<reference>.py) and
                              program mapping (references/<program>.py)
                              to use
  references/<reference>.py   the plain model, and `work(conf)`: the
                              operations and bytes each prefill call and
                              decode round needs (see work.py), which
                              the rooflines and `step_mfu` read
  traffic/<traffic>.json      the mix, read by traffic.py
  cells/<workload>.json       slots, max_len, arrival rate, and the limit
                              of the correctness comparison
  metrics/<metric>.py         one reader per metric: read(run) -> number
                              or None

The run builds the program's `Engine` for the configuration and drives its
own `admit_wave` and `decode_round` calls: the loop of `Engine.run`, with
requests admitted as they come due. Set-up makes the weights on the device
from the seed, warms every program the mix can reach, and ramps the
traffic until every slot has been refilled once (backlog mixes) or for a
fixed time (open-loop mixes). Then the window is measured. After it, the
program's state is freed and a sample of the finished requests is
compared with the plain float32 reference (check.py). Every request is
served greedily: one sampling setting, one sampler program per live-slot
count, and every served token can be checked.
"""
from __future__ import annotations

import gc
import importlib
import importlib.util
import json
import os
import statistics
import sys
import time
from collections import deque
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

import traffic as traffic_gen
import reduce_trace as trace_red
import work

ROOT = Path(__file__).resolve().parent          # perfbench/
CHECKOUT = ROOT.parent
SRC = CHECKOUT / "src"
TRACE_DIR = CHECKOUT / ".perfbench" / "trace"
TRACE_WINDOW_S = 5.0       # a traced run traces at most this much window
RAMP_LIMIT_S = 300.0


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


@dataclass
class Cell:
    name: str
    chips: int
    conf: dict
    mix: dict
    params: dict                      # cells/<name>.json
    end_to_end: List[dict]
    per_layer: List[dict]


def load_cell(workload: str, bench_path: Path = CHECKOUT / "BENCHMARK.json"
              ) -> Cell:
    bench = json.loads(bench_path.read_text())
    wl = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if wl is None:
        raise KeyError(f"no workload {workload!r} in {bench_path}")
    cfg_entry = next(c for c in bench["configs"] if c["name"] == wl["config"])
    return Cell(
        name=workload, chips=int(wl["chips"]),
        conf=json.loads((CHECKOUT / cfg_entry["file"]).read_text()),
        mix=json.loads((ROOT / "traffic" / f"{wl['traffic']}.json")
                       .read_text()),
        params=json.loads((ROOT / "cells" / f"{workload}.json").read_text()),
        end_to_end=[m for m in bench["end_to_end"] if applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if applies(m, workload)])


# ---------------------------------------------------------------------------
# what a run records
# ---------------------------------------------------------------------------

@dataclass
class Call:
    kind: str                 # wave | refill | decode
    index: int
    t0: float
    t1: float
    n: int                    # prompts prefilled, or live slots decoded
    flops: float
    bytes: float


@dataclass
class ReqRec:
    gen: traffic_gen.GenRequest
    req: object                     # the program's Request
    due: float                      # host clock
    admit: Optional[float] = None
    tokens: List[float] = field(default_factory=list)
    failed: bool = False


@dataclass
class Run:
    """Everything a metric reader may read. Host times are
    time.perf_counter() seconds; trace times are nanoseconds."""
    B: int
    open_loop: bool
    w0: float
    w1: float
    requests: List[ReqRec]
    calls: List[Call]
    setup: Dict[str, float]
    peaks: Optional[dict]
    trace: Optional[trace_red.TraceData] = None

    def calls_in_window(self, kind: Optional[str] = None) -> List[Call]:
        return [c for c in self.calls if c.t0 >= self.w0 and c.t0 < self.w1
                and (kind is None or c.kind == kind)]

    def has_device_trace(self) -> bool:
        return self.trace is not None and bool(self.trace.device_ops)

    def traced_calls(self):
        """(Call, span start, span end) of every call inside the traced
        window, in trace time."""
        lo, hi = self.trace.window()
        by_index = {c.index: c for c in self.calls}
        out = []
        for name, s, e in self.trace.calls():
            if s >= lo and e <= hi:
                c = by_index.get(trace_red.span_index(name))
                if c is not None:
                    out.append((c, s, e))
        return out

    def busy(self) -> List[trace_red.Busy]:
        if not hasattr(self, "_busy"):
            self._busy = [trace_red.Busy([(s, e) for _, s, e in ops])
                          for ops in self.trace.device_ops]
        return self._busy

    def device_seconds(self, lo: float, hi: float) -> float:
        """Busy device seconds in [lo, hi] (trace ns), averaged over
        the device planes."""
        b = self.busy()
        return sum(x.within(lo, hi) for x in b) / len(b) / 1e9


class CompileCounter:
    """Backend compiles (persistent-cache lookups included), counted by
    JAX's monitoring events."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self, jax):
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.count += 1


# ---------------------------------------------------------------------------
# the engine driver
# ---------------------------------------------------------------------------

class Driver:
    """The loop of `Engine.run`, with requests admitted as they come due,
    and a timestamp on every token when the call that made it returns."""

    def __init__(self, engine, Request, mix, seed, vocab, rate, counts,
                 clock=time.perf_counter):
        import jax
        self.jax = jax
        self.engine = engine
        self.Request = Request
        self.clock = clock
        self.counts = counts
        self.backlog = mix["arrivals"] == "backlog"
        self.stream = traffic_gen.stream(mix, seed, vocab, rate)
        self.next_gen = None
        self.waiting: deque = deque()
        self.recs: Dict[int, ReqRec] = {}
        self.calls: List[Call] = []
        self.t_sched = None

    def _make(self, g: traffic_gen.GenRequest) -> ReqRec:
        req = self.Request(uid=g.index, prompt=g.prompt,
                           max_new_tokens=g.out_len, eos_id=-1)
        rec = ReqRec(gen=g, req=req, due=self.t_sched + g.due)
        self.recs[g.index] = rec
        return rec

    def _release_due(self, now: float):
        if self.backlog:
            while len(self.waiting) < 2 * self.engine.B:
                self.waiting.append(self._make(next(self.stream)))
            return
        while True:
            if self.next_gen is None:
                self.next_gen = next(self.stream)
            if self.t_sched + self.next_gen.due > now:
                return
            self.waiting.append(self._make(self.next_gen))
            self.next_gen = None

    def next_due(self) -> float:
        if self.next_gen is None:
            self.next_gen = next(self.stream)
        return self.t_sched + self.next_gen.due

    def _finish(self, rec: ReqRec):
        if rec.req.done and len(rec.req.output) != rec.req.max_new_tokens:
            rec.failed = True

    def step(self, until: float):
        """One iteration: admit what is due into free slots, then one
        decode round over the live slots."""
        eng, clock = self.engine, self.clock
        now = clock()
        self._release_due(now)
        slots = eng.slot_req
        if self.waiting and any(r is None for r in slots):
            kind = "wave" if all(r is None for r in slots) else "refill"
            idx = len(self.calls)
            pending = [rec.req for rec in self.waiting]
            t0 = clock()
            with self.jax.profiler.TraceAnnotation(f"pb.{kind}#{idx}"):
                admitted = eng.admit_wave(pending)
            t1 = clock()
            lens = []
            for _ in admitted:
                rec = self.waiting.popleft()
                rec.admit = t0
                rec.tokens.append(t1)
                lens.append(len(rec.req.prompt))
                self._finish(rec)
            f, b = self.counts.prefill(lens)
            self.calls.append(Call(kind, idx, t0, t1, len(lens), f, b))
        live = [r for r in eng.slot_req if r is not None]
        if live:
            ctx = [len(r.prompt) + len(r.output) for r in live]
            idx = len(self.calls)
            t0 = clock()
            with self.jax.profiler.TraceAnnotation(f"pb.decode#{idx}"):
                eng.decode_round()
            t1 = clock()
            for r in live:
                rec = self.recs[r.uid]
                rec.tokens.append(t1)
                self._finish(rec)
            f, b = self.counts.decode_round(ctx)
            self.calls.append(Call("decode", idx, t0, t1, len(live), f, b))
        elif not self.waiting:
            wait = min(self.next_due(), until) - clock()
            if wait > 0:
                time.sleep(wait)


def warm_up(engine, Request, mix, max_len: int, open_loop: bool):
    """Run every program the mix can reach once, through the engine's own
    admit_wave and decode_round: the whole-batch prefill at every prompt
    length; the sampler and slot gather at every live-slot count the
    window can see (all B in a backlog window, 1..B in an open-loop one);
    the batch-1 refill at every prompt length, with its insert into every
    slot. Leaves every slot free."""
    B = engine.B
    buckets = sorted(mix["prompt"]["buckets"])
    uid = iter(range(-1, -10**9, -1))

    def req(L, budget):
        return Request(uid=next(uid), prompt=[(7 * i + 3) % 1000
                                              for i in range(L)],
                       max_new_tokens=budget, eos_id=-1)

    def drain():
        while any(r is not None for r in engine.slot_req):
            engine.decode_round()

    for L in buckets:
        engine.admit_wave([req(L, 1) for _ in range(B)])
    for n in (range(1, B + 1) if open_loop else [B]):
        engine.admit_wave([req(buckets[0], 2) for _ in range(n)])
        drain()
    # slot i finishes after i + 1 rounds; each freed slot is refilled at
    # once with a long request, so one slot is free at a time
    engine.admit_wave([req(buckets[0], i + 2) for i in range(B)])
    for r in range(B):
        engine.decode_round()
        L = buckets[r % len(buckets)]
        engine.admit_wave([req(L, min(B + 1, max_len - L))])
    drain()


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def find_devices(jax, chips: int, require_chip: bool):
    devices = jax.devices()
    if require_chip:
        if devices[0].platform not in ("tpu", "gpu"):
            raise SystemExit(f"perfbench: needs an accelerator, JAX found "
                             f"{devices[0].platform!r}")
        if len(devices) < chips:
            raise SystemExit(f"perfbench: the cell needs {chips} chips, JAX "
                             f"found {len(devices)}")
    return devices


def enable_compile_cache(jax):
    """JAX's persistent compile cache at a fixed path inside the checkout,
    unless JAX_COMPILATION_CACHE_DIR names one. Small programs are cached
    too, so a second run compiles nothing."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(CHECKOUT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def sub_seeds(seed: int) -> Dict[str, int]:
    """32-bit seeds for each consumer, drawn from the run's seed."""
    w, e, c = np.random.SeedSequence([seed, 0]).generate_state(3)
    return {"weights": int(w), "engine": int(e) & 0x7FFFFFFF,
            "check": int(c)}


@dataclass
class Outcome:
    run: Run
    check: dict
    compiles_in_window: int
    memory_peak_bytes: Optional[int]
    devices: list


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_chip: bool = True,
             peaks: Optional[dict] = None, controls=(),
             compile_cache: bool = True) -> Outcome:
    import jax
    import check as checker

    devices = find_devices(jax, cell.chips, require_chip)
    if peaks is None:
        peaks = work.peaks_for(devices[0].device_kind)
    if compile_cache:
        enable_compile_cache(jax)
    counter = CompileCounter(jax)

    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))
    from repro import models
    from repro.serving.engine import Engine, Request

    conf, mix, cp = cell.conf, cell.mix, cell.params
    ref = importlib.import_module(f"references.{conf['reference']}")
    if not callable(getattr(ref, "work", None)):
        raise SystemExit(f"perfbench: the reference module {ref.__name__} "
                         "has no work(conf), the count of the operations "
                         "and bytes each call needs (see work.py)")
    counts = ref.work(conf)
    prog = importlib.import_module(f"references.{conf['program']}")
    cfg = prog.program_config(conf)
    seeds = sub_seeds(seed)
    B, max_len = int(cp["slots"]), int(cp["max_len"])
    open_loop = mix["arrivals"] != "backlog"

    abstract = models.abstract_params(cfg)
    padded = abstract["embed"].shape[0]
    make = jax.jit(lambda k: prog.program_params(
        conf, ref.init_weights(conf, k), padded))
    key = jax.random.PRNGKey(seeds["weights"])
    ours = jax.eval_shape(make, key)
    if (jax.tree.structure(ours) != jax.tree.structure(abstract)
            or any(a.shape != b.shape or a.dtype != b.dtype for a, b in
                   zip(jax.tree.leaves(ours), jax.tree.leaves(abstract)))):
        raise SystemExit("perfbench: the program's parameter tree is not "
                         "the one references/"
                         f"{conf['program']}.py builds")
    params = jax.block_until_ready(make(key))
    engine = Engine(cfg, params, B, max_len, seed=seeds["engine"])
    t_init = time.perf_counter()

    warm_up(engine, Request, mix, max_len, open_loop)
    jax.block_until_ready(engine.cache)
    t_warm = time.perf_counter()

    drv = Driver(engine, Request, mix, seed, conf["vocab_size"],
                 cp.get("rate"), counts)
    drv.t_sched = time.perf_counter()
    ramp = mix.get("ramp", "refill_every_slot")
    first_wave = None
    refilled = set()
    limit = drv.t_sched + RAMP_LIMIT_S
    while True:
        drv.step(limit)
        now = time.perf_counter()
        if now > limit:
            raise SystemExit("perfbench: the ramp did not end")
        if ramp == "refill_every_slot":
            slots = engine.slot_req
            if first_wave is None:
                first_wave = {r.uid for r in slots if r is not None}
            refilled |= {i for i, r in enumerate(slots)
                         if r is not None and r.uid not in first_wave}
            if len(refilled) == B:
                break
        elif now - drv.t_sched >= float(ramp.split(":")[1]):
            break
    t_ramp = time.perf_counter()

    window = min(seconds, TRACE_WINDOW_S) if trace else seconds
    if trace:
        import shutil
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        TRACE_DIR.mkdir(parents=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # the harness's own spans suffice
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    c0 = counter.count
    w0 = time.perf_counter()
    w1 = w0 + window
    with jax.profiler.TraceAnnotation("pb.window"):
        while time.perf_counter() < w1:
            drv.step(w1)
    compiles = counter.count - c0
    if trace:
        jax.profiler.stop_trace()

    stats = [d.memory_stats() or {} for d in devices[:cell.chips]]
    peak = max((s.get("peak_bytes_in_use", 0) for s in stats), default=0) \
        or None
    run = Run(B=B, open_loop=open_loop, w0=w0, w1=w1,
              requests=list(drv.recs.values()), calls=drv.calls,
              setup={"init_s": t_init - t_start, "warmup_s": t_warm - t_init,
                     "ramp_s": t_ramp - t_warm, "setup_s": w0 - t_start},
              peaks=peaks)
    if trace:
        run.trace = trace_red.read_profile(str(TRACE_DIR))

    # free the program's state before the reference runs
    finished = [r for r in run.requests if r.req.done and not r.failed]
    sample = checker.pick(finished, cell.mix.get("check_tokens", 300),
                          seeds["check"])
    served = [(list(r.req.prompt), list(r.req.output)) for r in sample]
    drv.engine = None
    del engine, params, drv
    gc.collect()
    result = checker.compare(ref, conf, seeds["weights"], served, max_len,
                             int(mix["output"]["max"]), controls)
    return Outcome(run=run, check=result, compiles_in_window=compiles,
                   memory_peak_bytes=peak, devices=devices[:cell.chips])


def percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, float), q))


def median(values) -> float:
    return float(statistics.median(values))
