#!/usr/bin/env python3
"""Chip smoke run: serve qwen3-1.7b at full width on one TPU, then check it.

    python3 chip_smoke.py

1. Drives the user entry point `repro.launch.serve.main` (planner report,
   Engine, SlotScheduler, KV cache) with qwen3-1.7b at `--preset full`:
   28 layers at published widths, random bf16 weights from seed 0.
   8 requests over 4 slots with mixed budgets, so both admission paths
   run: the whole-batch prefill and the per-slot refill (batch-1 prefill
   scattered into the slot).
2. Checks, on the same chip and weights, the engine's own jitted programs
   against `models.forward` (teacher-forced logits of the same tokens):
   the whole-batch prefill logits of a right-padded prompt, the refill
   prefill logits, and one decode step from a refilled slot against
   forward on the extended sequence. Every logit must be finite and every
   request must get its `max_new_tokens`.

Exits non-zero, and prints no result, where JAX finds no TPU or the
repository's `src/` is not beside this file. It starts no child process.
The lines before the last are smoke output, not benchmark numbers. The
last line is one JSON object:
    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

SERVE_ARGV = ["--arch", "qwen3-1.7b", "--preset", "full", "--batch", "4",
              "--requests", "8", "--max-len", "1024", "--max-new", "8"]

# Bound on the relative RMS error of a row of logits, |engine - forward| /
# |forward| in the 2-norm. Both sides run the same bf16 weights on the same
# chip; they differ in the attention and cache code paths, in batch shape,
# and in where the bf16 residual stream is rounded (batch shape alone moves
# a 12-layer model's logits by ~1e-2 on XLA's CPU backend). A wrong context
# (one prompt token changed) must miss by more than TOL and by at least
# CONTRAST times the observed error, so that the check can tell. Only the
# real vocabulary is compared: the padded entries are -1e30 on both sides
# and would swamp both norms.
TOL = 5e-2
CONTRAST = 4.0


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


class CompileLog:
    """Backend compiles (with persistent-cache lookups), their seconds and
    persistent-cache hits, from jax.monitoring events."""

    # jax._src.dispatch.BACKEND_COMPILE_EVENT: one per backend compile,
    # persistent-cache lookup included
    COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self, jax):
        self.count, self.seconds, self.cache_hits = 0, 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._hit)

    def _duration(self, event, duration, **_):
        if event == self.COMPILE_EVENT:
            self.count += 1
            self.seconds += duration

    def _hit(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def rel_err(a, b, vocab: int) -> float:
    import numpy as np
    a = np.asarray(a, np.float32)[:vocab]
    b = np.asarray(b, np.float32)[:vocab]
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def check_logits(eng, wave, refill) -> dict:
    """Relative errors of the engine's prefill/refill/decode logits
    against `models.forward` on the same tokens."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro import models

    cfg, params, B, T = eng.cfg, eng.params, eng.B, eng.max_len
    fwd = jax.jit(lambda p, t: models.forward(cfg, p, t, remat=False)[0])
    V = cfg.vocab_size

    def ref(tokens):
        return np.asarray(fwd(params, jnp.asarray([tokens], jnp.int32))[0, -1],
                          np.float32)

    def finite(x, what):
        if not np.all(np.isfinite(np.asarray(x, np.float32))):
            fail(f"non-finite logits in {what}")
        return x

    errs = {}
    # whole-batch prefill: row 0 is right-padded to the wave's longest prompt
    S = max(len(r.prompt) for r in wave)
    toks = np.zeros((B, S), np.int32)
    lens = np.ones((B,), np.int32)
    for i, r in enumerate(wave):
        toks[i, :len(r.prompt)] = r.prompt
        lens[i] = len(r.prompt)
    logits, _ = eng._prefill(params, jnp.asarray(toks),
                             models.init_cache(cfg, B, T), jnp.asarray(lens),
                             None)
    errs["wave_prefill"] = rel_err(finite(logits[0], "wave prefill"),
                                   ref(wave[0].prompt), V)

    # refill: batch-1 prefill, scatter into the last slot, one decode step
    slot = B - 1
    one = models.init_cache(cfg, 1, T)
    logits1, one = eng._prefill(
        params, jnp.asarray([refill.prompt], jnp.int32), one,
        jnp.asarray([len(refill.prompt)], jnp.int32), None)
    errs["refill_prefill"] = rel_err(finite(logits1[0], "refill prefill"),
                                     ref(refill.prompt), V)
    nxt = int(np.argmax(np.asarray(logits1[0])))
    cache = eng._insert(models.init_cache(cfg, B, T), one, slot=slot)
    tok = np.zeros((B,), np.int32)
    tok[slot] = nxt
    logits_d, _ = eng._decode(params, jnp.asarray(tok), cache)
    row = finite(logits_d[slot], "decode")
    ext = list(refill.prompt) + [nxt]
    errs["decode"] = rel_err(row, ref(ext), V)
    wrong = [(ext[0] + 1) % V] + ext[1:]
    errs["decode_vs_wrong_context"] = rel_err(row, ref(wrong), V)
    return errs


def main() -> int:
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repository sources at {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1

    from repro import models
    from repro.launch import compile_cache, serve

    cache_dir = compile_cache.enable()
    log = CompileLog(jax)
    print(f"smoke: device {dev.device_kind} x{len(devices)}; "
          f"compile cache {cache_dir}")

    t0 = time.perf_counter()
    eng, done = serve.main(SERVE_ARGV)
    wall = time.perf_counter() - t0
    B = eng.B
    short = [r.uid for r in done if len(r.output) != r.max_new_tokens]
    if len(done) != 8 or short:
        fail(f"served {len(done)} of 8 requests; short outputs: {short}")
    if eng.stats["waves"] < 1 or eng.stats["refills"] < 1:
        fail(f"both admission paths must run: {eng.stats}")
    print(f"smoke: {models.param_count(eng.params)} params; "
          f"{eng.stats['tokens_out']} tokens served in {wall:.3f}s wall "
          f"(compiles included), {eng.stats['waves']} batch prefill(s), "
          f"{eng.stats['refills']} refill(s), {eng.stats['steps']} decode "
          f"steps")

    errs = check_logits(eng, done[:B], done[B])
    print(f"smoke: logits relative RMS error vs models.forward "
          f"(tolerance {TOL}): " + ", ".join(f"{k}={v:.3e}"
                                            for k, v in errs.items()))
    worst = max(errs["wave_prefill"], errs["refill_prefill"], errs["decode"])
    if worst > TOL:
        fail(f"logits disagree with models.forward: {errs}")
    if errs["decode_vs_wrong_context"] <= max(TOL, CONTRAST * worst):
        fail(f"check cannot tell a wrong context apart: {errs}")

    stats = dev.memory_stats() or {}
    print(f"smoke: {log.count} compiles, {log.seconds:.3f}s compiling, "
          f"{log.cache_hits} persistent-cache hits; peak device bytes "
          f"{stats.get('peak_bytes_in_use', 'not reported')}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
