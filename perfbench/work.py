"""The yardstick of the work: the table of device peaks, the least time a
call's operations and bytes take on the chip, and helpers for counting
them.

Each configuration counts its own work. Its reference module
(references/<reference>.py) has `work(conf)`, built once before the
window, which returns an object with two methods, each returning
(flops, bytes):

  decode_round(ctx_lens)   one decode step of len(ctx_lens) live
                           sequences; ctx_lens are the keys each attends
                           over (its context including the new token)
  prefill(prompt_lens)     one prefill call over these prompts (a refill
                           is one prompt, a wave is several)

Counts come from shapes and the lengths the harness knows, never from the
program. They count what the work needs, not what the program moves
today: every weight read once per program call, the K/V of each live
sequence's actual context (not the cache's `max_len`), K/V written once,
and no copy of the cache. A call can therefore not read over 100% of its
roofline, and whatever today's code moves beyond this is headroom. An
architecture whose needed bytes depend on routing counts them from shapes,
and argues that the count is at most what the work needs.

A multiply-add counts as 2 operations.
"""
from __future__ import annotations

# Published peaks per chip, keyed by JAX's `device_kind`.
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "bytes_per_s": 819e9,
                    "source": "Google Cloud documentation, 'TPU v5e': "
                              "197 TFLOP/s bf16, 819 GB/s HBM"},
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


def causal_attention_flops(per_pair: float, n: int) -> float:
    """Attention over n new tokens that each attend to themselves and every
    token before them: n (n + 1) / 2 (query, key) pairs of `per_pair`
    operations each."""
    return per_pair * n * (n + 1) / 2.0


def least_seconds(flops: float, bytes_: float, peaks: dict) -> float:
    return max(flops / peaks["flops"], bytes_ / peaks["bytes_per_s"])


def bound(flops: float, bytes_: float, peaks: dict) -> str:
    return ("compute" if flops / peaks["flops"] >= bytes_ / peaks["bytes_per_s"]
            else "memory")
