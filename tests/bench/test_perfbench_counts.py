"""Each configuration counts its own work: the committed configurations'
counts, pinned bit for bit, and a configuration whose reference module
counts differently, driven through the harness."""
import json
import sys
import types

import pytest

import harness
import tiny_cells
from references import qwen

# Cell of each committed configuration: its traffic and cell parameters.
CELLS = {"qwen3-1.7b": ("chat-backlog", "qwen3-1.7b.chat-backlog"),
         "qwen2-0.5b": ("short-backlog", "qwen2-0.5b.short-backlog")}

# Matmul parameters per token, weight bytes, K/V bytes per token.
SIZES = {"qwen3-1.7b": (1409286144, 3441397760, 114688),
         "qwen2-0.5b": (357826560, 988153344, 12288)}

# One decode round of n live slots, over the contexts of `_contexts`.
DECODE = {
    "qwen3-1.7b": {1: (3910664192.0, 3676278784.0),
                   16: (59790589952.0, 5809475584.0),
                   32: (118729736192.0, 7751831552.0)},
    "qwen2-0.5b": {1: (1015447552.0, 992085504.0),
                   16: (16063430656.0, 1024820736.0),
                   32: (32121356288.0, 1060701696.0)},
}

# A prefill at each prompt bucket of the cell's traffic: one prompt (a
# refill), and one prompt in each slot (a wave).
PREFILL = {
    "qwen3-1.7b": {
        128: ((363293310976.0, 3456077824.0),
              (5812692975616.0, 3676278784.0)),
        256: ((729722388480.0, 3470757888.0),
              (11675558215680.0, 3911159808.0)),
        512: ((1473854832640.0, 3500118016.0),
              (23581677322240.0, 4380921856.0)),
        768: ((2233019662336.0, 3529478144.0),
              (35728314597376.0, 4850683904.0)),
        1024: ((3007216877568.0, 3558838272.0),
               (48115470041088.0, 5320445952.0)),
        1536: ((4600708464640.0, 3617558528.0),
               (73611335434240.0, 6259970048.0))},
    "qwen2-0.5b": {
        32: ((23218585600.0, 988546560.0),
             (742994739200.0, 1000736256.0)),
        64: ((46252982272.0, 988939776.0),
             (1480095432704.0, 1013319168.0)),
        128: ((92586016768.0, 989726208.0),
              (2962752536576.0, 1038484992.0)),
        256: ((186309050368.0, 991299072.0),
              (5961889611776.0, 1088816640.0))},
}

# 16 slots at 512 tokens of context, and one 512-token prompt.
AT_512 = {"qwen3-1.7b": ((56933482496.0, 4380921856.0),
                         (1473854832640.0, 3500118016.0)),
          "qwen2-0.5b": ((16511401984.0, 1088816640.0),
                         (377982976000.0, 994444800.0))}


def _load(name):
    traffic, cell = CELLS[name]
    return (json.loads((harness.ROOT / "configs" / f"{name}.json")
                       .read_text()),
            json.loads((harness.ROOT / "traffic" / f"{traffic}.json")
                       .read_text()),
            json.loads((harness.ROOT / "cells" / f"{cell}.json").read_text()))


def _contexts(mix, params, n):
    """n contexts spread over the cell's range, the longest first."""
    lo, hi = mix["prompt"]["min"] + 1, params["max_len"]
    return [hi - (i * 997) % (hi - lo + 1) for i in range(n)]


@pytest.mark.parametrize("name", sorted(SIZES))
def test_model_sizes_are_pinned(name):
    counts = qwen.work(_load(name)[0])
    assert (counts.matmul_params, counts.weight_bytes,
            counts.kv_bytes_per_token) == SIZES[name]


@pytest.mark.parametrize("name,n", [(name, n) for name in sorted(DECODE)
                                    for n in sorted(DECODE[name])])
def test_decode_round_counts_are_pinned(name, n):
    conf, mix, params = _load(name)
    assert qwen.work(conf).decode_round(_contexts(mix, params, n)) \
        == DECODE[name][n]


@pytest.mark.parametrize("name,p", [(name, p) for name in sorted(PREFILL)
                                    for p in sorted(PREFILL[name])])
def test_prefill_counts_are_pinned(name, p):
    conf, mix, params = _load(name)
    assert p in mix["prompt"]["buckets"]
    counts = qwen.work(conf)
    refill, wave = PREFILL[name][p]
    assert counts.prefill([p]) == refill
    assert counts.prefill([p] * params["slots"]) == wave


@pytest.mark.parametrize("name", sorted(AT_512))
def test_counts_at_512_tokens_are_pinned(name):
    counts = qwen.work(_load(name)[0])
    assert counts.decode_round([512] * 16) == AT_512[name][0]
    assert counts.prefill([512]) == AT_512[name][1]


def _reference(name, work=None):
    """A reference module registered where the harness looks it up: qwen's
    model, with `work` as its count, or no count at all."""
    mod = types.ModuleType(f"references.{name}")
    for attr in ("dims", "init_weights", "logits"):
        setattr(mod, attr, getattr(qwen, attr))
    if work is not None:
        mod.work = work
    return mod


def test_a_configuration_brings_its_own_counts(monkeypatch):
    seen = []

    class Doubled:
        """qwen's counts, doubled, each recorded as it is handed out."""

        def __init__(self, conf):
            self.base = qwen.work(conf)

        def _twice(self, kind, lens, fb):
            out = (2 * fb[0], 2 * fb[1])
            seen.append((kind, list(lens), out))
            return out

        def decode_round(self, ctx_lens):
            return self._twice("decode", ctx_lens,
                               self.base.decode_round(ctx_lens))

        def prefill(self, prompt_lens):
            return self._twice("prefill", prompt_lens,
                               self.base.prefill(prompt_lens))

    monkeypatch.setitem(sys.modules, "references.doubled",
                        _reference("doubled", Doubled))
    conf = dict(tiny_cells.CONF, reference="doubled")
    out = tiny_cells.run(tiny_cells.cell(conf), seed=5)
    calls = out.run.calls
    assert calls and len(calls) == len(seen)
    base = qwen.work(conf)
    for call, (kind, lens, fb) in zip(calls, seen):
        assert (call.kind == "decode") == (kind == "decode")
        assert call.n == len(lens)
        assert (call.flops, call.bytes) == fb
        plain = getattr(base, "decode_round" if kind == "decode"
                        else "prefill")(lens)
        assert fb == (2 * plain[0], 2 * plain[1])


def test_a_reference_without_counts_is_refused_when_the_cell_loads(
        monkeypatch):
    monkeypatch.setitem(sys.modules, "references.nocounts",
                        _reference("nocounts"))
    conf = dict(tiny_cells.CONF, reference="nocounts")
    with pytest.raises(SystemExit, match="references.nocounts"):
        tiny_cells.run(tiny_cells.cell(conf), seed=5)
