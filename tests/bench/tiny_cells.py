"""Small cells for the benchmark's CPU tests, driven through the same
harness as the chip cells.

CONF is the Qwen3 block at toy widths: fast, for the driver and the
compile count. WIDE is wide enough (d 768) that the next token is not
simply the input token repeated, as it is in a toy model with random
weights, and its attention is sharp (scores spread by 4), so that the
context decides the token. The correctness tests need both, or a broken
decode would still serve the right tokens."""
import harness

CONF = {
    "name": "tiny-qwen3", "source": "test", "reference": "qwen",
    "program": "qwen_program", "model_type": "qwen3", "hidden_act": "silu",
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "intermediate_size": 128, "num_hidden_layers": 2,
    "vocab_size": 512, "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
    "tie_word_embeddings": True,
}

WIDE = dict(CONF, name="wide-qwen3", hidden_size=768, num_attention_heads=12,
            num_key_value_heads=4, head_dim=64, intermediate_size=1536,
            num_hidden_layers=4, vocab_size=4096,
            assumed={"attention_score_std": 4.0})

MIX = {
    "arrivals": "backlog", "block": 16,
    "prompt": {"median": 12, "sigma": 0.6, "min": 4, "max": 32,
               "buckets": [8, 16, 32]},
    "output": {"median": 6, "sigma": 0.6, "min": 2, "max": 12},
    "ramp": "refill_every_slot", "check_tokens": 60,
}

OPEN_MIX = dict(MIX, arrivals="poisson", ramp="seconds:0.5")

PEAKS = {"flops": 1e12, "bytes_per_s": 1e11}

# Limit of the logit gap for WIDE on the CPU. Readings on seeds 1-5 and 31
# (MIX, 0.5 s windows, 51-55 tokens compared): the bf16 program 0 to
# 0.066, the int8 control 0.058 to 0.331, the fp8 control 0.438 to 0.892.
# On seeds 1-5 with the control test's longer answers (5 s windows, 120
# tokens compared, beside a loaded test run): the program 0.010 to 0.057,
# int8 0.158 to 0.397, fp8 0.685 to 0.930.
WIDE_LIMIT = 0.2


def cell(conf=None, mix=None, **params):
    p = {"slots": 4, "max_len": 48, "rate": 40.0,
         "logit_gap_limit": WIDE_LIMIT}
    p.update(params)
    return harness.Cell(name="tiny", chips=1, conf=dict(conf or CONF),
                        mix=dict(mix or MIX), params=p, end_to_end=[],
                        per_layer=[])


def run(c, seed, seconds=0.5, trace=False, controls=()):
    import time
    return harness.run_cell(c, seed, seconds, trace,
                            t_start=time.perf_counter(), require_chip=False,
                            peaks=PEAKS, controls=controls,
                            compile_cache=False)
