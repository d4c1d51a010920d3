"""The comparison that decides `correct`.

After the window, a sample of the (greedy) requests the engine finished is
drawn from the seed, the longest of them always in it, until it holds
`check_tokens` served tokens. The plain float32 reference (imported from
references/, it imports nothing of the program) runs once over each
prompt with its served tokens, teacher-forced. At each served position the
gap is the reference's best logit minus the reference's logit of the token
the engine served. The number compared is the widest gap over the sample.

This covers the whole served path at the timed sizes: the first token of a
request comes from the whole-batch wave prefill or from the batch-1 refill
and its insert into a slot; every later token from decode through the
cache, beside the other live slots.

`controls` computes, on the same sample, the gap of the token that the
reference in a lower precision puts first (see references/*.py `quant`);
`verdict` judges that gap as it judges the program's.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np


def pick(finished: Sequence, check_tokens: int, seed: int) -> List:
    """The longest finished request (by served tokens) and others drawn
    from the seed until `check_tokens` served tokens are in the sample."""
    if not finished:
        return []
    order = sorted(finished, key=lambda r: (-len(r.req.output), r.gen.index))
    sample, rest = [order[0]], order[1:]
    rng = np.random.default_rng(seed)
    total = len(order[0].req.output)
    for i in rng.permutation(len(rest)):
        if total >= check_tokens:
            break
        sample.append(rest[i])
        total += len(rest[i].req.output)
    return sample


def _pad_len(n: int, max_len: int) -> int:
    """Pad sequence lengths to one of four sizes: few programs to compile."""
    for div in (8, 4, 2, 1):
        if n <= max(max_len // div, 1):
            return max(max_len // div, 1)
    raise ValueError(f"sequence of {n} tokens exceeds max_len {max_len}")


def compare(ref, conf: dict, weight_seed: int,
            served: Sequence[Tuple[List[int], List[int]]], max_len: int,
            n_max: int, controls: Sequence[str] = ()) -> Dict:
    import jax
    import jax.numpy as jnp

    w = jax.jit(lambda k: ref.init_weights(conf, k))(
        jax.random.PRNGKey(weight_seed))
    fwd = jax.jit(lambda w, t, want, quant: ref.logits(conf, w, t, want,
                                                       quant),
                  static_argnames="quant")

    @jax.jit
    def gaps(lg, tok):
        return lg.max(-1) - jnp.take_along_axis(lg, tok[:, None], 1)[:, 0]

    widest, n_tok = 0.0, 0
    ctrl = {q: 0.0 for q in controls}
    for prompt, out in served:
        P, m = len(prompt), len(out)
        seq = prompt + out[:-1]
        tokens = np.zeros(_pad_len(len(seq), max_len), np.int32)
        tokens[:len(seq)] = seq
        want = np.full(n_max, P - 1, np.int32)
        want[:m] = np.arange(P - 1, P - 1 + m)
        tok = np.zeros(n_max, np.int32)
        tok[:m] = out
        lg = fwd(w, tokens, want, None)
        g = np.asarray(gaps(lg, tok))[:m]
        widest = max(widest, float(g.max()))
        n_tok += m
        for q in controls:
            top = jnp.argmax(fwd(w, tokens, want, q), -1).astype(jnp.int32)
            ctrl[q] = max(ctrl[q], float(np.asarray(gaps(lg, top))[:m].max()))
        del lg
    return {"logit_gap": widest, "tokens": n_tok, "requests": len(served),
            "controls": ctrl}


def verdict(result: Dict, failed: int, limit: float) -> bool:
    """`correct`: no request failed, something was compared, and the
    widest gap is within the cell's limit."""
    return failed == 0 and result["tokens"] > 0 and result["logit_gap"] <= limit
