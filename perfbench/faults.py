"""Faults planted in the timed path underneath a run, for the tests at a
tiny size and for calibrate.py's readings at a cell's own size. Each
breaks what the served tokens depend on, so `correct` has to come out
false under it. A cell on one chip has no exchange between chips to
leave out.

Each fault takes a `setattr`-like function (pytest's
`monkeypatch.setattr`, or plain `setattr` in a process of its own) and is
planted before the run builds its Engine."""
from __future__ import annotations


def state_unchanged(patch):
    """Decode returns the cache it was given: the state never advances."""
    import repro.models as models
    orig = models.decode_step

    def decode_step(cfg, params, token, cache):
        logits, _ = orig(cfg, params, token, cache)
        return logits, cache

    patch(models, "decode_step", decode_step)


def half_batch_left_out(patch):
    """Decode serves the first half of the batch's logits to the second
    half too."""
    import repro.models as models
    orig = models.decode_step

    def decode_step(cfg, params, token, cache):
        logits, cache = orig(cfg, params, token, cache)
        B = logits.shape[0]
        h = B // 2
        return logits.at[h:].set(logits[:B - h]), cache

    patch(models, "decode_step", decode_step)


def token_altered(patch):
    """Every sampled token is replaced by the next id."""
    import repro.serving.engine as engine
    orig = engine.sample_per_request

    def sample_per_request(logits, key, params):
        return (orig(logits, key, params) + 1) % logits.shape[-1]

    patch(engine, "sample_per_request", sample_per_request)


FAULTS = {f.__name__: f for f in (state_unchanged, half_batch_left_out,
                                  token_altered)}
