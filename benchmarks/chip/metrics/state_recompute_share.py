"""Share of the cached prefix tokens that window steps recomputed, %: 100 x
the sum of (``matched_tokens`` - ``resumed_tokens``) over the sum of
``matched_tokens``, from the engine's ``BatchRecord``s. A hybrid resumes
from the deepest snapshot, so the tokens past it that the cache held are
computed again."""


def read(ctx):
    matched = sum(getattr(b, "matched_tokens", 0) for b in ctx.batches)
    if not matched:
        return None
    resumed = sum(getattr(b, "resumed_tokens", 0) for b in ctx.batches)
    return 100.0 * (matched - resumed) / matched
