"""Share of paid forward slots whose token-wise chunks the step skipped,
because they lay past the prompt's last token, over window steps, %: 100 x
the sum of ``skipped_tokens`` over the sum of ``padded_tokens`` of the
engine's ``BatchRecord``s. A record without the counter (a program that
skips nothing) reads 0."""


def read(ctx):
    paid = sum(b.padded_tokens for b in ctx.batches)
    if not paid:
        return None
    skipped = sum(getattr(b, "skipped_tokens", 0) for b in ctx.batches)
    return 100.0 * skipped / paid
