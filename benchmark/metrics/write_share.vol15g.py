"""Share of the window's generate_batch calls (`volume`/`ec.generate_batch`
spans) in encode.vol15g that the engine spent in its `ec`/`write` spans;
the warm-up call's spans are left out (span_share.window), which is
exact while the span ring still holds every span of the warm-up call
when the window's first drain reads it."""

from span_share import stage_share


def read(ctx):
    return stage_share(ctx, "write", "ec.generate_batch")
