"""Share of the window's generate_batch calls (`volume`/`ec.generate_batch`
spans) that the engine spent striping large rows: Σ of the window's
`ec`/`rows.large` spans, each from a volume's first large-row pread to
the moment its last large-row launch is enqueued, over Σ of the calls'
ms. Each call records one such span per volume, so the window's are the
last ones (span_share.window). None where the program records no
`ec`/`rows.large` span."""

from span_share import window


def read(ctx):
    per = ("volume", "ec.generate_batch")
    part = window(ctx, "ec", "rows.large", per)
    whole = sum(window(ctx, *per, per))
    if not part or whole <= 0:
        return None
    return 100.0 * sum(part) / whole
