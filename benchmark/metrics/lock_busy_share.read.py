"""Share of the traced window in which the EC volume's one lock is held:
the ms of the window's GETs' `ec`/`locked` spans over the window. The
warm-up GETs' holds, which compile the recover shapes, are left out
(span_share.window)."""

from span_share import GET, window


def read(ctx):
    held = window(ctx, "ec", "locked", GET)
    span_s = ctx["trace"]["window_s"]
    if not held or span_s <= 0:
        return None
    return 100.0 * sum(held) / (1000.0 * span_s)
