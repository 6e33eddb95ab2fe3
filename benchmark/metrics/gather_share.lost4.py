"""Share of the rebuild.lost4 window that ec.rebuild's gather takes: Σ
of the window's `volume`/`ec.copy` spans (one per survivor copied onto
the rebuilder, each the rebuilder's /admin/ec/copy) over the window's
time. The window's copies are the last `work["copies"]` of the drained
spans (set-up's spread and the warm-up's copies finish before them).
None where the program records no such span."""

from span_share import spans


def read(ctx):
    n = ctx["work"].get("copies", 0)
    got = spans(ctx, "volume", "ec.copy")
    seconds = ctx["work"].get("seconds", 0)
    if n <= 0 or len(got) < n or seconds <= 0:
        return None
    return 100.0 * sum(got[-n:]) / (1000.0 * seconds)
