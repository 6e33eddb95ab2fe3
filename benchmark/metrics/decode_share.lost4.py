"""Share of the rebuild.lost4 window that the rebuild itself takes: Σ of
the window's `volume`/`ec.rebuild` spans (one per op: the rebuilder's
/admin/ec/rebuild, all four targets out of one stripe-batched pass)
over the window's time. The window's are the last `work["ops"]`
drained (the warm-up's finishes before them). None where the program
records no such span."""

from span_share import spans


def read(ctx):
    n = ctx["work"].get("ops", 0)
    got = spans(ctx, "volume", "ec.rebuild")
    seconds = ctx["work"].get("seconds", 0)
    if n <= 0 or len(got) < n or seconds <= 0:
        return None
    return 100.0 * sum(got[-n:]) / (1000.0 * seconds)
