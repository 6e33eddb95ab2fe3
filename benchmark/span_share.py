"""Arithmetic shared by the readers of the program's spans.

`ctx["spans"]` maps (tier, op) to the durations in ms of the spans that
run.SpanDrain copied from the program's span ring (util/tracing.py),
each list in the order the spans finished. Its first copy also takes
the spans that set-up and warm-up left in the ring, and those hold the
warm-up's compiles, so a reader keeps only the window's share: the
spans of the window's `ctx["work"]["ops"]` operations are the last ones
of each list. Every reader returns None where a span it needs is
missing, as in a program that does not record it.
"""

from __future__ import annotations

# the volume server's entry span of one GET
GET = ("volume", "read")


def spans(ctx: dict, tier: str, op: str) -> list[float]:
    return ctx["spans"].get((tier, op), [])


def window(ctx: dict, tier: str, op: str, per: tuple[str, str]) -> list:
    """The (tier, op) spans of the window's operations, where each
    operation is one `per` span: of the n `per` spans drained, the last
    `ops` are the window's, and of the (tier, op) spans the same share,
    from the end. Exact where every drained operation records as many of
    them, as a call that repeats the same work does, and the ring still
    held the first drained operation's spans; where an operation records
    more or fewer (a GET the needle cache answers takes no lock), it errs
    by the few spans around the window's first operation."""
    got, n = spans(ctx, tier, op), len(spans(ctx, *per))
    if not got or not n:
        return []
    ops = min(ctx["work"]["ops"], n)
    return got[len(got) - round(len(got) * ops / n):]


def mean_ms(ctx: dict, tier: str, op: str, per: tuple[str, str]):
    """Mean duration of the window's (tier, op) spans, in ms."""
    got = window(ctx, tier, op, per)
    return sum(got) / len(got) if got else None


def stage_share(ctx: dict, stage: str, call: str):
    """100 x the ms of the window's `ec`/<stage> spans over the ms of its
    `volume`/<call> spans: the share of the window's calls that the
    engine spent in that host stage."""
    part = window(ctx, "ec", stage, ("volume", call))
    whole = sum(window(ctx, "volume", call, ("volume", call)))
    if not part or whole <= 0:
        return None
    return 100.0 * sum(part) / whole
