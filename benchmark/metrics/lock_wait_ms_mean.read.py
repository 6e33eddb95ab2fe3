"""Mean `ec`/`lock_wait` span of the window's GETs: how long
EcVolume.read_needle waits for the volume's one lock."""

from span_share import GET, mean_ms


def read(ctx):
    return mean_ms(ctx, "ec", "lock_wait", GET)
