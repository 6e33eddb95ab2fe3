"""Mean time a GET of the window spends on, or waiting for, the volume
server's event loop: its `volume`/`read` span less its `exec`/`queue`
and `store`/`read` spans. A GET has one of each, except one the needle
cache answers on the loop, which has neither of the inner two: its
whole span is loop time, so the inner spans are summed over the
window's GETs rather than averaged over the ones that have them."""

from span_share import GET, window


def read(ctx):
    gets = window(ctx, *GET, GET)
    queued = window(ctx, "exec", "queue", GET)
    if not gets or not queued:
        return None
    inner = sum(queued) + sum(window(ctx, "store", "read", GET))
    return (sum(gets) - inner) / len(gets)
