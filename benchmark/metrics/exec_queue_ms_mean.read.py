"""Mean `exec`/`queue` span of the window's GETs: how long a GET's store
read waits between its submit to the volume server's executor and its
start in a worker thread."""

from span_share import GET, mean_ms


def read(ctx):
    return mean_ms(ctx, "exec", "queue", GET)
