"""Share of the HBM roofline in the encode.vol15g window, as
gf_roofline.encode reads it: the least time the window's encode work
needs at the chip's HBM peak, (10 data + 4 parity) x shard bytes per
volume per call, over all device busy time."""


def read(ctx):
    busy = ctx["trace"]["busy_s"]
    work = ctx["work"]["hbm_bytes"]
    if busy <= 0 or work <= 0:
        return None
    return 100.0 * work / ctx["peak"]["hbm_bytes_per_s"] / busy
