"""Share of the HBM roofline in the rebuild.lost4 window: (10 survivors
+ 4 lost) x shard bytes per op, the four targets coming out of a 4 x 10
inverse matrix, at the chip's HBM peak, over all device busy time."""


def read(ctx):
    busy = ctx["trace"]["busy_s"]
    work = ctx["work"]["hbm_bytes"]
    if busy <= 0 or work <= 0:
        return None
    return 100.0 * work / ctx["peak"]["hbm_bytes_per_s"] / busy
