"""Bytes moved per byte repaired in the rebuild.lost4 window: the
window's delta of SeaweedFS_ec_repair_bytes_total{stage="copied"} (shard
and index files fetched onto the rebuilder) over its delta of
{stage="rebuilt"} (shard bytes the rebuild wrote). None where the
program has no such counter, or nothing was rebuilt."""


def read(ctx):
    moved = ctx["work"].get("repair_bytes")
    if not moved or moved["rebuilt"] <= 0:
        return None
    return moved["copied"] / moved["rebuilt"]
