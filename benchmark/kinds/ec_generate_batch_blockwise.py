"""`ec_generate_batch` at the size of a full cold volume: that kind's
set-up, call and window, with the two changes a volume of many GB forces.

- Warm-up: the untimed call writes its shard files to the null device
  (`os.devnull`, through links at their paths), so a run writes the fill
  and one call's shards, 38 GB, and not 60: the one-chip benchmark host
  ends a command whose disk writes pass 45 GiB. The call is otherwise the
  window's: the same reads, launches and shapes.
- Fill: each volume's needles are made as they are written
  (`StreamedVolume`): the sizes, cookies and bytes `volumes.make` gives
  for the seed, without ever holding the volume's bytes whole.
- Check: every byte of the last call's 14 shard files against RS(10,4)
  of the .dat from the reference (`blockwise_mismatch`). A task holds one
  column slice of a stripe row (at most `SLICE` bytes of each of its ten
  blocks, the same offset in each, so a small row is one slice), and the
  tasks in flight are bounded so that together they stay under
  `CHECK_BUDGET` bytes of host memory.

Tasks are kept small on purpose. On the one-chip v5e benchmark host,
buffers of several MB allocated and freed at GB/s are given back to the
host more slowly than they are taken: 16 MB slices grew its used memory
past 24 GB within 15 s, 4 MB slices to 19 GB, 1 MB slices held it under
0.7 GB (PERF.md, Findings).
"""

from __future__ import annotations

import asyncio
import json
import os
import time
import tracemalloc
from concurrent.futures import ThreadPoolExecutor

import numpy as np

import generator as g
import reference
import volumes

base = g.load("ec_generate_batch")

SLICE = 1 << 20           # bytes of each block a task compares
CHECK_BUDGET = 4 << 30    # host bytes all tasks in flight may hold
# bytes a task holds per byte of its stream length: 10 data streams, 4
# parity streams, the coder's lookup (its uint16 indices taken as 8-byte
# ones: 4, and its output: 1), one shard file's bytes and their
# comparison
TASK_COPIES = 21
CHUNK_WORDS = 8 << 20     # 64 MB of needle bytes made at a time


class StreamedVolume:
    """`volumes.make`'s needles of one volume, made as `volumes.fill` asks
    for them, in order: the same sizes, cookies and bytes."""

    def __init__(self, spec: dict, seed: int, vid: int):
        order_ss, bytes_ss = np.random.SeedSequence([seed, vid]).spawn(2)
        rng = np.random.default_rng(order_ss)
        self.vid = vid
        self.sizes = rng.permutation(volumes.needle_sizes(spec))
        self.cookies = rng.integers(0, 1 << 32, self.sizes.size,
                                    dtype=np.uint64)
        self._bits = np.random.SFC64(bytes_ss)
        self._chunk = np.empty(0, np.uint8)
        self._pos = 0
        self._next = 0

    def payload(self, i: int) -> bytes:
        if i != self._next:
            raise ValueError(f"needle {i} asked for before {self._next}")
        left = int(self.sizes[i])
        parts = []
        while left:
            if self._pos == self._chunk.size:
                self._chunk = self._bits.random_raw(CHUNK_WORDS).view(
                    np.uint8)
                self._pos = 0
            take = min(left, self._chunk.size - self._pos)
            parts.append(self._chunk[self._pos:self._pos + take])
            self._pos += take
            left -= take
        self._next += 1
        return b"".join(parts)


def _pread_full(fd: int, out: np.ndarray, pos: int) -> int:
    """Fill `out` from the file at `pos` on, looping on short reads;
    bytes past the end of the file are left as they are."""
    view = memoryview(out)
    got = 0
    while got < len(view):
        n = os.preadv(fd, [view[got:]], pos + got)
        if not n:
            break
        got += n
    return got


def check_tasks(rows: list[tuple[int, int, int]], slice_bytes: int
                ) -> list[tuple[int, int, int, int, int]]:
    """(.dat offset, block, shard offset, column, width) per task: each
    row of reference.stripe_rows in column slices of `slice_bytes`."""
    return [(pos, block, soff, col, min(slice_bytes, block - col))
            for pos, block, soff in rows
            for col in range(0, block, slice_bytes)]


def blockwise_mismatch(dat_path: str, cfg: dict,
                       targets: dict[int, list[str]],
                       slice_bytes: int = SLICE,
                       budget: int = CHECK_BUDGET) -> int:
    """Bytes of the shard files in `targets` (shard id -> files) that
    differ from the reference's RS(10,4) of the .dat; a missing, short
    or long file counts every byte it lacks or adds. The count
    `generator.stripe_mismatch` gives, in tasks of bounded size."""
    coder = reference.Coder()
    size = os.path.getsize(dat_path)
    rows = reference.stripe_rows(size, cfg["ec_large_block"],
                                 cfg["ec_small_block"])
    shard = reference.shard_size(size, cfg["ec_large_block"],
                                 cfg["ec_small_block"])
    tasks = check_tasks(rows, slice_bytes)
    widest = max((t[4] for t in tasks), default=1)
    threads = max(1, min(os.cpu_count() or 1,
                         budget // (TASK_COPIES * widest)))
    paths = [p for ps in targets.values() for p in ps]
    fds = {p: os.open(p, os.O_RDONLY) for p in paths if os.path.exists(p)}
    dat = os.open(dat_path, os.O_RDONLY)

    def work(task) -> int:
        pos, block, soff, col, width = task
        data = [np.zeros(width, np.uint8) for _ in range(reference.DATA)]
        for i, d in enumerate(data):
            _pread_full(dat, d, pos + i * block + col)
        want = coder.shards(list(targets), data)
        return sum(g.differing(os.pread(fds[p], width, soff + col), want[sid])
                   for sid, ps in targets.items() for p in ps if p in fds)

    try:
        with ThreadPoolExecutor(threads) as ex:
            wrong = sum(ex.map(work, tasks))
        for p in paths:
            wrong += (max(0, os.fstat(fds[p]).st_size - shard) if p in fds
                      else shard)
    finally:
        os.close(dat)
        for fd in fds.values():
            os.close(fd)
    return wrong


def _striped() -> dict[str, float] | None:
    """SeaweedFS_ec_stripe_bytes_total by block kind, where the program
    counts it."""
    from seaweedfs_tpu.stats import metrics
    c = getattr(metrics, "EC_STRIPE_BYTES", None)
    if c is None:
        return None
    return {k: c.labels(block=k)._value.get() for k in ("large", "small")}


class Traffic(base.Traffic):
    sink = False   # the next call writes its shards to the null device

    async def setup(self) -> None:
        self.sink = True
        try:
            await super().setup()
        finally:
            self.sink = False

    async def call(self) -> None:
        if self.sink:
            for path in self.outputs:
                if not path.endswith(".ecx"):
                    os.symlink(os.devnull, path)
        await super().call()

    async def fill(self, vids: list[int]) -> dict:
        for vid in vids:
            t0 = time.perf_counter()
            d = StreamedVolume(self.cfg["needles"], self.seed, vid)
            await asyncio.to_thread(volumes.fill, self.cl.vs.store, d,
                                    self.cfg["replication"])
            print(json.dumps({"phase": "fill", "vid": vid,
                              "needles": int(d.sizes.size),
                              "write_s": time.perf_counter() - t0}),
                  flush=True)
        await self.cl.vs.heartbeat_once()
        return {}

    async def window(self, seconds: float) -> dict:
        before = _striped()
        w = await super().window(seconds)
        after = _striped()
        if before is not None:
            print(json.dumps({"phase": "stripe_bytes",
                              **{k: after[k] - before[k] for k in after}}),
                  flush=True)
        return w

    def check(self) -> list[tuple[str, float, float]]:
        tracemalloc.start()
        t = time.perf_counter()
        try:
            wrong_shard = wrong_ecx = 0
            for vid in self.vids:
                path = self.cl.base(vid)
                wrong_shard += blockwise_mismatch(
                    path + ".dat", self.cfg,
                    {sid: [path + g.ext(sid)] for sid in range(g.SHARDS)})
                with open(path + ".idx", "rb") as f:
                    ecx = reference.sorted_index(f.read())
                wrong_ecx += g.differing(g.read_or_none(path + ".ecx"),
                                         np.frombuffer(ecx, np.uint8))
            earlier = (g.files_differing(self.earlier,
                                         g.digests(self.outputs))
                       if self.earlier else 0)
            peak = tracemalloc.get_traced_memory()[1]
        finally:
            tracemalloc.stop()
        print(json.dumps({"phase": "check_memory", "traced_peak_bytes": peak,
                          "seconds": time.perf_counter() - t}), flush=True)
        return [("shard_bytes_wrong", wrong_shard, 0),
                ("ecx_bytes_wrong", wrong_ecx, 0),
                ("earlier_files_differing", earlier, 0),
                ("calls_failed", self.failed, 0)]


def faults() -> dict:
    return base.faults()
