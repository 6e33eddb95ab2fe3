"""EC file pipelines: volume <-> 14 shard files, driven by the TPU encoder.

Reference workflow (ec_encoder.go):
  WriteEcFiles (:53)        .dat -> .ec00...ec13, streaming row batches
  WriteSortedFileFromIdx(:26) .idx -> .ecx sorted index
  RebuildEcFiles (:57)      regenerate missing shard files from >=10 present
  ec_decoder.go WriteDatFile(:150) shards -> .dat (ec.decode)

The reference streams 256KB x 10 buffers through an AVX2 encoder; here each
row batch is a host->HBM transfer and one kernel launch, so the dispatch
unit is much larger: 1 MB windows gathered eight at a time by the
stripe-batch engine (ec/batch.py) — 8 MB per shard per dispatch, the same
DMA-bound payload as the pre-batching 8 MB buffer, at an in-flight block
the resident-byte budget can hold.

Each host stage of the served paths is an `ec` span (util/tracing.py):
`pread` (input file reads), `h2d` (host staging and the enqueued
transfer), `launch` (host time to enqueue the device work), `readback`
(host blocked on the device and the copy back) and `write` (output file
writes), each carrying the bytes it moved.
"""

from __future__ import annotations

import contextvars
import os
import queue
import threading

import numpy as np

from ..storage import types as t
from ..storage.needle_map import walk_index_blob, write_sorted_index
from ..util import tracing
from . import gf
from .batch import (DEFAULT_BATCH_WINDOWS, _words_len, add_stat,
                    clamp_batch_windows, transform_block_async,
                    window_blocks)
from .locate import LARGE_BLOCK_SIZE, SMALL_BLOCK_SIZE, n_large_block_rows

# read-ahead / dispatch-ahead depth of the threaded encode pipeline: 2 is
# enough to overlap file reads, host<->device transfer + kernel time, and
# file writes (classic double buffering); more just holds memory
_PIPE_DEPTH = 2

# buffers one preadv call may scatter into (Linux's UIO_MAXIOV is 1024;
# POSIX guarantees 16)
try:
    _IOV_MAX = max(16, os.sysconf("SC_IOV_MAX"))
except (AttributeError, ValueError, OSError):
    _IOV_MAX = 16


def to_ext(shard_id: int) -> str:
    return ".ec%02d" % shard_id


def get_encoder(backend: str = "auto"):
    """backend: 'tpu' | 'cpu' | 'auto' (tpu if a TPU is attached).

    The BASELINE `-ec.backend` switch: the volume CLI's -ecBackend flag
    (exported as SWTPU_EC_BACKEND) overrides 'auto', so an operator can
    pin the CPU path on TPU hosts or fail fast when the TPU is absent."""
    if backend == "auto":
        backend = os.environ.get("SWTPU_EC_BACKEND", "auto").lower()
    if backend not in ("auto", "tpu", "cpu"):
        raise ValueError(
            f"unknown EC backend {backend!r}: use auto | tpu | cpu")
    if backend == "tpu":
        # an explicit pin fails fast instead of silently degrading to
        # XLA-on-CPU when the accelerator is absent
        import jax
        if jax.default_backend() != "tpu":
            raise RuntimeError(
                "EC backend pinned to 'tpu' but no TPU is attached "
                f"(jax backend: {jax.default_backend()})")
    if backend == "auto":
        try:
            import jax
        except ImportError:  # jax-less host: the CPU is all there is
            backend = "cpu"
        else:
            # a backend-init error raises: a TPU host whose chip failed
            # to come up must not quietly serve XLA-on-CPU
            backend = "tpu" if jax.default_backend() == "tpu" else "cpu"
    if backend == "tpu":
        from .encoder_jax import JaxEncoder
        return JaxEncoder()
    from .encoder_cpu import CpuEncoder
    return CpuEncoder()


def backend_resolves_to_tpu(backend: str) -> bool:
    """Whether the -ecBackend value `backend` selects the TPU here,
    answered WITHOUT initialising a JAX backend: the `-workers N`
    supervisor asks before forking, and a parent that touched the chip
    would hold it. 'auto' means a TPU that JAX_PLATFORMS admits and the
    PCI bus shows."""
    if backend != "auto":
        return backend == "tpu"
    platforms = os.environ.get("JAX_PLATFORMS", "").strip()
    if platforms:
        # JAX ranks an explicit list by order: the first entry becomes
        # jax.default_backend() whenever it initialises
        return platforms.split(",")[0].strip() == "tpu"
    try:
        import jax  # noqa: F401
    except ImportError:  # jax-less host: the CPU is all there is
        return False
    try:
        # JAX's own PCI scan, the one its TPU plugin runs at init
        from jax._src.hardware_utils import \
            num_available_tpu_chips_and_device_id as tpu_chips
    except ImportError as e:
        raise RuntimeError(
            "-ecBackend auto: this JAX has no TPU probe "
            "(jax._src.hardware_utils), so whether a chip is attached "
            "cannot be told without taking it; pin -ecBackend tpu or "
            "cpu") from e
    return tpu_chips()[0] > 0


def _transform_buffers_async(encoder, coeff: np.ndarray,
                             buffers: list[np.ndarray]):
    """Launch the GF transform over equal-length host byte buffers and
    return a thunk that yields the output byte buffers when called: the
    encoder's one launch (`transform_async`), asynchronous on the JAX
    backend and eager on the host encoders."""
    return encoder.transform_async(coeff, buffers)


def _transform_buffers(encoder, coeff: np.ndarray,
                       buffers: list[np.ndarray]) -> list[np.ndarray]:
    """Apply a GF coefficient matrix to equal-length host byte buffers."""
    return _transform_buffers_async(encoder, coeff, buffers)()


def _iter_row_batches(dat_size: int, large_block: int, small_block: int,
                      buffer_size: int):
    """Yield (start, block_size, buf_size, batch_index) specs covering the
    volume in row order (encodeData/encodeDataOneBatch split,
    ec_encoder.go:114-186)."""
    remaining = dat_size
    processed = 0
    large_row = large_block * gf.DATA_SHARDS
    while remaining > large_row:
        buf = min(buffer_size, large_block)
        assert large_block % buf == 0, (large_block, buf)
        for b in range(large_block // buf):
            yield processed, large_block, buf, b
        processed += large_row
        remaining -= large_row
    while remaining > 0:
        buf = min(buffer_size, small_block)
        assert small_block % buf == 0, (small_block, buf)
        for b in range(small_block // buf):
            yield processed, small_block, buf, b
        processed += small_block * gf.DATA_SHARDS
        remaining -= small_block * gf.DATA_SHARDS


class _RowSpans:
    """The `ec`/`rows.large` and `ec`/`rows.small` spans of one volume's
    encode: one per run of consecutive rows of one block size (a volume
    has at most a large run, then a small one). A run's span opens at
    its first pread and finishes once the last of its
    `_iter_row_batches` specs is enqueued on the encoder, with `bytes`
    the .dat bytes the run covers; those bytes then count in
    SeaweedFS_ec_stripe_bytes_total{block}. Spans of a failed encode
    finish as errors in `close` and count nothing."""

    def __init__(self, dat_size: int, large_block: int, small_block: int,
                 buffer_size: int):
        n_large = n_large_block_rows(large_block, dat_size)
        large = n_large * large_block * gf.DATA_SHARDS
        small_rows = -(-(dat_size - large) // (small_block * gf.DATA_SHARDS))
        # [block kind, specs not yet enqueued, .dat bytes]
        self._runs = [
            ["large", n_large * (large_block
                                 // min(buffer_size, large_block)), large],
            ["small", small_rows * (small_block
                                    // min(buffer_size, small_block)),
             dat_size - large]]
        self._split = self._runs[0][1]   # specs of the large run
        self._open: dict[int, object] = {}

    def reading(self, spec: int) -> None:
        """Spec `spec` (its index in `_iter_row_batches`) is about to be
        read: open its run's span if this is the run's first read."""
        r = int(spec >= self._split)
        if r not in self._open and self._runs[r][1] > 0:
            self._open[r] = tracing.start("ec", "rows." + self._runs[r][0])

    def enqueued(self, spec: int) -> None:
        """Spec `spec`'s group is enqueued: the run ends with its last."""
        r = int(spec >= self._split)
        run = self._runs[r]
        run[1] -= 1
        if run[1] == 0:
            self._open.pop(r).finish(nbytes=run[2])
            _count_striped(run[0], run[2])

    def close(self) -> None:
        while self._open:
            self._open.popitem()[1].finish(status="error")


def _count_striped(block: str, nbytes: int) -> None:
    """SeaweedFS_ec_stripe_bytes_total{block}: .dat bytes striped in
    `block` ("large" or "small") stripe rows, by both encode engines."""
    try:
        from ..stats import metrics
    except ImportError:
        return
    if metrics.HAVE_PROMETHEUS:
        metrics.EC_STRIPE_BYTES.labels(block=block).inc(int(nbytes))


def count_repair(stage: str, nbytes: int) -> None:
    """SeaweedFS_ec_repair_bytes_total{stage}: `copied` bytes fetched
    from another server for a repair, `rebuilt` shard bytes written by
    `rebuild_ec_files`."""
    try:
        from ..stats import metrics
    except ImportError:
        return
    if metrics.HAVE_PROMETHEUS:
        metrics.EC_REPAIR_BYTES.labels(stage=stage).inc(int(nbytes))


def _close_outputs(files) -> None:
    """Close written shard files inside an `ec`/`write` span: a close
    can block on write-back of what the writes left in the page cache
    (network filesystems flush on close), which is output cost too."""
    with tracing.start("ec", "write"):
        for f in files:
            f.close()


def _count_encoded(nbytes: int) -> None:
    """SeaweedFS_ec_encode_bytes_total: data bytes handed to the parity
    transform, counted where both encode paths (`encode_volume` and the
    served `write_ec_files_batched`) stage a launch, on any backend."""
    try:
        from ..stats import metrics
    except ImportError:
        return
    if metrics.HAVE_PROMETHEUS:
        metrics.EC_ENCODE_BYTES.inc(int(nbytes))


def _use_overlap(encoder) -> bool:
    """Thread-overlap pays only when launch() is genuinely asynchronous
    (JAX dispatch returns before the device finishes). For host encoders
    the transform is eager, so the threads just add queue hand-off and
    GIL contention — measured 2x SLOWER on a single-core host — and the
    plain serial loop wins.

    This is THE async-dispatch predicate: JaxEncoder's transform_async
    is the one launch that returns before the work is done."""
    try:
        from .encoder_jax import JaxEncoder
    except ImportError:  # jax-less host: CPU encoders only, eager
        return False
    return isinstance(encoder, JaxEncoder)


def _run_overlapped(read_batches, launch, write_result,
                    overlap: bool = True) -> None:
    """Three-stage threaded pipeline: a reader thread fills a bounded
    queue of input batches, the caller thread launches the (async) device
    transform, and a writer thread blocks on readback + file writes.

    With JAX async dispatch this overlaps file reads, host->device
    transfer + kernel time, and file writes — the fix for the fully
    serial round-3 pipeline (SURVEY §7 hard-part #1). Queue depth
    _PIPE_DEPTH bounds in-flight memory to ~2 batches.

    read_batches: generator yielding input batch objects.
    launch(batch) -> (batch, thunk) launched work.
    write_result(batch, thunk): called in writer-thread order.
    overlap=False degrades to the serial loop (host encoders).
    """
    if not overlap:
        for batch in read_batches:
            write_result(*launch(batch))
        return
    q_read: queue.Queue = queue.Queue(maxsize=_PIPE_DEPTH)
    q_write: queue.Queue = queue.Queue(maxsize=_PIPE_DEPTH)
    errs: list[BaseException] = []

    def reader() -> None:
        try:
            for batch in read_batches:
                if errs:
                    break
                q_read.put(batch)
        except BaseException as e:  # noqa: BLE001 — propagated below
            errs.append(e)
        finally:
            q_read.put(None)

    def writer() -> None:
        draining = False
        while True:
            item = q_write.get()
            if item is None:
                return
            if draining:
                continue
            try:
                write_result(*item)
            except BaseException as e:  # noqa: BLE001 — propagated below
                errs.append(e)
                draining = True  # keep consuming so the caller never blocks

    # each thread runs in a copy of the caller's context, so the stage
    # spans it opens parent under the caller's span
    rt = threading.Thread(target=contextvars.copy_context().run,
                          args=(reader,), daemon=True)
    wt = threading.Thread(target=contextvars.copy_context().run,
                          args=(writer,), daemon=True)
    rt.start()
    wt.start()
    try:
        while True:
            batch = q_read.get()
            if batch is None:
                break
            if errs:
                continue  # drain reader output without launching more
            try:
                q_write.put(launch(batch))
            except BaseException as e:  # noqa: BLE001
                errs.append(e)
    finally:
        q_write.put(None)
        rt.join()
        wt.join()
    if errs:
        raise errs[0]


def encode_volume(base_name: str, encoder=None,
                  large_block: int = LARGE_BLOCK_SIZE,
                  small_block: int = SMALL_BLOCK_SIZE,
                  buffer_size: int = 1024 * 1024,
                  batch_windows: int = DEFAULT_BATCH_WINDOWS,
                  stats: dict | None = None) -> None:
    """Stripe <base>.dat into <base>.ec00 .. .ec13 (WriteEcFiles) with
    the stripe-batch engine: up to `batch_windows` stripe windows
    gather into one shard-major (10, R) block — row i holds shard
    position i's windows back to back, zero-padded to the words
    kernel's block quantum — and ONE transform dispatch emits all four
    parity rows for every window in the block — ceil(W/B) dispatches
    per uniform-window run instead of W (ec/batch.py). A volume has at
    most two such runs: groups flush once at the large->small
    block-size boundary (mixed window lengths never share a block), so
    the whole-volume count is bounded by ceil(W_large/B) +
    ceil(W_small/B) — the exact ceil(W/B) the bench gates on holds
    when the buffer size divides both areas into equal windows (its
    geometry).

    Windows whose preads are contiguous in the .dat (consecutive
    buffers of the same block row) coalesce into one read per shard
    position, so the batch cuts syscalls the same ratio it cuts
    dispatches. Each data row and each parity row goes to its shard
    file as one write. File I/O still overlaps the device transform
    via the double-buffered reader/writer threads (`_run_overlapped`);
    `stats` accumulates the deterministic accounting
    (windows/batches/dispatches/preads/bytes) tools/bench_ec.py
    gates on."""
    encoder = encoder or get_encoder()
    parity = gf.parity_matrix()
    dat_path = base_name + ".dat"
    dat_size = os.path.getsize(dat_path)
    outs = [open(base_name + to_ext(i), "wb") for i in range(gf.TOTAL_SHARDS)]
    f = open(dat_path, "rb")
    rows = _RowSpans(dat_size, large_block, small_block, buffer_size)

    def groups():
        """(index of the group's first spec, the group's specs)."""
        pending: list[tuple] = []
        first = 0
        limit = 1
        for i, spec in enumerate(_iter_row_batches(
                dat_size, large_block, small_block, buffer_size)):
            if pending and (spec[2] != pending[0][2]
                            or len(pending) >= limit):
                yield first, pending
                pending = []
            if not pending:
                first = i
                # resident budget: data rows + parity rows per window
                limit = clamp_batch_windows(batch_windows, spec[2],
                                            gf.TOTAL_SHARDS)
            pending.append(spec)
        if pending:
            yield first, pending

    def read_block(group):
        """One shard-major (10, R) block read straight into its rows;
        contiguous window reads coalesce into single preads per shard
        position. Returns (block, preads, take): the first `take`
        bytes of each row are the group's windows, the rest is pad."""
        buf = group[0][2]
        take = len(group) * buf
        block = np.empty((gf.DATA_SHARDS, _words_len(take)), np.uint8)
        preads = got = 0
        with tracing.start("ec", "pread") as sp:
            for i in range(gf.DATA_SHARDS):
                runs: list[list[int]] = []
                for start, bs, _, b in group:
                    off = start + bs * i + b * buf
                    if runs and off == runs[-1][0] + runs[-1][1]:
                        runs[-1][1] += buf
                    else:
                        runs.append([off, buf])
                w = 0
                for off, ln in runs:
                    r = _pread_into(f.fileno(), [block[i, w:w + ln]], off)
                    got += r
                    # past the .dat's end the stripe reads as zeros
                    block[i, w + r:w + ln] = 0
                    w += ln
                preads += len(runs)
            block[:, take:] = 0
            sp.nbytes = got
        return block, preads, take

    def batches():
        for first, group in groups():
            specs = range(first, first + len(group))
            for i in specs:
                rows.reading(i)
            yield (*read_block(group), len(group), specs)

    def launch(item):
        block, preads, take, count, specs = item
        nbytes = take * gf.DATA_SHARDS
        add_stat(stats, preads=preads, bytes_read=nbytes, windows=count,
                 bytes_in=nbytes)
        _count_encoded(nbytes)
        thunk = transform_block_async(encoder, parity, block, stats)
        for i in specs:
            rows.enqueued(i)
        return item, thunk

    def write_result(item, thunk):
        block, _, take = item[:3]
        parities = thunk()      # one row per parity shard
        with tracing.start("ec", "write") as sp:
            for out, row in zip(outs, [*block, *parities]):
                out.write(row[:take])
            sp.nbytes = take * gf.TOTAL_SHARDS

    try:
        _run_overlapped(batches(), launch, write_result,
                        overlap=_use_overlap(encoder))
    finally:
        rows.close()
        f.close()
        _close_outputs(outs)


def write_ec_files(base_name: str, encoder=None,
                   large_block: int = LARGE_BLOCK_SIZE,
                   small_block: int = SMALL_BLOCK_SIZE,
                   buffer_size: int = 1024 * 1024,
                   batch_windows: int = DEFAULT_BATCH_WINDOWS,
                   stats: dict | None = None) -> None:
    """Historical name for `encode_volume` (WriteEcFiles) — same
    batched engine, byte-identical shard files at any batch size."""
    encode_volume(base_name, encoder=encoder, large_block=large_block,
                  small_block=small_block, buffer_size=buffer_size,
                  batch_windows=batch_windows, stats=stats)


def write_ec_files_batched(base_names: list[str], encoder=None,
                           large_block: int = LARGE_BLOCK_SIZE,
                           small_block: int = SMALL_BLOCK_SIZE,
                           buffer_size: int = 8 * 1024 * 1024,
                           batch_volumes: int = 8) -> None:
    """Stripe SEVERAL volumes' .dat files with batched kernel launches —
    the rack-encode configuration (BASELINE.json 64x30GB; reference
    encodes volumes serially, command_ec_encode.go:89).

    The GF transform is independent per byte column, so equal-length
    buffer groups from DIFFERENT volumes concatenate into one stream per
    shard position: one kernel launch then carries up to
    batch_volumes x 10 x buffer_size bytes. This is the single-chip
    expression of parallel/mesh.py's "vol" axis; on a multi-chip mesh the
    same batch shards over devices.

    Parity buffers surface in flush order, not stream order, so every
    parity write lands at an explicitly recorded shard offset. In a
    large row the ten preads of a buffer lie a large block apart in the
    .dat; in a small row they are contiguous.
    """
    import collections

    encoder = encoder or get_encoder()
    parity = gf.parity_matrix()
    outs: dict[str, list] = {}
    # buf_len -> list of (data_buffers, base, parity_shard_offset,
    # the volume's _RowSpans, the buffer's spec index)
    pending: dict[int, list] = {}
    row_spans: list[_RowSpans] = []
    pending_refs: dict[str, int] = {}   # base -> unflushed group count
    fully_enqueued: set[str] = set()
    # launched-but-unwritten kernel batches: lets the next group's file
    # reads overlap this group's device time (dispatch-ahead)
    inflight: collections.deque = collections.deque()

    def maybe_close(base: str) -> None:
        # bound open fds: at most batch_volumes in-flight volumes keep
        # their 14 shard files open (a 64-volume rack batch would
        # otherwise hold ~900 fds past the default 1024 soft limit)
        if base in fully_enqueued and pending_refs.get(base, 0) == 0:
            _close_outputs(outs.pop(base, []))

    def drain_one() -> None:
        group, thunk = inflight.popleft()
        parities = thunk()
        with tracing.start("ec", "write") as sp:
            off = 0
            for buffers, base, shard_off, *_ in group:
                ln = len(buffers[0])
                for p, pbuf in enumerate(parities):
                    f = outs[base][gf.DATA_SHARDS + p]
                    f.seek(shard_off)
                    f.write(np.asarray(pbuf[off:off + ln],
                                       np.uint8).tobytes())
                off += ln
            sp.nbytes = off * len(parities)
        for _, base, *_ in group:
            pending_refs[base] -= 1
            maybe_close(base)

    def flush(buf_len: int) -> None:
        group = pending.pop(buf_len, [])
        if not group:
            return
        # joining each shard position's buffers is host staging too; the
        # transfer's own span carries the bytes
        with tracing.start("ec", "h2d"):
            cat = [np.concatenate([g[0][i] for g in group])
                   if len(group) > 1 else group[0][0][i]
                   for i in range(gf.DATA_SHARDS)]
        _count_encoded(len(cat[0]) * gf.DATA_SHARDS)
        inflight.append(
            (group, _transform_buffers_async(encoder, parity, cat)))
        for _, _, _, runs, spec in group:
            runs.enqueued(spec)
        while len(inflight) > _PIPE_DEPTH:
            drain_one()

    try:
        for base in base_names:
            dat_path = base + ".dat"
            dat_size = os.path.getsize(dat_path)
            outs[base] = [open(base + to_ext(i), "wb")
                          for i in range(gf.TOTAL_SHARDS)]
            shard_pos = 0
            rows = _RowSpans(dat_size, large_block, small_block,
                             buffer_size)
            row_spans.append(rows)
            with open(dat_path, "rb") as f:
                for spec, (start, block_size, buf, b) in enumerate(
                        _iter_row_batches(dat_size, large_block,
                                          small_block, buffer_size)):
                    rows.reading(spec)
                    raws = []
                    with tracing.start("ec", "pread") as sp:
                        for i in range(gf.DATA_SHARDS):
                            f.seek(start + block_size * i + b * buf)
                            raws.append(f.read(buf))
                        sp.nbytes = sum(map(len, raws))
                    raws = [raw + b"\x00" * (buf - len(raw)) for raw in raws]
                    with tracing.start("ec", "write") as sp:
                        for i, raw in enumerate(raws):
                            outs[base][i].write(raw)
                        sp.nbytes = buf * gf.DATA_SHARDS
                    buffers = [np.frombuffer(raw, np.uint8) for raw in raws]
                    pending.setdefault(buf, []).append(
                        (buffers, base, shard_pos, rows, spec))
                    pending_refs[base] = pending_refs.get(base, 0) + 1
                    shard_pos += buf
                    if len(pending[buf]) >= batch_volumes:
                        flush(buf)
            fully_enqueued.add(base)
            maybe_close(base)
        for buf_len in list(pending):
            flush(buf_len)
        while inflight:
            drain_one()
    finally:
        for runs in row_spans:
            runs.close()
        for fs in outs.values():
            for f in fs:
                f.close()


def write_sorted_file_from_idx(base_name: str,
                               ext: str = ".ecx") -> None:
    """<base>.idx -> sorted <base>.ecx (WriteSortedFileFromIdx,
    ec_encoder.go:26-50). Tombstoned entries keep TOMBSTONE size."""
    with open(base_name + ".idx", "rb") as f:
        blob = f.read()
    entries = list(walk_index_blob(blob))
    write_sorted_index(entries, base_name + ext)


def present_shards(base_name: str) -> list[int]:
    return [i for i in range(gf.TOTAL_SHARDS)
            if os.path.exists(base_name + to_ext(i))]


def _pread_into(fd: int, rows: list[np.ndarray], pos: int) -> int:
    """Fill the byte rows `rows` in turn with the file's bytes from
    `pos` on by scatter reads (`os.preadv`), looping on short reads
    until the rows are full or the file ends; returns the bytes read."""
    views = [memoryview(r) for r in rows]
    got = i = 0
    while i < len(views):
        n = os.preadv(fd, views[i:i + _IOV_MAX], pos + got)
        if not n:
            break
        got += n
        while i < len(views) and n >= len(views[i]):
            n -= len(views[i])
            i += 1
        if n:
            views[i] = views[i][n:]
    return got


def _rebuild_rows(base_name: str, encoder, targets: list[int],
                  use: list[int], buffer_size: int,
                  stats: dict | None,
                  batch_windows: int = DEFAULT_BATCH_WINDOWS) -> None:
    """Regenerate the `targets` shard files from the k `use` shards
    through the stripe-batch engine: up to `batch_windows` buffer
    windows gather into one shard-major (k, R) block, each survivor's
    bytes for the block read by ONE pread into one contiguous row, and
    ONE launch emits ALL target rows for every window in the block
    (len(targets) x k coefficients) — ceil(W/B) dispatches per rebuild
    instead of W.

    The launch is encode's `_transform_buffers_async` on the block's k
    rows, cut to a multiple of the words kernel's block quantum
    (`_WORD_QUANTUM`, to which R is rounded up): each row reaches the
    device as a free (wm, 128) u32 view, so the kernel runs with no
    device-side transpose, reshape or stack around it, and each target's
    readback row is written to its file as it comes back. Blocks are
    reused once their readback returns; `stats` counts
    `blocks_allocated` and `blocks_reused`."""
    coeff = gf.cached_shard_rows(tuple(targets), tuple(use))
    shard_size = os.path.getsize(base_name + to_ext(use[0]))
    ins = [open(base_name + to_ext(i), "rb") for i in use]
    outs = [open(base_name + to_ext(i), "wb") for i in targets]
    n_windows = -(-shard_size // buffer_size) if shard_size else 0
    # resident budget: survivor rows in + target rows out per window
    batch_windows = clamp_batch_windows(batch_windows, buffer_size,
                                        len(use) + len(targets))
    shape = (len(use), _words_len(min(batch_windows, n_windows)
                                  * buffer_size))
    # blocks whose readback has returned, reused by the reader; the
    # pipeline's queues bound how many are ever allocated
    free: list[np.ndarray] = []

    def batches():
        for wi, count in window_blocks(n_windows, batch_windows):
            pos = wi * buffer_size
            take = min(count * buffer_size, shard_size - pos)
            n = _words_len(take)
            try:
                block = free.pop()
                add_stat(stats, blocks_reused=1)
            except IndexError:
                block = np.empty(shape, np.uint8)
                add_stat(stats, blocks_allocated=1)
            with tracing.start("ec", "pread") as sp:
                got = 0
                for i, f in enumerate(ins):
                    r = _pread_into(f.fileno(), [block[i, :take]], pos)
                    got += r
                    # zero what was not read (the tail's pad, a short
                    # survivor): a reused block still holds an earlier
                    # block's bytes there. GF of zero columns is zero,
                    # and the pad is sliced off before writing
                    block[i, r:n] = 0
                sp.nbytes = got
            add_stat(stats, preads=len(ins), bytes_read=take * len(ins))
            yield block, count, take

    def launch(item):
        block, count, take = item
        add_stat(stats, dispatches=1, batches=1, windows=count,
                 bytes_in=count * buffer_size * len(use), launches=1)
        rows = list(block[:, :_words_len(take)])
        return item, _transform_buffers_async(encoder, coeff, rows)

    def write_result(item, thunk):
        block, _, take = item
        rebuilt = thunk()       # one row per target
        # the result is back, so the device transfer that read the block
        # is done: only now may the reader overwrite it. A stage that
        # fails drops its blocks instead
        free.append(block)
        with tracing.start("ec", "write") as sp:
            for row, o in zip(rebuilt, outs):
                o.write(row[:take])
            add_stat(stats, bytes_rebuilt=take * len(outs))
            sp.nbytes = take * len(outs)

    try:
        _run_overlapped(batches(), launch, write_result,
                        overlap=_use_overlap(encoder))
    finally:
        for f in ins:
            f.close()
        _close_outputs(outs)


def rebuild_ec_files(base_name: str, encoder=None,
                     buffer_size: int = 1024 * 1024,
                     sequential: bool = False,
                     stats: dict | None = None,
                     batch_windows: int = DEFAULT_BATCH_WINDOWS,
                     targets: "list[int] | None" = None,
                     use: "list[int] | None" = None) -> list[int]:
    """Regenerate missing shard files from >=10 present ones
    (RebuildEcFiles -> rebuildEcFiles, ec_encoder.go:227-281).
    Returns the rebuilt shard ids.

    Default is the stripe-batched whole-volume rebuild: ALL missing
    shards of the volume come out of one coefficient-matrix dispatch
    per `batch_windows`-window block — the survivors are read ONCE
    (one pread per survivor per block) and every lost row rides the
    same launch. `sequential=True` keeps the pre-batching per-shard
    shape (one full pass of survivor reads + one launch per window
    PER lost shard) as the baseline tools/bench_ec.py measures the
    batching win against; `stats` (optional dict) accumulates
    bytes_read / bytes_rebuilt / launches / dispatches / preads /
    windows / seconds for that repair-bandwidth accounting, and
    blocks_allocated / blocks_reused for the reader's block reuse.

    `targets` restricts WHICH absent shards are regenerated (the
    rebuild-to-target admin route: a node rebuilding one shard it will
    host must not also materialize every other missing shard only to
    delete it again); None keeps the rebuild-everything default.
    `use` restricts WHICH present shards feed the reconstruction (the
    same route's validated clean-input set: the first-k-on-disk
    default could otherwise pick up a local shard the caller knows to
    be rotten); None keeps the first-k default. The shard bytes written
    count in SeaweedFS_ec_repair_bytes_total{stage="rebuilt"}."""
    import time as _time

    encoder = encoder or get_encoder()
    have = present_shards(base_name)
    missing = [i for i in range(gf.TOTAL_SHARDS) if i not in have]
    if targets is not None:
        absent = set(missing)
        bad = [t for t in targets if t not in absent]
        if bad:
            raise ValueError(
                f"rebuild targets {bad} already present on disk")
        missing = sorted(set(targets))
    if not missing:
        return []
    if use is not None:
        absent_use = [s for s in use if s not in have]
        if absent_use:
            raise ValueError(
                f"rebuild inputs {absent_use} not present on disk")
        have = sorted(set(use))
    if len(have) < gf.DATA_SHARDS:
        raise ValueError(
            f"unrepairable: only {len(have)} shards present, "
            f"need {gf.DATA_SHARDS}")
    use = have[:gf.DATA_SHARDS]
    t0 = _time.perf_counter()
    if sequential:
        for target in missing:
            _rebuild_rows(base_name, encoder, [target], use,
                          buffer_size, stats, batch_windows=1)
    else:
        _rebuild_rows(base_name, encoder, missing, use,
                      buffer_size, stats, batch_windows=batch_windows)
    count_repair("rebuilt", len(missing)
                 * os.path.getsize(base_name + to_ext(use[0])))
    if stats is not None:
        stats["seconds"] = stats.get("seconds", 0.0) + \
            (_time.perf_counter() - t0)
        stats["rebuilt"] = missing
    return missing


def write_dat_file(base_name: str, dat_size: int,
                   large_block: int = LARGE_BLOCK_SIZE,
                   small_block: int = SMALL_BLOCK_SIZE,
                   buffer_size: int = 8 * 1024 * 1024) -> None:
    """Reassemble <base>.dat from the 10 data shard files (ec.decode;
    ec_decoder.go:150-191)."""
    from .locate import locate_data
    ins = []
    for i in range(gf.DATA_SHARDS):
        path = base_name + to_ext(i)
        if not os.path.exists(path):
            raise FileNotFoundError(
                f"data shard {i} missing; rebuild first: {path}")
        ins.append(open(path, "rb"))
    try:
        with open(base_name + ".dat", "wb") as out:
            pos = 0
            while pos < dat_size:
                take = min(buffer_size, dat_size - pos)
                for iv in locate_data(large_block, small_block, dat_size,
                                      pos, take):
                    sid, soff = iv.to_shard_and_offset(large_block,
                                                       small_block)
                    ins[sid].seek(soff)
                    out.write(ins[sid].read(iv.size))
                pos += take
    finally:
        for f in ins:
            f.close()


def write_idx_file_from_ec_index(base_name: str) -> None:
    """<base>.ecx (+ .ecj tombstone replay) -> <base>.idx
    (WriteIdxFileFromEcIndex, ec_decoder.go:17-42). Entries copy over
    as-is (tombstoned ones keep their TOMBSTONE size); any unfolded .ecj
    keys are appended as delete entries so the rebuilt needle map agrees
    with the EC delete journal."""
    from ..storage.needle_map import pack_entry
    with open(base_name + ".ecx", "rb") as f:
        # tombstoned .ecx entries keep their original offset (in-place
        # MarkNeedleDeleted), but the reassembled .dat is truncated to the
        # live extent (FindDatFileSize skips deletes) — rewrite them to
        # offset 0 like the reference's nm.Delete idx entries, or the
        # loaded volume's integrity check would see an index entry past
        # the data end
        entries = [(key, 0 if size == t.TOMBSTONE_FILE_SIZE else off, size)
                   for key, off, size in walk_index_blob(f.read())]
    ecj_path = base_name + ".ecj"
    if os.path.exists(ecj_path):
        with open(ecj_path, "rb") as f:
            j = f.read()
        for i in range(len(j) // 8):
            key = int.from_bytes(j[i * 8:(i + 1) * 8], "big")
            entries.append((key, 0, t.TOMBSTONE_FILE_SIZE))
    with open(base_name + ".idx", "wb") as f:
        for key, off, size in entries:
            f.write(pack_entry(key, off, size))


def find_dat_file_size(base_name: str,
                       version: int = t.CURRENT_VERSION) -> int:
    """Logical volume size from the .ecx index (FindDatFileSize,
    ec_decoder.go:47-69): max(offset + record length) over entries."""
    size = 8  # superblock
    with open(base_name + ".ecx", "rb") as f:
        blob = f.read()
    for key, off, esize in walk_index_blob(blob):
        if esize == t.TOMBSTONE_FILE_SIZE:
            continue
        end = off + t.actual_size(esize, version)
        size = max(size, end)
    return size
