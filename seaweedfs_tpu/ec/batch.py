"""Stripe-batch EC execution engine — one transform dispatch per B windows.

The paper's headline transform (RS(10,4) GF(2^8) as a batched
Cauchy-matrix multiply over device-resident stripe batches) is only as
fast as its *scheduling*: window-at-a-time dispatch is latency-bound
long before the field math matters (PAPERS.md 2108.02692 and 1312.5155
draw the same conclusion at CPU scale). This module is the shared
execution engine for the bulk EC paths — whole-volume encode
(`pipeline.encode_volume`) and parity scrub (`EcVolume.verify_parity` /
`ec/scrub.py`):

* gather **B stripe windows into one `(B, k, L)` uint8 block**;
* run encode / verify as **ONE batched transform per block**
  (`encoder.transform_batch`: the CPU backends flatten the
  batch into the byte axis — the GF transform is columnwise, so the
  batch dim is free — while `JaxEncoder` jits a vmapped bitplane
  transform once per `(rows, k)` shape and shards the block along the
  batch dim via `NamedSharding(P('batch'))` when more than one device
  is attached);
* account every dispatch, pread and byte **deterministically** (the
  `stats` dicts below), so tools/bench_ec.py can gate the batching win
  on arithmetic instead of wall clock.

Whole-volume rebuild (`pipeline.rebuild_ec_files`) batches its windows
the same way — `window_blocks` and `clamp_batch_windows` size its blocks
and it keeps the same `stats` — but gathers them shard-major, one
contiguous row per survivor, and launches the rows through the
pipeline's per-row `_transform_buffers_async`, so the block reaches the
kernel as free u32 word views with no device-side layout work.

The GF(256) transform is independent per byte column, so batching is
*exact*: a `(B, k, L)` block transforms to the same bytes as B separate
`(k, L)` windows — the numpy per-window oracle remains the byte-identity
gate for every backend (tests/test_ec_batch.py).
"""

from __future__ import annotations

import numpy as np

from ..util import tracing

# windows gathered per transform dispatch: enough to amortise dispatch
# latency into noise, small enough that python-side window bookkeeping
# stays trivial. The REQUESTED width; the resident-memory ceiling is
# clamp_batch_windows below.
DEFAULT_BATCH_WINDOWS = 8

# resident-byte ceiling for one gathered block. A batch width is a
# latency knob, not a licence to hold GBs: 8 x 14 rows x 4 MB windows
# would pin 448 MB (and ~double that with the read-ahead block), where
# the pre-batching paths peaked near one 8 MB buffer's 112 MB. Every
# bulk path clamps its effective width so a block stays under this
# budget — at large windows batching degrades gracefully toward the
# old per-window footprint instead of OOMing the host. The default
# 128 MB admits a full 8-window batch of the bulk paths' 1 MB default
# windows (8 x 14 x 1 MB = 112 MB — byte-for-byte the same payload
# per dispatch as the pre-batching 8 MB-buffer window, just batched);
# the background scrubber passes its own tighter budget because for
# it the bound is an I/O *burst* limit, not only memory.
BLOCK_BYTE_BUDGET = 128 << 20


def clamp_batch_windows(batch_windows: int, window_bytes: int,
                        rows: int, budget: int | None = None) -> int:
    """Effective batch width: the requested window count bounded so one
    (B, rows, window_bytes) block stays inside the byte budget
    (always at least 1 — a single window must still fit the old way)."""
    if batch_windows < 1:
        return 1
    if window_bytes <= 0 or rows <= 0:
        return batch_windows
    if budget is None:
        budget = BLOCK_BYTE_BUDGET
    return max(1, min(batch_windows, budget // (rows * window_bytes)))


def add_stat(stats: dict | None, **kv) -> None:
    """Accumulate deterministic accounting counters into an optional
    stats dict (windows / batches / dispatches / preads / bytes...)."""
    if stats is None:
        return
    for k, v in kv.items():
        stats[k] = stats.get(k, 0) + v


def transform_block(encoder, coeff: np.ndarray, block: np.ndarray,
                    stats: dict | None = None) -> np.ndarray:
    """Apply a (rows, k) GF(256) coefficient matrix to a (B, k, L)
    window block in ONE dispatch -> (B, rows, L) uint8."""
    return transform_block_async(encoder, coeff, block, stats)()


def transform_block_async(encoder, coeff: np.ndarray, block: np.ndarray,
                          stats: dict | None = None):
    """Launch the batched transform; returns a thunk yielding the
    (B, rows, L) numpy result. On the JAX backend the dispatch is
    asynchronous and the thunk blocks on readback, so the caller can
    overlap the NEXT block's preads with this block's device time —
    the same double-buffering contract as pipeline's per-window
    `_transform_buffers_async`, now per B windows."""
    block = np.asarray(block, np.uint8) if not hasattr(block, "devices") \
        else block
    add_stat(stats, dispatches=1, batches=1, windows=int(block.shape[0]),
             bytes_in=int(block.nbytes))
    out = encoder.transform_batch(coeff, block)

    def readback() -> np.ndarray:
        with tracing.start("ec", "readback") as sp:
            got = np.asarray(out)
            sp.nbytes = got.nbytes
        return got
    return readback


def verify_block(encoder, block: np.ndarray,
                 stats: dict | None = None) -> list[bool]:
    """Recompute parity for a (B, k+m, L) block and compare against its
    stored parity rows in ONE dispatch -> per-window verdicts.

    Zero-padded tail windows verify clean by construction: parity of
    all-zero data is all-zero, which is exactly what a shard read past
    EOF returns for the stored rows."""
    block = np.asarray(block, np.uint8)
    add_stat(stats, dispatches=1, batches=1, windows=int(block.shape[0]),
             bytes_in=int(block.nbytes))
    return [bool(ok) for ok in encoder.verify_batch(block)]


def localize_corrupt_rows(encoder, rows: np.ndarray) -> "list[int]":
    """Pin a corrupt stripe window's rot to ONE shard row, if possible.

    `rows` is a (total_shards, L) uint8 window whose parity check
    failed. For each hypothesis "shard c is the corrupt one", shard c
    is reconstructed from k of the OTHER rows and the whole window is
    re-verified with the reconstruction substituted: with a single
    corrupt row only the true culprit's hypothesis makes the stripe
    consistent (any other hypothesis leaves the corrupt row in the
    equations, which then cannot all hold). Returns [culprit] when
    exactly one hypothesis survives, [] when the window is ambiguous
    (multi-shard rot) — the autopilot DEFERS unlocalized windows
    rather than guessing which copy to destroy.

    Cost: total_shards reconstruct+verify passes over ONE window, paid
    only for corrupt windows — rot is rare by construction.
    """
    from . import gf

    total = int(rows.shape[0])
    k = gf.DATA_SHARDS
    culprits: "list[int]" = []
    for c in range(total):
        sources = [s for s in range(total) if s != c][:k]
        coeff = gf.cached_shard_rows((c,), tuple(sources))
        from .pipeline import _transform_buffers
        rec = _transform_buffers(encoder, coeff,
                                 [np.ascontiguousarray(rows[s])
                                  for s in sources])[0]
        cand = np.array(rows, np.uint8, copy=True)
        cand[c] = np.frombuffer(
            np.asarray(rec, np.uint8).tobytes(), np.uint8)
        if bool(encoder.verify_batch(cand[None, :, :])[0]):
            culprits.append(c)
    return culprits if len(culprits) == 1 else []


def window_blocks(total_windows: int, batch_windows: int):
    """Yield (first_window_index, count) specs covering total_windows
    in ceil(total/batch) blocks — THE dispatch-count contract the
    bench smoke asserts."""
    if batch_windows < 1:
        batch_windows = 1
    wi = 0
    while wi < total_windows:
        count = min(batch_windows, total_windows - wi)
        yield wi, count
        wi += count
