"""Stripe-batch EC execution engine — one transform dispatch per B windows.

The paper's headline transform (RS(10,4) GF(2^8) as a batched
Cauchy-matrix multiply over device-resident stripe batches) is only as
fast as its *scheduling*: window-at-a-time dispatch is latency-bound
long before the field math matters (PAPERS.md 2108.02692 and 1312.5155
draw the same conclusion at CPU scale). This module is the shared
execution engine for the bulk EC paths — whole-volume encode
(`pipeline.encode_volume`), parity scrub (`EcVolume.verify_parity` /
`ec/scrub.py`) and whole-volume rebuild (`pipeline.rebuild_ec_files`):

* gather **B stripe windows into one shard-major `(k, R)` uint8
  block**: row i holds shard position i's windows back to back,
  zero-padded to the words kernel's block quantum (`_WORD_QUANTUM`);
* run encode / verify / rebuild as **ONE transform per block**: the
  block's k rows go to the encoder's one launch,
  `encoder.transform_async`, which on the JAX backend hands each row
  to the kernel as a free (wm, 128) u32 view, with no device-side
  layout work around it;
* account every dispatch, pread and byte **deterministically** (the
  `stats` dicts below), so tools/bench_ec.py can gate the batching win
  on arithmetic instead of wall clock.

The GF(256) transform is independent per byte column, so batching is
*exact*: a block of B windows transforms to the same bytes as B
separate windows — the numpy per-window oracle remains the
byte-identity gate for every backend (tests/test_ec_batch.py).
"""

from __future__ import annotations

import numpy as np

from . import gf

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

# the GF words kernel's block quantum in bytes (ops/gf256_pallas.py:
# _DEFAULT_BM rows of 128 u32 words): a byte row of a multiple of it
# views as (wm, 128) u32 words with no copy (`bytes_to_words`)
_WORD_QUANTUM = 256 * 512


def _words_len(n: int) -> int:
    """`n` bytes rounded up to the words kernel's block quantum."""
    return -(-n // _WORD_QUANTUM) * _WORD_QUANTUM


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


def transform_block_async(encoder, coeff: np.ndarray, block: np.ndarray,
                          stats: dict | None = None):
    """Apply a (rows, k) coefficient matrix to a shard-major (k, R)
    block as ONE dispatch: row i holds shard position i's windows back
    to back, and R is a multiple of `_WORD_QUANTUM`, so every row
    reaches the device as a free word view. Returns the encoder's
    thunk, which yields the `rows` output rows of R bytes; on the JAX
    backend the launch is asynchronous and the thunk blocks on
    readback, so the caller can overlap the NEXT block's preads with
    this block's device time.

    Counts the dispatch and the batch; the caller, which alone knows
    its windows, counts `windows` and `bytes_in`."""
    add_stat(stats, dispatches=1, batches=1)
    return encoder.transform_async(coeff, list(block))


def verify_block(encoder, block: np.ndarray, count: int,
                 stats: dict | None = None) -> list[bool]:
    """Recompute parity over a shard-major (k+m, count x size) block of
    `count` windows in ONE dispatch and compare it with the block's
    stored parity rows window by window -> per-window verdicts.

    Zero-padded tail windows verify clean by construction: parity of
    all-zero data is all-zero, which is exactly what a shard read past
    EOF returns for the stored rows."""
    k = gf.DATA_SHARDS
    n = block.shape[1]
    add_stat(stats, windows=count, bytes_in=int(block.nbytes))
    data = block[:k]
    if n % _WORD_QUANTUM:
        data = np.zeros((k, _words_len(n)), np.uint8)
        data[:, :n] = block[:k]
    parity = transform_block_async(encoder, gf.parity_matrix(), data,
                                   stats)()
    ok = np.ones(count, bool)
    for got, stored in zip(parity, block[k:]):
        ok &= (got[:n] == stored).reshape(count, -1).all(axis=1)
    return ok.tolist()


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
    from .pipeline import _transform_buffers

    total = int(rows.shape[0])
    culprits: "list[int]" = []
    for c in range(total):
        sources = [s for s in range(total) if s != c][:gf.DATA_SHARDS]
        coeff = gf.cached_shard_rows((c,), tuple(sources))
        cand = np.array(rows, np.uint8, copy=True)
        cand[c] = _transform_buffers(encoder, coeff,
                                     [rows[s] for s in sources])[0]
        if verify_block(encoder, cand, 1)[0]:
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
