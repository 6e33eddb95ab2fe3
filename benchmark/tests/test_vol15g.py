"""CPU tests of encode.vol15g's kind (`kinds/ec_generate_batch_blockwise.py`)
and its metric readers. Run by hand with the other benchmark tests:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q -p no:cacheprovider

The volumes here have a large row and a tail of small rows, in blocks of
64 KB and 4 KB, so the blockwise check meets both kinds of row.
"""

from __future__ import annotations

import asyncio
import os
import sys
import tracemalloc

import numpy as np
import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, ROOT)

import generator  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402
import trace_reduce  # noqa: E402
import volumes  # noqa: E402

CELL = "encode.vol15g"
KIND = generator.load("ec_generate_batch_blockwise")
LB, SB = 64 << 10, 4 << 10
GEOMETRY = {"ec_large_block": LB, "ec_small_block": SB}
NEW = ["gf_roofline.vol15g", "device_idle.vol15g", "large_row_share.vol15g",
       "write_share.vol15g", "h2d_share.vol15g", "pread_share.vol15g"]
CALL = ("volume", "ec.generate_batch")
# the cell at a CPU size: 1 MB large blocks, 16 KB small ones, and 12 MB
# of needles, so one large row and a tail of small rows
TINY = {"ec_large_block": 1 << 20, "ec_small_block": 16 << 10,
        "volume_size_limit_mb": 64,
        "needles": {"dist": "log_uniform", "min": 100, "max": 200000,
                    "fill_mb": 12}}


def encode(tmp_path, dat_size: int, fault: str | None = None) -> str:
    """A .dat of random bytes striped twice (warm-up, then the window's
    call) by the served engine, with `fault` planted for both."""
    from seaweedfs_tpu.ec import pipeline as pl
    base = str(tmp_path / "1")
    rng = np.random.default_rng(dat_size)
    with open(base + ".dat", "wb") as f:
        f.write(rng.integers(0, 256, dat_size, dtype=np.uint8).tobytes())
    undo = KIND.faults()[fault]() if fault else None
    try:
        for _ in range(2):
            for sid in range(reference.TOTAL):
                if os.path.exists(base + generator.ext(sid)):
                    os.unlink(base + generator.ext(sid))
            pl.write_ec_files_batched([base], encoder=pl.get_encoder("cpu"),
                                      large_block=LB, small_block=SB)
    finally:
        if undo is not None:
            undo()
    return base


def targets(base: str) -> dict:
    return {sid: [base + generator.ext(sid)]
            for sid in range(reference.TOTAL)}


def test_tasks_cover_every_byte_once():
    size = 2 * 10 * LB + 37 * 10 * SB + 123
    rows = reference.stripe_rows(size, LB, SB)
    covered = set()
    for pos, block, soff, col, width in KIND.check_tasks(rows, LB // 4):
        assert width <= LB // 4
        span = set(range(soff + col, soff + col + width))
        assert not span & covered
        covered |= span
    assert covered == set(range(reference.shard_size(size, LB, SB)))


@pytest.mark.parametrize("fault", [None, *KIND.faults()])
def test_blockwise_counts_as_stripe_mismatch(tmp_path, fault):
    """One large row and a 300 KB tail of small rows, sound or with each
    of the kind's faults planted: the blockwise check, in 16 KB slices,
    counts what generator.stripe_mismatch counts."""
    base = encode(tmp_path, 10 * LB + 300 * 1024, fault)
    want = generator.stripe_mismatch(base + ".dat", GEOMETRY, targets(base))
    got = KIND.blockwise_mismatch(base + ".dat", GEOMETRY, targets(base),
                                  slice_bytes=16 << 10)
    assert got == want
    assert (got == 0) == (fault is None)


def test_short_and_long_files_count(tmp_path):
    base = encode(tmp_path, 2 * 10 * LB + 5 * 10 * SB + 9)
    shard = os.path.getsize(base + generator.ext(0))
    os.truncate(base + generator.ext(3), shard - 1000)
    with open(base + generator.ext(12), "ab") as f:
        f.write(b"x" * 77)
    os.unlink(base + generator.ext(13))
    got = KIND.blockwise_mismatch(base + ".dat", GEOMETRY, targets(base),
                                  slice_bytes=16 << 10)
    assert got == generator.stripe_mismatch(base + ".dat", GEOMETRY,
                                            targets(base))
    assert got == 1000 + 77 + shard


def test_peak_stays_within_the_slice_budget(tmp_path):
    """256 KB slices of a 4 MB large block, one task at a time: the
    check never holds half a large row (10 x 4 MB), the coder's lookup
    tables (about 4 MB, whatever the size) included."""
    block = 64 * LB
    big = {"ec_large_block": block, "ec_small_block": SB}
    base = str(tmp_path / "1")
    rng = np.random.default_rng(3)
    with open(base + ".dat", "wb") as f:
        f.write(rng.integers(0, 256, 10 * block + 40 * SB,
                             dtype=np.uint8).tobytes())
    from seaweedfs_tpu.ec import pipeline as pl
    pl.write_ec_files_batched([base], encoder=pl.get_encoder("cpu"),
                              large_block=block, small_block=SB)
    slice_bytes = 4 * LB
    tracemalloc.start()
    try:
        wrong = KIND.blockwise_mismatch(
            base + ".dat", big, targets(base), slice_bytes=slice_bytes,
            budget=KIND.TASK_COPIES * slice_bytes)
        peak = tracemalloc.get_traced_memory()[1]
    finally:
        tracemalloc.stop()
    assert wrong == 0
    assert peak < 10 * block / 2, peak


def test_streamed_volume_is_volumes_make():
    spec = {"dist": "log_uniform", "min": 100, "max": 40000, "fill_mb": 3}
    want = volumes.make(spec, 2**33 + 1, 2)
    got = KIND.StreamedVolume(spec, 2**33 + 1, 2)
    assert np.array_equal(got.sizes, want.sizes)
    assert np.array_equal(got.cookies, want.cookies)
    assert b"".join(got.payload(i) for i in range(got.sizes.size)) \
        == want.blob
    with pytest.raises(ValueError):
        got.payload(0)


def test_cell_loads_by_name():
    spec = run.find_cell(CELL)
    assert spec["config"]["name"] == "cold_ec_15g"
    assert spec["config"]["volume_size_limit_mb"] == 15000
    assert spec["traffic"]["kind"] == "ec_generate_batch_blockwise"
    assert {m["name"] for m in spec["end_to_end"]} == {"encode_GBps",
                                                       "setup_s"}
    assert [m["name"] for m in spec["per_layer"]] == NEW
    rows = reference.stripe_rows(
        int(volumes.needle_sizes(spec["config"]["needles"]).sum()),
        spec["config"]["ec_large_block"], spec["config"]["ec_small_block"])
    assert sum(b == 1 << 30 for _, b, _ in rows) == 1


def span_ctx(spans: dict, ops: int = 1) -> dict:
    return {"spans": spans, "work": {"ops": ops, "hbm_bytes": 0},
            "trace": {"busy_s": 0.0, "window_s": 1.0, "device_ops": 0},
            "peak": {"hbm_bytes_per_s": 819e9}}


def test_readers_none_without_their_spans():
    # the parent program records the stages but no row spans
    spans = {CALL: [1000.0, 900.0], ("ec", "write"): [400.0, 300.0],
             ("ec", "h2d"): [100.0, 90.0], ("ec", "pread"): [200.0, 180.0]}
    assert run.read_metric("large_row_share.vol15g", span_ctx(spans)) is None
    assert run.read_metric("gf_roofline.vol15g", span_ctx(spans)) is None
    assert run.read_metric("write_share.vol15g", span_ctx(spans)) \
        == pytest.approx(100 * 300 / 900)
    for name in NEW[2:]:
        assert run.read_metric(name, span_ctx({})) is None, name
    spans[("ec", "rows.large")] = [700.0, 600.0]
    assert run.read_metric("large_row_share.vol15g", span_ctx(spans)) \
        == pytest.approx(100 * 600 / 900)


def test_cell_rehearsal_with_its_spans(tmp_path, monkeypatch):
    """The cell at a CPU size through run.run_cell with the span drain
    on: correct, and each span reader gives the share the window's own
    call records (its trace's spans), warm-up left out. The trace of the
    CPU has no chip, so its reduction is stood in for."""
    from seaweedfs_tpu.util import tracing
    monkeypatch.setattr(trace_reduce, "reduce", lambda devices, host: {
        "busy_s": 0.001, "window_s": 1.0, "device_ops": 1,
        "breakdown": {"device_ops": [], "idle_gaps": []}})
    spec = run.find_cell(CELL)
    spec = dict(spec, config=dict(spec["config"], **TINY))
    # the ring as a run's fresh process has it, without earlier tests' spans
    tracing.reset()
    # a window of one call after the warm-up's, as on the chip
    out = asyncio.run(run.run_cell(spec, 2**32 + 9, 0.0, True, str(tmp_path),
                                   peak={"hbm_bytes_per_s": 819e9}))
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == set(NEW)
    with tracing._lock:
        ring = list(tracing._ring)
    call = [s for s in ring if (s.tier, s.op) == CALL][-1]
    mine = [s for s in ring if s.trace == call.trace and s.tier == "ec"]

    def share(op):
        return 100 * sum(s.dur for s in mine if s.op == op) / call.dur
    for name, op in [("large_row_share.vol15g", "rows.large"),
                     ("write_share.vol15g", "write"),
                     ("h2d_share.vol15g", "h2d"),
                     ("pread_share.vol15g", "pread")]:
        assert out["metrics"][name]["value"] == pytest.approx(share(op)), \
            name
    assert 0 < out["metrics"]["large_row_share.vol15g"]["value"] < 100
