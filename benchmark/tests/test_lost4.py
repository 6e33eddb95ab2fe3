"""CPU tests of rebuild.lost4's kind (`kinds/ec_rebuild_server_lost.py`)
and its metric readers. Run by hand with the other benchmark tests:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q -p no:cacheprovider

Each run of the cell waits for the master to reap the lost server: three
of the master's 5 s pulses, so about 20 s a run. That each control and
fault of the kind fails the check at this size is
test_benchmark.py's `test_fault_is_not_correct`, which takes every
cell's `faults()`.
"""

from __future__ import annotations

import asyncio
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, ROOT)

import generator  # noqa: E402
import run  # noqa: E402
import trace_reduce  # noqa: E402

CELL = "rebuild.lost4"
KIND = generator.load("ec_rebuild_server_lost")
NEW = ["gf_roofline.lost4", "device_idle.lost4", "gather_share.lost4",
       "decode_share.lost4", "moved_per_repaired.lost4"]
# the cell at a CPU size: 16 KB small blocks, 2 MB of needles
TINY = {"ec_large_block": 1 << 20, "ec_small_block": 16 << 10,
        "volume_size_limit_mb": 64,
        "needles": {"dist": "log_uniform", "min": 100, "max": 40000,
                    "fill_mb": 2}}
PEAK = {"hbm_bytes_per_s": 819e9}


def tiny_spec() -> dict:
    spec = run.find_cell(CELL)
    return dict(spec, config=dict(spec["config"], **TINY))


def test_cell_loads_by_name():
    spec = run.find_cell(CELL)
    cfg = spec["config"]
    assert cfg["name"] == "cold_ec_1g_4srv"
    assert cfg["volume_servers"] == 4 and cfg["racks"] == 1
    base = run.load_json(HERE, "configs", "cold_ec_1g.json")
    for key in ("data_shards", "parity_shards", "ec_large_block",
                "ec_small_block", "volume_size_limit_mb", "replication",
                "volume_flags", "needles", "reduced", "source_values"):
        assert cfg[key] == base[key], key
    assert spec["traffic"]["kind"] == "ec_rebuild_server_lost"
    assert spec["traffic"]["lost_holds"] == {"data": 3, "parity": 1}
    assert spec["cell"]["chips"] == 1
    assert {m["name"] for m in spec["end_to_end"]} == {"rebuild_GBps",
                                                       "setup_s"}
    assert [m["name"] for m in spec["per_layer"]] == NEW


def ctx(spans: dict, work: dict, busy_s: float = 0.0) -> dict:
    return {"spans": spans, "work": {"ops": 2, "hbm_bytes": 0, **work},
            "trace": {"busy_s": busy_s, "window_s": 1.0, "device_ops": 0},
            "peak": PEAK}


def test_readers_none_without_their_spans_or_counter():
    for name in NEW[2:]:
        assert run.read_metric(name, ctx({}, {})) is None, name
    assert run.read_metric("gf_roofline.lost4", ctx({}, {})) is None
    # the parent program: the root spans, no repair counter
    spans = {("volume", "ec.copy"): [50.0] * 3 + [100.0] * 14,
             ("volume", "ec.rebuild"): [900.0, 400.0, 300.0]}
    work = {"copies": 14, "seconds": 2.0}
    assert run.read_metric("moved_per_repaired.lost4",
                           ctx(spans, work)) is None
    assert run.read_metric("gather_share.lost4", ctx(spans, work)) == \
        pytest.approx(100 * 1400 / 2000)
    assert run.read_metric("decode_share.lost4", ctx(spans, work)) == \
        pytest.approx(100 * 700 / 2000)
    work["repair_bytes"] = {"copied": 7e8, "rebuilt": 4e8}
    assert run.read_metric("moved_per_repaired.lost4",
                           ctx(spans, work)) == pytest.approx(1.75)
    work["hbm_bytes"] = 819e6
    assert run.read_metric("gf_roofline.lost4", ctx(spans, work, 0.01)) \
        == pytest.approx(10.0)


def test_cell_rehearsal_with_its_readers(tmp_path, monkeypatch):
    """The cell at a CPU size through run.run_cell, traced: correct,
    every reader reads, and the repair counter's ratio is the bytes the
    rebuilder fetched (each survivor it lacked, with the .ecx) over the
    four shards it wrote. The trace of the CPU has no chip, so its
    reduction is stood in for."""
    monkeypatch.setattr(trace_reduce, "reduce", lambda devices, host: {
        "busy_s": 0.001, "window_s": 1.0, "device_ops": 1,
        "breakdown": {"device_ops": [], "idle_gaps": []}})
    out = asyncio.run(run.run_cell(tiny_spec(), 2**32 + 29, 1.0, True,
                                   str(tmp_path), peak=PEAK))
    assert out["correct"], out["checks"]
    assert out["failed"] == 0
    assert set(out["checks"]) == {
        "shard_bytes_wrong", "earlier_files_differing",
        "survivors_differing", "shards_missing", "borrowed_copies_left",
        "ops_failed"}
    got = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(got) == set(NEW)
    assert 0 < got["gather_share.lost4"] < 100
    assert 0 < got["decode_share.lost4"] < 100
    assert got["gather_share.lost4"] + got["decode_share.lost4"] < 100
    # 7 survivors copied a rebuild (the harness's server, holding three
    # survivors, is the freest), each with the .ecx, for 4 shards written
    assert 7 / 4 < got["moved_per_repaired.lost4"] < 7 / 4 * 1.1
