"""CPU tests of the readers of the program's spans (span_share.py and the
metrics that use it). Run by hand with the other benchmark tests:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q -p no:cacheprovider
"""

from __future__ import annotations

import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, ROOT)

import run  # noqa: E402

BENCH = run.load_json(ROOT, "BENCHMARK.json")
STAGES = ("pread", "h2d", "launch", "readback", "write")
SHARES = [f"{s}_share.encode" for s in STAGES]
READS = ["exec_queue_ms_mean.read", "lock_wait_ms_mean.read",
         "lock_busy_share.read", "loop_ms_mean.read"]
CALL = ("volume", "ec.generate_batch")


def ctx(spans: dict, ops: int = 0, window_s: float = 10.0) -> dict:
    return {"spans": spans, "work": {"ops": ops},
            "trace": {"busy_s": 0.0, "window_s": window_s, "device_ops": 0}}


def encode_calls(n: int, launch_ms: float = 5.0) -> dict:
    """n generate_batch calls of 1,000 ms, each with the same stage
    spans: 10 preads of 20 ms, 2 h2d of 50, 2 launches, 1 readback of
    300 and 2 writes of 150 ms."""
    per = {"pread": [20.0] * 10, "h2d": [50.0] * 2,
           "launch": [launch_ms] * 2, "readback": [300.0],
           "write": [150.0] * 2}
    spans = {CALL: [1000.0] * n}
    for st, durs in per.items():
        spans[("ec", st)] = durs * n
    return spans


def joined(*parts: dict) -> dict:
    """Span lists drained one after the other, in finish order."""
    out: dict = {}
    for p in parts:
        for k, v in p.items():
            out.setdefault(k, []).extend(v)
    return out


def test_every_span_metric_is_declared():
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name in SHARES + READS:
        m = by_name[name]
        assert m["source"] == "program_span" and m["better"] == "lower"
        assert m["unit"] == ("ms" if name.endswith("_ms_mean.read")
                             else "%")
        assert m["workloads"]


def test_stage_shares():
    want = {"pread": 20.0, "h2d": 10.0, "launch": 1.0, "readback": 30.0,
            "write": 30.0}
    c = ctx(encode_calls(3), ops=3)
    for st, share in want.items():
        assert run.read_metric(f"{st}_share.encode", c) == \
            pytest.approx(share)
    # a stage with no span, or no call to measure against: nothing
    for name in SHARES:
        assert run.read_metric(name, ctx({}, ops=3)) is None
        assert run.read_metric(name, ctx({CALL: [1000.0]}, ops=1)) is None
        assert run.read_metric(name, ctx(
            {k: v for k, v in encode_calls(1).items() if k != CALL},
            ops=1)) is None


@pytest.mark.parametrize("warm_ups", [1, 2])
def test_stage_shares_leave_out_the_warm_up_call(warm_ups):
    """The drain's first copy takes the warm-up call, whose launches
    hold the compiles and whose call is that much longer: the shares
    read the window's calls alone, as without the warm-up."""
    warm = encode_calls(warm_ups, launch_ms=4000.0)
    warm[CALL] = [9000.0] * warm_ups
    drained = ctx(joined(warm, encode_calls(3)), ops=3)
    window_only = ctx(encode_calls(3), ops=3)
    for name in SHARES:
        assert run.read_metric(name, drained) == \
            pytest.approx(run.read_metric(name, window_only))
    assert run.read_metric("launch_share.encode", drained) == \
        pytest.approx(1.0)


def read_spans(gets: int, wait_ms: float, held_ms: float,
               cache_hits: int = 0) -> dict:
    """GETs of 10 ms: 1 ms in the executor queue, 4 ms in the store,
    each that the needle cache does not answer with one lock wait and
    one hold."""
    locked = gets - cache_hits
    return {("volume", "read"): [10.0] * gets,
            ("exec", "queue"): [1.0] * locked,
            ("store", "read"): [4.0] * locked,
            ("ec", "lock_wait"): [wait_ms] * locked,
            ("ec", "locked"): [held_ms] * locked}


def test_read_path_metrics():
    spans = {("volume", "read"): [30.0, 20.0, 1.0, 29.0],  # one cache hit
             ("exec", "queue"): [4.0, 2.0, 3.0],
             ("store", "read"): [10.0, 8.0, 12.0],
             ("ec", "lock_wait"): [0.0, 6.0, 3.0],
             ("ec", "locked"): [2.0, 4.0, 3.0]}
    c = ctx(spans, ops=4, window_s=0.1)
    assert run.read_metric("exec_queue_ms_mean.read", c) == pytest.approx(3)
    assert run.read_metric("lock_wait_ms_mean.read", c) == pytest.approx(3)
    # 9 ms held in a 100 ms window
    assert run.read_metric("lock_busy_share.read", c) == pytest.approx(9)
    # (80 - 9 - 30) ms over 4 GETs
    assert run.read_metric("loop_ms_mean.read", c) == pytest.approx(41 / 4)
    for name in READS:
        assert run.read_metric(name, ctx({}, ops=4)) is None
    # a program without the executor or lock spans: the GET and store
    # spans alone give nothing
    old = {k: v for k, v in spans.items()
           if k in (("volume", "read"), ("store", "read"))}
    for name in READS:
        assert run.read_metric(name, ctx(old, ops=4)) is None


def test_read_path_metrics_leave_out_the_warm_up():
    """65 warm-up GETs whose holds compile the recover shapes: the
    readings are the window's 4,000 GETs', as without them."""
    window_only = ctx(read_spans(4000, wait_ms=20.0, held_ms=2.0), ops=4000)
    drained = ctx(joined(read_spans(65, wait_ms=300.0, held_ms=250.0),
                         read_spans(4000, wait_ms=20.0, held_ms=2.0)),
                  ops=4000)
    for name in READS:
        assert run.read_metric(name, drained) == \
            pytest.approx(run.read_metric(name, window_only))
    assert run.read_metric("lock_busy_share.read", drained) == \
        pytest.approx(80.0)
    assert run.read_metric("loop_ms_mean.read", drained) == \
        pytest.approx(5.0)
