"""util/tracing.py span recorder + the observability satellites:
traceparent parse/format, context parenthood, ring + in-flight
introspection payloads, cross-worker merge, the no-op fast path,
slow-request logging, the prometheus bridge, metrics push-loop error
accounting, merged-metrics integer formatting, and per-worker pprof
dump paths."""

from __future__ import annotations

import asyncio
import os
import subprocess
import sys
import time

import pytest

from seaweedfs_tpu.util import tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_tracing():
    tracing.init(sample=1.0, slow_ms=0.0)
    tracing.reset()
    yield
    tracing.init(sample=1.0, slow_ms=0.0)
    tracing.reset()


# ---------------------------------------------------------------------------
# traceparent


def test_traceparent_roundtrip():
    with tracing.start_root("volume", "read") as sp:
        tp = sp.traceparent()
    parsed = tracing.parse_traceparent(tp)
    assert parsed is not None
    trace, parent, flags = parsed
    assert trace == sp.trace and parent == sp.span_id and flags & 1


@pytest.mark.parametrize("bad", [
    "", "00", "00-short-span-01", "zz-" + "0" * 32 + "-" + "1" * 16,
    "00-" + "g" * 32 + "-" + "1" * 16 + "-01",
    "ff-" + "0" * 32 + "-" + "1" * 16 + "-01",
])
def test_traceparent_malformed(bad):
    assert tracing.parse_traceparent(bad) is None


def test_unsampled_traceparent_is_noop():
    tp = "00-" + "a" * 32 + "-" + "b" * 16 + "-00"
    assert not tracing.start_root("volume", "read", traceparent=tp)


def test_incoming_sampled_trace_joins_even_at_sample_zero():
    tracing.init(sample=0.0)
    assert not tracing.start_root("volume", "read")   # local roll: off
    tp = "00-" + "a" * 32 + "-" + "b" * 16 + "-01"
    sp = tracing.start_root("volume", "read", traceparent=tp)
    assert sp and sp.trace == "a" * 32 and sp.parent == "b" * 16
    sp.cancel()


# ---------------------------------------------------------------------------
# parenthood + ring payloads


def test_child_spans_nest_and_self_time_sums_to_wall():
    with tracing.start_root("s3", "get") as root:
        with tracing.start("filer", "stream") as mid:
            with tracing.start("client", "read") as leaf:
                leaf.nbytes = 42
            assert leaf.trace == root.trace
            assert leaf.parent == mid.span_id
        assert mid.parent == root.span_id
    d = tracing.traces_dict()
    assert d["spans"] == 3 and len(d["traces"]) == 1
    g = d["traces"][0]
    assert g["trace_id"] == root.trace
    # non-overlapping attribution: per-span self time sums to ~the
    # trace's wall time
    assert abs(sum(s["self_ms"] for s in g["spans"]) - g["dur_ms"]) < 1.0
    assert set(g["tiers"]) == {"s3", "filer", "client"}


def test_no_active_parent_means_noop_child():
    assert not tracing.start("store", "read")


def test_events_and_attrs_recorded_and_bounded():
    with tracing.start_root("client", "read", fid="3,01ab") as sp:
        for i in range(200):
            sp.event("retry", attempt=i)
        sp.set("source", "cache")
    g = tracing.traces_dict()["traces"][0]
    s = g["spans"][0]
    assert s["attrs"]["fid"] == "3,01ab"
    assert s["attrs"]["source"] == "cache"
    assert len(s["events"]) == 64          # bounded
    assert s["events"][0]["name"] == "retry"
    assert "t_ms" in s["events"][0]


def test_cancel_discards_span():
    sp = tracing.start_root("volume", "read")
    sp.cancel()
    sp.finish()
    assert tracing.traces_dict()["spans"] == 0
    assert tracing.requests_dict()["inflight"] == 0


def test_requests_dict_shows_inflight_with_age():
    sp = tracing.start_root("volume", "read")
    try:
        time.sleep(0.01)
        r = tracing.requests_dict()
        assert r["inflight"] == 1
        assert r["requests"][0]["tier"] == "volume"
        assert r["requests"][0]["age_ms"] >= 10
    finally:
        sp.finish()
    assert tracing.requests_dict()["inflight"] == 0


def test_explicit_status_survives_exception_exit():
    with pytest.raises(ValueError):
        with tracing.start_root("volume", "read") as sp:
            sp.status = "404"
            raise ValueError("gone")
    s = tracing.traces_dict()["traces"][0]["spans"][0]
    assert s["status"] == "404"
    with pytest.raises(ValueError):
        with tracing.start_root("volume", "read"):
            raise ValueError("boom")
    statuses = {s["status"]
                for g in tracing.traces_dict()["traces"]
                for s in g["spans"]}
    assert "error" in statuses


def test_ring_is_bounded():
    tracing.init(sample=1.0, ring=32)
    for _ in range(100):
        tracing.start_root("volume", "read").finish()
    assert tracing.traces_dict(recent=1000)["spans"] == 32
    tracing.init(sample=1.0, ring=2048)


def test_traces_query_clamps_zero_and_negative_counts():
    for _ in range(3):
        tracing.start_root("volume", "read").finish()
    full = tracing.traces_dict()
    assert len(full["traces"]) == 3
    # ?n=0 must be EMPTY (a -0 slice would return the whole ring)
    z = tracing.traces_query({"n": "0", "slowest": "0"})
    assert z["traces"] == [] and z["slowest"] == []
    neg = tracing.traces_query({"n": "-5", "slowest": "-1"})
    assert neg["traces"] == [] and neg["slowest"] == []
    with pytest.raises(ValueError):
        tracing.traces_query({"n": "bogus"})


def test_merge_payloads_dedupes_and_merges():
    with tracing.start_root("volume", "read") as a:
        pass
    p1 = tracing.traces_dict()
    tracing.reset()
    # a second "worker" carries a different span of the SAME trace
    with tracing.start_root("volume", "read",
                            traceparent=a.traceparent()):
        with tracing.start("store", "read"):
            pass
    p2 = tracing.traces_dict()
    merged = tracing.merge_payloads([p1, p2, p2])   # p2 twice: dedupe
    assert merged["spans"] == 3
    assert len(merged["traces"]) == 1
    assert merged["traces"][0]["trace_id"] == a.trace


def test_executor_context_propagation_pattern():
    """The volume server carries the request context into executor
    threads via contextvars.copy_context — store spans must parent
    under the request span."""
    import contextvars

    async def body():
        with tracing.start_root("volume", "read") as root:
            ctx = contextvars.copy_context()

            def work():
                with tracing.start("store", "read") as sp:
                    sp.set("source", "pread")

            await asyncio.get_running_loop().run_in_executor(
                None, lambda: ctx.run(work))
        return root

    root = asyncio.run(body())
    g = tracing.traces_dict()["traces"][0]
    store = [s for s in g["spans"] if s["tier"] == "store"][0]
    assert store["parent"] == root.span_id


def test_run_in_executor_times_the_queue():
    """Under a trace every executor hop records an `exec`/`queue` span
    from the submit to the start in a worker thread: behind a busy
    one-thread pool the second hop waits for the first to finish."""
    from concurrent.futures import ThreadPoolExecutor

    async def body():
        loop = asyncio.get_running_loop()
        loop.set_default_executor(ThreadPoolExecutor(1))
        with tracing.start_root("volume", "read") as root:
            def work():
                with tracing.start("store", "read"):
                    time.sleep(0.05)
            await asyncio.gather(tracing.run_in_executor(work),
                                 tracing.run_in_executor(work))
        # untraced hops record nothing
        await tracing.run_in_executor(time.sleep, 0)
        return root

    root = asyncio.run(body())
    spans = tracing.traces_dict()["traces"][0]["spans"]
    queued = [s for s in spans if (s["tier"], s["op"]) == ("exec", "queue")]
    assert len(queued) == 2
    assert all(s["parent"] == root.span_id for s in queued)
    assert max(s["dur_ms"] for s in queued) >= 40
    stores = [s for s in spans if s["tier"] == "store"]
    assert [s["parent"] for s in stores] == [root.span_id] * 2
    assert tracing.traces_dict()["spans"] == 5
    assert tracing.requests_dict()["inflight"] == 0


def test_overlapped_pipeline_threads_keep_the_parent():
    """_run_overlapped's reader and writer threads run in copies of the
    caller's context: spans opened there parent under the caller's."""
    from seaweedfs_tpu.ec.pipeline import _run_overlapped

    def read_batches():
        for i in range(3):
            with tracing.start("ec", "pread"):
                pass
            yield i

    def launch(i):
        with tracing.start("ec", "launch"):
            return i, None

    def write_result(i, _):
        with tracing.start("ec", "write"):
            pass

    with tracing.start_root("volume", "ec.rebuild") as root:
        _run_overlapped(read_batches(), launch, write_result)
    spans = tracing.traces_dict()["traces"][0]["spans"]
    ops = sorted(s["op"] for s in spans if s["tier"] == "ec")
    assert ops == ["launch"] * 3 + ["pread"] * 3 + ["write"] * 3
    assert all(s["parent"] == root.span_id
               for s in spans if s["tier"] == "ec")


def test_timed_lock_records_wait_and_hold_after_release():
    """timed_lock times the wait and the hold as child spans that reach
    the ring only once the lock is free again, on a raise too; untraced
    it only takes the lock."""
    import threading
    lock = threading.Lock()
    with tracing.timed_lock(lock, "ec"):
        assert lock.locked()
    assert tracing.traces_dict()["spans"] == 0
    with tracing.start_root("volume", "read") as root:
        lock.acquire()
        threading.Timer(0.03, lock.release).start()
        with pytest.raises(KeyError):
            with tracing.timed_lock(lock, "ec"):
                assert tracing.traces_dict()["spans"] == 0
                time.sleep(0.02)
                raise KeyError("gone")
    assert not lock.locked()
    spans = {s["op"]: s for s in tracing.traces_dict()["traces"][0]["spans"]}
    assert spans["lock_wait"]["dur_ms"] >= 20
    assert spans["locked"]["dur_ms"] >= 15
    assert spans["lock_wait"]["parent"] == root.span_id
    assert spans["locked"]["parent"] == root.span_id
    wait, held = spans["lock_wait"], spans["locked"]
    assert held["start_ms"] >= wait["start_ms"] + wait["dur_ms"] - 1


def test_concurrent_spans_and_readers_lose_nothing():
    """Many threads finishing spans while readers copy the ring, the
    in-flight table and the exemplars: no span is lost and no reader
    raises."""
    import sys
    import threading
    n_threads, per_thread = 24, 40   # 1,920 spans: the ring holds 2,048
    errors: list = []
    done = threading.Event()

    def write():
        for _ in range(per_thread):
            with tracing.start_root("volume", "read"):
                with tracing.start("ec", "pread"):
                    pass

    def read():
        try:
            while not done.is_set():
                tracing.traces_dict(recent=5, slowest=5)
                tracing.requests_dict()
                tracing.trace_spans_dict("0" * 32)
                tracing.drain_exemplars()
        except Exception as e:  # noqa: BLE001 — reported below
            errors.append(e)

    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        readers = [threading.Thread(target=read) for _ in range(2)]
        writers = [threading.Thread(target=write) for _ in range(n_threads)]
        for t in readers + writers:
            t.start()
        for t in writers:
            t.join(timeout=60)
        done.set()
        for t in readers:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(switch)
    assert not any(t.is_alive() for t in readers + writers)
    assert errors == []
    assert tracing.traces_dict(recent=0, slowest=0)["spans"] == \
        2 * n_threads * per_thread
    assert tracing.requests_dict()["inflight"] == 0


def test_importing_tracing_does_not_import_jax():
    """The client tools and the load generator import tracing and must
    never pull in JAX: spans write profiler events only where the
    process already imported it."""
    code = ("import sys\n"
            "from seaweedfs_tpu.util import tracing\n"
            "with tracing.start_root('client', 'read'):\n"
            "    with tracing.start('client', 'hop'):\n"
            "        pass\n"
            "assert tracing.traces_dict()['spans'] == 2\n"
            "assert 'jax' not in sys.modules, 'jax imported'\n")
    p = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr


def test_spans_are_profiler_host_events(tmp_path):
    """While a JAX profiler session runs, a span is also a host event
    `<tier>.<op>` covering the span, including one finished on another
    thread than the one that opened it; outside a session, none."""
    import glob
    import threading

    import jax
    from jax.profiler import ProfileData

    with tracing.start_root("volume", "early"):
        pass
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracing.start_root("volume", "ec.rebuild"):
            with tracing.start("ec", "pread"):
                time.sleep(0.01)
            handed = tracing.start("exec", "queue")
            t = threading.Thread(target=lambda: (time.sleep(0.01),
                                                 handed.finish()))
            t.start()
            t.join()
    finally:
        jax.profiler.stop_trace()
    with tracing.start_root("volume", "late"):
        pass
    pb = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    assert len(pb) == 1
    host = {}
    for plane in ProfileData.from_file(pb[0]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    host.setdefault(e.name, []).append(e.duration_ns)
    assert len(host["ec.pread"]) == 1 and host["ec.pread"][0] >= 10e6
    assert host["exec.queue"][0] >= 10e6
    assert host["volume.ec.rebuild"][0] >= host["ec.pread"][0]
    assert "volume.early" not in host and "volume.late" not in host


def test_slow_request_glog_line(capsys):
    tracing.init(sample=1.0, slow_ms=1.0)
    with tracing.start_root("volume", "read"):
        time.sleep(0.01)
    err = capsys.readouterr().err
    assert "slow request" in err and "trace=" in err
    # child spans of a fast parent never log
    tracing.init(sample=1.0, slow_ms=10_000.0)
    with tracing.start_root("volume", "read"):
        pass
    assert "slow request" not in capsys.readouterr().err


def test_prometheus_histogram_agrees_with_ring():
    metrics = pytest.importorskip("seaweedfs_tpu.stats.metrics")
    if not metrics.HAVE_PROMETHEUS:
        pytest.skip("prometheus_client unavailable")
    before = metrics.REQUEST_DURATION.labels(
        "testtier", "testop", "ok")._sum.get()
    with tracing.start_root("testtier", "testop"):
        pass
    after = metrics.REQUEST_DURATION.labels(
        "testtier", "testop", "ok")._sum.get()
    assert after > before


# ---------------------------------------------------------------------------
# satellites: metrics push loop, merge formatting, pprof


def test_push_loop_counts_and_logs_failures(capsys, monkeypatch):
    metrics = pytest.importorskip("seaweedfs_tpu.stats.metrics")
    if not metrics.HAVE_PROMETHEUS:
        pytest.skip("prometheus_client unavailable")

    async def body():
        # unresolvable gateway: every push fails fast-ish; two loop
        # turns prove the counter moves and the first failure logs
        task = asyncio.get_event_loop().create_task(
            metrics.push_loop("127.0.0.1:1", "testjob",
                              interval_seconds=0.05))
        for _ in range(100):
            await asyncio.sleep(0.05)
            if metrics.METRICS_PUSH_ERRORS._value.get() >= 1:
                break
        task.cancel()
        try:
            await task
        except asyncio.CancelledError:
            pass

    before = metrics.METRICS_PUSH_ERRORS._value.get()
    asyncio.run(body())
    assert metrics.METRICS_PUSH_ERRORS._value.get() > before
    assert "metrics push to 127.0.0.1:1 failed" in capsys.readouterr().err


def test_merge_metrics_integer_roundtrip_with_histograms():
    prometheus_client = pytest.importorskip("prometheus_client")
    from prometheus_client import CollectorRegistry, Histogram
    from prometheus_client.parser import text_string_to_metric_families
    from seaweedfs_tpu.stats.metrics import merge_metrics_texts

    texts = []
    for observations in ([0.1, 0.2, 3.0], [0.4]):
        reg = CollectorRegistry()
        h = Histogram("SeaweedFS_test_merge_seconds", "merge test",
                      registry=reg)
        for v in observations:
            h.observe(v)
        texts.append(prometheus_client.generate_latest(reg))
    merged = merge_metrics_texts(texts).decode()
    # bucket/count values are integral: no trailing .0, no exponent
    for line in merged.splitlines():
        if line.startswith("SeaweedFS_test_merge_seconds_count"):
            assert line.endswith(" 4"), line
        if line.startswith("SeaweedFS_test_merge_seconds_bucket"):
            val = line.rsplit(" ", 1)[1]
            assert "." not in val and "e" not in val, line
    # and the merged exposition still parses as prometheus text
    fams = {f.name: f for f in
            text_string_to_metric_families(merged)}
    fam = fams["SeaweedFS_test_merge_seconds"]
    samples = {(s.name, s.labels.get("le")): s.value
               for s in fam.samples}
    assert samples[("SeaweedFS_test_merge_seconds_count", None)] == 4
    assert samples[("SeaweedFS_test_merge_seconds_sum", None)] == \
        pytest.approx(3.7)
    # a large counter sum renders as plain digits, never 9.0072e+15
    big = merge_metrics_texts(
        [b"c_total 9007199254740992.0\n", b"c_total 1024.0\n"]).decode()
    assert big.startswith("c_total 9007199254742016\n"), big


def test_pprof_worker_suffix(tmp_path):
    from seaweedfs_tpu.util.pprof import profile_path
    assert profile_path("/x/prof.out", -1) == "/x/prof.out"
    assert profile_path("/x/prof.out", 2) == "/x/prof.out.w2"

    # smoke: the dump file actually appears at the suffixed path when
    # a profiled process exits (atexit-driven, so a real subprocess)
    cpu = tmp_path / "cpu.prof"
    code = (
        "from seaweedfs_tpu.util.pprof import setup_profiling\n"
        f"setup_profiling({str(cpu)!r}, worker_index=1)\n"
        "sum(range(1000))\n")
    subprocess.run([sys.executable, "-c", code], check=True,
                   env=dict(os.environ, PYTHONPATH=REPO), timeout=60)
    assert (tmp_path / "cpu.prof.w1").exists()
    assert not cpu.exists()
    import pstats
    pstats.Stats(str(tmp_path / "cpu.prof.w1"))  # parseable dump
