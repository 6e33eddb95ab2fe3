"""EC end-to-end oracle: the reference ec_test.go pattern.

Build a real volume of random needles, stripe it to 14 shard files, then
prove every needle reads back bit-identically (a) through direct stripe
math and (b) with shards destroyed, through on-the-fly reconstruction.
Then rebuild the missing shard files and compare byte-for-byte.
"""

import hashlib
import itertools
import os
import random
import time

import numpy as np
import pytest

from seaweedfs_tpu.ec import gf
from seaweedfs_tpu.ec.ec_volume import EcVolume, NotFoundError
from seaweedfs_tpu.ec.locate import locate_data, shard_file_size
from seaweedfs_tpu.ec import pipeline as pl
from seaweedfs_tpu.storage.needle import Needle
from seaweedfs_tpu.storage.volume import Volume

# Small geometry so tests exercise both large and small block areas fast.
LB = 16 * 1024   # large block
SB = 1024        # small block


@pytest.fixture(scope="module")
def ec_fixture(tmp_path_factory):
    """A volume with ~200 needles striped into 14 shards."""
    d = str(tmp_path_factory.mktemp("ecvol"))
    v = Volume(d, "", 5)
    rng = random.Random(11)
    contents = {}
    for i in range(1, 201):
        data = bytes(rng.getrandbits(8) for _ in range(rng.randint(1, 4096)))
        v.write_needle(Needle(cookie=i ^ 0x5A, id=i, data=data))
        contents[i] = data
    # tombstone a few
    for i in (17, 99):
        v.delete_needle(Needle(cookie=i ^ 0x5A, id=i))
        del contents[i]
    v.close()

    base = os.path.join(d, "5")
    enc = pl.get_encoder("cpu")
    pl.write_ec_files(base, encoder=enc, large_block=LB, small_block=SB,
                      buffer_size=SB)
    pl.write_sorted_file_from_idx(base)
    return d, base, contents


def test_shard_files_created(ec_fixture):
    d, base, _ = ec_fixture
    dat_size = os.path.getsize(base + ".dat")
    want = shard_file_size(dat_size, LB, SB)
    for i in range(14):
        assert os.path.getsize(base + pl.to_ext(i)) == want, i


def test_locate_data_unit():
    # mirrors TestLocateData (ec_test.go:187): intervals tile the request
    dat_size = 2 * LB * 10 + 3 * SB * 10 + 100
    for off, size in [(0, 1), (LB - 1, 2), (2 * LB * 10 - 1, 2),
                      (2 * LB * 10 + 5, SB * 3), (0, dat_size)]:
        ivs = locate_data(LB, SB, dat_size, off, size)
        assert sum(iv.size for iv in ivs) == size
        # re-read through shard mapping must cover contiguous logical range
        total = 0
        for iv in ivs:
            sid, soff = iv.to_shard_and_offset(LB, SB)
            assert 0 <= sid < 10
            assert soff >= 0
            total += iv.size
        assert total == size


def test_direct_reads_match(ec_fixture):
    d, base, contents = ec_fixture
    ev = EcVolume(d, "", 5, large_block=LB, small_block=SB,
                  encoder=pl.get_encoder("cpu"))
    for nid, data in contents.items():
        n = ev.read_needle(nid, cookie=nid ^ 0x5A)
        assert n.data == data, nid
    for nid in (17, 99):
        with pytest.raises(NotFoundError):
            ev.read_needle(nid)
    ev.close()


def test_degraded_reads_all_loss_patterns(ec_fixture, tmp_path):
    """Read through reconstruction with 4 shards gone (multiple patterns)."""
    d, base, contents = ec_fixture
    sample = dict(itertools.islice(contents.items(), 25))
    for missing in [(0, 1, 2, 3), (10, 11, 12, 13), (0, 5, 9, 12)]:
        ev = EcVolume(d, "", 5, large_block=LB, small_block=SB,
                      encoder=pl.get_encoder("cpu"))
        for sid in missing:
            ev.shards.pop(sid).close()
        for nid, data in sample.items():
            n = ev.read_needle(nid)
            assert n.data == data, (missing, nid)
        ev.close()


def test_rebuild_missing_shards(ec_fixture, tmp_path):
    d, base, contents = ec_fixture
    # copy shard files to a scratch dir, drop 4, rebuild, compare
    import shutil
    scratch = str(tmp_path / "rebuild")
    os.makedirs(scratch)
    nb = os.path.join(scratch, "5")
    originals = {}
    for i in range(14):
        src = base + pl.to_ext(i)
        with open(src, "rb") as f:
            originals[i] = hashlib.sha256(f.read()).hexdigest()
        if i not in (2, 6, 11, 13):
            shutil.copy(src, nb + pl.to_ext(i))
    rebuilt = pl.rebuild_ec_files(nb, encoder=pl.get_encoder("cpu"))
    assert sorted(rebuilt) == [2, 6, 11, 13]
    for i in rebuilt:
        with open(nb + pl.to_ext(i), "rb") as f:
            assert hashlib.sha256(f.read()).hexdigest() == originals[i], i


def test_rebuild_sequential_matches_batched(ec_fixture, tmp_path):
    """The bench baseline: sequential per-shard rebuild produces
    byte-identical shards to the batched one-matmul-per-window path,
    and the stats show batched reading the survivors ONCE while
    sequential re-reads them per lost shard."""
    import shutil
    d, base, _ = ec_fixture
    lost = (2, 6, 11, 13)
    originals = {}
    dirs = {}
    for mode in ("seq", "batch"):
        scratch = str(tmp_path / mode)
        os.makedirs(scratch)
        dirs[mode] = os.path.join(scratch, "5")
        for i in range(14):
            src = base + pl.to_ext(i)
            if mode == "seq":
                with open(src, "rb") as f:
                    originals[i] = hashlib.sha256(f.read()).hexdigest()
            if i not in lost:
                shutil.copy(src, dirs[mode] + pl.to_ext(i))
    stats = {"seq": {}, "batch": {}}
    assert sorted(pl.rebuild_ec_files(
        dirs["seq"], encoder=pl.get_encoder("cpu"), sequential=True,
        stats=stats["seq"])) == list(lost)
    assert sorted(pl.rebuild_ec_files(
        dirs["batch"], encoder=pl.get_encoder("cpu"),
        stats=stats["batch"])) == list(lost)
    for mode in ("seq", "batch"):
        for i in lost:
            with open(dirs[mode] + pl.to_ext(i), "rb") as f:
                assert hashlib.sha256(
                    f.read()).hexdigest() == originals[i], (mode, i)
    assert stats["seq"]["bytes_read"] == \
        len(lost) * stats["batch"]["bytes_read"]
    assert stats["seq"]["bytes_rebuilt"] == stats["batch"]["bytes_rebuilt"]
    assert stats["batch"]["launches"] < stats["seq"]["launches"]


def test_rebuild_unrepairable(tmp_path, ec_fixture):
    import shutil
    d, base, _ = ec_fixture
    nb = str(tmp_path / "5")
    for i in range(9):  # only 9 shards
        shutil.copy(base + pl.to_ext(i), nb + pl.to_ext(i))
    with pytest.raises(ValueError, match="unrepairable"):
        pl.rebuild_ec_files(nb, encoder=pl.get_encoder("cpu"))


def test_decode_back_to_dat(ec_fixture, tmp_path):
    import shutil
    d, base, contents = ec_fixture
    nb = str(tmp_path / "5")
    for i in range(10):
        shutil.copy(base + pl.to_ext(i), nb + pl.to_ext(i))
    shutil.copy(base + ".ecx", nb + ".ecx")
    dat_size = os.path.getsize(base + ".dat")
    # trailing tombstone records have no live .ecx entry, so the recovered
    # size covers the live prefix only (same as reference FindDatFileSize)
    found = pl.find_dat_file_size(nb)
    assert found <= dat_size
    pl.write_dat_file(nb, found, large_block=LB, small_block=SB)
    with open(base + ".dat", "rb") as a, open(nb + ".dat", "rb") as b:
        assert a.read(found) == b.read()


def test_ec_delete_journal(ec_fixture):
    d, base, contents = ec_fixture
    ev = EcVolume(d, "", 5, large_block=LB, small_block=SB,
                  encoder=pl.get_encoder("cpu"))
    victim = next(iter(contents))
    ev.read_needle(victim)
    ev.delete_needle(victim)
    with pytest.raises(NotFoundError):
        ev.read_needle(victim)
    ev.close()
    # journal recorded
    with open(base + ".ecj", "rb") as f:
        assert int.from_bytes(f.read(8), "big") == victim
    # reopening still sees the tombstone (persisted into .ecx)
    ev2 = EcVolume(d, "", 5, large_block=LB, small_block=SB)
    with pytest.raises(NotFoundError):
        ev2.read_needle(victim)
    ev2.close()


def test_batched_encode_matches_serial(tmp_path):
    """write_ec_files_batched must produce byte-identical shard files to
    the serial path for volumes of DIFFERENT sizes (rack-encode shape,
    uneven tail), including parity placement across flush groups."""
    import random as _random
    rng = _random.Random(23)
    sizes = [5 * LB * 10 + 3 * SB * 10 + 40,   # large rows + ragged tail
             2 * SB * 10 + 7,                  # small rows only
             LB * 10 + SB * 10]                # exact boundary
    serial, batched = [], []
    for i, size in enumerate(sizes):
        payload = bytes(rng.getrandbits(8) for _ in range(size))
        for tag, acc in (("s", serial), ("b", batched)):
            base = str(tmp_path / f"{tag}{i}")
            with open(base + ".dat", "wb") as f:
                f.write(payload)
            acc.append(base)
    enc = pl.get_encoder("cpu")
    for base in serial:
        pl.write_ec_files(base, encoder=enc, large_block=LB,
                          small_block=SB, buffer_size=SB)
    pl.write_ec_files_batched(batched, encoder=enc, large_block=LB,
                              small_block=SB, buffer_size=SB,
                              batch_volumes=4)
    for sbase, bbase in zip(serial, batched):
        for sid in range(14):
            with open(sbase + pl.to_ext(sid), "rb") as a, \
                    open(bbase + pl.to_ext(sid), "rb") as b:
                assert a.read() == b.read(), (sbase, sid)


def test_overlapped_pipeline_error_propagates(tmp_path):
    """A transform failure mid-stream must raise out of write_ec_files
    (not deadlock the reader/writer threads) and must not be swallowed."""
    import threading

    d = str(tmp_path)
    v = Volume(d, "", 7)
    rng = random.Random(3)
    for i in range(1, 30):
        v.write_needle(Needle(cookie=1, id=i,
                              data=bytes(rng.getrandbits(8)
                                         for _ in range(2000))))
    v.close()
    base = os.path.join(d, "7")

    from seaweedfs_tpu.ec.encoder_jax import JaxEncoder

    calls = {"n": 0}
    from seaweedfs_tpu.ec import pipeline as plmod
    from seaweedfs_tpu.ec.encoder_cpu import CpuEncoder
    orig = plmod.transform_block_async

    def exploding(encoder, coeff, block, stats=None):
        calls["n"] += 1
        if calls["n"] == 3:
            raise RuntimeError("kaboom")
        # stay off the device path under the fake: compute via numpy
        return orig(CpuEncoder(use_native=False), coeff, block, stats)

    plmod.transform_block_async = exploding
    try:
        before = threading.active_count()
        with pytest.raises(RuntimeError, match="kaboom"):
            # JaxEncoder selects the THREADED pipeline (_use_overlap),
            # which is the error path under test; batch_windows=1
            # keeps enough blocks in the stream for call 3 to land
            pl.write_ec_files(base, encoder=JaxEncoder(),
                              large_block=LB, small_block=SB,
                              buffer_size=SB, batch_windows=1)
        # pipeline threads joined, none leaked
        assert threading.active_count() <= before
    finally:
        plmod.transform_block_async = orig


def test_ec_backend_env_override(monkeypatch):
    """SWTPU_EC_BACKEND (the volume CLI's -ecBackend flag) pins the
    engine choice regardless of the attached accelerator."""
    from seaweedfs_tpu.ec.encoder_cpu import CpuEncoder

    monkeypatch.setenv("SWTPU_EC_BACKEND", "cpu")
    assert isinstance(pl.get_encoder(), CpuEncoder)
    # a tpu pin on a host without a TPU fails fast (tests run on cpu)
    monkeypatch.setenv("SWTPU_EC_BACKEND", "tpu")
    with pytest.raises(RuntimeError, match="no TPU is attached"):
        pl.get_encoder()
    # explicit argument still wins over the env
    assert isinstance(pl.get_encoder("cpu"), CpuEncoder)
    # garbage values are rejected, not silently mapped to cpu
    monkeypatch.setenv("SWTPU_EC_BACKEND", "gpu")
    with pytest.raises(ValueError, match="unknown EC backend"):
        pl.get_encoder()


def test_auto_backend_raises_when_backend_init_fails(monkeypatch):
    """'auto' picks the CPU only where JAX is absent: a backend that
    fails to come up raises instead of quietly serving XLA-on-CPU."""
    import jax

    def broken():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.delenv("SWTPU_EC_BACKEND", raising=False)
    monkeypatch.setattr(jax, "default_backend", broken)
    with pytest.raises(RuntimeError, match="initialize backend"):
        pl.get_encoder("auto")
    from seaweedfs_tpu.models import ec_model
    with pytest.raises(RuntimeError, match="initialize backend"):
        ec_model._default_use_pallas()


@pytest.mark.parametrize("flag,platforms,want", [
    ("tpu", "cpu", True),       # an explicit pin is taken at its word
    ("cpu", "tpu", False),
    ("auto", "cpu", False),     # JAX_PLATFORMS decides 'auto' first
    ("auto", "tpu", True),
    ("auto", "tpu,cpu", True),
    ("auto", "cpu,tpu", False),  # JAX ranks the list by order
])
def test_backend_resolves_without_jax_init(monkeypatch, flag, platforms,
                                           want):
    monkeypatch.setenv("JAX_PLATFORMS", platforms)
    assert pl.backend_resolves_to_tpu(flag) is want


def test_auto_probe_exists_and_reads_the_bus(monkeypatch):
    """With JAX_PLATFORMS unset, 'auto' asks JAX's own PCI probe: it must
    exist under the installed JAX, and this sandbox shows no chip."""
    from jax._src import hardware_utils
    assert callable(hardware_utils.num_available_tpu_chips_and_device_id)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    assert pl.backend_resolves_to_tpu("auto") is \
        (hardware_utils.num_available_tpu_chips_and_device_id()[0] > 0)


def test_auto_probe_missing_is_a_clear_error(monkeypatch):
    import sys
    import types
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setitem(sys.modules, "jax._src.hardware_utils",
                        types.ModuleType("jax._src.hardware_utils"))
    with pytest.raises(RuntimeError, match="pin -ecBackend"):
        pl.backend_resolves_to_tpu("auto")


def test_compile_cache_dir_env_wins(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR, when set, is the directory; otherwise
    the entry point's; every compile is cached, however quick."""
    import jax
    from seaweedfs_tpu.ops import compile_cache
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    try:
        monkeypatch.delenv(compile_cache.ENV, raising=False)
        mine = str(tmp_path / "mine")
        assert compile_cache.enable(mine) == mine
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
        theirs = str(tmp_path / "theirs")
        monkeypatch.setenv(compile_cache.ENV, theirs)
        jax.config.update("jax_compilation_cache_dir", theirs)
        assert compile_cache.enable(mine) == theirs
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)


def test_workers_refuse_a_tpu_backend(tmp_path):
    """-workers N on a TPU backend fails at start-up naming ROADMAP R1,
    instead of forking N processes that race for one chip."""
    import subprocess
    import sys
    proc = subprocess.run(
        [sys.executable, "-m", "seaweedfs_tpu.cli", "volume",
         "-workers", "2", "-ecBackend", "tpu", "-dir", str(tmp_path),
         "-port", "0"],
        capture_output=True, text=True, timeout=60)
    assert proc.returncode != 0
    assert "R1" in proc.stderr and "one chip" in proc.stderr
    assert not any(p.name.startswith("w") for p in tmp_path.iterdir())


# ---------------------------------------------------------------------------
# stage spans of the served EC paths (util/tracing.py)

STAGES = ("pread", "h2d", "launch", "readback", "write")


def _ec_spans(root) -> "dict[str, list[dict]]":
    """The `ec` spans of root's trace by op; each must parent to root."""
    from seaweedfs_tpu.util import tracing
    spans = tracing.trace_spans_dict(root.trace)["spans"]
    by_op: dict = {}
    for s in spans:
        if s["tier"] == "ec":
            assert s["parent"] == root.span_id, s
            by_op.setdefault(s["op"], []).append(s)
    return by_op


@pytest.fixture
def fresh_tracing():
    from seaweedfs_tpu.util import tracing
    tracing.init(sample=1.0, slow_ms=0.0)
    tracing.reset()
    yield tracing
    tracing.reset()


def _dat_files(tmp_path, sizes, seed=29):
    rng = np.random.default_rng(seed)
    bases = []
    for i, size in enumerate(sizes):
        base = str(tmp_path / f"v{i}")
        with open(base + ".dat", "wb") as f:
            f.write(rng.integers(0, 256, size, dtype=np.uint8).tobytes())
        bases.append(base)
    return bases


def test_batched_encode_stage_spans(tmp_path, fresh_tracing):
    """write_ec_files_batched with a JaxEncoder under a request span:
    every stage is a child span of the request, the pread spans add up
    to the .dat bytes, the write spans to the 14 shard files, and the
    byte counter counts the served path."""
    from seaweedfs_tpu.ec.encoder_jax import JaxEncoder
    from seaweedfs_tpu.stats import metrics
    bases = _dat_files(tmp_path, [3 * SB * 10 + 77, 2 * SB * 10])
    before = (metrics.EC_ENCODE_BYTES._value.get()
              if metrics.HAVE_PROMETHEUS else 0)
    with fresh_tracing.start_root("volume", "ec.generate_batch") as root:
        pl.write_ec_files_batched(bases, encoder=JaxEncoder(),
                                  large_block=LB, small_block=SB,
                                  buffer_size=SB, batch_volumes=3)
    by_op = _ec_spans(root)
    # no large rows: each volume's one small run
    assert sorted(by_op) == sorted(STAGES + ("rows.small",))

    def total(op):
        return sum(s["bytes"] for s in by_op[op])
    dat = sum(os.path.getsize(b + ".dat") for b in bases)
    shards = sum(os.path.getsize(b + pl.to_ext(i))
                 for b in bases for i in range(14))
    data_shards = sum(os.path.getsize(b + pl.to_ext(i))
                      for b in bases for i in range(10))
    assert total("pread") == dat
    assert total("write") == shards
    assert total("h2d") == total("launch") == data_shards
    assert total("readback") == shards - data_shards
    # 4 + 2 rows, launches of 3 rows: one of each device stage a launch,
    # and h2d twice, for joining the launch's rows and for the transfer
    assert len(by_op["pread"]) == 6
    assert len(by_op["launch"]) == len(by_op["readback"]) == 2
    assert len(by_op["h2d"]) == 4
    # a write span per row, per launch's parity, per volume's closing
    assert len(by_op["write"]) == 6 + 2 + 2
    if metrics.HAVE_PROMETHEUS:
        assert metrics.EC_ENCODE_BYTES._value.get() - before == data_shards
    assert sorted(s["bytes"] for s in by_op["rows.small"]) == sorted(
        os.path.getsize(b + ".dat") for b in bases)


# the served route at a geometry with one large row and a small tail in
# the row mix of a full 30,000 MB volume: 68% of the bytes in large rows
LB_MIX = 64 * 1024
SB_MIX = 4 * 1024
DAT_MIX = 10 * LB_MIX + 300 * 1024


def _oracle_shards(dat: bytes, large: int, small: int):
    """RS(10,4) shards of `dat` in SeaweedFS's stripe layout with the CPU
    encoder, and the .dat bytes in large and in small rows."""
    from seaweedfs_tpu.ec.encoder_cpu import CpuEncoder
    enc = CpuEncoder()
    shards = [bytearray() for _ in range(14)]
    pos = 0
    split = {"large": 0, "small": 0}
    for block in (large, small):
        kind = "large" if block == large else "small"
        while (len(dat) - pos > 10 * large if block == large
               else pos < len(dat)):
            row = dat[pos:pos + 10 * block].ljust(10 * block, b"\0")
            data = [np.frombuffer(row[i * block:(i + 1) * block], np.uint8)
                    for i in range(10)]
            for sid, out in enumerate(enc.encode(data)):
                shards[sid] += np.asarray(out, np.uint8).tobytes()
            split[kind] += min(10 * block, len(dat) - pos)
            pos += 10 * block
    return [bytes(s) for s in shards], split


def _sorted_idx(idx: bytes) -> bytes:
    """The .ecx of an .idx: the last entry per key, by ascending key."""
    last = {}
    for i in range(0, len(idx), 16):
        last[idx[i:i + 8]] = idx[i:i + 16]
    return b"".join(last[k] for k in sorted(last))


@pytest.mark.parametrize("enc", ["cpu", "jax"])
def test_generate_batch_route_large_and_small_rows(tmp_path, fresh_tracing,
                                                   monkeypatch, enc):
    """/admin/ec/generate_batch on a volume of one 10 x 64 KB large row
    and a 300 KB tail of 4 KB small rows: every shard and the .ecx are
    byte-exact, the stripe-bytes counter splits the .dat as the stripe
    layout does, and each row kind is one span with those bytes."""
    import asyncio

    from seaweedfs_tpu.stats import metrics
    from tests.cluster_util import Cluster

    if enc == "jax":
        monkeypatch.setattr(pl, "get_encoder",
                            lambda backend="auto": _encoder("jax"))
    striped = {k: (metrics.EC_STRIPE_BYTES.labels(block=k)._value.get()
                   if metrics.HAVE_PROMETHEUS else 0)
               for k in ("large", "small")}

    async def main():
        async with Cluster(str(tmp_path), n_servers=1,
                           ec_large_block=LB_MIX,
                           ec_small_block=SB_MIX) as c:
            vs = c.servers[0]
            vol = vs.store.add_volume(7)
            rng = np.random.default_rng(5)
            nid = 0
            while vol.data_size() < DAT_MIX:
                nid += 1
                vol.write_needle(Needle(cookie=nid, id=nid, data=rng.integers(
                    0, 256, int(rng.integers(1, 8192)),
                    dtype=np.uint8).tobytes()))
            fresh_tracing.reset()
            async with c.http.post(
                    f"http://{vs.url}/admin/ec/generate_batch",
                    params={"volumes": "7"}) as resp:
                assert resp.status == 200, await resp.text()
            return vol.file_name()
    base = asyncio.run(main())
    with open(base + ".dat", "rb") as f:
        dat = f.read()
    assert len(dat) > 10 * LB_MIX
    want, split = _oracle_shards(dat, LB_MIX, SB_MIX)
    assert split == {"large": 10 * LB_MIX, "small": len(dat) - 10 * LB_MIX}
    for sid in range(14):
        with open(base + pl.to_ext(sid), "rb") as f:
            assert f.read() == want[sid], sid
    with open(base + ".idx", "rb") as f, open(base + ".ecx", "rb") as g:
        assert g.read() == _sorted_idx(f.read())
    if metrics.HAVE_PROMETHEUS:
        for k in ("large", "small"):
            got = metrics.EC_STRIPE_BYTES.labels(block=k)._value.get()
            assert got - striped[k] == split[k], k
    with fresh_tracing._lock:
        rows = [(s.op, s.nbytes) for s in fresh_tracing._ring
                if s.tier == "ec" and s.op.startswith("rows.")]
    assert sorted(rows) == [("rows.large", split["large"]),
                            ("rows.small", split["small"])]


def test_rebuild_stage_spans(tmp_path, fresh_tracing):
    """rebuild_ec_files with a JaxEncoder: the reader, launcher and
    writer threads' stage spans all parent to the request span; pread
    bytes are the survivors read, write bytes the shards restored."""
    from seaweedfs_tpu.ec.encoder_jax import JaxEncoder
    (base,) = _dat_files(tmp_path, [5 * SB * 10 + 9])
    pl.write_ec_files(base, encoder=pl.get_encoder("cpu"), large_block=LB,
                      small_block=SB, buffer_size=SB)
    want = {}
    for sid in (0, 12):
        with open(base + pl.to_ext(sid), "rb") as f:
            want[sid] = f.read()
        os.remove(base + pl.to_ext(sid))
    fresh_tracing.reset()
    with fresh_tracing.start_root("volume", "ec.rebuild") as root:
        assert pl.rebuild_ec_files(base, encoder=JaxEncoder(),
                                   buffer_size=SB,
                                   batch_windows=2) == [0, 12]
    for sid, data in want.items():
        with open(base + pl.to_ext(sid), "rb") as f:
            assert f.read() == data
    by_op = _ec_spans(root)
    assert sorted(by_op) == sorted(STAGES)
    shard = len(want[0])
    assert sum(s["bytes"] for s in by_op["pread"]) == 10 * shard
    assert sum(s["bytes"] for s in by_op["write"]) == 2 * shard
    # 6 windows in blocks of 2: three of every stage, and the outputs'
    # closing is one more write
    assert {op: len(v) for op, v in by_op.items()} == \
        dict({op: 3 for op in STAGES}, write=4)


def _encoder(name):
    if name == "jax":
        from seaweedfs_tpu.ec.encoder_jax import JaxEncoder
        return JaxEncoder()
    return pl.get_encoder("cpu")


def _striped(tmp_path, lost, dat_size=40 * SB * 10 - 5):
    """A .dat striped to 14 shard files; the `lost` shards' bytes are
    returned and their files removed."""
    (base,) = _dat_files(tmp_path, [dat_size])
    pl.write_ec_files(base, encoder=pl.get_encoder("cpu"), large_block=LB,
                      small_block=SB, buffer_size=SB)
    want = {}
    for sid in lost:
        with open(base + pl.to_ext(sid), "rb") as f:
            want[sid] = f.read()
        os.remove(base + pl.to_ext(sid))
    return base, want


@pytest.mark.parametrize("buffer_size,batch_windows,sequential,lost", [
    (2000, 2, False, (0, 12)),  # a ragged last window in a one-window tail
    (SB, 4, False, (0, 12)),    # whole windows, ten blocks
    (SB, 1, True, (0, 12)),     # the sequential baseline's one-window blocks
    (2000, 2, False, (0,)),
    (2000, 2, False, (1, 4, 10, 13)),
], ids=["ragged_tail", "whole_windows", "sequential", "lost1_ragged_tail",
        "lost4_ragged_tail"])
@pytest.mark.parametrize("enc", ["cpu", "jax"])
def test_rebuild_reuses_blocks(tmp_path, monkeypatch, enc, buffer_size,
                               batch_windows, sequential, lost):
    """The rebuild reader reads survivors into reused shard-major blocks
    and launches each block's rows as one `_transform_buffers_async`
    call: the restored shards are byte-identical to what the CPU oracle
    striped, the rows are views of the block that view as kernel words
    with no copy, every block is either new or reused, and no call
    allocates more than its pipeline can hold."""
    from seaweedfs_tpu.ec.batch import _WORD_QUANTUM
    from seaweedfs_tpu.ops import gf256_pallas as gp
    assert _WORD_QUANTUM == gp._DEFAULT_BM * gp._BLOCK_BYTES
    base, want = _striped(tmp_path, lost)
    encoder = _encoder(enc)
    launched = pl._transform_buffers_async
    blocks_seen: list = []

    def recording(encoder, coeff, rows):
        block = rows[0].base
        assert block is not None and block.shape[0] == len(rows) == \
            gf.DATA_SHARDS
        for row in rows:
            assert np.shares_memory(row, block)
            assert np.shares_memory(gp.bytes_to_words(row), row)
        if not any(b is block for b in blocks_seen):
            blocks_seen.append(block)
        return launched(encoder, coeff, rows)
    monkeypatch.setattr(pl, "_transform_buffers_async", recording)
    stats: dict = {}
    assert pl.rebuild_ec_files(base, encoder=encoder,
                               buffer_size=buffer_size,
                               batch_windows=batch_windows,
                               sequential=sequential,
                               stats=stats) == list(lost)
    for sid, data in want.items():
        with open(base + pl.to_ext(sid), "rb") as f:
            assert f.read() == data, sid
    shard = len(want[lost[0]])
    windows = -(-shard // buffer_size)
    calls = len(lost) if sequential else 1
    blocks = -(-windows // batch_windows) * calls
    assert blocks >= 8 * calls
    assert stats["launches"] == stats["dispatches"] == blocks
    assert stats["windows"] == windows * calls
    assert stats["blocks_allocated"] + stats["blocks_reused"] == blocks
    assert stats["blocks_reused"] > 0
    assert len(blocks_seen) == stats["blocks_allocated"]
    # reader, two queued, launcher, two queued, writer
    bound = 2 * pl._PIPE_DEPTH + 3 if pl._use_overlap(encoder) else 1
    assert stats["blocks_allocated"] <= bound * calls


@pytest.mark.parametrize("enc", ["cpu", "jax"])
def test_rebuild_short_survivor_reads_zeros(tmp_path, enc):
    """A survivor cut short by less than a window reads as zeros past
    its end, as if padded, though earlier blocks filled the reused
    block's memory with survivor bytes."""
    from seaweedfs_tpu.ec.encoder_cpu import CpuEncoder
    base, want = _striped(tmp_path, (0,))
    shard = len(want[0])
    short = base + pl.to_ext(7)
    os.truncate(short, shard - SB // 3)
    use = list(range(1, 11))
    survivors = []
    for sid in use:
        with open(base + pl.to_ext(sid), "rb") as f:
            raw = f.read()
        survivors.append(np.frombuffer(raw + bytes(shard - len(raw)),
                                       np.uint8))
    (expect,) = pl._transform_buffers(
        CpuEncoder(use_native=False), gf.cached_shard_rows((0,), tuple(use)),
        survivors)
    stats: dict = {}
    assert pl.rebuild_ec_files(base, encoder=_encoder(enc), buffer_size=SB,
                               batch_windows=4, stats=stats) == [0]
    assert stats["blocks_reused"] > 0
    with open(base + pl.to_ext(0), "rb") as f:
        got = f.read()
    assert got == np.asarray(expect, np.uint8).tobytes()
    # the cut bytes were not zeros, so the restored shard is not the
    # original there: the zeros did stand in for them
    assert got[:shard - SB] == want[0][:shard - SB]
    assert got != want[0]


def test_rebuild_block_outlives_its_readback(tmp_path, monkeypatch):
    """A block goes back for reuse only once its readback has returned:
    every launched block still holds the bytes it was launched with
    when its thunk finishes."""
    import hashlib
    lost = (3,)
    base, want = _striped(tmp_path, lost)
    launched = pl._transform_buffers_async
    seen = []

    def digest(rows):
        h = hashlib.sha1()
        for r in rows:
            h.update(r.tobytes())
        return h.hexdigest()

    def recording(encoder, coeff, rows):
        before = digest(rows)
        thunk = launched(encoder, coeff, rows)

        def readback():
            # leave the reader time to overwrite a block released early
            time.sleep(0.005)
            out = thunk()
            seen.append((before, digest(rows)))
            return out
        return readback
    monkeypatch.setattr(pl, "_transform_buffers_async", recording)
    stats: dict = {}
    assert pl.rebuild_ec_files(base, encoder=_encoder("jax"),
                               buffer_size=SB, batch_windows=1,
                               stats=stats) == list(lost)
    assert stats["blocks_reused"] > 0
    assert len(seen) == stats["launches"]
    assert all(a == b for a, b in seen)
    with open(base + pl.to_ext(3), "rb") as f:
        assert f.read() == want[3]


@pytest.mark.parametrize("path", ["rebuild", "encode_volume",
                                  "verify_window_block",
                                  "write_ec_files_batched"])
def test_bulk_paths_launch_word_rows(tmp_path, monkeypatch, path):
    """Every bulk path hands JaxEncoder's one launch k 1-D uint8 rows,
    each a whole number of the words kernel's block quanta (so each
    views as kernel words with no copy), and gets back what the CPU
    encoder computes from the same rows."""
    from seaweedfs_tpu.ec.batch import _WORD_QUANTUM
    from seaweedfs_tpu.ec.encoder_cpu import CpuEncoder
    from seaweedfs_tpu.ec.encoder_jax import JaxEncoder

    launched = JaxEncoder.transform_async
    calls = []

    def recording(self, coeff, rows):
        assert len(rows) == np.shape(coeff)[1] == gf.DATA_SHARDS
        for row in rows:
            assert row.dtype == np.uint8 and row.ndim == 1
            assert len(row) % _WORD_QUANTUM == 0, len(row)
        want = CpuEncoder(use_native=False).transform_async(coeff, rows)()
        thunk = launched(self, coeff, rows)

        def checked():
            got = thunk()
            assert [bytes(g) for g in got] == [bytes(w) for w in want]
            calls.append(len(rows[0]))
            return got
        return checked
    monkeypatch.setattr(JaxEncoder, "transform_async", recording)

    def digests(base):
        out = []
        for sid in range(gf.TOTAL_SHARDS):
            with open(base + pl.to_ext(sid), "rb") as f:
                out.append(hashlib.sha256(f.read()).hexdigest())
        return out

    if path == "rebuild":
        base, want = _striped(tmp_path, (0, 12))
        assert pl.rebuild_ec_files(base, encoder=JaxEncoder(),
                                   buffer_size=SB, batch_windows=4) == [0, 12]
        for sid, data in want.items():
            with open(base + pl.to_ext(sid), "rb") as f:
                assert f.read() == data, sid
    elif path == "encode_volume":
        base, _ = _striped(tmp_path, ())
        want = digests(base)
        pl.encode_volume(base, encoder=JaxEncoder(), large_block=LB,
                         small_block=SB, buffer_size=SB, batch_windows=8)
        assert digests(base) == want
    elif path == "verify_window_block":
        base, _ = _striped(tmp_path, ())
        with open(base + pl.to_ext(11), "r+b") as f:
            f.seek(2 * SB + 5)
            b = f.read(1)
            f.seek(2 * SB + 5)
            f.write(bytes([b[0] ^ 0x21]))
        # EcVolume opens <dir>/<vid>.*: the stripe needs no needle index
        vol = str(tmp_path / "7")
        for ext in [pl.to_ext(sid) for sid in range(gf.TOTAL_SHARDS)]:
            os.rename(base + ext, vol + ext)
        with open(vol + ".ecx", "wb"):
            pass
        ev = EcVolume(str(tmp_path), "", 7, large_block=LB, small_block=SB,
                      encoder=JaxEncoder())
        try:
            assert ev.verify_window_block(0, 4, SB) == \
                [True, True, False, True]
        finally:
            ev.close()
    else:
        # the served engine joins whole buffers: at buffers of the
        # quantum, as the cells stripe, every joined row is whole too
        q = _WORD_QUANTUM
        bases = _dat_files(tmp_path, [2 * q * 10 * 2 + q * 10 + 7,
                                      q * 10 * 2])
        want = []
        for base in bases:
            pl.encode_volume(base, encoder=CpuEncoder(), large_block=2 * q,
                             small_block=q, buffer_size=q)
            want.append(digests(base))
        pl.write_ec_files_batched(bases, encoder=JaxEncoder(),
                                  large_block=2 * q, small_block=q,
                                  buffer_size=q)
        assert [digests(b) for b in bases] == want
    assert calls


def test_concurrent_degraded_reads_time_the_lock(ec_fixture, fresh_tracing):
    """Two concurrent read_needle calls on one EcVolume: each records
    `lock_wait` then `locked`. The lock covers the .ecx lookup only: the
    call that comes second waits for the first's lookup, and not for
    its shard reads. A recover's own stages nest under `ec`/`recover`."""
    import threading

    from seaweedfs_tpu.ec.encoder_jax import JaxEncoder
    d, base, contents = ec_fixture
    ev = EcVolume(d, "", 5, large_block=LB, small_block=SB,
                  encoder=JaxEncoder())
    ev.shards.pop(0).close()
    # past the needle test_ec_delete_journal tombstones in the fixture
    nids = sorted(contents)[-2:]

    def race(name, delay):
        """Both reads, the second started once the first is inside the
        slowed `name`; -> each read's (lock_wait, locked) ms."""
        inner = getattr(ev, name)
        held = threading.Event()

        def slow(*args):
            held.set()
            time.sleep(delay)
            return inner(*args)
        setattr(ev, name, slow)
        got = {}

        def get(nid):
            with fresh_tracing.start_root("volume", "read") as root:
                got[nid] = (ev.read_needle(nid), root)
        first = threading.Thread(target=get, args=(nids[0],))
        first.start()
        assert held.wait(5)
        second = threading.Thread(target=get, args=(nids[1],))
        second.start()
        first.join()
        second.join()
        delattr(ev, name)
        out = []
        for nid in nids:
            n, root = got[nid]
            assert n.data == contents[nid]
            by_op = _ec_spans(root)
            assert len(by_op["lock_wait"]) == len(by_op["locked"]) == 1
            out.append((by_op["lock_wait"][0]["dur_ms"],
                        by_op["locked"][0]["dur_ms"]))
        return out

    # a slow lookup holds the lock: the second read waits for it
    (wait0, held0), (wait1, _) = race("find_needle", 0.05)
    assert wait1 > wait0 and wait1 >= 20
    assert held0 >= 45
    # slow shard reads run after the release: nobody waits for them
    (_, held0), (wait1, held1) = race("_read_shard_interval", 0.2)
    assert wait1 < 100 and held0 < 100 and held1 < 100
    # a recover of shard 0 on the device path: its stages nest under it
    needle = next(nid for nid in sorted(contents)[1:]
                  if any(iv.to_shard_and_offset(LB, SB)[0] == 0
                         for iv in locate_data(LB, SB, ev.dat_size,
                                               *_record(ev, nid))))
    fresh_tracing.reset()
    with fresh_tracing.start_root("volume", "read") as root:
        assert ev.read_needle(needle).data == contents[needle]
    spans = fresh_tracing.trace_spans_dict(root.trace)["spans"]
    rec = [s for s in spans if s["op"] == "recover"]
    assert rec
    under = {s["op"] for s in spans if s["parent"] == rec[0]["span"]}
    assert {"pread", "h2d", "launch", "readback"} <= under
    ev.close()


def _record(ev, nid):
    from seaweedfs_tpu.storage import types as t
    off, size = ev.find_needle(nid)
    return off, t.actual_size(size, ev.version)


def test_profiler_trace_holds_stage_events(tmp_path, fresh_tracing):
    """Under jax.profiler.start_trace on the CPU, the .xplane.pb host
    plane holds the engine's stages as `<tier>.<op>` events."""
    import glob

    import jax
    from jax.profiler import ProfileData

    from seaweedfs_tpu.ec.encoder_jax import JaxEncoder
    bases = _dat_files(tmp_path, [2 * SB * 10 + 5])
    enc = JaxEncoder()
    pl.write_ec_files_batched(bases, encoder=enc, large_block=LB,
                              small_block=SB, buffer_size=SB)
    trace_dir = tmp_path / "trace"
    jax.profiler.start_trace(str(trace_dir))
    try:
        with fresh_tracing.start_root("volume", "ec.generate_batch"):
            pl.write_ec_files_batched(bases, encoder=enc, large_block=LB,
                                      small_block=SB, buffer_size=SB)
    finally:
        jax.profiler.stop_trace()
    (pb,) = glob.glob(str(trace_dir / "**" / "*.xplane.pb"), recursive=True)
    names = {e.name for p in ProfileData.from_file(pb).planes
             if p.name.startswith("/host:")
             for line in p.lines for e in line.events}
    assert {"ec." + op for op in STAGES} <= names
    assert "volume.ec.generate_batch" in names
