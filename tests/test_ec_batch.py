"""Stripe-batch engine property tests: random window batches and random
missing-sets through every available backend (cpu-numpy / cpu-native /
jax) must be byte-identical to the per-window numpy oracle — encode,
verify verdicts, and reconstruction alike (ec/batch.py + the encoders'
one launch). Runs under JAX_PLATFORMS=cpu in tier-1; jax and the
native kernel skip cleanly when unavailable."""

import hashlib
import os
import random

import numpy as np
import pytest

from seaweedfs_tpu.ec import batch as ecb
from seaweedfs_tpu.ec import gf
from seaweedfs_tpu.ec import pipeline as pl
from seaweedfs_tpu.ec.ec_volume import EcVolume
from seaweedfs_tpu.ec.encoder_cpu import CpuEncoder
from seaweedfs_tpu.storage.needle import Needle
from seaweedfs_tpu.storage.volume import Volume

BACKENDS = ("cpu-numpy", "cpu-native", "jax")


def make_encoder(name):
    if name == "cpu-numpy":
        return CpuEncoder(use_native=False)
    if name == "cpu-native":
        from seaweedfs_tpu.native import gf256 as _native
        if not _native.available():
            pytest.skip("native GF(256) kernel not built on this host")
        return CpuEncoder(use_native=True)
    jax = pytest.importorskip("jax")
    del jax
    from seaweedfs_tpu.ec.encoder_jax import JaxEncoder
    return JaxEncoder()


@pytest.fixture(params=BACKENDS)
def backend(request):
    return request.param, make_encoder(request.param)


@pytest.fixture(scope="module")
def oracle():
    return CpuEncoder(use_native=False)


def _oracle_full(oracle, block):
    """Per-window numpy encode: THE byte-identity reference."""
    return np.stack([np.stack(oracle.encode(list(w))) for w in block])


def _shard_major(windows, pad=True):
    """(B, rows, L) windows -> (rows, B*L): row i holds every window's
    row i back to back, zero-padded to the word quantum when `pad`."""
    bsz, rows, n = windows.shape
    width = ecb._words_len(bsz * n) if pad else bsz * n
    block = np.zeros((rows, width), np.uint8)
    block[:, :bsz * n] = windows.transpose(1, 0, 2).reshape(rows, -1)
    return block


# ---------------------------------------------------------------------
# encode
# ---------------------------------------------------------------------

def test_batch_encode_matches_perwindow_oracle(backend, oracle):
    name, enc = backend
    rng = np.random.default_rng(101)
    # batch sizes incl. B=1 and non-multiples of device counts; window
    # lengths incl. odd (not a block-quantum multiple)
    for bsz, n in [(1, 512), (3, 1000), (8, 4096), (5, 64)]:
        windows = rng.integers(0, 256, (bsz, gf.DATA_SHARDS, n)
                               ).astype(np.uint8)
        want = _shard_major(_oracle_full(oracle, windows), pad=False)
        # the engine counts exactly one dispatch per block
        stats = {}
        par = ecb.transform_block_async(enc, gf.parity_matrix(),
                                        _shard_major(windows), stats)()
        assert len(par) == gf.PARITY_SHARDS, name
        for got, row in zip(par, want[gf.DATA_SHARDS:]):
            assert np.array_equal(got[:bsz * n], row), (name, bsz, n)
            # the pad's parity is the parity of zeros
            assert not got[bsz * n:].any(), (name, bsz, n)
        assert stats == {"dispatches": 1, "batches": 1}


# ---------------------------------------------------------------------
# verify
# ---------------------------------------------------------------------

def test_batch_verify_localizes_random_corruption(backend, oracle):
    name, enc = backend
    rng = np.random.default_rng(202)
    windows = rng.integers(0, 256, (6, gf.DATA_SHARDS, 777)
                           ).astype(np.uint8)
    full = _shard_major(_oracle_full(oracle, windows), pad=False)
    stats = {}
    assert ecb.verify_block(enc, full, 6, stats) == [True] * 6, name
    assert stats == {"dispatches": 1, "batches": 1, "windows": 6,
                     "bytes_in": full.nbytes}
    for _ in range(8):
        bad = full.copy()
        hits = sorted({int(rng.integers(0, 6))
                       for _ in range(rng.integers(1, 4))})
        for w in hits:
            sid = int(rng.integers(0, gf.TOTAL_SHARDS))
            off = int(rng.integers(0, 777))
            bad[sid, w * 777 + off] ^= int(rng.integers(1, 256))
        verdicts = ecb.verify_block(enc, bad, 6)
        assert verdicts == [w not in hits for w in range(6)], \
            (name, hits, verdicts)


def test_unified_verify_signature(backend, oracle):
    """Every backend answers the same verify_block(block, count) ->
    verdicts, and localize_corrupt_rows pins one rotten row to its
    shard the same way on each — the calls EcVolume and the scrubber
    make with no per-encoder branching."""
    name, enc = backend
    rng = np.random.default_rng(303)
    window = rng.integers(0, 256, (gf.DATA_SHARDS, 640)).astype(np.uint8)
    full = np.stack(oracle.encode(list(window)))
    assert ecb.verify_block(enc, full, 1) == [True], name
    bad = full.copy()
    bad[11, 3] ^= 0x40
    assert ecb.verify_block(enc, bad, 1) == [False], name
    assert ecb.localize_corrupt_rows(enc, bad) == [11], name


# ---------------------------------------------------------------------
# reconstruct
# ---------------------------------------------------------------------

def test_batch_reconstruct_random_missing_sets(backend, oracle):
    """Random missing-sets of size 1..4: rebuilding the lost rows from
    k survivors must be byte-identical to the originals on every
    backend, for every window of the batch."""
    name, enc = backend
    rng = np.random.default_rng(404)
    windows = rng.integers(0, 256, (4, gf.DATA_SHARDS, 1536)
                           ).astype(np.uint8)
    full = _shard_major(_oracle_full(oracle, windows))
    n = 4 * 1536
    cases = [(0,), (13,), (0, 1, 2, 3), (10, 11, 12, 13), (0, 5, 11, 13)]
    for _ in range(10):
        m = int(rng.integers(1, gf.PARITY_SHARDS + 1))
        cases.append(tuple(sorted(
            rng.choice(gf.TOTAL_SHARDS, size=m, replace=False).tolist())))
    for missing in cases:
        present = [i for i in range(gf.TOTAL_SHARDS)
                   if i not in missing][:gf.DATA_SHARDS]
        coeff = gf.cached_shard_rows(tuple(missing), tuple(present))
        rec = ecb.transform_block_async(enc, coeff, full[present])()
        for got, sid in zip(rec, missing):
            assert np.array_equal(got[:n], full[sid, :n]), (name, missing)


# ---------------------------------------------------------------------
# the three bulk paths: batched == per-window on a real volume
# ---------------------------------------------------------------------

LB, SB = 16 * 1024, 1024
Q = ecb._WORD_QUANTUM
# (large block, small block, scrub window): 1 KB windows pad every
# block's rows to the word quantum; windows of the quantum pad none
GEOMETRY = {"unaligned": (LB, SB, 4 * 1024), "aligned": (2 * Q, Q, Q)}


def _volume(tmp_path_factory, large_block, small_block, needle_bytes):
    """A volume of random needles big enough to span BOTH striping
    areas: >= 1 large-block row (where consecutive windows are
    contiguous per shard and preads coalesce) plus a small-block tail;
    striped by the CPU encoder, with its .ecx."""
    d = str(tmp_path_factory.mktemp("ecbatch"))
    v = Volume(d, "", 9)
    rng = random.Random(7)
    lo, hi = needle_bytes
    total, i = 0, 0
    while total < 10 * (large_block + 2 * small_block) + 5000:
        i += 1
        size = rng.randint(lo, hi)
        v.write_needle(Needle(cookie=i, id=i, data=rng.randbytes(size)))
        total += size
    v.close()
    base = os.path.join(d, "9")
    pl.encode_volume(base, encoder=pl.get_encoder("cpu"),
                     large_block=large_block, small_block=small_block,
                     buffer_size=small_block)
    pl.write_sorted_file_from_idx(base)
    return d, base


@pytest.fixture(scope="module")
def small_volume(tmp_path_factory):
    return _volume(tmp_path_factory, LB, SB, (2000, 5000))


@pytest.fixture(scope="module")
def quantum_volume(tmp_path_factory):
    return _volume(tmp_path_factory, 2 * Q, Q, (20000, 50000))


def _geometry_volume(request, geometry):
    return request.getfixturevalue(
        "small_volume" if geometry == "unaligned" else "quantum_volume")


def _shard_digest(base):
    h = hashlib.sha256()
    for sid in range(gf.TOTAL_SHARDS):
        with open(base + pl.to_ext(sid), "rb") as f:
            h.update(f.read())
    return h.hexdigest()


@pytest.mark.parametrize("geometry", list(GEOMETRY))
def test_encode_volume_batched_is_byte_identical(request, geometry):
    d, base = _geometry_volume(request, geometry)
    lb, sb, _ = GEOMETRY[geometry]
    want = _shard_digest(base)
    sums, stats = {}, {}
    for bw in (1, 8):
        s: dict = {}
        pl.encode_volume(base, encoder=pl.get_encoder("cpu"),
                         large_block=lb, small_block=sb, buffer_size=sb,
                         batch_windows=bw, stats=s)
        sums[bw], stats[bw] = _shard_digest(base), s
    assert sums[1] == sums[8] == want
    w = stats[1]["windows"]
    assert stats[1]["dispatches"] == w
    assert stats[8]["dispatches"] <= -(-w // 8)
    assert stats[8]["preads"] < stats[1]["preads"]
    # the unpadded bytes: ten rows of every window, at either width
    assert stats[1]["bytes_in"] == stats[8]["bytes_in"] == w * 10 * sb


@pytest.mark.parametrize("geometry", list(GEOMETRY))
def test_verify_parity_batched_matches_perwindow(request, geometry):
    d, base = _geometry_volume(request, geometry)
    lb, sb, window = GEOMETRY[geometry]
    ev = EcVolume(d, "", 9, large_block=lb, small_block=sb,
                  encoder=pl.get_encoder("cpu"))
    p = base + pl.to_ext(11)
    offs = (window + 3, 3 * window + 9)
    try:
        # plant rot in two windows of a parity shard (bytes no
        # foreground read visits)
        with open(p, "r+b") as f:
            for off in offs:
                f.seek(off)
                b = f.read(1)
                f.seek(off)
                f.write(bytes([b[0] ^ 0x55]))
        r1 = ev.verify_parity(window, batch_windows=1)
        rb = ev.verify_parity(window, batch_windows=8)
        assert r1["bad_windows"] == rb["bad_windows"] == [window, 3 * window]
        assert r1["windows"] == rb["windows"]
        assert r1["dispatches"] == r1["windows"]
        assert rb["dispatches"] <= -(-r1["windows"] // 8)
        assert rb["preads"] < r1["preads"]
    finally:
        with open(p, "r+b") as f:
            for off in offs:
                f.seek(off)
                b = f.read(1)
                f.seek(off)
                f.write(bytes([b[0] ^ 0x55]))
        ev.close()


def test_rebuild_batched_is_byte_identical(small_volume):
    d, base = small_volume
    originals = {}
    for sid in (2, 12):
        with open(base + pl.to_ext(sid), "rb") as f:
            originals[sid] = f.read()
        os.remove(base + pl.to_ext(sid))
    stats: dict = {}
    rebuilt = pl.rebuild_ec_files(base, encoder=pl.get_encoder("cpu"),
                                  buffer_size=SB, batch_windows=8,
                                  stats=stats)
    assert sorted(rebuilt) == [2, 12]
    for sid, want in originals.items():
        with open(base + pl.to_ext(sid), "rb") as f:
            assert f.read() == want, sid
    w = -(-len(originals[2]) // SB)
    assert stats["launches"] <= -(-w // 8)
