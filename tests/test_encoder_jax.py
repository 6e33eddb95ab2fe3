"""JAX + Pallas encoder vs CPU oracle (runs on the 8-device CPU platform)."""

import itertools

import numpy as np
import pytest

from seaweedfs_tpu.ec import gf
from seaweedfs_tpu.ec.encoder_cpu import CpuEncoder
from seaweedfs_tpu.ec.encoder_jax import JaxEncoder


@pytest.fixture(scope="module")
def rng():
    return np.random.default_rng(7)


def test_jax_encode_matches_cpu(rng):
    cpu, tpu = CpuEncoder(), JaxEncoder()
    data = rng.integers(0, 256, (10, 1024)).astype(np.uint8)
    want = cpu.encode([d for d in data])
    got = tpu.transform_async(gf.parity_matrix(), list(data))()
    assert len(got) == 4
    for p in range(4):
        assert got[p].dtype == np.uint8 and got[p].shape == (1024,)
        assert np.array_equal(got[p], want[10 + p]), f"parity {p}"


def test_jax_encode_batched(rng):
    """Three windows laid shard-major, each row the windows back to
    back: one launch encodes every window at once."""
    tpu = JaxEncoder()
    cpu = CpuEncoder()
    batch = rng.integers(0, 256, (3, 10, 256)).astype(np.uint8)
    rows = batch.transpose(1, 0, 2).reshape(10, 3 * 256)
    got = tpu.transform_async(gf.parity_matrix(), list(rows))()
    for b in range(3):
        want = cpu.encode([d for d in batch[b]])
        for p in range(4):
            assert np.array_equal(got[p][b * 256:(b + 1) * 256],
                                  want[10 + p]), (b, p)


def test_jax_reconstruct_subsets(rng):
    tpu = JaxEncoder()
    cpu = CpuEncoder()
    shards = cpu.encode([rng.integers(0, 256, 128).astype(np.uint8)
                         for _ in range(10)])
    # a representative set of loss patterns incl. worst case (all parity lost
    # is trivial; all-4-losses-in-data is the hard one)
    for missing in [(0,), (13,), (0, 1, 2, 3), (10, 11, 12, 13),
                    (0, 5, 11, 13)]:
        use = [i for i in range(14) if i not in missing][:10]
        coeff = gf.shard_rows(list(missing), use)
        out = tpu.transform_async(coeff, [shards[i] for i in use])()
        for row, i in zip(out, missing):
            assert np.array_equal(row, shards[i]), (missing, i)


def test_jax_verify(rng):
    from seaweedfs_tpu.ec.batch import verify_block

    tpu = JaxEncoder()
    data = rng.integers(0, 256, (10, 64)).astype(np.uint8)
    parity = tpu.transform_async(gf.parity_matrix(), list(data))()
    full = np.concatenate([data, np.stack(parity)])
    assert verify_block(tpu, full, 1) == [True]
    full[11, 3] ^= 0x40
    assert verify_block(tpu, full, 1) == [False]


def test_pallas_interpret_matches_cpu(rng):
    """Pallas kernel in interpreter mode (CPU) vs oracle, incl. padding."""
    from seaweedfs_tpu.ops.gf256_pallas import gf256_matmul_pallas

    cpu = CpuEncoder()
    coeff = gf.parity_matrix()
    consts = gf.bitplane_constants(coeff)
    # n deliberately not a multiple of the 128KB block quantum
    n = 1000
    data = rng.integers(0, 256, (10, n)).astype(np.uint8)
    got = np.asarray(gf256_matmul_pallas(consts, data, block_bm=8,
                                         interpret=True))
    want = cpu.encode([d for d in data])[10:]
    assert got.shape == (4, n)
    for p in range(4):
        assert np.array_equal(got[p], want[p]), f"parity {p}"


def test_pallas_interpret_reconstruct_coeff(rng):
    from seaweedfs_tpu.ops.gf256_pallas import gf256_matmul_pallas

    cpu = CpuEncoder()
    shards = cpu.encode([rng.integers(0, 256, 512).astype(np.uint8)
                         for _ in range(10)])
    present = [1, 2, 3, 4, 5, 6, 7, 8, 9, 10]  # shard 0 lost, use parity 10
    coeff = gf.shard_rows([0], present)
    consts = gf.bitplane_constants(coeff)
    stacked = np.stack([shards[i] for i in present])
    got = np.asarray(gf256_matmul_pallas(consts, stacked, block_bm=8,
                                         interpret=True))
    assert np.array_equal(got[0], shards[0])


def test_stacked_transform_matches_oracle():
    """gf256_stacked_transform: the (B, k, wm, 128) single-ref batch
    kernel (the mesh path's workhorse) against the CPU oracle, including
    a wm that forces the gcd block-size fallback."""
    import jax
    from seaweedfs_tpu.ec.encoder_cpu import CpuEncoder
    from seaweedfs_tpu.ops.gf256_pallas import (gf256_stacked_transform,
                                                u8_to_words, words_to_u8)

    rng = np.random.default_rng(9)
    cpu = CpuEncoder(use_native=False)
    for b, n in ((1, 512), (3, 5 * 512), (2, 3 * 512)):
        data = rng.integers(0, 256, (b, 10, n)).astype(np.uint8)
        x = u8_to_words(jax.numpy.asarray(data))
        out = words_to_u8(gf256_stacked_transform(
            gf.bitplane_constants(gf.parity_matrix()), x, block_bm=2))
        got = np.asarray(out)
        for v in range(b):
            want = cpu.encode(list(data[v]))[10:]
            for p in range(4):
                assert np.array_equal(got[v, p], want[p]), (b, n, v, p)


@pytest.mark.parametrize("n,as_bytes", [
    (1, False), (1000, True), (4097, False), (128 * 1024 - 1, True),
    (128 * 1024, False)])
def test_transform_rows_either_side_of_the_quantum(rng, n, as_bytes,
                                                   monkeypatch):
    """A recover of shard 0 from ten survivors, byte-equal to the CPU
    oracle on both sides of the 128 KiB block quantum, with rows given
    as arrays or bytes. Below it the ten rows cross in one transfer
    (one block); at it, one transfer a row."""
    import jax
    puts = []
    put = jax.device_put

    def counting_put(x, *a, **k):
        puts.append(x)
        return put(x, *a, **k)
    monkeypatch.setattr(jax, "device_put", counting_put)
    cpu = CpuEncoder()
    shards = cpu.encode([rng.integers(0, 256, n).astype(np.uint8)
                         for _ in range(10)])
    use = list(range(1, 11))
    rows = [shards[i].tobytes() if as_bytes else shards[i] for i in use]
    out = JaxEncoder().transform_async(gf.shard_rows([0], use), rows)()
    assert len(out) == 1 and out[0].dtype == np.uint8
    assert np.array_equal(out[0], shards[0])
    assert len(puts) == (1 if n < 128 * 1024 else 10)
