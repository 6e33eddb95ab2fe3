"""Compile rehearsals for a described TPU v5e (on-chip-measurement guide
section 2): the Pallas kernels of the served EC path, at the widths
chip_smoke.py runs, compiled by the TPU compiler with no chip attached.

This catches what interpret mode cannot — tiling the chip refuses, fast
memory over budget, a program that does not fit 16 GiB of HBM — at no
chip time. Nothing runs, so nothing here is a result or a time.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.
"""

import pytest

import jax
import jax.numpy as jnp

from seaweedfs_tpu.ec import gf
from seaweedfs_tpu.ops import gf256_pallas as gp

HBM_BYTES = 16 << 30
MB = 1 << 20


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    with pytest.MonkeyPatch.context() as mp:
        # the compiler would otherwise log under /tmp
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means "cannot"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # an entry compiled for a described chip cannot be read back
        # without one: keep these compiles out of the persistent cache
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


def _check(compiled) -> None:
    assert "tpu_custom_call" in compiled.as_text()
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes + m.generated_code_size_in_bytes)
    assert total < HBM_BYTES, total


ENCODE = gf.parity_matrix()
# worst-case rebuild: data shards 0-3 lost, rebuilt from shards 4-13
REBUILD4 = gf.shard_rows([0, 1, 2, 3], list(range(4, gf.TOTAL_SHARDS)))


@pytest.mark.parametrize("coeff", [ENCODE, REBUILD4],
                         ids=["encode", "rebuild4"])
@pytest.mark.parametrize("shard_mb", [8, 32, 64])
def test_words_transform_compiles(one_chip, coeff, shard_mb):
    # 8 MB/shard: the stripe-batch block (8 windows x 1 MB) ec.encode
    # dispatches for small rows and ec.rebuild for every block; 32
    # MB/shard: a scrub block (8 windows x 4 MB); 64 MB/shard: the
    # large-row flush (8 buffers of 8 MB joined per shard) and
    # chip_smoke's kernel check
    consts = gf.bitplane_constants(coeff)
    x = jax.ShapeDtypeStruct((shard_mb * MB // 512, 128), jnp.uint32,
                             sharding=one_chip)
    fn = jax.jit(lambda *ws: gp.gf256_words_transform(
        consts, list(ws), interpret=False))
    _check(fn.lower(*[x] * coeff.shape[1]).compile())


@pytest.mark.parametrize("wm", [2048, 1000], ids=["wm2048", "ragged1000"])
def test_stacked_transform_compiles(one_chip, wm):
    # ragged wm=1000: the gcd fallback picks bm=8
    consts = gf.bitplane_constants(ENCODE)
    x = jax.ShapeDtypeStruct((8, gf.DATA_SHARDS, wm, 128), jnp.uint32,
                             sharding=one_chip)
    fn = jax.jit(lambda v: gp.gf256_stacked_transform(consts, v,
                                                      interpret=False))
    _check(fn.lower(x).compile())


@pytest.mark.parametrize("wm", [8, 256])
def test_small_recover_compiles(one_chip, wm):
    # JaxEncoder's one-block path for rows below the block quantum: a
    # recover of shard 0 from ten survivors, 4 KiB (a 1 KB interval)
    # and 128 KiB tiles (its largest shape)
    consts = gf.bitplane_constants(
        gf.shard_rows([0], list(range(1, gf.DATA_SHARDS + 1))))
    x = jax.ShapeDtypeStruct((1, gf.DATA_SHARDS, wm, 128), jnp.uint32,
                             sharding=one_chip)
    fn = jax.jit(lambda v: gp.gf256_stacked_transform(consts, v,
                                                      interpret=False))
    _check(fn.lower(x).compile())


def test_described_chip_has_published_peaks(one_chip):
    # bench.py looks the HBM ceiling up by this kind and refuses an
    # unknown one: the described v5e must be in its table
    import bench

    dev = next(iter(one_chip.device_set))
    assert (dev.platform, dev.device_kind) == ("tpu", "TPU v5 lite")
    assert bench.hbm_bound_gbps(dev.device_kind) == 819.0
