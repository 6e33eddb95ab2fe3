"""Pallas TPU kernel for the GF(256) shard transform.

This is the TPU replacement for klauspost/reedsolomon's PSHUFB/AVX2
galois-multiply assembly (reference dep of ec_encoder.go:192). One kernel
evaluates out = C (x) data over GF(256), where C is a small (rows, k)
coefficient matrix (4x10 for RS(10,4) encode; (r,10) for reconstruct) and
data is k shard byte-streams.

Math: gf_mul(c, x) = XOR_j bit_j(x) * gf_mul(c, 1<<j), so the transform is
AND/XOR over the 8 bitplanes of each input byte with 8 precomputed constant
bytes per coefficient. To quadruple VPU lane utilisation the byte streams
are viewed as uint32 words and all bitplane ops are done byte-wise inside
the word:

    bits  = (x >> j) & 0x01010101          # bit j of each of the 4 bytes
    acc  ^= bits * K                       # K < 256: no cross-byte carries

Layout: each shard is its own (wm, 128) uint32 array — the natural TPU tile
for 32-bit data, with zero padding waste (a single (k, n) array would pad
the k=10 sublane dim to the tile quantum and transpose-copy in HBM). Byte
streams convert to this shape with a free numpy view on host. The kernel
reads each input block exactly once from HBM and the grid pipeline
double-buffers HBM->VMEM DMAs automatically.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu  # noqa: F401  (memory spaces)

from ..ec import gf

_LANES = 128
# Sublane rows of 128 u32 words per block per shard:
# bm=256 -> 128 KiB/shard-block, 1.25 MiB input block for k=10.
_DEFAULT_BM = 256
_BLOCK_BYTES = _LANES * 4
# the kernel's name in compiled programs and device traces, whichever
# call builds it
KERNEL_NAME = "gf256_transform"


def _interpret_default() -> bool:
    """Interpret mode only where the platform really is the CPU; on any
    other platform the kernel compiles for the chip or fails loudly."""
    return jax.default_backend() == "cpu"


def _accumulate_bitplanes(consts: np.ndarray, read_shard) -> list:
    """Shared kernel body: XOR-accumulate the 8 bitplanes of each of k
    input blocks against the per-row constants. read_shard(i) -> the
    i-th shard's (bm, 128) uint32 block; returns one accumulator per
    output row (None where a row's coefficients are all zero)."""
    rows, k, _ = consts.shape
    accs = [None] * rows
    for i in range(k):
        xi = read_shard(i)
        for j in range(8):
            ks = [int(consts[r, i, j]) for r in range(rows)]
            if not any(ks):
                continue
            bits = jax.lax.shift_right_logical(
                xi, jnp.uint32(j)) & jnp.uint32(0x01010101)
            for r in range(rows):
                if ks[r] == 0:
                    continue
                term = bits * jnp.uint32(ks[r])
                accs[r] = term if accs[r] is None else accs[r] ^ term
    return accs


def _make_kernel(consts: np.ndarray):
    """consts: (rows, k, 8) uint8 bitplane constants (host)."""
    rows, k, _ = consts.shape

    def kernel(*refs):
        ins, outs = refs[:k], refs[k:]
        accs = _accumulate_bitplanes(consts, lambda i: ins[i][...])
        for r in range(rows):
            outs[r][...] = (accs[r] if accs[r] is not None
                            else jnp.zeros_like(ins[0][...]))

    return kernel


@functools.lru_cache(maxsize=256)
def _build_call(consts_key: bytes, rows: int, k: int, wm: int, bm: int,
                interpret: bool):
    consts = np.frombuffer(consts_key, dtype=np.uint8).reshape(rows, k, 8)
    spec = pl.BlockSpec((bm, _LANES), lambda i: (i, 0))
    return pl.pallas_call(
        _make_kernel(consts),
        out_shape=[jax.ShapeDtypeStruct((wm, _LANES), jnp.uint32)] * rows,
        grid=(wm // bm,),
        in_specs=[spec] * k,
        out_specs=[spec] * rows,
        interpret=interpret,
        name=KERNEL_NAME,
    )


def gf256_words_transform(consts: np.ndarray, words: list[jax.Array],
                          block_bm: int = _DEFAULT_BM,
                          interpret: bool | None = None) -> list[jax.Array]:
    """Fast path: k device arrays of (wm, 128) uint32 -> rows arrays alike.

    wm must be a multiple of block_bm (callers pad the byte streams to the
    block quantum: block_bm * 512 bytes). This is the shape the EC pipeline
    and bench feed directly (numpy `.view(np.uint32).reshape(-1, 128)` of a
    shard byte buffer is free).
    """
    consts = np.ascontiguousarray(consts, dtype=np.uint8)
    rows, k, _ = consts.shape
    assert len(words) == k, (len(words), k)
    wm = words[0].shape[0]
    bm = min(block_bm, wm)
    assert wm % bm == 0, (wm, bm)
    if interpret is None:
        interpret = _interpret_default()
    call = _build_call(consts.tobytes(), rows, k, wm, bm, interpret)
    return call(*words)


def _make_stacked_kernel(consts: np.ndarray):
    """Single-ref variant: in (1, k, bm, 128), out (1, rows, bm, 128).
    Same bitplane math as _make_kernel, but volumes/shards live in one
    contiguous array — the layout the mesh-batched rack encode uses, so
    no per-shard slicing copies are needed."""
    rows, k, _ = consts.shape

    def kernel(in_ref, out_ref):
        accs = _accumulate_bitplanes(consts, lambda i: in_ref[0, i])
        for r in range(rows):
            out_ref[0, r] = (accs[r] if accs[r] is not None
                             else jnp.zeros_like(in_ref[0, 0]))

    return kernel


@functools.lru_cache(maxsize=256)
def _build_stacked_call(consts_key: bytes, rows: int, k: int, b: int,
                        wm: int, bm: int, interpret: bool):
    consts = np.frombuffer(consts_key, dtype=np.uint8).reshape(rows, k, 8)
    return pl.pallas_call(
        _make_stacked_kernel(consts),
        out_shape=jax.ShapeDtypeStruct((b, rows, wm, _LANES), jnp.uint32),
        grid=(b, wm // bm),
        in_specs=[pl.BlockSpec((1, k, bm, _LANES), lambda v, i: (v, 0, i, 0))],
        out_specs=pl.BlockSpec((1, rows, bm, _LANES),
                               lambda v, i: (v, 0, i, 0)),
        interpret=interpret,
        name=KERNEL_NAME,
    )


def gf256_stacked_transform(consts: np.ndarray, x: jax.Array,
                            block_bm: int = _DEFAULT_BM,
                            interpret: bool | None = None) -> jax.Array:
    """Batched fast path: (B, k, wm, 128) uint32 -> (B, rows, wm, 128).

    One pallas_call carries a whole batch of volumes (grid = B x wm/bm);
    the rack-encode mesh path calls this per-device inside shard_map, and
    JaxEncoder sends rows shorter than the block quantum through it as
    one (1, k, wm, 128) block.
    """
    consts = np.ascontiguousarray(consts, dtype=np.uint8)
    rows, k, _ = consts.shape
    b, kk, wm, lanes = x.shape
    assert kk == k and lanes == _LANES, (x.shape, consts.shape)
    # bm must divide wm exactly; fall back to the gcd for word counts
    # that aren't multiples of the preferred block (mesh callers only
    # guarantee 512-byte alignment per device)
    bm = min(block_bm, wm)
    if wm % bm:
        import math
        bm = math.gcd(wm, bm)
    if interpret is None:
        interpret = _interpret_default()
    call = _build_stacked_call(consts.tobytes(), rows, k, b, wm, bm,
                               interpret)
    return call(x)


def u8_to_words(d: jax.Array) -> jax.Array:
    """(..., n) uint8 -> (..., n//512, 128) uint32 on device (free bitcast;
    n must be a multiple of 512). Matches bytes_to_words' host layout."""
    *batch, n = d.shape
    assert n % (_BLOCK_BYTES) == 0, n
    w = jax.lax.bitcast_convert_type(
        d.reshape(*batch, n // 4, 4), jnp.uint32)
    return w.reshape(*batch, n // _BLOCK_BYTES, _LANES)


def words_to_u8(w: jax.Array) -> jax.Array:
    """(..., wm, 128) uint32 -> (..., wm*512) uint8 on device."""
    *batch, wm, lanes = w.shape
    b8 = jax.lax.bitcast_convert_type(w, jnp.uint8)  # (..., wm, 128, 4)
    return b8.reshape(*batch, wm * lanes * 4)


def bytes_to_words(buf: np.ndarray | bytes, block_bm: int = _DEFAULT_BM
                   ) -> np.ndarray:
    """Host-side free-ish view of a byte stream as (wm, 128) uint32,
    zero-padded to the block quantum."""
    arr = np.frombuffer(buf, dtype=np.uint8) if isinstance(
        buf, (bytes, bytearray, memoryview)) else np.asarray(buf, np.uint8)
    quantum = block_bm * _BLOCK_BYTES
    padded = -(-arr.size // quantum) * quantum
    if padded != arr.size:
        out = np.zeros(padded, np.uint8)
        out[:arr.size] = arr
        arr = out
    return arr.view(np.uint32).reshape(-1, _LANES)


def words_to_bytes(words: np.ndarray, n: int) -> np.ndarray:
    """Inverse of bytes_to_words, truncated to n bytes."""
    return np.asarray(words).reshape(-1).view(np.uint8)[:n]


def gf256_matmul_pallas(consts: np.ndarray, data: jax.Array,
                        block_bm: int = _DEFAULT_BM,
                        interpret: bool | None = None) -> jax.Array:
    """Generic API: out[..., r, :] = XOR_i gf_mul(coeff[r,i], data[..., i, :]).

    consts: (rows, k, 8) uint8 from gf.bitplane_constants (host constant).
    data: (..., k, n) uint8 jax array. Convenience wrapper around the words
    fast path — converts layout on device, so prefer gf256_words_transform
    for bulk streaming work.
    """
    consts = np.ascontiguousarray(consts, dtype=np.uint8)
    rows, k, _ = consts.shape
    data = jnp.asarray(data, jnp.uint8)
    *batch, kk, n = data.shape
    assert kk == k, (data.shape, consts.shape)

    flat = jnp.moveaxis(data, -2, 0).reshape(k, -1) if batch else data
    total = flat.shape[1]
    if total == 0:
        return jnp.zeros(tuple(batch) + (rows, n), jnp.uint8)
    bm = min(block_bm, max(8, -(-total // _BLOCK_BYTES)))
    quantum = bm * _BLOCK_BYTES
    padded = -(-total // quantum) * quantum
    if padded != total:
        flat = jnp.pad(flat, ((0, 0), (0, padded - total)))

    words = [
        jax.lax.bitcast_convert_type(
            flat[i].reshape(padded // 4, 4), jnp.uint32).reshape(-1, _LANES)
        for i in range(k)
    ]
    outs = gf256_words_transform(consts, words, bm, interpret)
    out = jnp.stack([
        jax.lax.bitcast_convert_type(o.reshape(-1), jnp.uint8
                                     ).reshape(-1)[:total]
        for o in outs
    ])
    if batch:
        out = jnp.moveaxis(out.reshape([rows] + batch + [n]), 0, -2)
    return out
