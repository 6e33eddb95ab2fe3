"""JAX RS(10,4) encoder — the TPU-native GF(256) shard transform.

Re-expresses the reference's klauspost/reedsolomon Encode/Reconstruct
(amd64 PSHUFB assembly, called at ec_encoder.go:192,264 and store_ec.go:322)
as a jittable bitplane transform:

    gf_mul(c, x) = XOR_{j in bits(x)} gf_mul(c, 1 << j)

so a (rows, k) GF(256) coefficient matrix applied to k shard byte-streams
becomes, for each output row, an accumulation of AND/XOR over the 8
bitplanes of each input shard — pure uint8 VPU ops with no gathers, no
data-dependent control flow, and static shapes. XLA fuses the whole
transform into a few elementwise loops; the Pallas kernel in
ops/gf256_pallas.py implements the same math with explicit HBM->VMEM
double-buffering for peak bandwidth.

The coefficient matrix is a *constant* under jit (closed over, shaped
(rows, k, 8) by gf.bitplane_constants), so each distinct transform —
encode's (4,10) parity map or a particular reconstruction's (r,10) map —
compiles once and is cached.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..util import tracing
from . import gf

# name scope of the jitted GF transforms' device ops, those that
# JaxEncoder.transform_batch dispatches among them
TRANSFORM_SCOPE = "ec_transform_batch"


def _apply_bitplanes(consts: np.ndarray, data: jax.Array) -> jax.Array:
    """out[..., r, :] = XOR_i gf_mul(coeff[r, i], data[..., i, :]).

    consts: (rows, k, 8) uint8 bitplane constants (host numpy, becomes a
            compile-time constant).
    data:   (..., k, n) uint8 shard bytes.
    returns (..., rows, n) uint8.
    """
    rows, k, _ = consts.shape
    out = []
    for r in range(rows):
        acc = None
        for i in range(k):
            row = consts[r, i]
            if not row.any():
                continue
            x = data[..., i, :]
            term = None
            for j in range(8):
                cj = int(row[j])
                if cj == 0:
                    continue
                # 0x00/0xFF mask of bit j of every byte of shard i
                mask = ((x >> j) & 1) * jnp.uint8(0xFF)
                t = mask & jnp.uint8(cj)
                term = t if term is None else term ^ t
            if term is None:
                continue
            acc = term if acc is None else acc ^ term
        out.append(acc if acc is not None
                   else jnp.zeros(data.shape[:-2] + (data.shape[-1],), jnp.uint8))
    return jnp.stack(out, axis=-2)


@functools.lru_cache(maxsize=256)
def _compiled_transform(coeff_key: bytes, rows: int, k: int, use_pallas: bool):
    """jit-compiled transform for a fixed coefficient matrix; its device
    ops carry TRANSFORM_SCOPE in their op_name metadata."""
    coeff = np.frombuffer(coeff_key, dtype=np.uint8).reshape(rows, k)
    consts = gf.bitplane_constants(coeff)
    if use_pallas:
        from ..ops.gf256_pallas import gf256_matmul_pallas
        body = gf256_matmul_pallas
    else:
        body = _apply_bitplanes

    @jax.jit
    def fn(data):
        with jax.named_scope(TRANSFORM_SCOPE):
            return body(consts, data)
    return fn


@functools.lru_cache(maxsize=256)
def _compiled_batch_transform(coeff_key: bytes, rows: int, k: int):
    """jit of the VMAPPED bitplane transform for a fixed coefficient
    matrix — compiled once per (rows, k) coefficient shape (jit's own
    shape cache then holds one executable per (B, L) block shape).
    The stripe-batch engine's device path: one dispatch carries a whole
    (B, k, L) window block, and with the block sharded along the batch
    dim XLA partitions the elementwise bitplane loops across devices
    with zero cross-device traffic (the transform is per-window)."""
    coeff = np.frombuffer(coeff_key, dtype=np.uint8).reshape(rows, k)
    consts = gf.bitplane_constants(coeff)

    def fn(d):
        with jax.named_scope(TRANSFORM_SCOPE):
            return _apply_bitplanes(consts, d)
    return jax.jit(jax.vmap(fn))


@functools.lru_cache(maxsize=8)
def _batch_sharding(ndev: int):
    """NamedSharding(P('batch')) over all attached devices (SNIPPETS.md
    [1] pattern); None when a single device makes sharding moot."""
    if ndev <= 1:
        return None
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()), ("batch",))
    return NamedSharding(mesh, P("batch"))


def shard_along_batch(block):
    """Place a (B, ...) block on the attached devices, sharded along
    the leading (batch) dim when >1 device is attached and B divides
    evenly; replicated single-device placement otherwise."""
    sharding = _batch_sharding(jax.device_count())
    if sharding is not None and block.shape[0] % jax.device_count() == 0:
        return jax.device_put(block, sharding)
    return jnp.asarray(block)


def _default_use_pallas() -> bool:
    """The Pallas kernel on the TPU, the XLA bitplane transform on the
    CPU. A backend-init error raises: it must never quietly select the
    CPU formulation on a host that has a chip."""
    return jax.default_backend() == "tpu"


def apply_transform(coeff: np.ndarray, data: jax.Array,
                    use_pallas: bool | None = None) -> jax.Array:
    """Apply a GF(256) coefficient matrix to shard data on-device."""
    coeff = np.ascontiguousarray(coeff, dtype=np.uint8)
    rows, k = coeff.shape
    if use_pallas is None:
        use_pallas = _default_use_pallas()
    fn = _compiled_transform(coeff.tobytes(), rows, k, bool(use_pallas))
    return fn(data)


class JaxEncoder:
    """Drop-in for CpuEncoder with device-resident math.

    Accepts shard arrays shaped (k, n) or batched (..., k, n); returns
    jnp arrays. Bytes in, bytes out at the pipeline level is handled by
    the callers in ec/pipeline.py.
    """

    def __init__(self, data_shards: int = gf.DATA_SHARDS,
                 parity_shards: int = gf.PARITY_SHARDS,
                 use_pallas: bool | None = None):
        self.k = data_shards
        self.m = parity_shards
        self.n = data_shards + parity_shards
        self.use_pallas = use_pallas
        self.parity_coeff = gf.parity_matrix(self.k, self.n)

    # -- batched API (stripe-batch engine, ec/batch.py) -------------------

    def transform_batch(self, coeff: np.ndarray, block) -> jax.Array:
        """Apply a (rows, k) coefficient matrix to a (B, k, L) window
        block as ONE vmapped device dispatch -> (B, rows, L).

        Dispatch is asynchronous (jax) — the caller reads back via
        np.asarray when it actually needs the bytes, which is what lets
        the engine overlap block N+1's preads with block N's kernel.
        On the Pallas path the batch folds into the byte axis instead
        (the transform is columnwise, and the explicit-DMA kernel
        already tiles the stream); the vmapped XLA path is the one that
        shards along the batch dim on a multi-device mesh.

        The host stages are `ec`/`h2d` (placing the block on the device)
        and `ec`/`launch` (enqueueing the layout ops and the transform).
        The jitted transform's device ops run under the name scope
        TRANSFORM_SCOPE; the eager transposes around the Pallas call
        carry none (a scope reaches only ops traced inside a jit)."""
        coeff = np.ascontiguousarray(coeff, dtype=np.uint8)
        rows, k = coeff.shape
        use_pallas = self.use_pallas
        if use_pallas is None:
            use_pallas = _default_use_pallas()
        if use_pallas:
            with tracing.start("ec", "h2d") as sp:
                # a block already on the device moves nothing
                if not isinstance(block, jax.Array):
                    sp.nbytes = block.nbytes
                block = jnp.asarray(block, jnp.uint8)
            with tracing.start("ec", "launch") as sp:
                bsz, k2, n = block.shape
                flat = block.transpose(1, 0, 2).reshape(k2, bsz * n)
                out = apply_transform(coeff, flat, True)
                out = out.reshape(rows, bsz, n).transpose(1, 0, 2)
                sp.nbytes = block.nbytes
            return out
        fn = _compiled_batch_transform(coeff.tobytes(), rows, k)
        with tracing.start("ec", "h2d") as sp:
            block = shard_along_batch(np.asarray(block, np.uint8))
            sp.nbytes = block.nbytes
        with tracing.start("ec", "launch") as sp:
            out = fn(block)
            sp.nbytes = block.nbytes
        return out

    def encode_batch(self, block) -> jax.Array:
        """(B, k, L) data windows -> (B, k+m, L) full shard windows."""
        block = jnp.asarray(block, jnp.uint8)
        parity = self.transform_batch(self.parity_coeff, block)
        return jnp.concatenate([block, parity], axis=1)

    def verify_batch(self, block) -> np.ndarray:
        """(B, k+m, L) stored windows -> (B,) bool verdicts; the parity
        recompute AND the comparison both run on device, one dispatch."""
        block = jnp.asarray(block, jnp.uint8)
        par = self.transform_batch(self.parity_coeff,
                                   block[:, :self.k, :])
        return np.asarray((par == block[:, self.k:, :]).all(axis=(1, 2)))

    def reconstruct_batch(self, present_rows: list[int],
                          want_rows: list[int], block) -> jax.Array:
        """Rebuild want_rows for every window of a (B, k, L) block of
        present shards (stacked in present_rows order) -> (B, r, L)."""
        coeff = gf.cached_shard_rows(tuple(want_rows),
                                     tuple(present_rows), self.k, self.n)
        return self.transform_batch(coeff, block)

    # data: (..., k, n) -> parity (..., m, n)
    def parity(self, data: jax.Array) -> jax.Array:
        return apply_transform(self.parity_coeff, data, self.use_pallas)

    def encode(self, data: jax.Array) -> jax.Array:
        """(..., k, n) data -> (..., k+m, n) full shard set."""
        data = jnp.asarray(data, jnp.uint8)
        return jnp.concatenate([data, self.parity(data)], axis=-2)

    def verify(self, shards) -> bool:
        """The unified backend verify: accepts a list of k+m equal-length
        rows OR a stacked (..., k+m, L) array — the same
        `verify(block) -> bool` signature as CpuEncoder."""
        shards = jnp.asarray(np.asarray(shards, np.uint8))
        par = self.parity(shards[..., :self.k, :])
        return bool(jnp.array_equal(par, shards[..., self.k:, :]))

    def reconstruct_rows(self, present_rows: list[int], shards: jax.Array,
                         want_rows: list[int]) -> jax.Array:
        """Rebuild want_rows from the k rows listed in present_rows.

        shards: (..., k, n) — the present shards stacked in present_rows
        order. The (len(want), k) coefficient matrix is inverted on host
        (tiny) exactly like reedsolomon.Reconstruct does before its matmul.
        """
        coeff = gf.shard_rows(list(want_rows), list(present_rows),
                              self.k, self.n)
        return apply_transform(coeff, jnp.asarray(shards, jnp.uint8),
                               self.use_pallas)

    def reconstruct(self, shards: list, data_only: bool = False) -> list:
        """List-of-(n,)-arrays-or-None API matching CpuEncoder.reconstruct."""
        present = [i for i, s in enumerate(shards) if s is not None]
        missing = [i for i, s in enumerate(shards) if s is None]
        if len(present) < self.k:
            raise ValueError(
                f"too few shards to reconstruct: {len(present)} < {self.k}")
        if data_only:
            missing = [i for i in missing if i < self.k]
        out = [None if s is None else np.asarray(s, dtype=np.uint8)
               for s in shards]
        if not missing:
            return out
        use = present[:self.k]
        stacked = jnp.stack([jnp.asarray(np.asarray(shards[i], np.uint8))
                             for i in use], axis=0)
        rebuilt = np.asarray(self.reconstruct_rows(use, stacked, missing))
        for row, idx in enumerate(missing):
            out[idx] = rebuilt[row]
        return out

    def reconstruct_data(self, shards: list) -> list:
        return self.reconstruct(shards, data_only=True)
