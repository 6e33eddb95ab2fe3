"""Flagship jittable pipelines ("models") of the framework.

The compute heart of the system is the RS(10,4) GF(256) shard transform;
these are its end-to-end jittable forms, the analog of a model-forward /
train-step in an ML framework:

  encode_step   — forward: 10 data shards -> 14 shards (parity matmul)
  rebuild_step  — recovery: any 10 shard rows -> requested lost rows
  verify_step   — recompute parity and reduce a mismatch count

Reference equivalents: reedsolomon Encode/Reconstruct at ec_encoder.go:192,
264 and store_ec.go:322.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..ec import gf


def _default_use_pallas() -> bool:
    """The Pallas kernel on the TPU, the XLA bitplane transform on the
    CPU. A backend-init error raises: it must never quietly select the
    CPU formulation on a host that has a chip."""
    return jax.default_backend() == "tpu"


def _apply_bitplanes(consts: np.ndarray, data: jax.Array) -> jax.Array:
    """out[..., r, :] = XOR_i gf_mul(coeff[r, i], data[..., i, :]) as
    plain XLA ops (the steps' CPU formulation).

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


def make_encode_step(use_pallas: bool | None = None):
    """Returns fn(data (..., 10, n) uint8) -> (..., 14, n) uint8, jittable."""
    consts = gf.bitplane_constants(gf.parity_matrix())
    if use_pallas is None:
        use_pallas = _default_use_pallas()

    if use_pallas:
        from ..ops.gf256_pallas import gf256_matmul_pallas

        def step(data):
            parity = gf256_matmul_pallas(consts, data)
            return jnp.concatenate([jnp.asarray(data, jnp.uint8), parity],
                                   axis=-2)
    else:
        def step(data):
            data = jnp.asarray(data, jnp.uint8)
            parity = _apply_bitplanes(consts, data)
            return jnp.concatenate([data, parity], axis=-2)
    return step


def make_rebuild_step(present_rows: list[int], want_rows: list[int],
                      use_pallas: bool | None = None):
    """Returns fn(shards (..., 10, n)) -> (..., len(want), n), jittable."""
    coeff = gf.shard_rows(list(want_rows), list(present_rows))
    consts = gf.bitplane_constants(coeff)
    if use_pallas is None:
        use_pallas = _default_use_pallas()

    if use_pallas:
        from ..ops.gf256_pallas import gf256_matmul_pallas

        def step(shards):
            return gf256_matmul_pallas(consts, shards)
    else:
        def step(shards):
            return _apply_bitplanes(consts, jnp.asarray(shards, jnp.uint8))
    return step


def make_verify_step(use_pallas: bool | None = None):
    """Returns fn(shards (..., 14, n)) -> scalar int32 mismatch count."""
    consts = gf.bitplane_constants(gf.parity_matrix())
    if use_pallas is None:
        use_pallas = _default_use_pallas()

    def step(shards):
        shards = jnp.asarray(shards, jnp.uint8)
        data, parity = shards[..., :gf.DATA_SHARDS, :], \
            shards[..., gf.DATA_SHARDS:, :]
        if use_pallas:
            from ..ops.gf256_pallas import gf256_matmul_pallas
            want = gf256_matmul_pallas(consts, data)
        else:
            want = _apply_bitplanes(consts, data)
        return jnp.sum((want != parity).astype(jnp.int32))
    return step


def example_inputs(batch: int = 0, n: int = 64 * 1024,
                   seed: int = 0) -> np.ndarray:
    rng = np.random.default_rng(seed)
    shape = (batch, gf.DATA_SHARDS, n) if batch else (gf.DATA_SHARDS, n)
    return rng.integers(0, 256, shape).astype(np.uint8)
