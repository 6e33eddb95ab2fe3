"""Pure-numpy RS(10,4) encoder — CPU baseline and correctness oracle.

Equivalent role to klauspost/reedsolomon's Encoder on the host
(reference call sites: ec_encoder.go:192, store_ec.go:322). The TPU path in
encoder_jax.py must match this byte-for-byte; bench.py uses this as the
host baseline the TPU kernel is measured against.
"""

from __future__ import annotations

import numpy as np

from . import gf


class CpuEncoder:
    """Table-lookup GF(256) encoder, vectorized with numpy.

    API mirrors the reedsolomon.Encoder surface the reference uses:
    encode / verify / reconstruct / reconstruct_data.
    Shards are a list of equal-length byte arrays (or None for missing).
    """

    def __init__(self, data_shards: int = gf.DATA_SHARDS,
                 parity_shards: int = gf.PARITY_SHARDS,
                 use_native: bool | None = None):
        self.k = data_shards
        self.m = parity_shards
        self.n = data_shards + parity_shards
        # native C kernel (AVX2 PSHUFB, native/gf256.c) when built; the
        # numpy path stays as the always-available correctness oracle
        if use_native is None:
            from ..native import gf256 as _native
            use_native = _native.available()
        self.use_native = use_native
        # Copy out of the lru_cache so instance mutation can't poison the
        # process-global matrix shared with every other encoder.
        self.matrix = gf.rs_matrix(self.k, self.n).copy()
        self.parity = self.matrix[self.k:]

    # -- core matmul ------------------------------------------------------

    def _apply(self, coeff: np.ndarray,
               inputs: list[np.ndarray]) -> list[np.ndarray]:
        if self.use_native and inputs and inputs[0].ndim == 1:
            from ..native import gf256 as _native
            return _native.transform(coeff, inputs)
        return self._apply_numpy(coeff, inputs)

    @staticmethod
    def _apply_numpy(coeff: np.ndarray,
                     inputs: list[np.ndarray]) -> list[np.ndarray]:
        """rows_out[r] = XOR_i mul_table(coeff[r,i])[inputs[i]]."""
        rows, k = coeff.shape
        assert k == len(inputs)
        out = []
        for r in range(rows):
            acc = np.zeros_like(inputs[0])
            for i in range(k):
                c = int(coeff[r, i])
                if c == 0:
                    continue
                elif c == 1:
                    acc ^= inputs[i]
                else:
                    acc ^= gf.mul_table(c)[inputs[i]]
            out.append(acc)
        return out

    def transform_async(self, coeff: np.ndarray, rows: list):
        """The encoders' one launch: out[r] = XOR_i gf_mul(coeff[r, i],
        rows[i]) over equal-length uint8 rows. The host computes it
        eagerly (native AVX2 kernel when built, numpy table lookups
        otherwise); the returned thunk only hands the rows back."""
        out = self._apply(np.asarray(coeff, np.uint8),
                          [np.asarray(r, np.uint8) for r in rows])
        return lambda: out

    # -- public API -------------------------------------------------------

    def encode(self, shards: list[np.ndarray | bytes | None]) -> list[np.ndarray]:
        """Compute parity from shards[:k]; returns a fresh list of k+m
        writable arrays (any parity entries passed in are ignored)."""
        data = [np.frombuffer(s, dtype=np.uint8).copy()
                if isinstance(s, (bytes, bytearray, memoryview))
                else np.asarray(s, dtype=np.uint8) for s in shards[:self.k]]
        parity = self._apply(self.parity, data)
        return data + parity

    def verify(self, shards) -> bool:
        """Recompute parity from shards[:k] and compare it with
        shards[k:]: a list of k+m equal-length rows or a stacked
        (k+m, L) uint8 array."""
        if len(shards) != self.n:
            return False
        data = [np.ascontiguousarray(s, dtype=np.uint8)
                for s in shards[:self.k]]
        parity = self._apply(self.parity, data)
        for got, want in zip(shards[self.k:], parity):
            if not np.array_equal(np.asarray(got, dtype=np.uint8), want):
                return False
        return True

    def reconstruct(self, shards: list[np.ndarray | None],
                    data_only: bool = False) -> list[np.ndarray]:
        """Rebuild missing (None) shards in place semantics; returns full list.

        Needs >= k present shards (reference guard:
        command_ec_rebuild.go:110 treats <10 as unrepairable).
        """
        present = [i for i, s in enumerate(shards) if s is not None]
        missing = [i for i, s in enumerate(shards) if s is None]
        if not missing:
            return [np.asarray(s, dtype=np.uint8) for s in shards]
        if len(present) < self.k:
            raise ValueError(
                f"too few shards to reconstruct: {len(present)} < {self.k}")
        use = present[:self.k]
        if data_only:
            missing = [i for i in missing if i < self.k]
        inputs = [np.asarray(shards[i], dtype=np.uint8) for i in use]
        coeff = gf.shard_rows(missing, use, self.k, self.n)
        rebuilt = self._apply(coeff, inputs)
        out = [None if s is None else np.asarray(s, dtype=np.uint8)
               for s in shards]
        for idx, row in zip(missing, rebuilt):
            out[idx] = row
        return out

    def reconstruct_data(self, shards: list[np.ndarray | None]) -> list[np.ndarray]:
        """Rebuild only the k data shards (reference: ReconstructData,
        store_ec.go:322 degraded-read path)."""
        return self.reconstruct(shards, data_only=True)
