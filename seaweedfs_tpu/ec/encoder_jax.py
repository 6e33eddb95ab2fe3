"""JAX RS(10,4) encoder — the TPU-native GF(256) shard transform.

Re-expresses the reference's klauspost/reedsolomon Encode/Reconstruct
(amd64 PSHUFB assembly, called at ec_encoder.go:192,264 and store_ec.go:322)
as the bitplane transform of ops/gf256_pallas.py:

    gf_mul(c, x) = XOR_{j in bits(x)} gf_mul(c, 1 << j)

so a (rows, k) GF(256) coefficient matrix applied to k shard byte-streams
becomes, for each output row, an accumulation of AND/XOR over the 8
bitplanes of each input shard — pure VPU ops with no gathers, no
data-dependent control flow, and static shapes. Each distinct transform
(encode's (4,10) parity map or a particular reconstruction's (r,10) map)
compiles once per stream length and is cached.

`transform_async` is the one way EC work reaches the chip: every bulk
path (encode, rebuild, scrub) and every recover hands it k byte rows.
"""

from __future__ import annotations

import jax
import numpy as np

from ..ops.gf256_pallas import (_BLOCK_BYTES, _DEFAULT_BM, _LANES,
                                bytes_to_words, gf256_stacked_transform,
                                gf256_words_transform, words_to_bytes)
from ..util import tracing
from . import gf


class JaxEncoder:
    """The device encoder: byte rows in, byte rows out, with the GF
    transform launched asynchronously on the attached device."""

    def transform_async(self, coeff: np.ndarray, rows: list):
        """Launch out[r] = XOR_i gf_mul(coeff[r, i], rows[i]) on the
        device and return a thunk that yields the output byte rows.

        Each equal-length uint8 row reaches the device as a (wm, 128)
        u32 view (`bytes_to_words`: free for a row of a multiple of the
        kernel's block quantum, a zero-padded copy otherwise). The
        dispatch is asynchronous and the thunk blocks on readback, so a
        caller can overlap the NEXT batch's file reads and transfers
        with this batch's kernel time (the reference overlaps nothing:
        its 256KB loop at ec_encoder.go:114-186 is serial).

        Rows shorter than that quantum (recovers of short intervals)
        go as one block instead (`_transform_small`).

        Host stages: `ec`/`h2d` (the views and their transfer),
        `ec`/`launch` (enqueueing the kernel) and, in the thunk,
        `ec`/`readback` (blocked on the device and the copy back)."""
        n = len(rows[0])
        if n < _DEFAULT_BM * _BLOCK_BYTES:
            return self._transform_small(coeff, rows, n)
        with tracing.start("ec", "h2d") as sp:
            words = [jax.device_put(bytes_to_words(r)) for r in rows]
            sp.nbytes = n * len(rows)
        with tracing.start("ec", "launch") as sp:
            outs = gf256_words_transform(gf.bitplane_constants(coeff), words)
            sp.nbytes = n * len(rows)

        def readback() -> list[np.ndarray]:
            with tracing.start("ec", "readback") as sp:
                sp.nbytes = n * len(outs)
                return [words_to_bytes(np.asarray(o), n).copy()
                        for o in outs]
        return readback

    @staticmethod
    def _transform_small(coeff: np.ndarray, rows: list, n: int):
        """transform_async for rows of n bytes below the block quantum:
        the k rows, zero-padded to a power-of-two count of 4 KiB tiles
        (at most 6 shapes), cross as ONE (1, k, wm, 128) block through
        the stacked kernel and come back in one readback. k transfers
        of a padded 128 KiB quantum each would move 32x the bytes of a
        1 KB recover, and each gives the interpreter lock to another
        thread of a server under concurrent reads."""
        wm = max(8, 1 << (-(-n // _BLOCK_BYTES) - 1).bit_length())
        with tracing.start("ec", "h2d") as sp:
            block = np.zeros((1, len(rows), wm * _BLOCK_BYTES), np.uint8)
            for i, r in enumerate(rows):
                block[0, i, :n] = (r if isinstance(r, np.ndarray)
                                   else np.frombuffer(r, np.uint8))
            x = jax.device_put(
                block.view(np.uint32).reshape(1, len(rows), wm, _LANES))
            sp.nbytes = n * len(rows)
        with tracing.start("ec", "launch") as sp:
            out = gf256_stacked_transform(gf.bitplane_constants(coeff), x)
            sp.nbytes = n * len(rows)

        def readback() -> list[np.ndarray]:
            with tracing.start("ec", "readback") as sp:
                o = np.asarray(out)
                sp.nbytes = n * o.shape[1]
                return [words_to_bytes(o[0, r], n).copy()
                        for r in range(o.shape[1])]
        return readback
