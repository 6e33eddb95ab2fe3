"""Benchmark: RS(10,4) erasure-coding throughput on the attached TPU chip.

ALWAYS prints exactly ONE JSON line on stdout, no matter what fails:
  {"metric": "...", "value": N, "unit": "GB/s", "vs_baseline": N, ...}

value      = sustained encode+rebuild data throughput per chip (GB/s of
             data-shard bytes processed; min of encode and worst-case
             4-missing rebuild — the BASELINE.json north-star metric).
vs_baseline= ratio vs the host CPU encoder measured in the same run (the
             stand-in for the reference's AVX2 reedsolomon path,
             /root/reference/go.mod:41 klauspost/reedsolomon).

Design:
  * All device work runs in a KILLABLE CHILD PROCESS ("python bench.py
    --child") with a wall-clock budget; the parent never touches JAX, so
    the child is the one process that holds the chip. A child that
    produces nothing within the init timeout is killed, retried once,
    then abandoned.
  * The child refuses to measure anything but a TPU, and looks the HBM
    ceiling up by `device_kind` (DEVICE_HBM_GBPS; an unknown kind is
    an error).
  * The child VERIFIES each kernel path on-device against the CPU oracle
    before timing it — a fast-but-wrong path is never reported.
  * The child measures incrementally (small shapes first) and streams each
    cumulative result as a JSON line; the parent keeps the last complete
    one, so even a mid-measurement kill yields a real number.
  * With no device result the line carries "value": null and an "error",
    and the exit code is non-zero: a CPU rate is never reported under the
    device metric's name (cpu_baseline_GBps is its own key).
Progress is logged to stderr so a hang is diagnosable.

Env knobs:
  SWTPU_BENCH_BUDGET_S   total wall-clock budget (default 420)
  SWTPU_BENCH_INIT_S     backend-init timeout per attempt (default 180)
  SWTPU_BENCH_BYTES      max bytes per shard in the largest stage
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np

_T0 = time.perf_counter()


def _log(msg: str) -> None:
    print(f"[bench +{time.perf_counter() - _T0:6.1f}s] {msg}",
          file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# CPU baseline (parent process; no jax import needed)
# ---------------------------------------------------------------------------


def bench_cpu(n_bytes_per_shard: int = 4 << 20) -> tuple[float, str]:
    """Host baseline: the best available CPU encoder — the native AVX2
    kernel (native/gf256.c, analog of the reference's reedsolomon assembly
    path) when built, else the numpy table-lookup fallback."""
    from seaweedfs_tpu.ec import gf
    from seaweedfs_tpu.ec.encoder_cpu import CpuEncoder

    enc = CpuEncoder()
    kind = "native-avx2" if enc.use_native else "numpy"
    rng = np.random.default_rng(7)
    data = [rng.integers(0, 256, n_bytes_per_shard).astype(np.uint8)
            for _ in range(gf.DATA_SHARDS)]
    enc.encode(list(data))  # warm tables
    t0 = time.perf_counter()
    iters = 3
    for _ in range(iters):
        enc.encode(list(data))
    dt = (time.perf_counter() - t0) / iters
    return gf.DATA_SHARDS * n_bytes_per_shard / dt / 1e9, kind


def bench_degraded_read(n_needles: int = 64, payload: int = 8 << 10,
                        reads: int = 300) -> dict:
    """p50/p99 latency of EcVolume.read_needle with one data shard file
    deleted — every read reconstructs its intervals from the 13 survivors
    (the BASELINE.json config-5 path; store_ec.go:319-373 analog).
    Host-path measurement: small recover intervals route to the CPU
    encoder (EcVolume.SMALL_RECOVER_BYTES), so no device in the loop."""
    import shutil
    import tempfile

    from seaweedfs_tpu.ec import pipeline as ecpl
    from seaweedfs_tpu.ec.ec_volume import EcVolume
    from seaweedfs_tpu.ec.encoder_cpu import CpuEncoder
    from seaweedfs_tpu.storage.needle import Needle
    from seaweedfs_tpu.storage.volume import Volume

    # CPU encoder throughout: this is a latency benchmark of the storage
    # path, and the parent must never init the device backend (that is
    # the killable child's job)
    enc = CpuEncoder()
    tmp = tempfile.mkdtemp(prefix="swtpu_bench_ec_")
    try:
        v = Volume(tmp, "", 1)
        rng = np.random.default_rng(11)
        for i in range(1, n_needles + 1):
            v.write_needle(Needle(cookie=0x1234, id=i,
                                  data=rng.integers(0, 256, payload)
                                  .astype(np.uint8).tobytes()))
        v.close()
        base = os.path.join(tmp, "1")
        ecpl.write_ec_files(base, encoder=enc)
        ecpl.write_sorted_file_from_idx(base)
        os.remove(base + ".ec00")  # lose a data shard
        ev = EcVolume(tmp, "", 1, encoder=enc)
        lat = []
        for r in range(reads):
            nid = (r % n_needles) + 1
            t0 = time.perf_counter()
            n = ev.read_needle(nid)
            lat.append((time.perf_counter() - t0) * 1e3)
            assert len(n.data) == payload
        ev.close()
        lat.sort()
        return {
            "degraded_read_p50_ms": round(lat[len(lat) // 2], 3),
            "degraded_read_p99_ms": round(lat[int(len(lat) * 0.99)], 3),
            "degraded_read_reads": reads,
        }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


# ---------------------------------------------------------------------------
# Child: all device work. Streams cumulative JSON results line-by-line.
# ---------------------------------------------------------------------------


def _emit(result: dict) -> None:
    print(json.dumps(result), flush=True)


def _verify_paths_on_device(n_small: int = 256 << 10) -> dict:
    """Encode+rebuild a small slab on device with each kernel path and
    compare byte-for-byte against the CPU oracle (the ec_test.go dual-read
    discipline applied to the kernel itself). Returns {path: True|err}."""
    import jax

    from seaweedfs_tpu.ec import gf
    from seaweedfs_tpu.ec.encoder_cpu import CpuEncoder
    from seaweedfs_tpu.ops import gf256_pallas as gp

    rng = np.random.default_rng(3)
    data = [rng.integers(0, 256, n_small).astype(np.uint8)
            for _ in range(gf.DATA_SHARDS)]
    oracle = CpuEncoder(use_native=False)
    reb_coeff = gf.shard_rows([0, 1, 2, 3], list(range(4, 14)))
    full = oracle.encode(list(data))
    want_parity = full[gf.DATA_SHARDS:]
    want_reb = oracle._apply_numpy(reb_coeff, full[4:14])

    # IMPORTANT: verify with the DEFAULT block_bm so the exact pallas_call
    # instantiation (BlockSpec/grid) that gets timed is the one checked;
    # n_small spans >1 grid block to cover the pipelined multi-block path
    words = [jax.device_put(gp.bytes_to_words(d)) for d in data]
    wn = words[0].shape[0] * 512
    reb_in = [jax.device_put(gp.bytes_to_words(full[i]))
              for i in range(4, 14)]
    enc_coeff = gf.parity_matrix()
    paths = {
        "vpu": lambda c, ws: gp.gf256_words_transform(
            gf.bitplane_constants(c), ws),
    }
    status: dict = {}
    for name, fn in paths.items():
        try:
            got_p = [gp.words_to_bytes(np.asarray(o), n_small)
                     for o in fn(enc_coeff, words)]
            got_r = [gp.words_to_bytes(np.asarray(o), n_small)
                     for o in fn(reb_coeff, reb_in)]
            ok = (all(np.array_equal(g, w)
                      for g, w in zip(got_p, want_parity))
                  and all(np.array_equal(g, w)
                          for g, w in zip(got_r, want_reb)))
            status[name] = True if ok else "MISMATCH vs CPU oracle"
        except Exception as e:  # noqa: BLE001 — one path must not kill both
            status[name] = f"{type(e).__name__}: {e}"[:200]
        _log(f"oracle check {name} ({wn}B/shard): {status[name]}")
    return status


def _roundtrip_latency() -> float:
    """Per-dispatch round-trip cost, measured so the timed chains can
    be sized to amortise it."""
    import jax
    import jax.numpy as jnp

    z = jax.device_put(np.zeros((8, 128), np.uint32))
    tiny = jax.jit(lambda x: jnp.sum(x))
    float(tiny(z))
    t0 = time.perf_counter()
    iters = 5
    for _ in range(iters):
        float(tiny(z))
    return (time.perf_counter() - t0) / iters


# Published per-chip HBM bandwidth keyed by jax `device_kind` (Google
# Cloud documentation, "TPU v5e": 819 GB/s). Every kernel step must at
# least read k*n bytes from HBM, so a measured data-GB/s above it is a
# harness artifact, never a real number (the round-3 bench published
# 83,886,080 "GB/s" when a clamp turned short timings into exactly
# bytes/ns). A kind not listed is an error, not a default.
DEVICE_HBM_GBPS = {"TPU v5 lite": 819.0}


def hbm_bound_gbps(device_kind: str) -> float:
    if device_kind not in DEVICE_HBM_GBPS:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}: add it to DEVICE_HBM_GBPS")
    return DEVICE_HBM_GBPS[device_kind]


class ImplausibleResult(Exception):
    pass


def _chained_gbs(transform, consts, words, n: int, chain_len: int,
                 rtt: float, bound_gbps: float,
                 budget_s: float | None = None
                 ) -> tuple[float, float, int]:
    """Sustained GB/s of data-shard bytes through the kernel.

    chain_len dependent kernel invocations run inside one jit (outputs
    feed the next step's inputs, preventing CSE); several chain calls
    are then DISPATCHED AHEAD and blocked on once, so the dispatch
    round-trip latency amortises across the whole timed region via JAX
    async dispatch instead of being subtracted out.

    Measurement honesty rules (the round-3 verdict's #1):
      * nothing is ever subtracted from a timing — any dispatch overhead
        that async dispatch fails to hide is COUNTED, so the number can
        only understate the kernel;
      * a chain too short to measure is grown, not corrected;
      * any result above the HBM ceiling `bound_gbps` raises
        ImplausibleResult.
    budget_s, when given, caps the wall clock this call may spend (a
    slow device with a multi-second rtt must not eat the whole child
    budget inside one measurement).
    Returns (gbs, total timed seconds, chain_len actually used).
    """
    import jax
    import jax.numpy as jnp

    k = len(words)
    rows = consts.shape[0]
    t_entry = time.perf_counter()

    def spent() -> float:
        return time.perf_counter() - t_entry

    def build(cl):
        @jax.jit
        def chain(*w):
            ws = list(w)
            for _ in range(cl):
                outs = list(transform(consts, ws))
                ws = (outs + ws)[:k]
            return sum(jnp.sum(x, dtype=jnp.uint32) for x in ws[:rows])
        return chain

    for _attempt in range(4):
        used_cl = chain_len  # the length the built chain ACTUALLY runs:
        #                      every timing below divides by this, never
        #                      by a post-growth value
        chain = build(used_cl)
        float(chain(*words))  # compile + warm
        t0 = time.perf_counter()
        float(chain(*words))
        dt1 = time.perf_counter() - t0
        if dt1 > 5 * rtt or used_cl >= 256:
            break
        if budget_s is not None and spent() > budget_s / 3:
            break  # growing further would recompile past the budget
        # chain too short for one dispatch to dominate its own rtt:
        # grow it (bounded) so the async loop below isn't dispatch-bound
        grow = max(2, int(5 * rtt / max(dt1, 1e-6)) + 1)
        chain_len = min(256, used_cl * grow)
        _log(f"  chain too short (dt={dt1 * 1e3:.0f}ms vs rtt="
             f"{rtt * 1e3:.0f}ms); growing chain to {chain_len}")
    # dispatch-ahead: enough chain calls that the timed region spans
    # >= ~10 rtts and ~1s of KERNEL time, blocking only on the last.
    # dt1 is a blocking timing, so it contains one full rtt that the
    # async loop will hide; size iters from the kernel-only estimate or
    # the one amortised rtt drags the reported number down by up to
    # rtt/target. (The subtraction here only SIZES the loop — the
    # reported figure still divides the full measured dt.)
    est_step = max(dt1 - rtt, dt1 / 4, 1e-6)
    target = max(1.0, 10 * rtt)
    iters = max(2, int(target / est_step) + 1)
    if budget_s is not None:
        # the budget cap must use the CONSERVATIVE blocking step time
        # dt1, not est_step: with async dispatch the in-loop deadline
        # below may never fire (dispatches return instantly) and the
        # final sync blocks for iters * real_step
        iters = min(iters,
                    max(2, int(max(budget_s - spent(), 0.0) / dt1) + 1))
    iters = min(iters, 100_000)
    t0 = time.perf_counter()
    r = None
    done = 0
    for _ in range(iters):
        r = chain(*words)
        done += 1
        # hard deadline: est_step can underestimate the real per-call
        # cost (e.g. a transient stall inflated the rtt probe),
        # so the loop itself must also respect the budget; dividing by
        # the count actually dispatched keeps the figure honest
        if done >= 2 and budget_s is not None and spent() > budget_s:
            break
    float(r)  # single sync point
    dt = time.perf_counter() - t0
    per_step = dt / (done * used_cl)
    gbs = k * n / per_step / 1e9
    if gbs > bound_gbps:
        raise ImplausibleResult(
            f"{gbs:.0f} GB/s exceeds the {bound_gbps:.0f} GB/s HBM "
            f"ceiling (dt={dt * 1e3:.1f}ms chain={used_cl} "
            f"iters={done}) — measurement artifact, not reported")
    return gbs, dt, used_cl


def child_main() -> None:
    deadline = _T0 + float(os.environ.get("SWTPU_BENCH_CHILD_S", "300"))

    def left() -> float:
        return deadline - time.perf_counter()

    import jax
    import jax.numpy as jnp

    from seaweedfs_tpu.ops import compile_cache

    _log("initialising jax backend ...")
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    _log(f"backend up: {device}")
    if dev.platform != "tpu":
        _emit({"stage": "done", "device": device,
               "error": f"no TPU: jax platform is {dev.platform!r}"})
        sys.exit(2)
    bound = hbm_bound_gbps(dev.device_kind)
    backend = dev.platform
    compile_cache.enable(os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        compile_cache.CACHE_DIRNAME))
    _emit({"stage": "init", "backend": backend, "device": device})
    # tiny-transfer probe BEFORE any real device work: a device that is
    # up but not answering fails here, not mid-large-device_put
    t0 = time.perf_counter()
    probe = jax.device_put(np.arange(256, dtype=np.uint8))
    assert int(jnp.sum(probe.astype(jnp.uint32))) == 255 * 128
    _log(f"tiny-transfer probe ok ({time.perf_counter() - t0:.2f}s)")
    _emit({"stage": "probe", "probe_s": round(time.perf_counter() - t0, 2)})

    from seaweedfs_tpu.ec import gf
    from seaweedfs_tpu.ops import gf256_pallas as gp

    status = _verify_paths_on_device()
    _emit({"stage": "oracle", "paths_verified": status})
    good = [p for p, st in status.items() if st is True]
    if not good:
        _emit({"stage": "done", "error": f"no kernel path passed the "
               f"on-device oracle check: {status}"})
        return

    rtt = _roundtrip_latency()
    _log(f"dispatch rtt {rtt * 1e3:.1f} ms")

    enc_coeff = gf.parity_matrix()
    # worst-case rebuild: all 4 lost are data shards, rebuilt from
    # shards 4..13 (6 data + 4 parity)
    reb_coeff = gf.shard_rows([0, 1, 2, 3], list(range(4, 14)))
    paths = {
        "vpu": lambda c, ws: gp.gf256_words_transform(
            gf.bitplane_constants(c), ws),
    }

    max_bytes = int(os.environ.get("SWTPU_BENCH_BYTES", str(256 << 20)))
    # chains sized so the timed region dwarfs the ~70ms dispatch rtt even
    # at ~100 GB/s (the adaptive growth in _chained_gbs backstops this)
    stages = [(s, c) for s, c in [
        (4 << 20, 64), (16 << 20, 32), (64 << 20, 32),
        (256 << 20, 16)] if s <= max_bytes]
    if not stages:  # tiny SWTPU_BENCH_BYTES: still measure one stage
        stages = [(max(128 << 10, (max_bytes // (128 << 10)) * (128 << 10)),
                   2)]
    detail: dict = {"dispatch_rtt_ms": round(rtt * 1e3, 1)}

    k = gf.DATA_SHARDS
    speeds: dict[str, float] = {}  # path -> best measured GB/s so far

    def emit_cumulative(n: int) -> None:
        """Stream the best-so-far result after EVERY measurement, so a
        budget kill can never lose numbers that were already measured
        (the round-3 16MB results died exactly that way)."""
        enc = max((v for d, v in detail.items()
                   if d.startswith("encode_") and isinstance(v, float)),
                  default=0.0)
        reb = max((v for d, v in detail.items()
                   if d.startswith("rebuild4_") and isinstance(v, float)),
                  default=0.0)
        stage_res = {"stage": f"measure_{n >> 20}MB", "backend": backend,
                     "encode_GBps": enc, "rebuild4_GBps": reb,
                     "paths": detail}
        if enc > 0 and reb > 0:  # "value" only once BOTH ops are measured
            stage_res["value"] = min(enc, reb)
        _emit(stage_res)

    def gen_words(n: int, seed: int = 0) -> list:
        # generate stripes ON DEVICE: PRNG keys are a few bytes, so the
        # stage measures the kernel and not a host->device copy
        make = jax.jit(
            lambda key: jax.random.bits(key, (n // 512, 128), jnp.uint32))
        words = [make(k_) for k_ in
                 jax.random.split(jax.random.PRNGKey(seed), k)]
        jax.block_until_ready(words)
        return words

    def chain_for(name: str, n: int, default: int) -> int:
        # size the chain from the measured speed so the timed region
        # lands near max(0.7s, 12*rtt) on the first try
        if name not in speeds:
            return default
        per_step = k * n / (speeds[name] * 1e9)
        return min(256, max(4, int(max(0.7, 12 * rtt) / per_step) + 1))

    def run_stage(n: int, chain_len: int) -> None:
        words = gen_words(n)
        best = max(speeds.values(), default=0.0)
        for name in sorted(good, key=lambda p: -speeds.get(p, 1e9)):
            if speeds.get(name, 1e9) < best / 5:
                # this path lost the race decisively at a smaller stage;
                # spend the remaining budget on the winner's curve
                _log(f"skipping {name} at {n >> 20}MB (lost race: "
                     f"{speeds[name]:.1f} vs {best:.1f} GB/s)")
                continue
            cl = chain_for(name, n, chain_len)
            for op, coeff in (("encode", enc_coeff), ("rebuild4", reb_coeff)):
                if left() < 15:
                    return
                try:
                    gbs, dt, used_chain = _chained_gbs(
                        paths[name], coeff, words, n, cl, rtt, bound,
                        budget_s=left() - 10)
                except Exception as e:  # noqa: BLE001
                    detail[f"{op}_{name}_error"] = str(e)[:200]
                    _log(f"{op}/{name} n={n >> 20}MB FAILED: {e}")
                    continue
                key = f"{op}_{name}"
                detail[key] = max(detail.get(key, 0.0), round(gbs, 2))
                detail[f"{key}_{n >> 20}MB"] = round(gbs, 2)
                speeds[name] = max(speeds.get(name, 0.0), gbs)
                _log(f"{op}/{name} n={n >> 20}MB chain={used_chain} "
                     f"dt={dt * 1e3:.0f}ms: {gbs:.2f} GB/s")
                emit_cumulative(n)

    def run_batched() -> None:
        # batched rack-encode config (BASELINE.json 64-volume shape scaled
        # to one chip): V volumes in one launch through the mesh "vol"
        # axis, routed through the same Pallas kernel via shard_map
        try:
            from seaweedfs_tpu.parallel import mesh as pmesh

            m = pmesh.make_mesh(jax.devices()[:1])
            vb, nb = 8, 16 << 20
            nb = min(nb, max_bytes)
            mk = jax.jit(lambda key: jax.random.randint(
                key, (vb, k, nb), 0, 256, jnp.uint8))
            vol_data = mk(jax.random.PRNGKey(1))
            jax.block_until_ready(vol_data)
            jax.block_until_ready(pmesh.batched_encode(m, vol_data))  # compile
            # size the iteration count so the timed loop dwarfs rtt
            t0 = time.perf_counter()
            jax.block_until_ready(pmesh.batched_encode(m, vol_data))
            once = time.perf_counter() - t0
            iters = max(2, int(20 * rtt / max(once, 1e-6)) + 1)
            t0 = time.perf_counter()
            for _ in range(iters):
                out = pmesh.batched_encode(m, vol_data)
            jax.block_until_ready(out)
            dt = (time.perf_counter() - t0) / iters
            gbs = vb * k * nb / dt / 1e9
            if gbs > bound:
                raise ImplausibleResult(
                    f"batched {gbs:.0f} GB/s exceeds HBM ceiling")
            _log(f"batched encode {vb}x{nb >> 20}MB iters={iters}: "
                 f"{gbs:.2f} GB/s")
            _emit({"stage": "batched", "batched_encode_GBps": round(gbs, 2)})
        except Exception as e:  # noqa: BLE001
            _emit({"stage": "batched",
                   "batched_encode_error": str(e)[:200]})

    def tune_block_bm() -> None:
        """Race the Pallas block size (grid tile height) on the encode
        path — leftover-budget autotune. Results land in detail as
        tune_bm<N>: deliberately OUTSIDE the encode_*/rebuild4_* prefixes
        the headline aggregation reads, so the published score reflects
        only the default kernel configuration; tuning data just informs
        moving the default in a future round."""
        n = min(16 << 20, max_bytes)
        words = gen_words(n, seed=2)
        cl = chain_for("vpu", n, 32)
        for bm in (128, 512, 1024):
            if left() < 40:
                return
            try:
                gbs, dt, used = _chained_gbs(
                    lambda c, ws, _bm=bm: gp.gf256_words_transform(
                        gf.bitplane_constants(c), ws, block_bm=_bm),
                    enc_coeff, words, n, cl, rtt, bound,
                    budget_s=left() - 20)
            except Exception as e:  # noqa: BLE001
                detail[f"tune_bm{bm}_error"] = str(e)[:120]
                continue
            detail[f"tune_bm{bm}"] = round(gbs, 2)
            _log(f"tune bm={bm}: {gbs:.2f} GB/s (default bm=256: "
                 f"{speeds.get('vpu', 0):.2f})")
            emit_cumulative(n)

    def run_crc() -> None:
        """Device CRC32C (ops/crc32c_jax.py GF(2)-matmul formulation,
        SURVEY §2b item 2) vs the host SSE4.2 path — decides whether
        folding checksums into the device pipeline pays."""
        try:
            from seaweedfs_tpu.ops.crc32c_jax import crc32c_batch
            from seaweedfs_tpu.util import crc32c as hostcrc

            bsz, n = 64, 1 << 20
            n = min(n, max(max_bytes, 64 << 10))
            mk = jax.jit(lambda key: jax.random.randint(
                key, (bsz, n), 0, 256, jnp.uint8))
            dev = mk(jax.random.PRNGKey(5))
            jax.block_until_ready(dev)
            # oracle first: a fast-but-wrong checksum is never reported
            got = np.asarray(crc32c_batch(dev[:2, :]))
            host = np.frombuffer(
                np.asarray(dev[:2, :]).tobytes(), np.uint8).reshape(2, n)
            want = [hostcrc.crc32c(r.tobytes()) for r in host]
            if list(got) != want:
                raise RuntimeError("device crc mismatch vs host oracle")
            jax.block_until_ready(crc32c_batch(dev))  # compile
            t0 = time.perf_counter()
            jax.block_until_ready(crc32c_batch(dev))  # warm timing probe
            once = time.perf_counter() - t0
            iters = min(50, max(2, int(max(1.0, 10 * rtt)
                                       / max(once, 1e-4)) + 1))
            t0 = time.perf_counter()
            for _ in range(iters):
                r = crc32c_batch(dev)
            jax.block_until_ready(r)
            dt = (time.perf_counter() - t0) / iters
            gbs = bsz * n / dt / 1e9
            if gbs > bound:
                raise ImplausibleResult(f"crc {gbs:.0f} GB/s > HBM bound")
            t0 = time.perf_counter()
            for r_ in host:
                hostcrc.crc32c(r_.tobytes())
            host_gbs = 2 * n / (time.perf_counter() - t0) / 1e9
            _log(f"crc32c device {gbs:.2f} GB/s vs host {host_gbs:.2f}")
            _emit({"stage": "crc", "crc_device_GBps": round(gbs, 3),
                   "crc_host_GBps": round(host_gbs, 3)})
        except Exception as e:  # noqa: BLE001
            _emit({"stage": "crc", "crc_error": str(e)[:200]})

    # schedule: first stage decides the kernel race, then the flagship
    # batched config runs EARLY (round-3 lost it to budget exhaustion at
    # the tail), then the winner's size curve, then block-size autotune
    # with whatever budget remains
    if stages:
        run_stage(*stages[0])
    if left() > 25:
        run_batched()
    for n, chain_len in stages[1:]:
        if left() < 30:
            _log(f"budget exhausted before stage n={n >> 20}MB — stopping")
            break
        run_stage(n, chain_len)
    if left() > 45:
        run_crc()
    if left() > 60 and "vpu" in good:
        tune_block_bm()
    _emit({"stage": "done", "backend": backend})


# ---------------------------------------------------------------------------
# Parent: spawn/kill child, merge its stream, ALWAYS print the final line.
# ---------------------------------------------------------------------------


def _run_child(budget_s: float, init_s: float) -> tuple[dict, str | None]:
    """Run the child under a wall-clock budget. Returns (merged result,
    error string or None). Kills the child if it produces nothing within
    init_s (wedged backend init) or overruns budget_s."""
    merged: dict = {}
    err: str | None = None
    env = dict(os.environ, SWTPU_BENCH_CHILD_S=str(max(budget_s - 5, 30)))
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--child"],
        stdout=subprocess.PIPE, stderr=sys.stderr, text=True, env=env,
        cwd=os.path.dirname(os.path.abspath(__file__)) or ".")
    lines: list[str] = []
    lock = threading.Lock()

    def reader() -> None:
        for line in proc.stdout:  # type: ignore[union-attr]
            with lock:
                lines.append(line)

    th = threading.Thread(target=reader, daemon=True)
    th.start()
    start = time.perf_counter()
    saw_output = False
    while True:
        alive = proc.poll() is None
        with lock:
            pending, lines = lines, []
        for line in pending:
            saw_output = True
            try:
                merged.update(json.loads(line))
            except json.JSONDecodeError:
                pass
        waited = time.perf_counter() - start
        if not alive:
            if proc.returncode != 0 and "value" not in merged:
                err = f"child exited rc={proc.returncode}"
            break
        if not saw_output and waited > init_s:
            err = f"backend init produced nothing in {init_s:.0f}s — killed"
            proc.kill()
            break
        if waited > budget_s:
            err = (None if "value" in merged
                   else f"budget {budget_s:.0f}s exceeded — killed")
            _log(f"child overran budget {budget_s:.0f}s; killing "
                 f"(have partial result: {'value' in merged})")
            proc.kill()
            break
        time.sleep(0.5)
    proc.wait(timeout=10)
    # final drain: lines still in the pipe / reader thread when the loop
    # broke (fast child, or kill paths) would otherwise be lost
    th.join(timeout=5)
    with lock:
        pending, lines = lines, []
    for line in pending:
        try:
            merged.update(json.loads(line))
        except json.JSONDecodeError:
            pass
    if err and err.startswith("child exited") and "value" in merged:
        err = None
    return merged, err


def main() -> None:
    budget = float(os.environ.get("SWTPU_BENCH_BUDGET_S", "420"))
    init_s = float(os.environ.get("SWTPU_BENCH_INIT_S", "180"))
    result = {
        "metric": "rs_10_4_encode_rebuild_GBps_per_chip",
        "value": None,
        "unit": "GB/s",
        "vs_baseline": None,
        "backend": "none",
    }
    try:
        cpu_gbs, cpu_kind = bench_cpu()
        result["cpu_baseline_GBps"] = round(cpu_gbs, 3)
        result["cpu_baseline_kind"] = cpu_kind
        _log(f"cpu baseline: {cpu_gbs:.3f} GB/s ({cpu_kind})")
    except Exception as e:  # noqa: BLE001
        cpu_gbs = 0.0
        result["cpu_error"] = f"{type(e).__name__}: {e}"[:300]
        _log(f"cpu baseline FAILED: {e}")

    try:
        dr = bench_degraded_read()
        result.update(dr)
        _log(f"degraded read p50={dr['degraded_read_p50_ms']}ms "
             f"p99={dr['degraded_read_p99_ms']}ms")
    except Exception as e:  # noqa: BLE001
        result["degraded_read_error"] = f"{type(e).__name__}: {e}"[:300]
        _log(f"degraded-read bench FAILED: {e}")

    merged: dict = {}
    err: str | None = None
    try:
        remaining = budget - (time.perf_counter() - _T0)
        merged, err = _run_child(remaining, min(init_s, remaining))
        if err and "value" not in merged:
            remaining = budget - (time.perf_counter() - _T0)
            if remaining > 90:
                _log(f"retrying child once ({err}); {remaining:.0f}s left")
                merged, err2 = _run_child(remaining,
                                          min(init_s, remaining - 30))
                err = err2 or err
    except Exception as e:  # noqa: BLE001 — the JSON line must still print
        err = f"{type(e).__name__}: {e}"[:300]

    if "device" in merged:
        result["device"] = merged["device"]
    if "paths_verified" in merged:
        result["paths_verified"] = merged["paths_verified"]
    if "value" in merged and merged.get("backend") == "tpu":
        result["backend"] = "tpu"
        result["value"] = round(float(merged["value"]), 2)
        for key in ("encode_GBps", "rebuild4_GBps", "paths",
                    "batched_encode_GBps",
                    "batched_encode_error", "crc_device_GBps",
                    "crc_host_GBps", "crc_error"):
            if key in merged:
                result[key] = merged[key]
        if cpu_gbs > 0:
            result["vs_baseline"] = round(result["value"] / cpu_gbs, 2)
    elif not err:
        err = "no device measurement"
    if err:
        result["error"] = err
    if merged.get("error"):
        result["error"] = (result.get("error", "") + "; " +
                           merged["error"]).strip("; ")
    print(json.dumps(result))
    if result["value"] is None:
        sys.exit(1)


if __name__ == "__main__":
    if "--child" in sys.argv[1:]:
        child_main()
    elif "--needle" in sys.argv[1:]:
        # needle data-plane benchmark incl. the -workers sweep
        # (tools/bench_needle.py; BENCH_NEEDLE.md documents results)
        import runpy
        sys.argv = [a for a in sys.argv if a != "--needle"]
        runpy.run_path(os.path.join(os.path.dirname(
            os.path.abspath(__file__)), "tools", "bench_needle.py"),
            run_name="__main__")
    else:
        main()
