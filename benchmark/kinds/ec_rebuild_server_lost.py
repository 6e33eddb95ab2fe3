"""One volume server of four lost with its shards, and shell ec.rebuild
restoring them, back to back, after one untimed op.

Set-up starts the configuration's other volume servers beside the
harness's, in this process and from the same parsed volume flags as
cluster.py starts the first; fills volume 1 on the harness's server
(keeping a link to its .dat, as ec_rebuild.py does); runs shell
ec.encode, which spreads the 14 shards 4/4/3/3 by free slots; records
every shard's SHA-1; then stops the added server whose shards are the
traffic's `lost_holds` mix (three data, one parity) and waits until the
master has reaped it (three pulses without a heartbeat).

An op is the restore after that loss: ec.rebuild copies onto the freest
server every survivor it lacks (/admin/ec/copy), rebuilds the missing
shards in one /admin/ec/rebuild and drops the borrowed copies. From the
second op on, it first unmounts and deletes the previous op's restored
shards on the rebuilder, which gives back the state after the loss.

`check` runs after the harness has stopped its cluster, so the window
records off the clock what it needs (the master's shard map after the
last op) and stops the kind's own servers, outside the timed span.
"""

from __future__ import annotations

import asyncio
import os
import sys
import time

import jax

import generator as g
import reference
import stats
from seaweedfs_tpu.server.volume_server import VolumeServer
from seaweedfs_tpu.shell import ec_commands as ec
from seaweedfs_tpu.storage.store import Store
from trace_reduce import PAUSE

REPAIR = "SeaweedFS_ec_repair_bytes_total"
STAGES = ("copied", "rebuilt")


class Traffic(g.Kind):

    VID = 1

    async def setup(self) -> None:
        self.live: dict[str, VolumeServer] = {self.cl.vs.url: self.cl.vs}
        self.dirs = {self.cl.vs.url: self.cl.dir}
        self.added: list[VolumeServer] = []
        self.rebuilder: str | None = None
        self.earlier: list[dict] = []
        self.copies: list[int] = []
        self.final_map: dict = {}
        try:
            await self._setup()
        except BaseException:
            await self.stop_servers()
            raise
        try:
            await self.op(-1)  # warm-up: the same op, untimed
        except Exception as e:  # noqa: BLE001 — the window's ops fail the
            # same way and are counted there
            print(f"warm-up op failed: {e!r}", file=sys.stderr, flush=True)

    async def _setup(self) -> None:
        vid = self.VID
        for i in range(1, self.cfg["volume_servers"]):
            await self.start_server(os.path.join(self.tmp, f"volume{i}"))
        await self.fill([vid])
        self.ref_dat = os.path.join(self.tmp, "ref.dat")
        os.link(self.cl.base(vid) + ".dat", self.ref_dat)
        async with self.cl.env() as env:
            await ec.ec_encode(env, vids=[vid])
        for vs in self.live.values():
            await vs.heartbeat_once()
        self.holder = {sid: urls[0] for sid, urls in
                       (await self.shard_map())["shards"].items()}
        if sorted(self.holder) != list(range(g.SHARDS)):
            raise RuntimeError(f"ec.encode placed {sorted(self.holder)}")
        self.shard = os.path.getsize(self.path(self.holder[0], 0))
        self.setup_digests = self.sid_digests(
            {sid: self.path(url, sid) for sid, url in self.holder.items()})
        victim = self.pick_lost()
        self.lost = sorted(s for s, u in self.holder.items() if u == victim)
        self.survivors = [s for s in range(g.SHARDS) if s not in self.lost]
        await self.lose(victim)

    async def start_server(self, d: str) -> None:
        """A volume server as cluster.py starts the harness's: the
        configuration's volume flags as the CLI parsed them, its stripe
        blocks, the same master."""
        args, cfg = self.cl.args, self.cfg
        store = Store([d], max_volume_counts=[int(args.max)],
                      compaction_bytes_per_second=args.compactionMBps
                      * 1024 * 1024,
                      index_type=args.index,
                      needle_cache_bytes=args.cache_mem * 1024 * 1024,
                      group_commit_window=args.groupcommit_ms / 1000.0,
                      fsync=args.fsync,
                      ec_small_recover_bytes=args.ec_smallrecover,
                      ec_large_block=cfg["ec_large_block"],
                      ec_small_block=cfg["ec_small_block"])
        vs = VolumeServer(store, self.cl.master.url, port=0,
                          batch_max=args.batch_max,
                          scrub_mbps=args.scrub_mbps,
                          scrub_interval=args.scrub_interval,
                          scrub_pause_ms=args.scrub_pause_ms,
                          scrub_batch=args.scrub_batch)
        self.added.append(vs)
        await vs.start()
        await vs.heartbeat_once()
        self.live[vs.url] = vs
        self.dirs[vs.url] = d

    def pick_lost(self) -> str:
        """The added server holding the traffic's mix of data and parity
        shards; of several, the one holding the highest shard id."""
        want = self.traffic["lost_holds"]
        held: dict[str, list[int]] = {}
        for sid, url in self.holder.items():
            held.setdefault(url, []).append(sid)
        fit = [u for u, sids in held.items()
               if u != self.cl.vs.url
               and sum(s < reference.DATA for s in sids) == want["data"]
               and sum(s >= reference.DATA for s in sids) == want["parity"]]
        if not fit:
            raise RuntimeError(f"no server holds {want}: {held}")
        return max(fit, key=lambda u: max(held[u]))

    async def lose(self, url: str) -> None:
        """Stop the server at `url` and wait until the master has reaped
        it (Topology.dead_nodes: three pulses without a heartbeat)."""
        vs = self.live.pop(url)
        await vs.stop()
        self.added.remove(vs)
        pulse = self.cl.master.topo.pulse_seconds
        deadline = time.monotonic() + 10 * pulse + 10
        async with self.cl.env() as env:
            while any(n["url"] == url for n in await env.list_nodes()):
                if time.monotonic() > deadline:
                    raise RuntimeError(f"the master never reaped {url}")
                await asyncio.sleep(0.25)

    async def stop_servers(self) -> None:
        while self.added:
            await self.added.pop().stop()

    async def shard_map(self) -> dict:
        async with self.cl.env() as env:
            return (await ec.ec_shard_map(env)).get(self.VID, {"shards": {}})

    def path(self, url: str, sid: int) -> str:
        return os.path.join(self.dirs[url], str(self.VID)) + g.ext(sid)

    @staticmethod
    def sid_digests(paths: dict[int, str]) -> dict[int, bytes | None]:
        got = g.digests(list(paths.values()))
        return {sid: got[p] for sid, p in paths.items()}

    async def op(self, i: int) -> int:
        vid, shards = str(self.VID), ",".join(map(str, self.lost))
        if self.rebuilder is not None:
            vs = self.live[self.rebuilder]
            async with self.cl.env() as env:
                await env.node_post(vs.url, "/admin/ec/unmount",
                                    volume=vid, shards=shards)
                await env.node_post(vs.url, "/admin/ec/delete_shards",
                                    volume=vid, shards=shards)
            await vs.heartbeat_once()
        async with self.cl.env() as env:
            res = await ec.ec_rebuild(env)
        if [r.get("rebuilt") for r in res] != [self.lost]:
            raise RuntimeError(f"ec.rebuild: {res}")
        self.rebuilder = res[0]["node"]
        self.copies.append(sum(self.holder[s] != self.rebuilder
                               for s in self.survivors))
        return reference.DATA * self.shard

    def restored(self) -> dict[int, str]:
        url = self.rebuilder or self.cl.vs.url
        return {sid: self.path(url, sid) for sid in self.lost}

    def keep_digests(self, i: int) -> None:
        self.earlier.append(self.sid_digests(self.restored()))

    @staticmethod
    def repair_bytes() -> dict | None:
        """The program's repair counter by stage, or None where the
        program does not count it."""
        try:
            from seaweedfs_tpu.stats import metrics
            reg = metrics.REGISTRY
        except (ImportError, AttributeError):
            return None
        got = {st: reg.get_sample_value(REPAIR, {"stage": st})
               for st in STAGES}
        return None if None in got.values() else got

    async def window(self, seconds: float) -> dict:
        try:
            before = self.repair_bytes()
            w = await self.serial_window(self.op, seconds, self.keep_digests)
            after = self.repair_bytes()
            # off the clock: what check needs from the live cluster
            t = time.perf_counter()
            with jax.profiler.TraceAnnotation(PAUSE):
                self.final_map = await self.shard_map()
                await self.stop_servers()
            self.paused += time.perf_counter() - t
        finally:
            await self.stop_servers()
        ops = w["ops"]
        work = {"ops": len(ops),
                "hbm_bytes": len(ops) * self.shard
                * (reference.DATA + len(self.lost)),
                "seconds": w["span"],
                "copies": sum(self.copies[len(self.copies) - len(ops):])}
        if before is not None and after is not None:
            work["repair_bytes"] = {st: after[st] - before[st]
                                    for st in STAGES}
        return {"t0": w["t0"], "t1": w["t1"],
                "e2e": {"rebuild_GBps": stats.rate(
                    sum(o[2] for o in ops), w["span"]) / 1e9},
                "work": work}

    def check(self) -> list[tuple[str, float, float]]:
        restored = self.restored()
        wrong = g.stripe_mismatch(self.ref_dat, self.cfg,
                                  {sid: [p] for sid, p in restored.items()})
        earlier = g.files_differing(self.earlier,
                                    self.sid_digests(restored))
        placed = self.final_map.get("shards", {})
        now = self.sid_digests({s: self.path(self.holder[s], s)
                                for s in self.survivors})
        survivors = sum(now[s] != self.setup_digests[s]
                        or self.holder[s] not in placed.get(s, [])
                        for s in self.survivors)
        # shard files on the rebuilder beyond what it held and restored
        url = self.rebuilder or self.cl.vs.url
        base = os.path.join(self.dirs[url], str(self.VID))
        keep = {s for s, u in self.holder.items() if u == url} \
            | set(self.lost)
        borrowed = sum(os.path.exists(base + g.ext(s))
                       for s in range(g.SHARDS) if s not in keep)
        return [("shard_bytes_wrong", wrong, 0),
                ("earlier_files_differing", earlier, 0),
                ("survivors_differing", survivors, 0),
                ("shards_missing", g.SHARDS - len(placed), 0),
                ("borrowed_copies_left", borrowed, 0),
                ("ops_failed", self.failed, 0)]


def copy_short():
    """Control: the first survivor each ec.rebuild copies lands 1 MB
    short (cut once the fetch has renamed it into place)."""
    import faults as f
    armed = [False]

    def rebuild(old):
        async def run(*a, **k):
            armed[0] = True
            return await old(*a, **k)
        return run

    def fetch(old):
        async def run(self, source, vid, collection, ext, base):
            err = await old(self, source, vid, collection, ext, base)
            if err is None and armed[0] and ext not in (".ecx", ".ecj"):
                armed[0] = False
                p = base + ext
                os.truncate(p, max(0, os.path.getsize(p) - (1 << 20)))
            return err
        return run
    undo = [f._patch(ec, "ec_rebuild", rebuild),
            f._patch(VolumeServer, "_fetch_shard_file", fetch)]
    return lambda: [u() for u in undo]


def rebuild_without_parity():
    """Control: the rebuild regenerates only the data shards among the
    missing ones and reports all of them rebuilt."""
    import faults as f
    from seaweedfs_tpu.ec import pipeline

    def wrap(old):
        def run(base, *a, targets=None, **k):
            missing = list(targets) if targets is not None else [
                s for s in range(g.SHARDS)
                if not os.path.exists(base + pipeline.to_ext(s))]
            old(base, *a, targets=[s for s in missing
                                   if s < reference.DATA], **k)
            return missing
        return run
    return f._patch(pipeline, "rebuild_ec_files", wrap)


def faults() -> dict:
    import faults as f
    return {"answer_altered": f.answer_altered, "half_batch": f.half_batch,
            "rebuild_unchanged": f.rebuild_unchanged,
            "second_call_altered": f.second_call_altered(
                "rebuild_ec_files",
                lambda rebuilt, base, *a, **k: base + g.ext(rebuilt[0])),
            "rebuild_short": f.rebuild_short,
            "copy_short": copy_short,
            "rebuild_without_parity": rebuild_without_parity}
