"""Cluster-wide EC workflows: ec.encode -> distributed reads ->
shard loss -> degraded read over the network -> ec.rebuild -> ec.balance.

This is the reference's north-star flow (SURVEY.md §3.3/3.4) running on the
in-proc cluster.
"""

import asyncio
import importlib.util
import os
import random
import time

import numpy as np
import pytest
from aiohttp import web
from cluster_util import Cluster, run

from seaweedfs_tpu.ec import gf
from seaweedfs_tpu.ec import pipeline as pl
from seaweedfs_tpu.shell.env import CommandEnv
from seaweedfs_tpu.shell import ec_commands as ec
from seaweedfs_tpu.stats import metrics
from seaweedfs_tpu.util import tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


async def _fill_volume(c: Cluster, n_files: int = 40) -> list[tuple[str, str, bytes]]:
    rng = random.Random(5)
    out = []
    for i in range(n_files):
        a = await c.assign(collection="ectest")
        data = bytes(rng.getrandbits(8)
                     for _ in range(rng.randint(500, 8000)))
        st, _ = await c.put(a["fid"], a["url"], data)
        assert st == 201
        out.append((a["fid"], a["publicUrl"], data))
    return out


def test_ec_encode_spread_read_rebuild_balance(tmp_path):
    async def body():
        async with Cluster(str(tmp_path), n_servers=4) as c:
            files = await _fill_volume(c)
            await c.heartbeat_all()
            async with CommandEnv(c.master.url, c.http) as env:
                vids = sorted({int(f.split(",")[0]) for f, _, _ in files})
                res = await ec.ec_encode(env, collection="ectest", vids=vids)
                assert res, "ec.encode produced no results"
                # shards spread over all 4 servers
                assignments = res[0]["assignments"]
                assert len(assignments) == 4
                assert sum(len(s) for s in assignments.values()) == 14

            # NO heartbeat_all here: ec mount/unmount/delete push
            # immediate delta heartbeats, so reads that land ANYWHERE in
            # the cluster right after ec.encode must already succeed —
            # waiting a pulse used to hide a window where remote-shard
            # lookups found nothing and reconstruction failed with too
            # few sources (the round-4 soak's 783-bad-read bug)
            for vs in c.servers:
                assert not any(int(v.split(",")[0]) in vs.store.volumes
                               for v, _, _ in files)
            for fid, url, data in files[:10]:
                st, got = await c.get(fid, url)
                assert st == 200 and got == data, fid

            # destroy one server's shard files -> degraded read still works
            async with CommandEnv(c.master.url, c.http) as env:
                smap = await ec.ec_shard_map(env)
            vid = vids[0]
            victim_url = smap[vid]["shards"][0][0]
            import seaweedfs_tpu.ec.pipeline as pl
            victim = next(v for v in c.servers if v.url == victim_url)
            lost = sorted(victim.store.ec_volumes[vid].shards)
            base = victim._base_name(vid, "ectest")
            victim.store.unmount_ec_shards(vid)
            for sid in lost:
                os.remove(base + pl.to_ext(sid))
            await c.heartbeat_all()

            for fid, url, data in files[:5]:
                server = next(s for s in c.servers if s.url != victim_url)
                st, got = await c.get(fid, server.url)
                assert st == 200 and got == data, ("degraded", fid)

            # ec.rebuild regenerates the lost shards somewhere
            async with CommandEnv(c.master.url, c.http) as env:
                results = await ec.ec_rebuild(env, collection="ectest")
            assert any(r.get("rebuilt") for r in results), results
            await c.heartbeat_all()
            async with CommandEnv(c.master.url, c.http) as env:
                smap = await ec.ec_shard_map(env)
            assert len(smap[vid]["shards"]) == gf.TOTAL_SHARDS

            # ec.balance produces no moves or only valid ones, and reads
            # still succeed afterwards
            async with CommandEnv(c.master.url, c.http) as env:
                moves = await ec.ec_balance(env, collection="ectest")
            await c.heartbeat_all()
            for fid, url, data in files[:5]:
                st, got = await c.get(fid, url)
                assert st == 200 and got == data, ("post-balance", fid)

            # EC delete broadcasts the tombstone to every shard holder
            del_fid, del_url, _ = files[0]
            assert await c.delete(del_fid, del_url) == 200
            for vs in c.servers:
                st, _ = await c.get(del_fid, vs.url)
                assert st == 404, ("ec-delete", vs.url)
    run(body())


def test_ec_rebuild_unrepairable_reported(tmp_path):
    async def body():
        async with Cluster(str(tmp_path), n_servers=2) as c:
            files = await _fill_volume(c, n_files=10)
            await c.heartbeat_all()
            vids = sorted({int(f.split(",")[0]) for f, _, _ in files})
            async with CommandEnv(c.master.url, c.http) as env:
                await ec.ec_encode(env, collection="ectest", vids=vids)
            await c.heartbeat_all()
            vid = vids[0]
            # destroy shards until < 10 remain
            import seaweedfs_tpu.ec.pipeline as pl
            removed = 0
            for vs in c.servers:
                ev = vs.store.ec_volumes.get(vid)
                if ev is None:
                    continue
                sids = sorted(ev.shards)
                for sid in sids:
                    if removed >= 5:
                        break
                    vs.store.unmount_ec_shards(vid, [sid])
                    p = vs._base_name(vid, "ectest") + pl.to_ext(sid)
                    if os.path.exists(p):
                        os.remove(p)
                    removed += 1
            assert removed == 5
            await c.heartbeat_all()
            async with CommandEnv(c.master.url, c.http) as env:
                results = await ec.ec_rebuild(env, collection="ectest")
            assert any("unrepairable" in str(r.get("error", ""))
                       for r in results), results
    run(body())


def test_ec_decode_back_to_normal_volume(tmp_path):
    """The un-EC path (command_ec_decode.go + VolumeEcShardsToVolume):
    encode -> delete original -> lose a data shard -> ec.decode -> every
    needle reads back from the reassembled NORMAL volume."""
    async def body():
        async with Cluster(str(tmp_path), n_servers=4) as c:
            files = await _fill_volume(c, n_files=25)
            await c.heartbeat_all()
            vids = sorted({int(f.split(",")[0]) for f, _, _ in files})
            async with CommandEnv(c.master.url, c.http) as env:
                await ec.ec_encode(env, collection="ectest", vids=vids)
            await c.heartbeat_all()
            vid = vids[0]
            # original volume is gone everywhere (sealed into shards)
            for vs in c.servers:
                assert vid not in vs.store.volumes

            # delete one needle through the EC path so the decode must
            # carry the tombstone into the rebuilt .idx
            del_fid, del_url, _ = files[-1]
            assert await c.delete(del_fid, del_url) == 200

            # lose one server's shards entirely: decode must gather +
            # reconstruct before reassembly
            import seaweedfs_tpu.ec.pipeline as pl
            async with CommandEnv(c.master.url, c.http) as env:
                smap = await ec.ec_shard_map(env)
            victim_url = smap[vid]["shards"][0][0]
            victim = next(v for v in c.servers if v.url == victim_url)
            lost = sorted(victim.store.ec_volumes[vid].shards)
            base = victim._base_name(vid, "ectest")
            victim.store.unmount_ec_shards(vid)
            for sid in lost:
                os.remove(base + pl.to_ext(sid))
            await c.heartbeat_all()

            async with CommandEnv(c.master.url, c.http) as env:
                results = await ec.ec_decode(env, collection="ectest",
                                             vids=[vid])
            assert results and "error" not in results[0], results
            target_url = results[0]["node"]
            await c.heartbeat_all()

            # the volume is back as a NORMAL volume on the target and the
            # EC shards are gone cluster-wide
            target = next(v for v in c.servers if v.url == target_url)
            assert vid in target.store.volumes
            for vs in c.servers:
                assert vid not in vs.store.ec_volumes
                b = vs._base_name(vid, "ectest")
                if b:
                    assert not any(
                        os.path.exists(b + pl.to_ext(s))
                        for s in range(14)), vs.url

            # every live needle reads back through the normal read path
            for fid, url, data in files[:-1]:
                if int(fid.split(",")[0]) != vid:
                    continue
                st, got = await c.get(fid, target.url)
                assert st == 200 and got == data, fid
            # the EC-deleted needle stays deleted in the rebuilt volume
            st, _ = await c.get(del_fid, target.url)
            assert st == 404
    run(body())


def test_ec_verify_scrub_detects_bit_rot(tmp_path):
    """ec.verify: clean volumes scrub clean; a single flipped byte in
    one shard file is reported as a corrupt window."""
    async def body():
        async with Cluster(str(tmp_path), n_servers=4) as c:
            files = await _fill_volume(c, n_files=20)
            await c.heartbeat_all()
            async with CommandEnv(c.master.url, c.http) as env:
                vids = sorted({int(f.split(",")[0]) for f, _, _ in files})
                await ec.ec_encode(env, collection="ectest", vids=vids)
                await c.heartbeat_all()
                reports = await ec.ec_verify(env, collection="ectest")
                assert reports, "no EC volumes scrubbed"
                for r in reports:
                    assert r.get("bad_windows") == [], r
                    assert r["windows"] >= 1

                # flip one byte in one mounted shard file, then re-scrub
                vid = reports[0]["volume"]
                victim = None
                for vs in c.servers:
                    ev = vs.store.ec_volumes.get(vid)
                    if ev and ev.shards:
                        sid = next(iter(ev.shards))
                        victim = ev.base_name + f".ec{sid:02d}"
                        break
                assert victim and os.path.getsize(victim) > 0
                with open(victim, "r+b") as f:
                    f.seek(os.path.getsize(victim) // 2)
                    b = f.read(1)
                    f.seek(-1, 1)
                    f.write(bytes([b[0] ^ 0xFF]))
                reports = await ec.ec_verify(env, volume_id=vid)
                assert len(reports) == 1
                # the scrubbing node may or may not be the corrupted
                # holder; verify through the node that holds the flipped
                # shard to pin detection
                bad = reports[0].get("bad_windows")
                if not bad:
                    for vs in c.servers:
                        ev = vs.store.ec_volumes.get(vid)
                        if ev and ev.shards:
                            rep = ev.verify_parity()
                            if rep["bad_windows"]:
                                bad = rep["bad_windows"]
                                break
                assert bad, "flipped byte not detected by any holder"
    run(body())


# ---------------------------------------------------------------------------
# a volume server lost from a four-server EC cluster (ec.rebuild's gather)
# ---------------------------------------------------------------------------


def _reference():
    """benchmark/reference.py, the plain RS(10,4) and stripe layout that
    imports nothing of the program, loaded by path."""
    path = os.path.join(ROOT, "benchmark", "reference.py")
    spec = importlib.util.spec_from_file_location("ec_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _reference_shards(dat: bytes, large: int, small: int) -> list[bytes]:
    """The 14 shard files the reference stripes and codes from `dat`."""
    ref = _reference()
    coder = ref.Coder()
    arr = np.frombuffer(dat, np.uint8)
    out = [bytearray() for _ in range(gf.TOTAL_SHARDS)]
    for pos, block, _ in ref.stripe_rows(len(dat), large, small):
        got = coder.shards(list(range(gf.TOTAL_SHARDS)),
                           ref.row_streams(arr, pos, block, 1))
        for sid in range(gf.TOTAL_SHARDS):
            out[sid] += got[sid].tobytes()
    return [bytes(b) for b in out]


def _holdings(c: Cluster, vid: int) -> dict:
    """Live server -> the shard ids of `vid` mounted on it."""
    return {vs: sorted(vs.store.ec_volumes[vid].shards)
            for vs in c.servers if vid in vs.store.ec_volumes}


async def _encoded_cluster(c: Cluster, tmp_path):
    """Fill, link each volume's .dat aside, ec.encode (spread 4/4/3/3
    over the four servers) and keep every shard file as encode wrote
    it. Returns (files, vids, dats, shards by (vid, sid))."""
    files = await _fill_volume(c)
    await c.heartbeat_all()
    vids = sorted({int(f.split(",")[0]) for f, _, _ in files})
    dats = {}
    for vid in vids:
        holder = next(vs for vs in c.servers if vid in vs.store.volumes)
        dats[vid] = str(tmp_path / f"ref_{vid}.dat")
        os.link(holder._base_name(vid, "ectest") + ".dat", dats[vid])
    async with CommandEnv(c.master.url, c.http) as env:
        await ec.ec_encode(env, collection="ectest", vids=vids)
    shards = {}
    for vid in vids:
        spread = _holdings(c, vid)
        assert sorted(len(s) for s in spread.values()) == [3, 3, 4, 4]
        for vs, sids in spread.items():
            for sid in sids:
                with open(vs._base_name(vid, "ectest") + pl.to_ext(sid),
                          "rb") as f:
                    shards[vid, sid] = f.read()
    return files, vids, dats, shards


async def _lose_server(c: Cluster, victim) -> None:
    """Stop `victim` and wait until the master has reaped it (3 pulses
    without a heartbeat)."""
    await victim.stop()
    c.servers.remove(victim)
    deadline = time.monotonic() + 10
    async with CommandEnv(c.master.url, c.http) as env:
        while any(n["url"] == victim.url for n in await env.list_nodes()):
            assert time.monotonic() < deadline, "master never reaped it"
            await asyncio.sleep(c.pulse)


def _pick(c: Cluster, vid: int, data: int, parity: int):
    """A server holding `data` data and `parity` parity shards of vid."""
    return next(vs for vs, sids in _holdings(c, vid).items()
                if sum(s < gf.DATA_SHARDS for s in sids) == data
                and sum(s >= gf.DATA_SHARDS for s in sids) == parity)


@pytest.mark.parametrize("loss", ["server_3data_1parity",
                                  "server_2data_1parity", "parity_only"])
def test_ec_rebuild_restores_a_lost_server(tmp_path, loss):
    """(a) the server holding three data shards and one parity shard is
    stopped and reaped; (b) one holding two data and one parity; (c) the
    four parity shards alone are deleted (BASELINE config 3). ec.rebuild
    gathers the survivors on one server and restores 14 of 14: every
    shard equals the file ec.encode wrote and the reference's RS(10,4)
    of the .dat, no borrowed copy stays on the rebuilder, and every
    needle reads back through a live server."""
    async def body():
        async with Cluster(str(tmp_path), n_servers=4) as c:
            files, vids, dats, shards = await _encoded_cluster(c, tmp_path)
            vid = vids[0]
            lost: dict[int, list[int]] = {}
            if loss == "parity_only":
                for vs, sids in _holdings(c, vid).items():
                    par = [s for s in sids if s >= gf.DATA_SHARDS]
                    vs.store.unmount_ec_shards(vid, par)
                    for sid in par:
                        os.remove(vs._base_name(vid, "ectest")
                                  + pl.to_ext(sid))
                lost[vid] = list(range(gf.DATA_SHARDS, gf.TOTAL_SHARDS))
                await c.heartbeat_all()
            else:
                data = 3 if loss == "server_3data_1parity" else 2
                victim = _pick(c, vid, data, 1)
                for v in vids:
                    lost[v] = _holdings(c, v).get(victim, [])
                await _lose_server(c, victim)
            async with CommandEnv(c.master.url, c.http) as env:
                results = await ec.ec_rebuild(env, collection="ectest")
            assert {r["volume"]: r["rebuilt"] for r in results} == \
                {v: s for v, s in lost.items() if s}, results
            await c.heartbeat_all()
            async with CommandEnv(c.master.url, c.http) as env:
                smap = await ec.ec_shard_map(env)
            by_url = {vs.url: vs for vs in c.servers}
            for v in vids:
                assert sorted(smap[v]["shards"]) == \
                    list(range(gf.TOTAL_SHARDS)), v
                with open(dats[v], "rb") as f:
                    want = _reference_shards(f.read(), c.ec_large_block,
                                             c.ec_small_block)
                for sid, urls in smap[v]["shards"].items():
                    for url in urls:
                        base = by_url[url]._base_name(v, "ectest")
                        with open(base + pl.to_ext(sid), "rb") as f:
                            got = f.read()
                        assert got == shards[v, sid], (v, sid, url)
                        assert got == want[sid], (v, sid, url)
                # on disk, each live server holds what the map places
                # there: the borrowed inputs went
                for vs in c.servers:
                    base = vs._base_name(v, "ectest")
                    on_disk = [] if base is None else \
                        pl.present_shards(base)
                    placed = [s for s, urls in smap[v]["shards"].items()
                              if vs.url in urls]
                    assert on_disk == sorted(placed), vs.url
            live = c.servers[0].url
            for fid, _, data in files:
                st, got = await c.get(fid, live)
                assert st == 200 and got == data, fid
    run(body())


def test_repair_counters_match_the_bytes_moved(tmp_path):
    """One ec.rebuild after a server loss: the repair counter's
    `copied` delta is the bytes of every file the rebuilder fetched
    (each survivor it lacked, with the .ecx), equal to the sum of the
    `ec`/`fetch` spans' bytes, and its `rebuilt` delta the restored
    shards' bytes."""
    def counter(stage: str) -> float:
        return metrics.REGISTRY.get_sample_value(
            "SeaweedFS_ec_repair_bytes_total", {"stage": stage}) or 0.0

    async def body():
        tracing.init(sample=1.0, slow_ms=0.0)
        async with Cluster(str(tmp_path), n_servers=4) as c:
            _, vids, _, shards = await _encoded_cluster(c, tmp_path)
            assert len(vids) == 1
            vid = vids[0]
            victim = _pick(c, vid, 3, 1)
            await _lose_server(c, victim)
            with tracing._lock:
                seen = {s.span_id for s in tracing._ring}
            before = {st: counter(st) for st in ("copied", "rebuilt")}
            async with CommandEnv(c.master.url, c.http) as env:
                [res] = await ec.ec_rebuild(env, collection="ectest")
            rebuilder = next(vs for vs in c.servers
                             if vs.url == res["node"])
            base = rebuilder._base_name(vid, "ectest")
            shard = len(shards[vid, 0])
            # survivors it already held (mounted now, less the restored)
            own = set(rebuilder.store.ec_volumes[vid].shards) \
                - set(res["rebuilt"])
            copies = gf.DATA_SHARDS - len(own)
            assert copies > 0
            ecx = os.path.getsize(base + ".ecx")
            want_copied = copies * (shard + ecx)
            assert counter("copied") - before["copied"] == want_copied
            assert counter("rebuilt") - before["rebuilt"] == \
                len(res["rebuilt"]) * shard == 4 * shard
            with tracing._lock:
                fetched = [s for s in tracing._ring
                           if s.span_id not in seen
                           and (s.tier, s.op) == ("ec", "fetch")]
            assert sum(s.nbytes for s in fetched) == want_copied
            assert sum(s.attrs["ext"] not in (".ecx", ".ecj")
                       for s in fetched) == copies
    run(body())


def test_ec_copy_from_a_source_that_dies_mid_body(tmp_path):
    """/admin/ec/copy from a source that sends a quarter of a shard
    file's declared length and drops the connection: the copy fails and
    leaves no file at the shard's name (nor its temp file)."""
    async def die_mid_body(req: web.Request) -> web.StreamResponse:
        resp = web.StreamResponse(headers={"Content-Length": str(1 << 20)})
        await resp.prepare(req)
        await resp.write(b"\x5a" * (256 << 10))
        req.transport.close()
        return resp

    async def body():
        app = web.Application()
        app.router.add_get("/admin/file", die_mid_body)
        runner = web.AppRunner(app)
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        port = site._server.sockets[0].getsockname()[1]
        try:
            async with Cluster(str(tmp_path), n_servers=1) as c:
                vs = c.servers[0]
                async with c.http.post(
                        f"http://{vs.url}/admin/ec/copy",
                        params={"volume": "7", "shards": "3",
                                "source": f"127.0.0.1:{port}"}) as resp:
                    assert resp.status == 502, await resp.text()
                left = sorted(os.listdir(vs.store.dirs[0]))
                assert not [n for n in left if n.startswith("7.ec03")], left
        finally:
            await runner.cleanup()
    run(body())
