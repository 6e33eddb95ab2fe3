"""Storage engine unit tests: needle format, maps, volume lifecycle.

Modeled on the reference's round-trip tests
(storage/needle/needle_read_write_test.go, file_id_test.go,
volume_ttl_test.go, storage/volume_vacuum_test.go patterns).
"""

import os

import pytest

from seaweedfs_tpu.storage import types as t
from seaweedfs_tpu.storage.needle import (
    FLAG_HAS_NAME, CrcMismatch, Needle)
from seaweedfs_tpu.storage.needle_map import (
    MemoryNeedleMap, SortedFileNeedleMap, write_sorted_index)
from seaweedfs_tpu.storage.super_block import ReplicaPlacement, SuperBlock
from seaweedfs_tpu.storage.volume import (
    AlreadyDeleted, NotFound, Volume, VolumeError)
from seaweedfs_tpu.util import crc32c


def test_crc32c_known_vectors():
    # RFC 3720 test vector: 32 bytes of zeros -> 0x8A9136AA
    assert crc32c.crc32c(b"\x00" * 32) == 0x8A9136AA
    assert crc32c.crc32c(b"123456789") == 0xE3069283
    # python fallback must agree with native
    assert crc32c._crc32c_py(0, b"123456789") == 0xE3069283


def test_needle_roundtrip_v3():
    n = Needle(cookie=0x12345678, id=0xABCDEF, data=b"hello world",
               name=b"x.txt", mime=b"text/plain", last_modified=1700000000,
               ttl=t.TTL.parse("3h"), pairs=b'{"a":"b"}')
    blob = n.to_bytes(t.VERSION3)
    assert len(blob) % 8 == 0
    m = Needle.from_bytes(blob, t.VERSION3)
    assert (m.cookie, m.id, m.data) == (n.cookie, n.id, b"hello world")
    assert m.name == b"x.txt"
    assert m.mime == b"text/plain"
    assert m.last_modified == 1700000000
    assert m.ttl == t.TTL.parse("3h")
    assert m.pairs == b'{"a":"b"}'
    assert m.append_at_ns == n.append_at_ns


def test_needle_roundtrip_versions():
    for version in (t.VERSION1, t.VERSION2, t.VERSION3):
        n = Needle(cookie=7, id=42, data=b"payload")
        m = Needle.from_bytes(n.to_bytes(version), version)
        assert m.data == b"payload", version


def test_needle_crc_check():
    n = Needle(cookie=1, id=2, data=b"data")
    blob = bytearray(n.to_bytes(t.VERSION3))
    blob[t.NEEDLE_HEADER_SIZE + 4] ^= 0xFF  # corrupt data byte
    with pytest.raises(CrcMismatch):
        Needle.from_bytes(bytes(blob), t.VERSION3)


def test_needle_empty_data_tombstone_shape():
    n = Needle(cookie=1, id=2, data=b"")
    blob = n.to_bytes(t.VERSION3)
    m = Needle.from_bytes(blob, t.VERSION3)
    assert m.size == 0 and m.data == b""


def test_file_id_roundtrip():
    fid = t.FileId(3, 0x01637037, 0xD6000000)
    s = str(fid)
    assert s.startswith("3,")
    back = t.FileId.parse(s)
    assert back == fid
    # known reference formatting: leading zero bytes of the key stripped
    assert t.FileId.parse("3,01637037d6aabbcc") is not None
    with pytest.raises(ValueError):
        t.FileId.parse("nocomma")


def test_ttl_parse_format():
    assert t.TTL.parse("") == t.TTL()
    assert str(t.TTL.parse("5d")) == "5d"
    assert t.TTL.parse("90") == t.TTL(90, t.TTL_MINUTE)
    tt = t.TTL.parse("7M")
    assert t.TTL.from_uint32(tt.to_uint32()) == tt
    assert t.TTL.parse("2w").minutes == 2 * 10080


def test_replica_placement():
    rp = ReplicaPlacement.parse("012")
    assert rp.copy_count == 4
    assert str(ReplicaPlacement.from_byte(rp.to_byte())) == "012"
    with pytest.raises(ValueError):
        ReplicaPlacement.parse("9zz")


def test_super_block_roundtrip():
    sb = SuperBlock(version=3, replica_placement=ReplicaPlacement.parse("001"),
                    ttl=t.TTL.parse("1h"), compaction_revision=5)
    back = SuperBlock.from_bytes(sb.to_bytes())
    assert back == sb


def test_memory_needle_map_idx_replay(tmp_path):
    idx = str(tmp_path / "1.idx")
    nm = MemoryNeedleMap(idx)
    nm.put(1, 8, 100)
    nm.put(2, 120, 50)
    nm.put(1, 256, 120)   # overwrite
    nm.delete(2, 512)
    nm.close()

    nm2 = MemoryNeedleMap(idx)
    assert nm2.get(1).offset == 256
    assert nm2.get(1).size == 120
    assert nm2.get(2).size == t.TOMBSTONE_FILE_SIZE  # tombstone retained
    assert nm2.file_count == 2
    assert nm2.deleted_count == 2  # one overwrite + one delete
    nm2.destroy()
    assert not os.path.exists(idx)


def test_sorted_file_map(tmp_path):
    path = str(tmp_path / "1.sdx")
    entries = [(k, k * 64, 10 + k) for k in range(0, 200, 3)]
    write_sorted_index(entries, path)
    sm = SortedFileNeedleMap(path)
    assert sm.get(3).size == 13
    assert sm.get(198).offset == 198 * 64
    assert sm.get(4) is None
    sm.close()


_SORTED_KEYS = list(range(5, 2000, 7))


@pytest.mark.parametrize("case,probes", [
    ("first", [_SORTED_KEYS[0]]),
    ("last", [_SORTED_KEYS[-1]]),
    ("absent", [0, 1, 2000, (1 << 64) - 1]),
    ("between", [6, 11, 13, _SORTED_KEYS[-1] - 1]),
    ("empty", [0, 5, (1 << 64) - 1]),
    ("mark_deleted", [12, _SORTED_KEYS[0], _SORTED_KEYS[-1]]),
    ("rewritten", [_SORTED_KEYS[0], _SORTED_KEYS[-1]]),
])
def test_sorted_file_map_over_mmap(tmp_path, case, probes):
    """Lookups through the mapped index agree with a plain bisect over
    the entries: first, last, absent and between-keys ids, an empty
    .ecx, a tombstone written through the file, which the next get_raw
    sees through the map, and an index rewritten (here emptied) while
    mapped, which the open map still reads as it was."""
    import bisect
    path = str(tmp_path / "1.ecx")
    keys = [] if case == "empty" else _SORTED_KEYS
    entries = {k: (k * 64, 10 + k) for k in keys}
    write_sorted_index([(k, *v) for k, v in entries.items()], path)

    def plain(key):
        i = bisect.bisect_left(keys, key)
        return entries[key] if i < len(keys) and keys[i] == key else None

    sm = SortedFileNeedleMap(path, writable=True)
    try:
        assert sm.count == len(keys)
        if case == "mark_deleted":
            assert not sm.mark_deleted(probes[0] + 1)   # absent key
            for key in probes:
                assert sm.mark_deleted(key)
                entries[key] = (entries[key][0], t.TOMBSTONE_FILE_SIZE)
                assert sm.get(key) is None
        if case == "rewritten":
            write_sorted_index([], path)
        for key in probes + keys:
            assert sm.get_raw(key) == plain(key), key
    finally:
        sm.close()
    if case == "mark_deleted":
        reopened = SortedFileNeedleMap(path)
        assert [reopened.get_raw(k) for k in keys] == \
            [plain(k) for k in keys]
        reopened.close()


def test_volume_write_read_delete(tmp_path):
    v = Volume(str(tmp_path), "", 1)
    n = Needle(cookie=0xAA, id=1, data=b"first")
    off, size = v.write_needle(n)
    assert off == 8  # right after superblock
    got = v.read_needle(1, cookie=0xAA)
    assert got.data == b"first"

    # overwrite with same cookie
    v.write_needle(Needle(cookie=0xAA, id=1, data=b"second"))
    assert v.read_needle(1).data == b"second"

    # overwrite with wrong cookie rejected
    with pytest.raises(VolumeError):
        v.write_needle(Needle(cookie=0xBB, id=1, data=b"evil"))

    # delete -> AlreadyDeleted on read
    reclaimed = v.delete_needle(Needle(cookie=0xAA, id=1))
    assert reclaimed > 0
    with pytest.raises(AlreadyDeleted):
        v.read_needle(1)
    with pytest.raises(NotFound):
        v.read_needle(999)
    v.close()


def test_volume_reload_after_crash(tmp_path):
    v = Volume(str(tmp_path), "c", 7)
    for i in range(10):
        v.write_needle(Needle(cookie=i, id=i, data=bytes([i]) * (i + 1)))
    v.delete_needle(Needle(cookie=3, id=3))
    v.close()

    v2 = Volume(str(tmp_path), "c", 7, create_if_missing=False)
    assert v2.read_needle(5).data == b"\x05" * 6
    with pytest.raises(AlreadyDeleted):
        v2.read_needle(3)
    st = v2.stat()
    assert st.file_count == 10
    assert st.deleted_count == 1
    v2.close()


def test_volume_torn_tail_truncated(tmp_path):
    v = Volume(str(tmp_path), "", 9)
    v.write_needle(Needle(cookie=1, id=1, data=b"good"))
    end = v.data_size()
    v.close()
    # simulate a torn write past the last indexed needle
    with open(str(tmp_path / "9.dat"), "ab") as f:
        f.write(b"\xde\xad\xbe\xef" * 3)
    v2 = Volume(str(tmp_path), "", 9, create_if_missing=False)
    assert v2.data_size() == end
    assert v2.read_needle(1).data == b"good"
    v2.close()


def test_volume_scan(tmp_path):
    v = Volume(str(tmp_path), "", 11)
    for i in range(1, 4):
        v.write_needle(Needle(cookie=i, id=i, data=b"x" * i))
    v.delete_needle(Needle(cookie=2, id=2))
    seen = []
    v.scan(lambda n, off: seen.append((n.id, n.size, off)))
    assert len(seen) == 4  # 3 writes + 1 tombstone
    assert seen[-1][1] == 0  # tombstone has size 0
    v.close()


def test_volume_rewrite_after_delete(tmp_path):
    v = Volume(str(tmp_path), "", 21)
    v.write_needle(Needle(cookie=1, id=7, data=b"one"))
    v.delete_needle(Needle(cookie=1, id=7))
    # re-writing a deleted id must succeed, even with a new cookie
    v.write_needle(Needle(cookie=2, id=7, data=b"two"))
    assert v.read_needle(7, cookie=2).data == b"two"
    v.close()


def test_volume_reopen_all_deleted(tmp_path):
    v = Volume(str(tmp_path), "", 22)
    v.write_needle(Needle(cookie=1, id=1, data=b"x"))
    v.delete_needle(Needle(cookie=1, id=1))
    v.close()
    v2 = Volume(str(tmp_path), "", 22, create_if_missing=False)
    with pytest.raises(AlreadyDeleted):
        v2.read_needle(1)
    v2.close()


def test_volume_reopen_keeps_trailing_tombstone(tmp_path):
    v = Volume(str(tmp_path), "", 23)
    v.write_needle(Needle(cookie=1, id=1, data=b"a"))
    v.write_needle(Needle(cookie=2, id=2, data=b"b"))
    v.delete_needle(Needle(cookie=1, id=1))
    end = v.data_size()
    v.close()
    v2 = Volume(str(tmp_path), "", 23, create_if_missing=False)
    assert v2.data_size() == end  # tombstone record NOT truncated
    records = []
    v2.scan(lambda n, off: records.append((n.id, n.size)))
    assert records[-1] == (1, 0)
    v2.close()


def test_needle_field_limits():
    from seaweedfs_tpu.storage.needle import NeedleError
    with pytest.raises(NeedleError):
        Needle(cookie=1, id=1, data=b"x", mime=b"m" * 256).to_bytes()
    with pytest.raises(NeedleError):
        Needle(cookie=1, id=1, data=b"x", pairs=b"p" * 65536).to_bytes()
    # name is clamped, not an error (reference truncates at 255)
    n = Needle(cookie=1, id=1, data=b"x", name=b"n" * 300)
    m = Needle.from_bytes(n.to_bytes(), 3)
    assert len(m.name) == 255


def test_volume_ttl_expiry(tmp_path):
    v = Volume(str(tmp_path), "", 13, ttl=t.TTL.parse("1m"))
    n = Needle(cookie=1, id=1, data=b"z", last_modified=100)  # long ago
    n.set_flag(0x08)  # has last modified
    v.write_needle(n)
    with pytest.raises(NotFound):
        v.read_needle(1)
    v.close()


def test_preallocate_keeps_append_offsets(tmp_path):
    """Preallocation must reserve blocks WITHOUT moving the append tail
    (FALLOC_FL_KEEP_SIZE, volume_create_linux.go:19): appends derive
    their offset from st_size."""
    from seaweedfs_tpu.storage.volume import Volume
    from seaweedfs_tpu.storage.needle import Needle

    v = Volume(str(tmp_path), "", 31, preallocate=1 << 20)
    off, _ = v.write_needle(Needle(cookie=5, id=1, data=b"pre" * 50))
    assert off < 4096, "append landed past the preallocated region"
    assert v.data_size() < 4096
    assert v.read_needle(1, cookie=5).data == b"pre" * 50
    v.close()
    # reload: integrity check passes, appends continue at the tail
    v2 = Volume(str(tmp_path), "", 31, create_if_missing=False)
    off2, _ = v2.write_needle(Needle(cookie=6, id=2, data=b"y"))
    assert off < off2 < 8192
    v2.close()


def test_ttl_volume_expiry_reclaims(tmp_path):
    """Whole-volume TTL reclamation rides the heartbeat walk
    (store.go:165-200 + volume.go expired/expiredLongEnough)."""
    import os
    import time as _time

    from seaweedfs_tpu.storage.needle import Needle
    from seaweedfs_tpu.storage.store import Store

    st = Store([str(tmp_path)])
    v = st.add_volume(5, ttl="1m")
    v.write_needle(Needle(cookie=1, id=1, data=b"short-lived"))
    hb = st.collect_heartbeat()
    assert [m.id for m in hb.volumes] == [5]

    # age past ttl but inside the grace window: no longer advertised,
    # files still on disk
    v.last_modified_ts = _time.time() - 63  # just past the 1m ttl
    hb = st.collect_heartbeat()
    assert hb.volumes == []
    assert os.path.exists(os.path.join(str(tmp_path), "5.dat"))
    assert 5 in st.volumes

    # age past ttl + removal delay: destroyed and reported deleted
    v.last_modified_ts = _time.time() - 3600
    hb = st.collect_heartbeat()
    assert hb.volumes == []
    assert [m.id for m in hb.deleted_volumes] == [5]
    assert 5 not in st.volumes
    assert not os.path.exists(os.path.join(str(tmp_path), "5.dat"))

    # non-TTL volumes are never reclaimed
    v2 = st.add_volume(6)
    v2.write_needle(Needle(cookie=1, id=1, data=b"eternal"))
    v2.last_modified_ts = _time.time() - 10_000_000
    hb = st.collect_heartbeat()
    assert [m.id for m in hb.volumes] == [6]
    st.close()


def test_ttl_watermark_survives_restart(tmp_path):
    """last_modified_ts must be restored on load, or TTL reclamation
    (store.go expired()) goes dead after a volume-server restart."""
    import time as _time

    from seaweedfs_tpu.storage.needle import (FLAG_HAS_LAST_MODIFIED,
                                              Needle)
    from seaweedfs_tpu.storage.volume import Volume

    v = Volume(str(tmp_path), "", 9)
    n = Needle(cookie=1, id=1, data=b"x",
               last_modified=int(_time.time()))
    n.set_flag(FLAG_HAS_LAST_MODIFIED)
    v.write_needle(n)
    wm = v.last_modified_ts
    assert wm > 0
    v.close()
    v2 = Volume(str(tmp_path), "", 9, create_if_missing=False)
    assert v2.last_modified_ts == wm
    v2.close()
