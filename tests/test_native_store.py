"""Native ovsdb_lite config store: transactions, durability, crash-torn
tails, compaction, and native<->python wire compatibility."""

import os
import struct

import pytest

pytestmark = pytest.mark.slow

from antrea_tpu.native import ConfigStore, native_available


BACKENDS = ["python"] + (["native"] if native_available() else [])


@pytest.fixture(params=BACKENDS)
def backend(request):
    return request.param


def _open(path, backend):
    return ConfigStore(str(path), force_python=(backend == "python"))


def test_native_toolchain_builds():
    """g++ is baked into this image: the native backend must be available
    (the Python fallback exists for toolchain-less consumers, not here)."""
    assert native_available()


def test_library_is_keyed_by_source_hash(tmp_path, monkeypatch):
    """A stale `.so` sitting next to the source (git ignores it, a tree
    copy carries it) is never loaded: the library's name carries the hash
    of the source it was built from."""
    import hashlib
    import shutil

    from antrea_tpu.native import store

    src = tmp_path / "ovsdb_lite.cc"
    shutil.copy(store._SRC, src)
    (tmp_path / "ovsdb_lite.so").write_bytes(b"stale")
    monkeypatch.setattr(store, "_SRC", str(src))
    so, err = store._build()
    assert err is None
    digest = hashlib.sha256(src.read_bytes()).hexdigest()[:16]
    assert os.path.basename(so) == f"ovsdb_lite.{digest}.so"
    assert os.path.getsize(so) > len(b"stale")
    assert store._build() == (so, None)  # cached, not rebuilt


def test_txn_commit_abort_and_reopen(tmp_path, backend):
    p = tmp_path / "db"
    with _open(p, backend) as s:
        assert s.backend == backend
        s.set("round", b"7")
        s.set("iface/pod-a", b'{"ofport": 3}')
        s.commit()
        s.set("round", b"8")
        s.abort()  # staged mutation discarded
        assert s.get("round") == b"7"
        s.set("iface/pod-b", b"x")
        s.delete("iface/pod-a")
        s.commit()
    with _open(p, backend) as s:
        assert s.get("round") == b"7"
        assert s.get("iface/pod-a") is None
        assert s.get("iface/pod-b") == b"x"
        assert s.keys() == ["iface/pod-b", "round"]


def test_torn_tail_record_is_dropped(tmp_path, backend):
    """A crash mid-commit leaves a torn trailing record: replay keeps every
    earlier transaction and drops only the torn one (OVSDB log model)."""
    p = tmp_path / "db"
    with _open(p, backend) as s:
        s.set("a", b"1")
        s.commit()
        s.set("b", b"2")
        s.commit()
    size = os.path.getsize(p)
    with open(p, "r+b") as f:  # tear the last record
        f.truncate(size - 3)
    with _open(p, backend) as s:
        assert s.get("a") == b"1"
        assert s.get("b") is None  # torn transaction atomically lost

    # Corrupt (bit-flipped) tail: checksum rejects it the same way.
    with _open(p, backend) as s:
        s.set("c", b"3")
        s.commit()
    with open(p, "r+b") as f:
        f.seek(-1, os.SEEK_END)
        last = f.read(1)
        f.seek(-1, os.SEEK_END)
        f.write(bytes([last[0] ^ 0xFF]))
    with _open(p, backend) as s:
        assert s.get("a") == b"1" and s.get("c") is None


def test_compaction_preserves_state(tmp_path, backend):
    p = tmp_path / "db"
    with _open(p, backend) as s:
        for i in range(50):
            s.set(f"k{i}", str(i).encode() * 10)
            s.commit()
        for i in range(0, 50, 2):
            s.delete(f"k{i}")
            s.commit()
        before = os.path.getsize(p)
        s.compact()
        after = os.path.getsize(p)
        assert after < before
        assert s.get("k1") == b"1" * 10 and s.get("k0") is None
    with _open(p, backend) as s:  # compacted journal replays
        assert len(s.keys()) == 25


@pytest.mark.skipif(not native_available(), reason="no g++")
def test_native_and_python_are_wire_compatible(tmp_path):
    """Both implementations speak the same journal format: files written
    by one open cleanly in the other."""
    p = tmp_path / "db"
    with ConfigStore(str(p)) as s:
        assert s.backend == "native"
        s.set("written-by", b"native")
        s.commit()
    with ConfigStore(str(p), force_python=True) as s:
        assert s.get("written-by") == b"native"
        s.set("also", b"python")
        s.commit()
    with ConfigStore(str(p)) as s:
        assert s.get("also") == b"python"
        assert s.get("written-by") == b"native"


def test_datapath_round_storage(tmp_path):
    """The cookie-round / external-IDs usage: the store carries the round
    across a restart (agent.go:486-512 model) next to the snapshot."""
    with ConfigStore(str(tmp_path / "conf.db")) as s:
        s.set("cookie/round", struct.pack("<Q", 41))
        s.set("external-ids/node", b"n0")
        s.commit()
        s.set("cookie/round", struct.pack("<Q", 42))
        s.commit()
    with ConfigStore(str(tmp_path / "conf.db")) as s:
        (round_,) = struct.unpack("<Q", s.get("cookie/round"))
        assert round_ == 42 and s.get("external-ids/node") == b"n0"
