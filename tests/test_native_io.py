"""Native C++ RecordIO reader tests: parity with the pure-Python parser
(reference analog: the C++ src/io/ iterators vs python/mxnet/recordio.py).
"""
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import recordio
from mxnet_tpu.native import NativeRecordReader, available

pytestmark = pytest.mark.skipif(not available(),
                                reason="native toolchain unavailable")


@pytest.fixture()
def recfile(tmp_path):
    p = str(tmp_path / "data.rec")
    w = recordio.MXRecordIO(p, "w")
    payloads = [b"first", b"y" * 4093,                  # unaligned length
                b"", b"w" * 100000,                     # large single
                b"last"]
    for b in payloads:
        w.write(b)
    w.close()
    return p, payloads


def test_native_matches_python_sequential(recfile):
    p, payloads = recfile
    r = NativeRecordReader(p)
    assert len(r) == len(payloads)
    for i, expect in enumerate(payloads):
        assert r.read(i) == expect
    r.close()
    # the MXRecordIO read path itself now uses the native reader
    rd = recordio.MXRecordIO(p, "r")
    assert rd._native is not None
    got = []
    while True:
        b = rd.read()
        if b is None:
            break
        got.append(b)
    assert got == payloads
    rd.close()


def test_python_fallback_parity(recfile, monkeypatch):
    p, payloads = recfile
    monkeypatch.setenv("MXNET_USE_NATIVE_IO", "0")
    rd = recordio.MXRecordIO(p, "r")
    assert rd._native is None
    got = []
    while True:
        b = rd.read()
        if b is None:
            break
        got.append(b)
    assert got == payloads
    rd.close()


def test_multipart_record(tmp_path):
    # force a continuation chain with a tiny chunk limit
    p = str(tmp_path / "chunked.rec")
    w = recordio.MXRecordIO(p, "w")
    big = bytes(range(256)) * 64          # 16 KiB
    orig = recordio.MXRecordIO._MAX_CHUNK
    recordio.MXRecordIO._MAX_CHUNK = 4096
    try:
        w.write(big)
        w.write(b"tail")
    finally:
        recordio.MXRecordIO._MAX_CHUNK = orig
    w.close()
    r = NativeRecordReader(p)
    assert len(r) == 2
    assert r.read(0) == big              # segments concatenated
    assert r.read(1) == b"tail"
    r.close()


def test_indexed_read_uses_native(tmp_path):
    p = str(tmp_path / "i.rec")
    pidx = str(tmp_path / "i.idx")
    w = recordio.MXIndexedRecordIO(pidx, p, "w")
    for i in range(10):
        w.write_idx(i, f"record-{i}".encode())
    w.close()
    r = recordio.MXIndexedRecordIO(pidx, p, "r")
    assert r._native is not None
    for i in (7, 0, 3, 9):
        assert r.read_idx(i) == f"record-{i}".encode()
    r.close()


def test_prefetch_delivers_epoch_order(recfile):
    p, payloads = recfile
    r = NativeRecordReader(p)
    order = list(np.random.RandomState(0).permutation(len(payloads)))
    for _ in range(2):      # re-arming after a completed epoch must work
        r.prefetch([int(i) for i in order])
        seen = []
        while True:
            i = r.prefetch_next()
            if i is None:
                break
            seen.append(i)
            r.read(i)
        assert seen == [int(i) for i in order]
    r.close()


def test_seek_read_and_tell_coherent(tmp_path):
    # the reference's seek+read and tell-while-indexing idioms must hold
    # on the native path (review regression)
    p = str(tmp_path / "s.rec")
    pidx = str(tmp_path / "s.idx")
    w = recordio.MXIndexedRecordIO(pidx, p, "w")
    for i in range(5):
        w.write_idx(i, f"rec-{i}".encode())
    w.close()
    r = recordio.MXIndexedRecordIO(pidx, p, "r")
    assert r._native is not None
    r.seek(3)
    assert r.read() == b"rec-3"
    assert r.read() == b"rec-4"      # position advanced past record 3
    r.reset()
    positions = []
    while True:
        pos = r.tell()
        buf = r.read()
        if buf is None:
            break
        positions.append(pos)
    assert positions == [r.idx[i] for i in range(5)]
    r.close()


def test_corrupt_file_raises(tmp_path):
    p = tmp_path / "bad.rec"
    p.write_bytes(b"\x00" * 64)
    with pytest.raises(IOError):
        NativeRecordReader(str(p))


def test_image_record_iter_native_path(tmp_path):
    # the ImageRecordIter pipeline rides the native reader end to end
    from mxnet_tpu.recordio import IRHeader, pack
    p = str(tmp_path / "img.rec")
    w = recordio.MXRecordIO(p, "w")
    rng = np.random.RandomState(0)
    for i in range(8):
        img = rng.randint(0, 255, (8, 8, 3), np.uint8)
        header = IRHeader(0, float(i % 2), i, 0)
        w.write(pack(header, img.tobytes()))
    w.close()
    rd = recordio.MXRecordIO(p, "r")
    assert rd._native is not None
    n = 0
    while True:
        s = rd.read()
        if s is None:
            break
        header, content = recordio.unpack(s)
        assert len(content) == 8 * 8 * 3
        n += 1
    assert n == 8


def test_library_freshness_is_decided_by_source_content(tmp_path,
                                                        monkeypatch):
    """A library is current only when the stamp beside it names the
    sources in the tree — a copied tree's file times say nothing."""
    from mxnet_tpu import native
    assert not native._needs_build()      # available() built + stamped
    stamp = tmp_path / "stamp"
    monkeypatch.setattr(native, "_STAMP_PATH", str(stamp))
    assert native._needs_build()          # no stamp: unknown origin
    stamp.write_text("0" * 64)
    assert native._needs_build()          # built from other sources
    stamp.write_text(native._source_hash())
    assert not native._needs_build()
