"""RecordIO / image / gluon.data / CSV / LibSVM tests
(reference models: tests/python/unittest/test_recordio.py,
test_image.py, test_gluon_data.py)."""
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import recordio
from mxnet_tpu.gluon import data as gdata


def test_recordio_roundtrip(tmp_path):
    frec = str(tmp_path / "test.rec")
    w = recordio.MXRecordIO(frec, "w")
    for i in range(5):
        w.write(f"record{i}".encode() * (i + 1))
    w.close()
    r = recordio.MXRecordIO(frec, "r")
    for i in range(5):
        assert r.read() == f"record{i}".encode() * (i + 1)
    assert r.read() is None
    r.close()


def test_indexed_recordio(tmp_path):
    frec = str(tmp_path / "test.rec")
    fidx = str(tmp_path / "test.idx")
    w = recordio.MXIndexedRecordIO(fidx, frec, "w")
    for i in range(10):
        w.write_idx(i, f"record{i}".encode())
    w.close()
    r = recordio.MXIndexedRecordIO(fidx, frec, "r")
    assert r.read_idx(7) == b"record7"
    assert r.read_idx(2) == b"record2"
    assert r.keys == list(range(10))
    r.close()


def test_pack_unpack_label():
    header = recordio.IRHeader(0, np.array([1.0, 2.0, 3.0], np.float32),
                               42, 0)
    s = recordio.pack(header, b"payload")
    h2, body = recordio.unpack(s)
    assert h2.id == 42
    np.testing.assert_allclose(h2.label, [1.0, 2.0, 3.0])
    assert body == b"payload"
    # scalar label
    s = recordio.pack(recordio.IRHeader(0, 5.0, 1, 0), b"x")
    h3, body = recordio.unpack(s)
    assert h3.label == 5.0 and body == b"x"


def test_pack_img_roundtrip(tmp_path):
    img = np.random.RandomState(0).randint(0, 255, (32, 32, 3), np.uint8)
    s = recordio.pack_img(recordio.IRHeader(0, 1.0, 0, 0), img,
                          quality=100, img_fmt=".png")
    header, decoded = recordio.unpack_img(s)
    assert header.label == 1.0
    np.testing.assert_array_equal(decoded, img)


def test_image_iter_from_rec(tmp_path):
    import cv2
    frec = str(tmp_path / "imgs.rec")
    fidx = str(tmp_path / "imgs.idx")
    w = recordio.MXIndexedRecordIO(fidx, frec, "w")
    rng = np.random.RandomState(0)
    for i in range(12):
        img = rng.randint(0, 255, (40, 40, 3), np.uint8)
        w.write_idx(i, recordio.pack_img(
            recordio.IRHeader(0, float(i % 3), i, 0), img, img_fmt=".png"))
    w.close()
    it = mx.image.ImageIter(batch_size=4, data_shape=(3, 32, 32),
                            path_imgrec=frec, path_imgidx=fidx,
                            rand_crop=True, rand_mirror=True)
    batch = next(iter(it))
    assert batch.data[0].shape == (4, 3, 32, 32)
    assert batch.label[0].shape == (4,)
    n = 1 + sum(1 for _ in it)
    assert n == 3


def test_image_augmenters():
    img = mx.nd.array(np.random.RandomState(0).randint(
        0, 255, (50, 60, 3)).astype(np.uint8), dtype="uint8")
    out = mx.image.resize_short(img, 32)
    assert min(out.shape[:2]) == 32
    out, _ = mx.image.center_crop(img, (24, 24))
    assert out.shape == (24, 24, 3)
    out, _ = mx.image.random_crop(img, (24, 24))
    assert out.shape == (24, 24, 3)
    out, _ = mx.image.random_size_crop(img, (24, 24), (0.5, 1.0),
                                       (0.75, 1.33))
    assert out.shape == (24, 24, 3)
    auglist = mx.image.CreateAugmenter((3, 24, 24), rand_crop=True,
                                       rand_mirror=True, mean=True,
                                       std=True, brightness=0.1)
    x = img
    for aug in auglist:
        x = aug(x)
    assert x.shape == (24, 24, 3)
    assert x.dtype == np.float32


def test_gluon_dataset_dataloader():
    x = np.arange(100).reshape(50, 2).astype(np.float32)
    y = np.arange(50).astype(np.float32)
    ds = gdata.ArrayDataset(x, y)
    assert len(ds) == 50
    sample = ds[3]
    np.testing.assert_allclose(np.asarray(sample[0]), x[3])
    loader = gdata.DataLoader(ds, batch_size=10, shuffle=False)
    batches = list(loader)
    assert len(batches) == 5
    np.testing.assert_allclose(batches[0][0].asnumpy(), x[:10])

    # transform
    ds2 = ds.transform_first(lambda a: a * 2)
    np.testing.assert_allclose(np.asarray(ds2[3][0]), x[3] * 2)

    # last_batch handling
    loader = gdata.DataLoader(ds, batch_size=15, last_batch="discard")
    assert len(list(loader)) == 3


def test_dataloader_multiworker():
    x = np.arange(64).reshape(32, 2).astype(np.float32)
    y = np.arange(32).astype(np.float32)
    ds = gdata.ArrayDataset(x, y)
    loader = gdata.DataLoader(ds, batch_size=8, num_workers=2)
    batches = list(loader)
    assert len(batches) == 4
    got = np.concatenate([b[0].asnumpy() for b in batches])
    np.testing.assert_allclose(got, x)


def test_dataloader_workers_refuse_device_backed_samples(monkeypatch):
    """On an accelerator backend a forked worker cannot index an array
    on the chip its parent holds (it hangs there): the loader says so
    before forking. numpy-backed datasets are served as ever."""
    import jax
    x = np.arange(64).reshape(16, 2, 2).astype(np.float32)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    loader = gdata.DataLoader(gdata.ArrayDataset(mx.nd.array(x)),
                              batch_size=8, num_workers=2)
    with pytest.raises(mx.MXNetError, match="forked worker"):
        next(iter(loader))
    loader = gdata.DataLoader(gdata.ArrayDataset(x), batch_size=8,
                              num_workers=2)
    assert len(list(loader)) == 2


def test_samplers():
    s = gdata.SequentialSampler(10)
    assert list(s) == list(range(10))
    rs = list(gdata.RandomSampler(10))
    assert sorted(rs) == list(range(10))
    bs = gdata.BatchSampler(gdata.SequentialSampler(10), 4, "keep")
    assert [len(b) for b in bs] == [4, 4, 2]
    bs = gdata.BatchSampler(gdata.SequentialSampler(10), 4, "discard")
    assert [len(b) for b in bs] == [4, 4]


def test_transforms():
    from mxnet_tpu.gluon.data.vision import transforms
    img = mx.nd.array(np.random.RandomState(0).randint(
        0, 255, (28, 28, 3)).astype(np.uint8), dtype="uint8")
    t = transforms.ToTensor()
    out = t(img)
    assert out.shape == (3, 28, 28)
    assert float(out.max().asscalar()) <= 1.0
    norm = transforms.Normalize([0.5, 0.5, 0.5], [0.2, 0.2, 0.2])
    out2 = norm(out)
    assert out2.shape == (3, 28, 28)
    comp = transforms.Compose([transforms.Resize(20), transforms.ToTensor()])
    out3 = comp(img)
    assert out3.shape == (3, 20, 20)


def test_csv_iter(tmp_path):
    data_path = str(tmp_path / "data.csv")
    rng = np.random.RandomState(0)
    arr = rng.randn(20, 4).astype(np.float32)
    np.savetxt(data_path, arr, delimiter=",")
    lbl_path = str(tmp_path / "label.csv")
    np.savetxt(lbl_path, np.arange(20.0), delimiter=",")
    it = mx.CSVIter(data_csv=data_path, data_shape=(4,),
                    label_csv=lbl_path, batch_size=6)
    batches = list(it)
    assert len(batches) == 4
    np.testing.assert_allclose(batches[0].data[0].asnumpy(), arr[:6],
                               rtol=1e-5)
    assert batches[-1].pad == 4


def test_libsvm_iter(tmp_path):
    p = str(tmp_path / "data.libsvm")
    with open(p, "w") as f:
        f.write("1 0:1.5 3:2.0\n0 1:1.0\n1 2:3.0 3:4.0\n")
    it = mx.LibSVMIter(data_libsvm=p, data_shape=(4,), batch_size=2)
    batch = next(iter(it))
    d = batch.data[0].asnumpy() if hasattr(batch.data[0], "asnumpy") else \
        np.asarray(batch.data[0])
    np.testing.assert_allclose(d[0], [1.5, 0, 0, 2.0])
    np.testing.assert_allclose(batch.label[0].asnumpy(), [1.0, 0.0])


def test_image_folder_dataset(tmp_path):
    import cv2
    for cls in ("cat", "dog"):
        os.makedirs(str(tmp_path / cls))
        for i in range(3):
            img = np.random.RandomState(i).randint(0, 255, (16, 16, 3),
                                                   np.uint8)
            cv2.imwrite(str(tmp_path / cls / f"{i}.png"), img)
    ds = gdata.vision.ImageFolderDataset(str(tmp_path))
    assert len(ds) == 6
    assert ds.synsets == ["cat", "dog"]
    img, label = ds[0]
    assert img.shape == (16, 16, 3)
    assert label == 0


def test_synthetic_dataset():
    ds = gdata.vision.SyntheticImageDataset(num_samples=10,
                                            shape=(3, 8, 8), classes=4)
    img, label = ds[0]
    assert img.shape == (8, 8, 3)
    assert 0 <= label < 4
    img2, _ = ds[0]
    np.testing.assert_array_equal(img.asnumpy(), img2.asnumpy())


def test_recordio_large_record_chunking(tmp_path):
    """Records >= 2^29 bytes use continuation chunks; emulate with a
    patched chunk size."""
    from mxnet_tpu import recordio as rio
    frec = str(tmp_path / "big.rec")
    w = rio.MXRecordIO(frec, "w")
    orig = rio.MXRecordIO._MAX_CHUNK
    try:
        rio.MXRecordIO._MAX_CHUNK = 10
        payload = bytes(range(256)) * 2  # 512 bytes -> many chunks
        w.write(payload)
        w.write(b"small")
        w.close()
        r = rio.MXRecordIO(frec, "r")
        assert r.read() == payload
        assert r.read() == b"small"
        r.close()
    finally:
        rio.MXRecordIO._MAX_CHUNK = orig


def test_dataloader_workers_with_recordfile(tmp_path):
    """Forked workers must not race on a shared RecordIO fd."""
    from mxnet_tpu import recordio as rio
    frec, fidx = str(tmp_path / "d.rec"), str(tmp_path / "d.idx")
    w = rio.MXIndexedRecordIO(fidx, frec, "w")
    for i in range(64):
        w.write_idx(i, f"payload-{i:04d}".encode() * 20)
    w.close()
    ds = gdata.RecordFileDataset(frec)
    loader = gdata.DataLoader(
        ds, batch_size=8, num_workers=2,
        batchify_fn=lambda recs: [bytes(r) for r in recs])
    seen = []
    for batch in loader:
        for rec in batch:
            assert rec[:8].startswith(b"payload-")
            seen.append(rec)
    assert len(seen) == 64


def test_libsvm_separate_label_file(tmp_path):
    pd = str(tmp_path / "d.libsvm")
    pl = str(tmp_path / "l.libsvm")
    with open(pd, "w") as f:
        f.write("0 0:1.0\n0 1:2.0\n")
    with open(pl, "w") as f:
        f.write("0:1.0 2:5.0\n1:3.0\n")
    it = mx.LibSVMIter(data_libsvm=pd, data_shape=(2,), label_libsvm=pl,
                       label_shape=(3,), batch_size=2)
    batch = next(iter(it))
    lab = batch.label[0].asnumpy()
    np.testing.assert_allclose(lab, [[1.0, 0, 5.0], [0, 3.0, 0]])


def test_rnn_unroll_valid_length():
    from mxnet_tpu.gluon import rnn
    cell = rnn.RNNCell(4, input_size=4)
    cell.initialize()
    x = [mx.nd.ones((2, 4)) for _ in range(5)]
    vl = mx.nd.array([2.0, 5.0])
    outputs, states = cell.unroll(5, x, layout="NTC", valid_length=vl)
    # sequence 0: outputs at steps >= 2 are masked to 0
    assert np.abs(outputs[3].asnumpy()[0]).sum() == 0
    assert np.abs(outputs[3].asnumpy()[1]).sum() > 0
    # sequence 0's state froze at step 2: rerun only 2 steps and compare
    cell.reset()
    outputs2, states2 = cell.unroll(2, x[:2], layout="NTC")
    np.testing.assert_allclose(states[0].asnumpy()[0],
                               states2[0].asnumpy()[0], rtol=1e-6)
