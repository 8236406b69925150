"""Native C++ data pipeline tests (reference analog: the C++ iterator tests
plus tests/python/unittest/test_io.py).  Oracle: the Python ImageRecordIter
decode path (same libjpeg family underneath)."""
import os

import numpy as np
import pytest

import tpu_mx as mx
from tpu_mx import recordio

cv2 = pytest.importorskip("cv2")


@pytest.fixture(scope="module")
def rec_file(tmp_path_factory):
    """24 small JPEG records, labels = index, various sizes."""
    d = tmp_path_factory.mktemp("rec")
    path = str(d / "data.rec")
    rng = np.random.RandomState(0)
    rec = recordio.MXRecordIO(path, "w")
    imgs = []
    for i in range(24):
        h, w = rng.randint(40, 90), rng.randint(40, 90)
        img = rng.randint(0, 255, (h, w, 3), np.uint8)
        header = recordio.IRHeader(0, float(i), i, 0)
        rec.write(recordio.pack_img(header, img, quality=95))
        imgs.append(img)
    rec.close()
    return path, imgs


def _pipe(path, **kw):
    from tpu_mx.lib.recordio_cpp import NativeImagePipe
    args = dict(batch_size=8, data_shape=(3, 32, 32), preprocess_threads=3,
                prefetch_buffer=3)
    args.update(kw)
    return NativeImagePipe(path, **args)


def test_native_builds_and_counts(rec_file):
    path, imgs = rec_file
    p = _pipe(path)
    assert len(p) == 24
    p.close()


def test_native_batches_and_labels(rec_file):
    path, _ = rec_file
    p = _pipe(path)
    seen_labels = []
    batches = 0
    while True:
        out = p.next_batch()
        if out is None:
            break
        data, label = out
        assert data.shape == (8, 3, 32, 32)
        assert data.dtype == np.float32
        assert np.isfinite(data).all()
        seen_labels.extend(label.tolist())
        batches += 1
    assert batches == 3
    assert sorted(int(l) for l in seen_labels) == list(range(24))
    p.close()


def test_native_epoch_reset_and_shuffle(rec_file):
    path, _ = rec_file
    p = _pipe(path, shuffle=True, seed=7)
    def epoch_labels():
        out, labels = p.next_batch(), []
        while out is not None:
            labels.extend(out[1].tolist())
            out = p.next_batch()
        return labels
    e1 = epoch_labels()
    p.reset()
    e2 = epoch_labels()
    assert sorted(e1) == sorted(e2) == list(map(float, range(24)))
    assert e1 != e2  # reshuffled across epochs
    p.close()


def test_native_matches_python_decode(rec_file):
    """Center-crop, no resize: native output must closely match the Python
    cv2 pipeline (both are libjpeg decodes; only rounding may differ)."""
    path, _ = rec_file
    from tpu_mx.io import ImageRecordIter
    py_iter = ImageRecordIter(path_imgrec=path, data_shape=(3, 32, 32),
                              batch_size=8, shuffle=False,
                              preprocess_threads=2, use_native=False)
    p = _pipe(path)
    nb = py_iter.next()
    py_data = nb.data[0].asnumpy()
    nat_data, nat_label = p.next_batch()
    assert nat_data.shape == py_data.shape
    # same labels, same order
    np.testing.assert_array_equal(nat_label,
                                  nb.label[0].asnumpy().astype(np.float32))
    diff = np.abs(nat_data - py_data)
    assert np.mean(diff) < 2.0 and np.median(diff) < 1.5, \
        f"decode divergence: mean {diff.mean()}, max {diff.max()}"
    p.close()


def test_native_mean_std_normalization(rec_file):
    path, _ = rec_file
    p0 = _pipe(path)
    p1 = _pipe(path, mean=(10.0, 20.0, 30.0), std=(2.0, 4.0, 8.0))
    d0, _ = p0.next_batch()
    d1, _ = p1.next_batch()
    for c, (m, s) in enumerate([(10, 2), (20, 4), (30, 8)]):
        np.testing.assert_allclose(d1[:, c], (d0[:, c] - m) / s,
                                   rtol=1e-5, atol=1e-5)
    p0.close()
    p1.close()


def test_native_deterministic_augment(rec_file):
    path, _ = rec_file
    a = _pipe(path, rand_crop=True, rand_mirror=True, seed=42,
              data_shape=(3, 24, 24))
    b = _pipe(path, rand_crop=True, rand_mirror=True, seed=42,
              data_shape=(3, 24, 24))
    da, la = a.next_batch()
    db, lb = b.next_batch()
    np.testing.assert_array_equal(da, db)
    np.testing.assert_array_equal(la, lb)
    a.close()
    b.close()


def test_native_bad_file(tmp_path):
    bad = tmp_path / "bad.rec"
    bad.write_bytes(b"not a recordio file at all")
    from tpu_mx.lib.recordio_cpp import NativeImagePipe
    with pytest.raises(IOError):
        NativeImagePipe(str(bad), batch_size=2, data_shape=(3, 8, 8))


def test_runtime_feature_flag():
    feats = mx.runtime.Features()
    assert feats.is_enabled("CPP_RECORDIO")


def test_image_record_iter_native_default(rec_file):
    """ImageRecordIter picks the native pipeline automatically and yields
    the same epoch as the Python path."""
    path, _ = rec_file
    from tpu_mx.io import ImageRecordIter
    it = ImageRecordIter(path_imgrec=path, data_shape=(3, 32, 32),
                         batch_size=8)
    assert it._native is not None
    labels = []
    for batch in it:
        assert batch.data[0].shape == (8, 3, 32, 32)
        labels.extend(batch.label[0].asnumpy().tolist())
        assert batch.pad == 0  # 24 % 8 == 0
    assert sorted(int(l) for l in labels) == list(range(24))
    it.reset()
    assert len(list(it)) == 3


def test_image_record_iter_native_pad(rec_file):
    path, _ = rec_file
    from tpu_mx.io import ImageRecordIter
    it = ImageRecordIter(path_imgrec=path, data_shape=(3, 16, 16),
                         batch_size=10)
    pads = [b.pad for b in it]
    assert pads == [0, 0, 6]  # 24 records, batch 10 -> last pad 6


def test_native_reset_recovers_from_bad_record(tmp_path):
    """A corrupt record fails the epoch; reset() must un-poison the pipe."""
    import struct
    path = str(tmp_path / "mixed.rec")
    rng = np.random.RandomState(0)
    rec = recordio.MXRecordIO(path, "w")
    img = rng.randint(0, 255, (40, 40, 3), np.uint8)
    rec.write(recordio.pack_img(recordio.IRHeader(0, 1.0, 0, 0), img))
    # corrupt record: valid header, garbage jpeg payload
    rec.write(struct.pack("<IfQQ", 0, 2.0, 1, 0) + b"\x00" * 64)
    rec.close()
    p = _pipe(path, batch_size=2, data_shape=(3, 16, 16),
              preprocess_threads=1)
    with pytest.raises(IOError):
        p.next_batch()
    p.reset()
    with pytest.raises(IOError):  # same data still fails, but freshly
        p.next_batch()
    p.close()


def test_use_native_true_raises_on_png(tmp_path):
    path = str(tmp_path / "png.rec")
    rec = recordio.MXRecordIO(path, "w")
    img = np.zeros((20, 20, 3), np.uint8)
    rec.write(recordio.pack_img(recordio.IRHeader(0, 0.0, 0, 0), img,
                                img_fmt=".png"))
    rec.close()
    from tpu_mx.io import ImageRecordIter
    from tpu_mx.base import MXNetError
    with pytest.raises(MXNetError, match="use_native"):
        ImageRecordIter(path_imgrec=path, data_shape=(3, 16, 16),
                        batch_size=1, use_native=True)


def test_native_split_record_roundtrip(tmp_path):
    """Records whose payload embeds the RecordIO magic are split by the
    dmlc writer; the native scanner must rejoin them with the magic bytes
    (recordio.py MXRecordIO.read does _MAGIC_BYTES.join)."""
    import struct
    path = str(tmp_path / "split.rec")
    magic = struct.pack("<I", 0xCED7230A)
    img = np.random.RandomState(3).randint(0, 255, (40, 40, 3), np.uint8)
    payload = recordio.pack_img(recordio.IRHeader(0, 7.0, 0, 0), img)
    # hand-write a dmlc-style split record: parts joined by magic
    cut = len(payload) // 2
    parts = [payload[:cut], payload[cut:]]
    joined = (magic + b"".join(parts[0:1]) + magic + parts[1])
    with open(path, "wb") as f:
        def emit(cflag, data):
            lrec = (cflag << 29) | len(data)
            f.write(magic + struct.pack("<I", lrec) + data)
            f.write(b"\x00" * ((4 - len(data) % 4) % 4))
        emit(1, parts[0])
        emit(3, parts[1])
    # python reader oracle
    r = recordio.MXRecordIO(path, "r")
    raw = r.read()
    r.close()
    assert raw == parts[0] + magic + parts[1]
    # the native pipe must decode it identically IF the rejoined payload is
    # a valid record; here the magic falls inside the jpeg stream, so just
    # check the pipe parses the file into exactly one record
    from tpu_mx.lib.recordio_cpp import NativeImagePipe
    p = NativeImagePipe(path, batch_size=1, data_shape=(3, 16, 16),
                        preprocess_threads=1)
    assert len(p) == 1
    p.close()


def test_sparse_dot_transpose_b():
    from tpu_mx.ndarray import sparse
    from tpu_mx import nd
    dense = np.zeros((2, 3), np.float32)
    dense[0, 1], dense[1, 2] = 2.0, 3.0
    csr = sparse.csr_matrix(dense)
    rhs = np.random.RandomState(0).rand(4, 3).astype(np.float32)
    out = sparse.dot(csr, nd.array(rhs), transpose_b=True)
    np.testing.assert_allclose(out.asnumpy(), dense @ rhs.T, rtol=1e-5)
    # dense · csr with transpose_a
    lhs = np.random.RandomState(1).rand(2, 5).astype(np.float32)
    out2 = sparse.dot(nd.array(lhs), csr, transpose_a=True)
    np.testing.assert_allclose(out2.asnumpy(), lhs.T @ dense, rtol=1e-5)


def test_libsvm_sparse_labels(tmp_path):
    d = tmp_path / "d.libsvm"
    l = tmp_path / "l.libsvm"
    d.write_text("0 0:1.0\n0 1:2.0\n")
    l.write_text("0 0:1.0 2:5.0\n0 1:3.0\n")
    from tpu_mx.io import LibSVMIter
    it = LibSVMIter(data_libsvm=str(d), data_shape=(3,), batch_size=2,
                    label_libsvm=str(l), label_shape=(3,))
    assert it.getpad() == 0  # before first batch: must not crash
    b = next(iter(it))
    np.testing.assert_array_equal(
        b.label[0].asnumpy(),
        np.array([[1.0, 0.0, 5.0], [0.0, 3.0, 0.0]], np.float32))


def test_native_im2rec_roundtrip(tmp_path):
    """The C++ packer's .rec/.idx must read back through the PYTHON
    recordio reader with intact headers/labels/ids and decodable images
    (format interchangeability with tools/im2rec.py, REF:tools/im2rec.cc)."""
    import cv2
    from tpu_mx import recordio
    from tpu_mx.lib.recordio_cpp import native_im2rec

    imgdir = tmp_path / "imgs"
    imgdir.mkdir()
    rng = np.random.RandomState(0)
    lines = []
    for i in range(6):
        img = (rng.rand(40 + i, 60, 3) * 255).astype(np.uint8)
        cv2.imwrite(str(imgdir / f"im{i}.jpg"),
                    img, [cv2.IMWRITE_JPEG_QUALITY, 95])
        # multi-label rows for i >= 3
        labels = [float(i)] if i < 3 else [float(i), float(i) * 0.5]
        lines.append("\t".join([str(i)] + [f"{v}" for v in labels]
                               + [f"im{i}.jpg"]))
    lst = tmp_path / "d.lst"
    lst.write_text("\n".join(lines) + "\n")

    n = native_im2rec(str(lst), str(imgdir), str(tmp_path / "d"),
                      resize=32, quality=90, num_thread=3)
    assert n == 6
    idx_lines = (tmp_path / "d.idx").read_text().strip().splitlines()
    assert len(idx_lines) == 6 and idx_lines[0].split("\t")[1] == "0"

    rec = recordio.MXIndexedRecordIO(str(tmp_path / "d.idx"),
                                     str(tmp_path / "d.rec"), "r")
    for i in range(6):
        header, img_bytes = recordio.unpack(rec.read_idx(i))
        assert header.id == i
        if i < 3:
            assert header.flag == 0 and abs(header.label - i) < 1e-6
        else:
            assert header.flag == 2
            np.testing.assert_allclose(header.label, [i, i * 0.5])
        arr = cv2.imdecode(np.frombuffer(img_bytes, np.uint8),
                           cv2.IMREAD_COLOR)
        assert arr is not None and min(arr.shape[:2]) == 32  # shorter side

    # and the native PIPE must accept the native-packed file too
    from tpu_mx.lib.recordio_cpp import NativeImagePipe
    pipe = NativeImagePipe(str(tmp_path / "d.rec"), batch_size=2,
                           data_shape=(3, 24, 24), resize=24,
                           preprocess_threads=2)
    data, label = pipe.next_batch()
    assert data.shape == (2, 3, 24, 24)


def test_native_im2rec_skips_bad_and_matches_upscale_semantics(tmp_path):
    """Missing files and non-JPEGs are SKIPPED (not fatal, matching the
    Python packer), and small images are stored unresized without
    upscale=True."""
    import cv2
    from tpu_mx import recordio
    from tpu_mx.lib.recordio_cpp import native_im2rec

    imgdir = tmp_path / "imgs"
    imgdir.mkdir()
    rng = np.random.RandomState(0)
    small = (rng.rand(20, 30, 3) * 255).astype(np.uint8)
    cv2.imwrite(str(imgdir / "small.jpg"), small)
    big = (rng.rand(100, 120, 3) * 255).astype(np.uint8)
    cv2.imwrite(str(imgdir / "big.jpg"), big)
    (imgdir / "fake.png").write_bytes(b"\x89PNG\r\n not a jpeg")
    lst = tmp_path / "d.lst"
    lst.write_text("0\t0.0\tsmall.jpg\n"
                   "1\t1.0\tmissing.jpg\n"
                   "2\t2.0\tfake.png\n"
                   "3\t3.0\tbig.jpg\n")
    n = native_im2rec(str(lst), str(imgdir), str(tmp_path / "d"), resize=64)
    assert n == 2  # small + big packed; missing + png skipped
    rec = recordio.MXIndexedRecordIO(str(tmp_path / "d.idx"),
                                     str(tmp_path / "d.rec"), "r")
    h0, img0 = recordio.unpack(rec.read_idx(0))
    a0 = cv2.imdecode(np.frombuffer(img0, np.uint8), cv2.IMREAD_COLOR)
    assert a0.shape[:2] == (20, 30)  # NOT upscaled to 64
    h3, img3 = recordio.unpack(rec.read_idx(3))
    a3 = cv2.imdecode(np.frombuffer(img3, np.uint8), cv2.IMREAD_COLOR)
    assert min(a3.shape[:2]) == 64   # downscaled
    # upscale=True does enlarge
    n = native_im2rec(str(lst), str(imgdir), str(tmp_path / "u"), resize=64,
                      upscale=True)
    rec = recordio.MXIndexedRecordIO(str(tmp_path / "u.idx"),
                                     str(tmp_path / "u.rec"), "r")
    hu, imgu = recordio.unpack(rec.read_idx(0))
    au = cv2.imdecode(np.frombuffer(imgu, np.uint8), cv2.IMREAD_COLOR)
    assert min(au.shape[:2]) == 64


def test_native_im2rec_dct_downscale_still_resizes(tmp_path):
    """An image whose short side is an exact power-of-two multiple of the
    target (128 -> 64) must STILL be written at short side 64: the
    downscale-only decision uses original dims, not the DCT-downscaled
    decode dims."""
    import cv2
    from tpu_mx import recordio
    from tpu_mx.lib.recordio_cpp import native_im2rec
    imgdir = tmp_path / "imgs"
    imgdir.mkdir()
    img = (np.random.RandomState(0).rand(128, 192, 3) * 255).astype(np.uint8)
    cv2.imwrite(str(imgdir / "a.jpg"), img)
    (tmp_path / "d.lst").write_text("0\t1.0\ta.jpg\n")
    n = native_im2rec(str(tmp_path / "d.lst"), str(imgdir),
                      str(tmp_path / "d"), resize=64)
    assert n == 1
    rec = recordio.MXIndexedRecordIO(str(tmp_path / "d.idx"),
                                     str(tmp_path / "d.rec"), "r")
    _h, img_bytes = recordio.unpack(rec.read_idx(0))
    a = cv2.imdecode(np.frombuffer(img_bytes, np.uint8), cv2.IMREAD_COLOR)
    assert min(a.shape[:2]) == 64, a.shape


def test_native_packed_rec_through_image_record_iter(tmp_path):
    """A --native-packed .rec feeds mx.io.ImageRecordIter end-to-end (the
    CLI drive's assertion, kept as a regression test)."""
    import cv2
    import tpu_mx as mx
    from tpu_mx.lib.recordio_cpp import native_im2rec
    imgdir = tmp_path / "imgs"
    imgdir.mkdir()
    rng = np.random.RandomState(2)
    lines = []
    for i in range(6):
        img = (rng.rand(40, 50, 3) * 255).astype(np.uint8)
        cv2.imwrite(str(imgdir / f"i{i}.jpg"), img)
        lines.append(f"{i}\t{float(i % 2)}\ti{i}.jpg")
    (tmp_path / "d.lst").write_text("\n".join(lines) + "\n")
    n = native_im2rec(str(tmp_path / "d.lst"), str(imgdir),
                      str(tmp_path / "d"), resize=32)
    assert n == 6
    it = mx.io.ImageRecordIter(path_imgrec=str(tmp_path / "d.rec"),
                               data_shape=(3, 28, 28), batch_size=3,
                               resize=28)
    batch = next(iter(it))
    assert batch.data[0].shape == (3, 3, 28, 28)
    assert batch.label[0].shape == (3,)


# ---------------------------------------------------------------------------
# native detection pipeline (VERDICT r3 ask#4;
# REF:src/io/iter_image_det_recordio.cc + image_det_aug_default.cc)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def det_rec_file(tmp_path_factory):
    """16 JPEG records with [cls,x1,y1,x2,y2]*m labels (m in 1..3)."""
    d = tmp_path_factory.mktemp("detrec")
    path = str(d / "det.rec")
    rng = np.random.RandomState(5)
    # indexed so the Python ImageDetIter (MXIndexedRecordIO) can read too
    rec = recordio.MXIndexedRecordIO(str(d / "det.idx"), path, "w")
    all_labels = []
    for i in range(16):
        h, w = rng.randint(50, 100), rng.randint(50, 100)
        img = rng.randint(0, 255, (h, w, 3), np.uint8)
        m = rng.randint(1, 4)
        rows = []
        for _ in range(m):
            x1, y1 = rng.uniform(0, 0.5, 2)
            bw, bh = rng.uniform(0.2, 0.45, 2)
            rows.append([float(rng.randint(0, 5)), x1, y1,
                         min(1.0, x1 + bw), min(1.0, y1 + bh)])
        label = np.asarray(rows, np.float32).ravel()
        header = recordio.IRHeader(0, label, i, 0)
        rec.write_idx(i, recordio.pack_img(header, img, quality=95))
        all_labels.append(np.asarray(rows, np.float32))
    rec.close()
    return path, all_labels


def _det_pipe(path, **kw):
    from tpu_mx.lib.recordio_cpp import NativeDetPipe
    args = dict(batch_size=4, data_shape=(3, 48, 48), max_objects=3,
                preprocess_threads=3, prefetch_buffer=3)
    args.update(kw)
    return NativeDetPipe(path, **args)


def test_det_pipe_shapes_and_padding(det_rec_file):
    path, labels = det_rec_file
    p = _det_pipe(path)
    seen = 0
    while True:
        out = p.next_batch()
        if out is None:
            break
        data, label = out
        assert data.shape == (4, 3, 48, 48)
        assert label.shape == (4, 3, 5)
        assert np.isfinite(data).all()
        for row_block in label:
            valid = row_block[:, 0] >= 0
            # all valid rows precede padding, coordinates normalized
            assert (row_block[~valid] == -1).all()
            assert (row_block[valid][:, 1:] >= 0).all()
            assert (row_block[valid][:, 1:] <= 1).all()
        seen += 1
    assert seen == 4
    p.close()


def test_det_pipe_boxes_match_python_iterator(det_rec_file, tmp_path):
    """No-augment path: native boxes must equal the Python ImageDetIter's
    exactly (force-resize keeps normalized boxes); pixels close on smooth
    images (random-noise JPEGs are a resampler-divergence worst case —
    cv2's fixed-point bilinear vs the native float bilinear legitimately
    differ there; see test_native_matches_python_decode for the
    decode-only tight bound)."""
    path, _ = det_rec_file
    # smooth synthetic images: low-frequency gradients
    spath = str(tmp_path / "smooth.rec")
    rec = recordio.MXIndexedRecordIO(str(tmp_path / "smooth.idx"), spath,
                                     "w")
    rng = np.random.RandomState(9)
    for i in range(16):
        h, w = rng.randint(50, 100), rng.randint(50, 100)
        yy, xx = np.mgrid[0:h, 0:w].astype(np.float32)
        img = np.stack([127 + 100 * np.sin(yy / h * 3 + c) *
                        np.cos(xx / w * 2 + c) for c in range(3)],
                       axis=-1).clip(0, 255).astype(np.uint8)
        label = np.asarray([1.0, 0.2, 0.2, 0.7, 0.7], np.float32)
        rec.write_idx(i, recordio.pack_img(
            recordio.IRHeader(0, label, i, 0), img, quality=95))
    rec.close()
    path = spath
    p = _det_pipe(path, batch_size=16, max_objects=3)
    data_n, label_n = p.next_batch()
    p.close()

    from tpu_mx.image.detection import (DetBorrowAug, DetForceResizeAug,
                                        ImageDetIter)
    from tpu_mx.image.image import CastAug
    # like-for-like resampling: the Python default is bicubic
    # (inter_method=2); the native pipeline is bilinear — pin bilinear
    it = ImageDetIter(16, (3, 48, 48), path_imgrec=path, max_objects=3,
                      aug_list=[DetForceResizeAug((48, 48), interp=1),
                                DetBorrowAug(CastAug())])
    batch = it.next()
    data_p = batch.data[0].asnumpy()
    label_p = batch.label[0].asnumpy()

    np.testing.assert_allclose(label_n, label_p, atol=1e-6)
    # uint8 bilinear resamplers: small per-pixel differences allowed
    assert np.mean(np.abs(data_n - data_p)) < 3.0
    assert np.max(np.abs(data_n - data_p)) < 64.0


def test_det_pipe_deterministic_augment(det_rec_file):
    path, _ = det_rec_file
    kw = dict(rand_crop=True, rand_mirror=True, seed=11, batch_size=16)
    p1 = _det_pipe(path, **kw)
    d1, l1 = p1.next_batch()
    p1.close()
    p2 = _det_pipe(path, **kw)
    d2, l2 = p2.next_batch()
    p2.close()
    np.testing.assert_array_equal(d1, d2)
    np.testing.assert_array_equal(l1, l2)
    # a different seed actually changes the augmentation draws
    p3 = _det_pipe(path, rand_crop=True, rand_mirror=True, seed=12,
                   batch_size=16)
    d3, _ = p3.next_batch()
    p3.close()
    assert np.abs(d1 - d3).max() > 0


def test_det_pipe_crop_keeps_covered_boxes(det_rec_file):
    """Cropped samples keep >=1 box, classes drawn from the original set,
    coordinates valid — the IoU-constrained-crop contract."""
    path, labels = det_rec_file
    p = _det_pipe(path, rand_crop=True, seed=3, batch_size=16,
                  min_object_covered=0.3)
    _, label = p.next_batch()
    p.close()
    for i in range(16):
        rows = label[i]
        valid = rows[rows[:, 0] >= 0]
        assert len(valid) >= 1  # the accepted crop covered >= one box
        orig_classes = set(labels[i][:, 0].tolist())
        assert set(valid[:, 0].tolist()) <= orig_classes
        assert (valid[:, 3] > valid[:, 1]).all()
        assert (valid[:, 4] > valid[:, 2]).all()


def test_det_pipe_mirror_flips_pixels_and_boxes(det_rec_file):
    path, _ = det_rec_file
    base = _det_pipe(path, batch_size=16, seed=21)
    d0, l0 = base.next_batch()
    base.close()
    mir = _det_pipe(path, batch_size=16, rand_mirror=True, seed=21)
    d1, l1 = mir.next_batch()
    mir.close()
    flipped = unchanged = 0
    for i in range(16):
        if np.array_equal(d1[i], d0[i]):
            unchanged += 1
            np.testing.assert_array_equal(l1[i], l0[i])
        else:
            np.testing.assert_array_equal(d1[i], d0[i][:, :, ::-1])
            flipped += 1
            v = l0[i][:, 0] >= 0
            np.testing.assert_allclose(l1[i][v, 1], 1.0 - l0[i][v, 3],
                                       atol=1e-6)
            np.testing.assert_allclose(l1[i][v, 3], 1.0 - l0[i][v, 1],
                                       atol=1e-6)
    assert flipped > 0 and unchanged > 0  # p=0.5 coin actually flipped


def test_image_det_record_iter_end_to_end(det_rec_file):
    path, _ = det_rec_file
    it = mx.io.ImageDetRecordIter(path, (3, 48, 48), batch_size=4)
    assert it.max_objects == 3  # header-only scan found the widest block
    batches = list(it)
    assert len(batches) == 4
    assert batches[0].data[0].shape == (4, 3, 48, 48)
    assert batches[0].label[0].shape == (4, 3, 5)
    it.reset()
    assert len(list(it)) == 4


@pytest.mark.slow
def test_det_native_throughput_3x_python(tmp_path):
    """VERDICT r3 ask#4 'done' bar: native det pipeline >=3x the Python
    iterator's throughput on the same records."""
    import time
    rng = np.random.RandomState(0)
    path = str(tmp_path / "perf.rec")
    rec = recordio.MXIndexedRecordIO(str(tmp_path / "perf.idx"), path, "w")
    for i in range(64):
        img = rng.randint(0, 255, (220, 220, 3), np.uint8)
        label = np.asarray([[1.0, 0.1, 0.1, 0.8, 0.8]], np.float32).ravel()
        rec.write_idx(i, recordio.pack_img(
            recordio.IRHeader(0, label, i, 0), img, quality=90))
    rec.close()

    def drain_native():
        p = _det_pipe(path, batch_size=16, data_shape=(3, 128, 128),
                      max_objects=1, rand_crop=True, rand_mirror=True,
                      preprocess_threads=4)
        n = 0
        for _ in range(2):
            while True:
                out = p.next_batch()
                if out is None:
                    break
                n += out[0].shape[0]
            p.reset()
        p.close()
        return n

    def drain_python():
        from tpu_mx.image.detection import ImageDetIter
        it = ImageDetIter(16, (3, 128, 128), path_imgrec=path,
                          max_objects=1, rand_crop=1, rand_mirror=True)
        n = 0
        for _ in range(2):
            for batch in it:
                n += batch.data[0].shape[0]
            it.reset()
        return n

    drain_native()  # warm the library/buffers outside the timed region
    t0 = time.perf_counter()
    n_native = drain_native()
    t_native = time.perf_counter() - t0
    t0 = time.perf_counter()
    n_python = drain_python()
    t_python = time.perf_counter() - t0
    assert n_native == n_python
    speedup = (t_python / n_python) / (t_native / n_native)
    assert speedup >= 3.0, f"native only {speedup:.2f}x python"


def test_det_pipe_corrupt_label_header_fails_gracefully(tmp_path):
    """A det record whose header flag is garbage (huge, wrapping in
    uint32 flag*4 arithmetic) must surface as a clean decode error with
    no multi-GB allocation.  The allocation side is only observable
    under an address-space cap, which can't be applied inside the pytest
    process — native/tpumx_io_test.cpp TestDetLabelBoundsOverflow does
    that (rlimit + bad_alloc, mutation-checked); this test pins the
    public-surface behavior."""
    path = str(tmp_path / "corrupt.rec")
    rec = recordio.MXRecordIO(path, "w")
    # flag = 0x40000006 = 1073741830: a true multiple of 5 whose flag*4
    # wraps to 24 in uint32 — under uint32 bounds math 24 <= the 64-byte
    # payload would pass the check; the size_t math rejects it
    assert 0x40000006 % 5 == 0 and (0x40000006 * 4) % 2 ** 32 == 24
    header = recordio.IRHeader(0x40000006, 0.0, 0, 0)
    import struct
    payload = struct.pack("<IfQQ", *header) + b"\x00" * 64
    rec.write(payload)
    rec.close()
    p = _det_pipe(path, batch_size=1, max_objects=2)
    with pytest.raises(IOError, match="decode failed"):
        p.next_batch()
    p.close()


@pytest.mark.slow
def test_native_cpp_unit_tier(tmp_path):
    """The C++ unit tier (SURVEY §4 REF:tests/cpp analog): compile and
    run native/tpumx_io_test.cpp — HashUniform determinism,
    ResizeBilinear invariants, RecordIO scan incl. corrupt magic, and
    the det label-header uint32-overflow regression, all at the C++
    level where Python tests can't reach."""
    import subprocess
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    src = os.path.join(repo, "native", "tpumx_io_test.cpp")
    binary = str(tmp_path / "tpumx_io_test")
    cc = subprocess.run(["g++", "-O1", "-std=c++17", src, "-o", binary,
                         "-ljpeg", "-lpthread"], timeout=180,
                        capture_output=True, text=True)
    assert cc.returncode == 0, f"native test compile failed:\n{cc.stderr}"
    out = subprocess.run([binary], capture_output=True, text=True,
                         timeout=60)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "ALL PASS" in out.stdout


def test_image_det_record_iter_python_fallback(det_rec_file):
    """use_native=False path: same iterator contract (shapes, label
    layout, epoch length) through the Python augmenters."""
    path, _ = det_rec_file
    it = mx.io.ImageDetRecordIter(path, (3, 48, 48), batch_size=4,
                                  use_native=False)
    batches = list(it)
    assert len(batches) == 4
    assert batches[0].data[0].shape == (4, 3, 48, 48)
    assert batches[0].label[0].shape == (4, 3, 5)
    lab = batches[0].label[0].asnumpy()
    valid = lab[lab[:, :, 0] >= 0]
    assert (valid[:, 1:] >= 0).all() and (valid[:, 1:] <= 1).all()
    it.reset()
    assert len(list(it)) == 4


def test_native_u8_nhwc_matches_f32_nchw(rec_file):
    """The uint8/NHWC TPU-feed variant must make the SAME augment
    decisions (counter-hash PRNG) and, normalized downstream, match the
    f32/NCHW output to float rounding."""
    path, _ = rec_file
    mean, std = (10.0, 20.0, 30.0), (2.0, 3.0, 4.0)
    kw = dict(batch_size=8, data_shape=(3, 32, 32), resize=40,
              rand_crop=True, rand_mirror=True, mean=mean, std=std,
              preprocess_threads=2, shuffle=True, seed=5)
    p32 = _pipe(path, **kw)
    pu8 = _pipe(path, output_dtype="uint8", output_layout="NHWC", **kw)
    d1, l1 = p32.next_batch()
    d2, l2 = pu8.next_batch()
    assert d1.dtype == np.float32 and d1.shape == (8, 3, 32, 32)
    assert d2.dtype == np.uint8 and d2.shape == (8, 32, 32, 3)
    np.testing.assert_array_equal(l1, l2)
    norm = (d2.astype(np.float32) - np.asarray(mean, np.float32)) \
        / np.asarray(std, np.float32)
    np.testing.assert_allclose(d1, norm.transpose(0, 3, 1, 2), atol=1e-5)
    p32.close()
    pu8.close()


def test_image_record_iter_output_flags(rec_file):
    """mx.io.ImageRecordIter surfaces the TPU-feed flags on both the
    native and the Python-fallback paths, with matching provide_data."""
    path, _ = rec_file
    for use_native in (True, False):
        it = mx.io.ImageRecordIter(
            path_imgrec=path, data_shape=(3, 32, 32), batch_size=8,
            resize=40, use_native=use_native, output_dtype="uint8",
            output_layout="NHWC", seed=3)
        assert it.provide_data[0].shape == (8, 32, 32, 3)
        b = it.next()
        arr = b.data[0].asnumpy()
        assert arr.shape == (8, 32, 32, 3)
        assert arr.dtype == np.uint8 or arr.max() > 1.5  # raw pixel range
        # raw pixels: no normalization applied
        assert arr.min() >= 0 and arr.max() <= 255


def test_device_prefetch_iter_normalizes_on_device(rec_file):
    """DevicePrefetchIter(normalize=...) applied to a uint8 NHWC feed
    must equal the host-normalized float iterator output."""
    path, _ = rec_file
    mean, std = (10.0, 20.0, 30.0), (2.0, 3.0, 4.0)
    common = dict(path_imgrec=path, data_shape=(3, 32, 32), batch_size=8,
                  resize=40, seed=11)
    it_f32 = mx.io.ImageRecordIter(
        mean_r=mean[0], mean_g=mean[1], mean_b=mean[2],
        std_r=std[0], std_g=std[1], std_b=std[2], **common)
    it_u8 = mx.io.DevicePrefetchIter(
        mx.io.ImageRecordIter(output_dtype="uint8", output_layout="NHWC",
                              **common),
        normalize=(mean, std), normalize_axis=-1)
    b1 = it_f32.next()
    b2 = it_u8.next()
    a1 = b1.data[0].asnumpy()                      # (B, C, H, W) normalized
    a2 = b2.data[0].asnumpy().transpose(0, 3, 1, 2)
    np.testing.assert_allclose(a1, a2, atol=1e-5)
    # labels untouched by normalize
    np.testing.assert_array_equal(b1.label[0].asnumpy(),
                                  b2.label[0].asnumpy())


def test_det_pipe_u8_nhwc_matches_f32_nchw(det_rec_file):
    """Det pipe TPU-feed variant: same counter-hash augment decisions, so
    u8/NHWC normalized downstream must match f32/NCHW, boxes identical."""
    path, _ = det_rec_file
    mean, std = (5.0, 6.0, 7.0), (2.0, 2.5, 3.0)
    kw = dict(rand_crop=True, rand_mirror=True, mean=mean, std=std,
              shuffle=True, seed=9)
    p32 = _det_pipe(path, **kw)
    pu8 = _det_pipe(path, output_dtype="uint8", output_layout="NHWC", **kw)
    d1, l1 = p32.next_batch()
    d2, l2 = pu8.next_batch()
    assert d1.shape == (4, 3, 48, 48) and d1.dtype == np.float32
    assert d2.shape == (4, 48, 48, 3) and d2.dtype == np.uint8
    np.testing.assert_array_equal(l1, l2)  # boxes bit-identical
    norm = (d2.astype(np.float32) - np.asarray(mean, np.float32)) \
        / np.asarray(std, np.float32)
    np.testing.assert_allclose(d1, norm.transpose(0, 3, 1, 2), atol=1e-5)
    p32.close()
    pu8.close()


def test_image_det_record_iter_u8_nhwc(det_rec_file):
    """mx.io.ImageDetRecordIter carries the TPU-feed flags (native-only;
    the variants must refuse the Python fallback rather than silently
    change contract)."""
    path, _ = det_rec_file
    it = mx.io.ImageDetRecordIter(path, (3, 48, 48), batch_size=4,
                                  output_dtype="uint8",
                                  output_layout="NHWC")
    assert it.provide_data[0].shape == (4, 48, 48, 3)
    b = it.next()
    arr = b.data[0].asnumpy()
    assert arr.shape == (4, 48, 48, 3) and arr.min() >= 0 and arr.max() <= 255
    assert b.label[0].shape == (4, it.max_objects, 5)
    with pytest.raises(Exception):
        mx.io.ImageDetRecordIter(path, (3, 48, 48), batch_size=4,
                                 output_dtype="uint8", use_native=False)


def test_device_prefetch_normalize_nchw_axis(rec_file):
    """The u8/NCHW + normalize_axis=1 combination (the SSD example's
    feed) must equal host-side f32 normalization too."""
    path, _ = rec_file
    mean, std = (9.0, 19.0, 29.0), (2.0, 4.0, 8.0)
    common = dict(path_imgrec=path, data_shape=(3, 32, 32), batch_size=8,
                  resize=40, seed=13)
    it_f32 = mx.io.ImageRecordIter(
        mean_r=mean[0], mean_g=mean[1], mean_b=mean[2],
        std_r=std[0], std_g=std[1], std_b=std[2], **common)
    it_u8 = mx.io.DevicePrefetchIter(
        mx.io.ImageRecordIter(output_dtype="uint8", **common),
        normalize=(mean, std), normalize_axis=1)
    a1 = it_f32.next().data[0].asnumpy()
    a2 = it_u8.next().data[0].asnumpy()
    np.testing.assert_allclose(a1, a2, atol=1e-5)


def test_native_library_keyed_by_content(tmp_path, monkeypatch):
    """The built library's name carries a hash of the source and the
    compiler command line: a second build from the same source lands on
    the same name, edited source on another, and a foreign .so under the
    old unkeyed name (e.g. copied in from another machine) is never
    loaded."""
    import ctypes
    from tpu_mx import lib
    src = os.path.abspath(lib._SRC)
    in_tree = lib.ensure_built()
    assert lib.build_key(src) in os.path.basename(in_tree)
    edited = tmp_path / "edited.cpp"
    with open(src, "rb") as f:
        edited.write_bytes(f.read() + b"\n// edited\n")
    assert lib.build_key(str(edited)) != lib.build_key(src)

    monkeypatch.setattr(lib, "_LIB_DIR", str(tmp_path))
    foreign = tmp_path / "libtpumx_io.so"
    foreign.write_bytes(b"built for another machine")
    rebuilt = lib.ensure_built()
    assert os.path.dirname(rebuilt) == str(tmp_path)
    assert os.path.basename(rebuilt) == os.path.basename(in_tree)
    assert rebuilt != str(foreign)
    ctypes.CDLL(rebuilt)
