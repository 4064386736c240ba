"""Data-path tests (SURVEY.md §4.5: reader decorators, datasets, feeder)."""

import numpy as np
import pytest

from paddle_tpu import data as pdata
from paddle_tpu.core import SeqBatch
from paddle_tpu.data import (DataFeeder, DenseSlot, DoubleBuffer, IndexSlot,
                             SeqSlot, SparseSlot, batch, buffered, chain,
                             compose, firstn, map_readers, shuffle, xmap_readers)
from paddle_tpu.data.dataset import (cifar, conll05, criteo, imdb, imikolov,
                                     mnist, movielens, mq2007, uci_housing,
                                     wmt14)


def _r(xs):
    return lambda: iter(xs)


def test_reader_decorators():
    assert list(map_readers(lambda a, b: a + b, _r([1, 2]), _r([10, 20]))()) == [11, 22]
    assert sorted(shuffle(_r(range(10)), 4, seed=0)()) == list(range(10))
    assert list(chain(_r([1]), _r([2, 3]))()) == [1, 2, 3]
    assert list(compose(_r([1, 2]), _r([(3, 4), (5, 6)]))()) == [(1, 3, 4), (2, 5, 6)]
    assert list(buffered(_r(range(5)), 2)()) == list(range(5))
    assert list(firstn(_r(range(100)), 3)()) == [0, 1, 2]
    got = sorted(xmap_readers(lambda x: x * 2, _r(range(8)), 3, 4)())
    assert got == [0, 2, 4, 6, 8, 10, 12, 14]
    got = list(xmap_readers(lambda x: x * 2, _r(range(8)), 3, 4, order=True)())
    assert got == [0, 2, 4, 6, 8, 10, 12, 14]
    bs = list(batch(_r(range(7)), 3)())
    assert bs == [[0, 1, 2], [3, 4, 5], [6]]
    assert list(batch(_r(range(7)), 3, drop_last=True)()) == [[0, 1, 2], [3, 4, 5]]


def test_compose_misaligned_raises():
    with pytest.raises(ValueError):
        list(compose(_r([1]), _r([1, 2]))())


def test_buffered_propagates_errors():
    def bad():
        yield 1
        raise RuntimeError("boom")
    with pytest.raises(RuntimeError):
        list(buffered(lambda: bad(), 2)())


def test_pad_to_bucket_and_next_bucket():
    from paddle_tpu.data.feeder import BucketSpec, next_bucket, pad_to_bucket
    assert next_bucket(5, (8, 16)) == 8
    assert next_bucket(9, (8, 16)) == 16
    assert next_bucket(17, (8, 16)) == 32     # pow-2 overflow past the list
    assert next_bucket(3) == 4                # no list: pure pow-2
    arr = np.arange(10, dtype=np.float32).reshape(2, 5)
    padded, true_len = pad_to_bucket(arr, 1, (8,))
    assert padded.shape == (2, 8) and true_len == 5
    np.testing.assert_array_equal(padded[:, :5], arr)
    assert np.all(padded[:, 5:] == 0)
    same, n = pad_to_bucket(padded, 1, (8,))  # already on a bucket: no-op
    assert same is padded and n == 8
    spec = BucketSpec({"w": (8,), "x": {"axis": 0, "buckets": (4,)}})
    p, n = spec.pad("w", arr)                 # default axis 1 for rank-2
    assert p.shape == (2, 8) and n == 5
    p, n = spec.pad("x", arr)                 # pinned axis 0
    assert p.shape == (4, 5) and n == 2


def test_feeder_dense_index_seq_sparse():
    feeder = DataFeeder([DenseSlot(3), IndexSlot(), SeqSlot(),
                         SparseSlot(100)])
    rows = [
        (np.ones(3), 1, [1, 2, 3], [4, 7]),
        (np.zeros(3), 0, [5], [9]),
    ]
    dense, idx, seq, (sp_ids, sp_vals) = feeder.feed(rows)
    assert dense.shape == (2, 3)
    assert idx.shape == (2,) and int(idx[0]) == 1
    assert isinstance(seq, SeqBatch)
    assert seq.data.shape[0] == 2 and int(seq.lengths[0]) == 3
    assert sp_ids.shape == sp_vals.shape and sp_ids.shape[0] == 2
    np.testing.assert_allclose(np.asarray(sp_vals[0])[:2], [1.0, 1.0])


def test_feeder_nested_seq():
    feeder = DataFeeder([SeqSlot(nested=True)])
    rows = [([[1, 2], [3]],), ([[4]],)]
    (nb,) = feeder.feed(rows)
    # 2-level LoD: [B, S, T] + sub/seq lengths (Argument.h:84-90 analog)
    assert nb.data.shape[:2] == (2, 2)
    np.testing.assert_array_equal(np.asarray(nb.seq_lengths), [2, 1])
    np.testing.assert_array_equal(np.asarray(nb.sub_lengths),
                                  [[2, 1], [1, 0]])
    np.testing.assert_array_equal(np.asarray(nb.data[0, 0, :2]), [1, 2])


def test_double_buffer_order_and_errors():
    out = list(DoubleBuffer(lambda: iter(range(10)), depth=3))
    assert out == list(range(10))
    def bad():
        yield 1
        raise ValueError("x")
    with pytest.raises(ValueError):
        list(DoubleBuffer(lambda: bad()))


@pytest.mark.parametrize("ds,checks", [
    (mnist, lambda s: (len(s[0]) == 784, 0 <= s[1] < 10)),
    (uci_housing, lambda s: (len(s[0]) == 13, len(s[1]) == 1)),
])
def test_dense_datasets(ds, checks):
    samples = list(firstn(ds.train(64), 5)())
    assert len(samples) == 5
    for s in samples:
        assert all(checks(s))
    # deterministic
    again = list(firstn(ds.train(64), 5)())
    np.testing.assert_allclose(again[0][0], samples[0][0])


def test_seq_datasets_schema():
    for ids, label in firstn(imdb.train(16), 4)():
        assert all(0 <= i < imdb.VOCAB for i in ids) and label in (0, 1)
    for tup in firstn(imikolov.train(16), 4)():
        assert len(tup) == 5
    for src, tin, tout in firstn(wmt14.train(16), 4)():
        assert len(tin) == len(tout) == len(src) + 1
        assert tin[0] == wmt14.START and tout[-1] == wmt14.END
    for words, tags in firstn(conll05.train(16), 4)():
        assert len(words) == len(tags)
    for u, g, a, j, m, cats, r in firstn(movielens.train(16), 4)():
        assert 1.0 <= r <= 5.0 and len(cats) >= 1
    for q, x, rel in firstn(mq2007.train(4), 4)():
        assert x.shape == (46,) and rel in (0, 1, 2)
    for dense, ids, y in firstn(criteo.train(16), 4)():
        assert len(dense) == 13 and len(ids) == 26 and y in (0, 1)
    for img, label in firstn(cifar.train10(8), 2)():
        assert len(img) == 3072


def test_image_pipeline_extras(tmp_path):
    """image.py parity additions: to_chw, PIL decode, load_and_transform,
    batch_images_from_tar (python/paddle/v2/image.py)."""
    import tarfile

    from PIL import Image

    from paddle_tpu.data import image as I

    im = np.random.RandomState(0).randint(0, 255, (40, 50, 3)).astype(np.uint8)
    chw = I.to_chw(im)
    assert chw.shape == (3, 40, 50)

    p = str(tmp_path / "im.png")
    Image.fromarray(im).save(p)
    back = I.load_image(p)
    np.testing.assert_array_equal(back, im)
    gray = I.load_image(p, is_color=False)
    assert gray.shape == (40, 50, 1)

    out = I.load_and_transform(p, resize=32, crop=24, is_train=False,
                               mean=[127.5, 127.5, 127.5])
    assert out.shape == (24, 24, 3)

    # tar batching
    tar_p = str(tmp_path / "imgs.tar")
    with tarfile.open(tar_p, "w") as tf:
        for i in range(5):
            q = str(tmp_path / f"i{i}.png")
            Image.fromarray(im).save(q)
            tf.add(q, arcname=f"i{i}.png")
    listfile = I.batch_images_from_tar(
        tar_p, "toy", {f"i{i}.png": i for i in range(5)}, num_per_batch=2)
    import pickle
    batches = open(listfile).read().splitlines()
    assert len(batches) == 3
    b0 = pickle.load(open(batches[0], "rb"))
    assert len(b0["data"]) == 2 and b0["label"] == [0, 1]
    assert I.load_image_bytes(b0["data"][0]).shape == (40, 50, 3)


def test_flowers_voc_datasets():
    from paddle_tpu.data.dataset import flowers, voc2012

    im, lb = next(iter(flowers.train(4)()))
    assert im.shape == (64, 64, 3) and im.dtype == np.uint8
    assert 0 <= lb < flowers.CLASSES
    # mapper pipeline like flowers.default_mapper
    from paddle_tpu.data import image as I
    mapped = next(iter(flowers.train(
        4, mapper=lambda s: (I.simple_transform(s[0], 48, 32, True), s[1]))()))
    assert mapped[0].shape == (32, 32, 3)

    img, mask = next(iter(voc2012.train(2)()))
    assert img.shape == (64, 64, 3) and mask.shape == (64, 64)
    assert mask.max() < voc2012.CLASSES


def test_mix_reader_ratio_and_drain():
    """MultiDataProvider analog: ratio-weighted interleave, exhausted
    sub-readers drop out, every sample eventually delivered."""
    from paddle_tpu.data import mix

    a = lambda: iter([("a", i) for i in range(30)])
    b = lambda: iter([("b", i) for i in range(10)])
    got = list(mix([(a, 3.0), (b, 1.0)], seed=0)())
    assert len(got) == 40
    assert sum(1 for s in got if s[0] == "a") == 30
    first20 = [s[0] for s in got[:20]]
    assert first20.count("a") > first20.count("b")   # ratio bias visible

    import pytest as _pytest
    with _pytest.raises(ValueError):
        mix([(a, 1.0), (b, 0.0)])


def test_binary_dataformat_roundtrip(tmp_path):
    """proto DataFormat parity (SURVEY §8.2): header+samples stream with the
    full slot classification (dense / sparse ±value / index / string, each
    optionally (nested) sequence) round-trips and feeds the pipeline."""
    from paddle_tpu.data import batch, format as F

    slots = [
        F.SlotDef(F.DENSE, dim=3),
        F.SlotDef(F.SPARSE_NON_VALUE, dim=100),
        F.SlotDef(F.SPARSE_VALUE, dim=100),
        F.SlotDef(F.INDEX),
        F.SlotDef(F.STRING),
        F.SlotDef(F.INDEX, seq=F.SEQ),
        F.SlotDef(F.DENSE, dim=2, seq=F.SUB_SEQ),
    ]
    samples = [
        (np.array([1.0, 2.0, 3.0], np.float32),
         [3, 7, 42],
         [(1, 0.5), (9, 2.5)],
         4,
         "hello world",
         [5, 6, 7, 8],
         [[np.array([1.0, 2.0], np.float32)],
          [np.array([3.0, 4.0], np.float32),
           np.array([5.0, 6.0], np.float32)]]),
        (np.array([9.0, 8.0, 7.0], np.float32),
         [],
         [],
         0,
         "",
         [1],
         [[np.array([0.5, 0.5], np.float32)]]),
    ]
    path = str(tmp_path / "data.ptdf")
    with open(path, "wb") as f:
        w = F.DataWriter(f, slots)
        for s in samples:
            w.write(s)

    with open(path, "rb") as f:
        r = F.DataReader(f)
        assert r.slots == slots
        back = list(r)
    assert len(back) == 2
    np.testing.assert_allclose(back[0][0], samples[0][0])
    assert back[0][1] == [3, 7, 42]
    assert back[0][2] == [(1, 0.5), (9, 2.5)]
    assert back[0][3] == 4 and back[0][4] == "hello world"
    assert back[0][5] == [5, 6, 7, 8]
    np.testing.assert_allclose(back[0][6][1][1], [5.0, 6.0])
    assert back[1][1] == [] and back[1][4] == ""

    # plugs into the decorator pipeline
    rows = list(batch(F.reader_creator(path), 2)())
    assert len(rows) == 1 and len(rows[0]) == 2

    # corrupted magic fails loudly
    with open(path, "rb") as f:
        bad = bytearray(f.read())
    bad[0] ^= 0xFF
    (tmp_path / "bad.ptdf").write_bytes(bytes(bad))
    with open(str(tmp_path / "bad.ptdf"), "rb") as f, \
            pytest.raises(IOError):
        F.DataReader(f)

    # corrupt in-record count fails loudly too (not silent truncation)
    good = bytearray(bad)
    good[0] ^= 0xFF                        # restore magic
    good[-30] ^= 0x7F                      # scramble a payload count/byte
    (tmp_path / "bad2.ptdf").write_bytes(bytes(good))
    with open(str(tmp_path / "bad2.ptdf"), "rb") as f:
        with pytest.raises((IOError, UnicodeDecodeError, ValueError)):
            list(F.DataReader(f))

    # dim enforcement at write time
    with open(str(tmp_path / "x.ptdf"), "wb") as f:
        w2 = F.DataWriter(f, [F.SlotDef(F.DENSE, dim=3)])
        with pytest.raises(ValueError):
            w2.write((np.zeros(5, np.float32),))
