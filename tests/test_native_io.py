"""Native C++ IO runtime tests (analog of the reference's
buffered_reader / blocking_queue C++ unit tests, SURVEY §4)."""
import threading

import numpy as np
import pytest

from paddle_tpu.io import native

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="no C++ toolchain")


def test_normalize_batch_matches_numpy():
    rng = np.random.default_rng(0)
    src = rng.integers(0, 256, (8, 16, 12, 3), dtype=np.uint8)
    mean = [10.0, 20.0, 30.0]
    std = [2.0, 3.0, 4.0]
    out = native.normalize_batch(src, mean, std, to_chw=True)
    ref = ((src.astype(np.float32) - np.float32(mean)) /
           np.float32(std)).transpose(0, 3, 1, 2)
    np.testing.assert_allclose(out, ref, atol=1e-5)
    out2 = native.normalize_batch(src, mean, std, to_chw=False)
    np.testing.assert_allclose(
        out2, (src.astype(np.float32) - np.float32(mean)) /
        np.float32(std), atol=1e-5)


def test_nhwc_to_nchw_and_gather():
    rng = np.random.default_rng(1)
    x = rng.normal(size=(4, 5, 6, 3)).astype("float32")
    np.testing.assert_array_equal(native.nhwc_to_nchw(x),
                                  x.transpose(0, 3, 1, 2))
    base = rng.integers(0, 255, (10, 33), dtype=np.uint8)
    idx = np.array([9, 0, 3, 3], np.int64)
    np.testing.assert_array_equal(native.gather_rows(base, idx), base[idx])


def test_native_queue_producer_consumer():
    q = native.NativeQueue(capacity=2)
    payloads = [np.full((5,), i, np.int32) for i in range(6)]
    got = []

    def producer():
        for p in payloads:
            assert q.push(p)
        q.close()

    t = threading.Thread(target=producer)
    t.start()
    while True:
        item = q.pop(20, np.int32, (5,))
        if item is None:
            break
        got.append(item.copy())
    t.join()
    assert len(got) == 6
    for i, g in enumerate(got):
        np.testing.assert_array_equal(g, payloads[i])


def test_queue_capacity_blocks():
    q = native.NativeQueue(capacity=1)
    assert q.push(np.zeros(3, np.uint8))
    assert q.size() == 1
    state = {}

    def push_second():
        state["r"] = q.push(np.ones(3, np.uint8))

    t = threading.Thread(target=push_second)
    t.start()
    t.join(timeout=0.2)
    assert t.is_alive()  # blocked on capacity
    q.pop(3)
    t.join(timeout=2)
    assert not t.is_alive() and state["r"]
    q.close()


def test_batch_normalize_transform():
    from paddle_tpu.vision.transforms import BatchNormalize
    rng = np.random.default_rng(2)
    src = rng.integers(0, 256, (4, 8, 8, 1), dtype=np.uint8)
    out = BatchNormalize([127.5], [127.5])(src)
    assert out.shape == (4, 1, 8, 8) and out.dtype == np.float32
    with pytest.raises(ValueError):
        BatchNormalize([0.0], [1.0])(src.astype("float32"))



def test_native_build_decides_by_source_hash(tmp_path):
    """The libraries are git-ignored, so a copy of the tree can carry a
    stale one with any file time: a rebuild is decided by the hash of
    the source kept beside the library, never by mtimes."""
    import ctypes
    import os
    import shutil

    from paddle_tpu.utils import native_build as nb
    if shutil.which("g++") is None:
        pytest.skip("no g++")
    src, so = str(tmp_path / "v.cc"), str(tmp_path / "libv.so")

    def build(value):
        with open(src, "w") as f:
            f.write(f'extern "C" int v() {{ return {value}; }}\n')
        nb._cache.pop(so, None)
        return nb.build_and_load(src, so)

    assert build(1).v() == 1
    # a source edit with an OLDER file time than the library still
    # rebuilds (dlopen caches by path: check the stamp, then a fresh
    # handle on a copy)
    old = os.path.getmtime(so) - 1000
    with open(src, "w") as f:
        f.write('extern "C" int v() { return 2; }\n')
    os.utime(src, (old, old))
    nb._cache.pop(so, None)
    assert nb.build_and_load(src, so) is not None
    copy = str(tmp_path / "libv2.so")
    shutil.copy(so, copy)
    assert ctypes.CDLL(copy).v() == 2
    # a library without its stamp (a stale binary carried along by a
    # copy of the tree) is rebuilt as well
    os.remove(so + ".sha256")
    with open(so, "wb") as f:
        f.write(b"not a library")
    nb._cache.pop(so, None)
    assert nb.build_and_load(src, so) is not None
