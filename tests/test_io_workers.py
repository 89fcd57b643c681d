"""Multiprocess DataLoader workers: fork + shared-memory handoff
(reference io/reader.py:216, io/dataloader/worker.py; VERDICT r4
missing #5)."""
import numpy as np
import pytest

import paddle_tpu as paddle


class _SquareDS(paddle.io.Dataset):
    def __len__(self):
        return 23

    def __getitem__(self, i):
        return (np.full((3,), i, np.float32), np.int64(i * i))


def test_mp_workers_match_inprocess_order_and_values():
    from paddle_tpu.io import DataLoader

    a = list(DataLoader(_SquareDS(), batch_size=4, shuffle=False,
                        num_workers=0))
    b = list(DataLoader(_SquareDS(), batch_size=4, shuffle=False,
                        num_workers=3, use_shared_memory=True,
                        timeout=60))
    assert len(a) == len(b) == 6
    for (xa, ya), (xb, yb) in zip(a, b):
        np.testing.assert_array_equal(xa.numpy(), xb.numpy())
        np.testing.assert_array_equal(ya.numpy(), yb.numpy())


class _WorkerProbeDS(paddle.io.Dataset):
    def __len__(self):
        return 8

    def __getitem__(self, i):
        from paddle_tpu.io import get_worker_info
        info = get_worker_info()
        assert info is not None and 0 <= info.id < info.num_workers
        return np.full((2,), info.id, np.float32)


def test_mp_workers_expose_worker_info():
    from paddle_tpu.io import DataLoader, get_worker_info

    assert get_worker_info() is None  # trainer process
    out = list(DataLoader(_WorkerProbeDS(), batch_size=2, shuffle=False,
                          num_workers=2, timeout=60))
    assert len(out) == 4


class _CrashDS(paddle.io.Dataset):
    def __len__(self):
        return 8

    def __getitem__(self, i):
        if i == 5:
            import os
            os._exit(3)          # simulated segfault in user data code
        return np.float32(i)


def test_mp_worker_crash_is_isolated():
    from paddle_tpu.io import DataLoader

    loader = DataLoader(_CrashDS(), batch_size=2, shuffle=False,
                        num_workers=2, timeout=60)
    with pytest.raises(RuntimeError, match="died|failed"):
        list(loader)


class _ShardedIterable(paddle.io.IterableDataset):
    """Shards itself via get_worker_info — the reference/torch contract
    (the loader must NOT also stride, or data would be lost)."""

    def __iter__(self):
        from paddle_tpu.io import get_worker_info
        info = get_worker_info()
        wid = info.id if info else 0
        n = info.num_workers if info else 1
        for i in range(12):
            if i % n == wid:
                yield np.full((2,), i, np.float32)


def test_mp_workers_iterable_dataset_shards_itself():
    from paddle_tpu.io import DataLoader

    out = list(DataLoader(_ShardedIterable(), batch_size=3,
                          num_workers=2, timeout=60))
    got = sorted(int(b.numpy()[r, 0]) for b in out
                 for r in range(b.shape[0]))
    assert got == list(range(12))


class _TensorDS(paddle.io.Dataset):
    """Dataset returning framework Tensors (worked via the threaded
    path pre-r5; must keep working through forked workers)."""

    def __len__(self):
        return 6

    def __getitem__(self, i):
        return paddle.to_tensor(np.full((2,), i, np.float32))


def test_mp_workers_accept_tensor_datasets():
    from paddle_tpu.io import DataLoader

    out = list(DataLoader(_TensorDS(), batch_size=2, shuffle=False,
                          num_workers=2, timeout=60))
    assert len(out) == 3
    np.testing.assert_array_equal(out[0].numpy()[:, 0], [0.0, 1.0])


def test_mp_workers_early_break_leaks_no_shm():
    import glob

    from paddle_tpu.io import DataLoader

    before = set(glob.glob("/dev/shm/psm_*"))
    loader = DataLoader(_SquareDS(), batch_size=2, shuffle=False,
                        num_workers=2, timeout=60)
    for step, _batch in enumerate(loader):
        if step == 1:
            break
    import time
    time.sleep(0.5)
    leaked = set(glob.glob("/dev/shm/psm_*")) - before
    assert not leaked, f"leaked shm segments: {leaked}"


def test_mp_workers_large_dataset_no_deadlock():
    # code-review r5: enqueue-all-then-drain deadlocked once the task
    # pipe filled; the bounded in-flight window must stream any size
    from paddle_tpu.io import DataLoader

    class Big(paddle.io.Dataset):
        def __len__(self):
            return 4000

        def __getitem__(self, i):
            return np.full((8,), i, np.float32)

    n = 0
    for batch in DataLoader(Big(), batch_size=8, shuffle=False,
                            num_workers=2, timeout=60):
        n += 1
    assert n == 500


def test_consumer_shm_attach_untracked(monkeypatch):
    """ADVICE r5 low: attaching (create=False) registers the segment
    with the CONSUMER's resource_tracker on CPython <= 3.12; since
    _decode immediately unlinks, that registration must be dropped or
    the tracker reports 'leaked shared_memory' at shutdown.  The
    register/unregister calls seen by this process must balance."""
    from multiprocessing import resource_tracker

    from paddle_tpu.io import worker as w

    calls = {"register": [], "unregister": []}
    orig_reg = resource_tracker.register
    orig_unreg = resource_tracker.unregister

    def reg(name, rtype):
        if rtype == "shared_memory":
            calls["register"].append(name)
        return orig_reg(name, rtype)

    def unreg(name, rtype):
        if rtype == "shared_memory":
            calls["unregister"].append(name)
        return orig_unreg(name, rtype)

    monkeypatch.setattr(resource_tracker, "register", reg)
    monkeypatch.setattr(resource_tracker, "unregister", unreg)
    arr = np.arange(12, dtype=np.float32).reshape(3, 4)
    # in-process round trip exercises BOTH sides' tracker bookkeeping:
    # _encode (creator) and _decode (consumer attach + unlink)
    desc = w._encode({"x": arr, "n": 3})
    out = w._decode(desc)
    np.testing.assert_array_equal(out["x"], arr)
    assert sorted(calls["register"]) == sorted(calls["unregister"])
    # the abandoned-batch path unlinks AND untracks too
    desc2 = w._encode([arr])
    w._unlink_desc(desc2)
    assert sorted(calls["register"]) == sorted(calls["unregister"])
