"""TCPStore (SURVEY D3) + paddle.distributed.rpc (D10). The RPC test
spawns three real worker processes — the reference's multi-process RPC
test pattern (test/rpc/)."""
import os
import subprocess
import sys
import textwrap
import threading

import pytest

import _children

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from paddle_tpu.distributed.store import TCPStore


def test_tcp_store_basics():
    master = TCPStore("127.0.0.1", 0, world_size=2, is_master=True, timeout=30)
    client = TCPStore("127.0.0.1", master.port, world_size=2, timeout=30)
    master.set("k", b"v")
    assert client.get("k") == b"v"
    assert client.add("ctr", 3) == 3
    assert master.add("ctr", 2) == 5
    assert client.delete_key("k") is True
    with pytest.raises(TimeoutError):
        client.get("missing", timeout=0.2)
    # blocking get is released by a later set
    got = []
    t = threading.Thread(
        target=lambda: got.append(master.get("late", timeout=5)))
    t.start()
    client.set("late", b"now")
    t.join(timeout=5)
    assert got == [b"now"]
    client.close()
    master.close()


def test_tcp_store_barrier():
    master = TCPStore("127.0.0.1", 0, world_size=3, is_master=True, timeout=30)
    clients = [TCPStore("127.0.0.1", master.port, timeout=30)
               for _ in range(2)]
    done = []

    def arrive(s, i):
        s.barrier("b1", 3, timeout=10)
        done.append(i)

    ts = [threading.Thread(target=arrive, args=(s, i))
          for i, s in enumerate(clients)]
    for t in ts:
        t.start()
    assert not done  # blocked until the third participant arrives
    master.barrier("b1", 3, timeout=10)
    for t in ts:
        t.join(timeout=10)
    assert sorted(done) == [0, 1]
    for s in clients + [master]:
        s.close()


WORKER = """
import os
import paddle_tpu.distributed.rpc as rpc

def add(a, b):
    return a + b

def whoami():
    return rpc.get_current_worker_info().name

def boom():
    raise ValueError("remote boom")

rank = int(os.environ["PADDLE_TRAINER_ID"])
me = rpc.init_rpc(f"worker{rank}", rank=rank, world_size=3,
                  master_endpoint=os.environ["MASTER"])
infos = rpc.get_all_worker_infos()
assert len(infos) == 3, infos
assert rpc.get_worker_info("worker0").rank == 0

# every worker calls its right neighbor
peer = f"worker{(rank + 1) % 3}"
assert rpc.rpc_sync(peer, add, args=(rank, 10)) == rank + 10
fut = rpc.rpc_async(peer, whoami)
assert fut.wait(15) == peer

if rank == 0:
    try:
        rpc.rpc_sync("worker1", boom)
        raise SystemExit("expected remote exception")
    except ValueError as e:
        assert "remote boom" in str(e)

rpc.shutdown()
print("RPC_OK", rank)
"""


def _free_port():
    import socket
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_rpc_three_workers(tmp_path):
    script = tmp_path / "rpc_worker.py"
    script.write_text(textwrap.dedent(WORKER))
    port = _free_port()
    procs = []
    try:
        for rank in range(3):
            env = {**os.environ, "PYTHONPATH": _REPO_ROOT,
                   "PADDLE_TRAINER_ID": str(rank),
                   "MASTER": f"127.0.0.1:{port}"}
            procs.append(_children.spawn(
                [sys.executable, str(script)], env=env, cwd=str(tmp_path),
                stderr=subprocess.STDOUT))
        outs = _children.outputs(procs, what="three rpc workers")
        for p, (out, _) in zip(procs, outs):
            assert p.returncode == 0, out
            assert "RPC_OK" in out
    finally:
        _children.kill(*procs)


def test_native_store_backend():
    """The C++ store server (native/store.cc) builds and serves the same
    protocol; full op matrix + barrier against it."""
    from paddle_tpu.distributed import native
    if native._load() is None:
        pytest.skip("no C++ toolchain for the native store")
    master = TCPStore("127.0.0.1", 0, is_master=True, timeout=30)
    assert master.is_native
    client = TCPStore("127.0.0.1", master.port, timeout=30)
    master.set("k", b"v1")
    assert client.get("k") == b"v1"
    client.set("k", b"v2")
    assert master.get("k") == b"v2"
    assert client.add("ctr", 5) == 5
    assert master.add("ctr", -2) == 3
    assert client.delete_key("k") is True
    assert client.delete_key("k") is False
    with pytest.raises(TimeoutError):
        client.get("missing", timeout=0.2)
    got = []
    t = threading.Thread(
        target=lambda: got.append(master.get("late", timeout=5)))
    t.start()
    client.set("late", b"now")
    t.join(timeout=5)
    assert got == [b"now"]
    for it in range(2):  # reusable barrier on the native server
        ts = threading.Thread(
            target=lambda: master.barrier("nb", 2, timeout=10))
        ts.start()
        client.barrier("nb", 2, timeout=10)
        ts.join(5)
        assert not ts.is_alive()
    client.close()
    master.close()


def test_python_fallback_store(monkeypatch):
    monkeypatch.setenv("PDTPU_NATIVE_STORE", "0")
    master = TCPStore("127.0.0.1", 0, is_master=True, timeout=30)
    assert not master.is_native
    client = TCPStore("127.0.0.1", master.port, timeout=30)
    master.set("k", b"v")
    assert client.get("k") == b"v"
    assert client.add("c", 2) == 2
    client.close()
    master.close()
