"""Parameter-server mode (SURVEY D9/D24): dense/sparse tables with
server-side accessors, sync + async semantics, the SparseEmbedding
worker layer, and the fleet PS role flow. Servers run in threads (they
are pure-Python TCP services); a subprocess test proves the role env
contract end to end."""
import os
import subprocess
import sys
import textwrap
import threading

import numpy as np
import pytest

import _children

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

import paddle_tpu as paddle
from paddle_tpu.distributed.ps import (PsClient, PsServer, PSOptimizer,
                                       SparseEmbedding)


@pytest.fixture()
def cluster():
    servers = [PsServer("127.0.0.1:0", n_workers=1).start()
               for _ in range(2)]
    client = PsClient([f"127.0.0.1:{s.port}" for s in servers])
    yield servers, client
    client.stop_servers()
    client.close()


def test_dense_table_sgd(cluster):
    _, client = cluster
    client.create_dense_table("w", (3,), rule="sgd", lr=0.1)
    client.init_dense("w", np.ones(3, np.float32))
    client.push_dense("w", np.full(3, 2.0, np.float32))
    value, version = client.pull_dense("w")
    np.testing.assert_allclose(value, 1.0 - 0.1 * 2.0)
    assert version == 1


def test_dense_table_adam_matches_local(cluster):
    _, client = cluster
    client.create_dense_table("w", (4,), rule="adam", lr=0.01)
    w0 = np.arange(4, dtype=np.float32)
    client.init_dense("w", w0)
    g = np.full(4, 0.5, np.float32)
    for _ in range(3):
        client.push_dense("w", g)
    value, _ = client.pull_dense("w")
    # local adam reference
    m = v = np.zeros(4, np.float32)
    w = w0.copy()
    for t in range(1, 4):
        m = 0.9 * m + 0.1 * g
        v = 0.999 * v + 0.001 * g * g
        w = w - 0.01 * (m / (1 - 0.9 ** t)) / (
            np.sqrt(v / (1 - 0.999 ** t)) + 1e-8)
    np.testing.assert_allclose(value, w, rtol=1e-6)


def test_sparse_rows_shard_across_servers(cluster):
    servers, client = cluster
    client.create_sparse_table("emb", 4, rule="sgd", lr=1.0)
    ids = np.array([0, 1, 2, 3, 7, 8])
    rows = client.pull_sparse("emb", ids)
    assert rows.shape == (6, 4)
    # rows shard id % 2 across the two server nodes
    assert set(servers[0]._sparse["emb"].rows) == {0, 2, 8}
    assert set(servers[1]._sparse["emb"].rows) == {1, 3, 7}
    # push a grad of 1 to every row: value drops by lr * 1
    client.push_sparse("emb", ids, np.ones((6, 4), np.float32))
    rows2 = client.pull_sparse("emb", ids)
    np.testing.assert_allclose(rows2, rows - 1.0, atol=1e-6)
    # duplicate id pull returns consistent rows
    r = client.pull_sparse("emb", np.array([5, 5]))
    np.testing.assert_allclose(r[0], r[1])


def test_sync_mode_waits_for_all_workers():
    server = PsServer("127.0.0.1:0", n_workers=2, sync=True).start()
    c1 = PsClient([f"127.0.0.1:{server.port}"])
    c2 = PsClient([f"127.0.0.1:{server.port}"])
    c1.create_dense_table("w", (2,), rule="sgd", lr=0.5)
    c1.init_dense("w", np.zeros(2, np.float32))

    v1 = c1.push_dense("w", np.ones(2, np.float32))
    # push returns the version that WILL contain this update (not yet
    # applied: only 1 of 2 workers pushed) — pulling at it must block
    assert v1 == 1
    got = []
    t = threading.Thread(
        target=lambda: got.append(c1.pull_dense("w", min_version=v1)))
    t.start()
    assert not got
    c2.push_dense("w", np.full(2, 3.0, np.float32))  # completes the step
    t.join(timeout=30)
    assert got, "the pull at the version of a completed step blocked 30 s"
    value, version = got[0]
    # sync applies the WORKER-MEAN grad: (1 + 3)/2 = 2 -> w = -0.5*2
    np.testing.assert_allclose(value, -1.0)
    assert version == 1
    c1.stop_servers()
    c1.close()
    c2.close()


def test_sparse_embedding_trains(cluster):
    """End-to-end: embedding regression through the PS converges."""
    _, client = cluster
    paddle.seed(0)
    emb = SparseEmbedding(client, "emb_t", (100, 8), rule="adam", lr=0.05)
    head = paddle.nn.Linear(8, 1)
    opt = PSOptimizer(client, layers=head, rule="adam", lr=0.05)
    opt._embeddings.append(emb)

    rng = np.random.default_rng(0)
    ids = rng.integers(0, 20, (16,))
    target = (ids % 3).astype("float32").reshape(-1, 1)

    losses = []
    for _ in range(60):
        out = head(emb(paddle.to_tensor(ids)))
        loss = ((out - paddle.to_tensor(target)) ** 2).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.2, (losses[0], losses[-1])


ROLE_SCRIPT = """
import os
import numpy as np
import paddle_tpu.distributed.fleet as fleet

fleet.init(is_collective=False)
if fleet.is_server():
    fleet.run_server()           # blocks until a worker stops it
else:
    assert fleet.is_worker()
    client = fleet.init_worker()
    client.create_dense_table("w", (2,), rule="sgd", lr=0.1)
    client.init_dense("w", np.zeros(2, np.float32))
    client.push_dense("w", np.ones(2, np.float32))
    value, _ = client.pull_dense("w")
    assert np.allclose(value, -0.1), value
    fleet.stop_worker()
    print("PS_ROLE_OK")
"""


def _free_port():
    import socket
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_fleet_ps_role_flow(tmp_path):
    script = tmp_path / "ps_node.py"
    script.write_text(textwrap.dedent(ROLE_SCRIPT))
    port = _free_port()
    base = {**os.environ, "PYTHONPATH": _REPO_ROOT,
            "PADDLE_PSERVERS_IP_PORT_LIST": f"127.0.0.1:{port}",
            "PADDLE_TRAINERS_NUM": "1"}
    server = worker = None
    try:
        server = _children.spawn(
            [sys.executable, str(script)],
            env={**base, "TRAINING_ROLE": "PSERVER",
                 "PADDLE_PORT": str(port)},
            cwd=str(tmp_path), stderr=subprocess.STDOUT)
        worker = _children.spawn(
            [sys.executable, str(script)],
            env={**base, "TRAINING_ROLE": "TRAINER",
                 "PADDLE_TRAINER_ID": "0"},
            cwd=str(tmp_path), stderr=subprocess.STDOUT)
        (wout, _), (sout, _) = _children.outputs(
            [worker, server], what="a trainer and its parameter server")
        assert worker.returncode == 0, wout
        assert "PS_ROLE_OK" in wout
        assert server.returncode == 0, sout
    finally:
        _children.kill(server, worker)


def test_ssd_table_exceeds_memory_budget(cluster):
    """Disk-spilling sparse table (VERDICT r2 missing #4): touch far more
    rows than the memory budget; every row survives eviction round trips
    with exact values."""
    servers, client = cluster
    dim, budget = 8, 16
    client.create_sparse_table("big", dim, rule="sgd", lr=1.0,
                               table_class="ssd", max_mem_rows=budget)
    ids = np.arange(200)
    first = client.pull_sparse("big", ids)            # materializes rows
    # push a known grad to every row: value' = value - 1.0 * g
    g = np.tile(np.arange(dim, dtype=np.float32), (len(ids), 1))
    client.push_sparse("big", ids, g)
    # revisit in a different order (forces disk loads of evicted rows)
    order = np.random.default_rng(0).permutation(ids)
    got = client.pull_sparse("big", order)
    want = first[order] - g[order]
    np.testing.assert_allclose(got, want, rtol=1e-6)
    # the hot set respected the budget and the tail lives on disk
    for s in range(2):
        mem, disk = client._call(s, "sparse_stats", "big")
        assert mem <= budget
        assert disk > 0


def test_ssd_table_adam_state_survives_eviction():
    """Optimizer state (m/v/t) must round-trip through the log store, not
    reset on eviction — two adam steps on an evicted row match two adam
    steps on an in-memory reference table."""
    from paddle_tpu.distributed.ps.service import _SparseTable
    from paddle_tpu.distributed.ps.ssd_table import SsdSparseTable

    acc = dict(rule="adam", lr=0.1)
    ssd = SsdSparseTable(4, acc, seed=0, max_mem_rows=2)
    ref = _SparseTable(4, acc, seed=0)
    ids = [0, 1, 2, 3, 4, 5]          # > budget: forces churn
    g = np.ones((len(ids), 4), np.float32)
    ssd.pull(ids)
    ref.pull(ids)
    for _ in range(2):
        ssd.push(ids, g)
        ref.push(ids, g)
    np.testing.assert_allclose(ssd.pull(ids), ref.pull(ids), rtol=1e-6)
    assert ssd.disk_rows > 0


def test_geo_async_mirrors_converge(cluster):
    """Geo-async (VERDICT r2 missing #4): two workers train local mirrors
    toward different targets with periodic delta sync; after syncs both
    mirrors hold the same global rows and the shared row moved toward the
    average of both targets."""
    from paddle_tpu.distributed.ps import GeoSparseMirror

    servers, client = cluster
    w1 = GeoSparseMirror(client, "emb", dim=4, geo_steps=5, lr=0.2)
    w2 = GeoSparseMirror(client, "emb", dim=4, geo_steps=5, lr=0.2)
    target = np.ones(4, np.float32)

    for _ in range(40):
        for w in (w1, w2):
            row = w.lookup([7])[0]
            w.update([7], [(row - target)])   # d/drow ||row - t||^2 / 2

    w1.sync(full_refresh=True)
    w2.sync(full_refresh=True)
    r1 = w1.lookup([7])[0]
    r2 = w2.lookup([7])[0]
    np.testing.assert_allclose(r1, r2, rtol=1e-5)   # same global row
    # converged near the target (both workers pull it the same way)
    assert np.abs(r1 - target).max() < 0.2


def test_geo_local_steps_do_not_touch_server(cluster):
    """Between geo syncs the server must see NO traffic for updates."""
    from paddle_tpu.distributed.ps import GeoSparseMirror

    servers, client = cluster
    w = GeoSparseMirror(client, "emb2", dim=4, geo_steps=1000, lr=0.1)
    w.lookup([3])
    before = client.pull_sparse("emb2", [3]).copy()
    for _ in range(10):
        row = w.lookup([3])[0]
        w.update([3], [row * 0 + 1.0])
    after = client.pull_sparse("emb2", [3])
    np.testing.assert_allclose(before, after)       # untouched globally
    w.sync()
    moved = client.pull_sparse("emb2", [3])
    assert np.abs(moved - before).max() > 0.5       # deltas arrived


def test_multi_slot_datafeed(tmp_path):
    """Reference MultiSlotDataFeed line format: per slot '<n> v1..vn';
    use_var slot declarations auto-install the parser."""
    from paddle_tpu.distributed import InMemoryDataset

    f = tmp_path / "part-000"
    # slots: click (1 int label), ids (sparse int64), dense (3 floats)
    f.write_text("1 1 3 101 102 103 3 0.5 0.25 0.125\n"
                 "1 0 2 7 9 3 1.0 2.0 3.0\n")
    ds = InMemoryDataset()
    ds.init(batch_size=2, use_var=[("click", "int64"), ("ids", "int64"),
                                   ("dense", "float32")])
    ds.set_filelist([str(f)])
    ds.load_into_memory()
    (batch,) = list(ds)
    assert len(batch) == 2
    s0, s1 = batch
    assert s0["click"].tolist() == [1] and s1["click"].tolist() == [0]
    assert s0["ids"].tolist() == [101, 102, 103]
    assert s1["ids"].tolist() == [7, 9]
    np.testing.assert_allclose(s0["dense"], [0.5, 0.25, 0.125])
    # malformed line raises with slot context
    bad = tmp_path / "bad"
    bad.write_text("1 1 5 101\n")
    ds2 = InMemoryDataset()
    ds2.init(batch_size=1, use_var=["click", "ids"])
    ds2.set_filelist([str(bad)])
    with pytest.raises(ValueError, match="ids"):
        ds2.load_into_memory()
        list(ds2)
