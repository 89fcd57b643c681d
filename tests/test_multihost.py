"""Real multi-process (multi-host simulation) test: two CPU processes
federate through the JAX coordination service via init_parallel_env
(using the launcher's env contract), and a pod-wide psum must see both
processes' contributions — the ``test_dist_base.py`` pattern of SURVEY
§4 (N local processes standing in for N hosts)."""
import subprocess
import sys
import textwrap

import _children


def test_two_process_allreduce(tmp_path):
    script = tmp_path / "worker.py"
    script.write_text(textwrap.dedent(f"""
        import os
        os.environ["JAX_PLATFORMS"] = "cpu"
        import jax
        jax.config.update("jax_platforms", "cpu")
        import numpy as np
        import paddle_tpu.distributed as dist
        from jax.sharding import Mesh, PartitionSpec as P
        from jax.experimental import multihost_utils

        dist.init_parallel_env()   # federates via JAX_COORDINATOR_ADDRESS
        assert jax.process_count() == 2, jax.process_count()
        assert jax.device_count() == 2
        rank = jax.process_index()

        mesh = Mesh(jax.devices(), ("x",))
        from paddle_tpu.core.meshutil import shard_map
        f = jax.jit(shard_map(lambda a: jax.lax.psum(a, "x"),
                              mesh=mesh, in_specs=P("x"),
                              out_specs=P()))
        garr = multihost_utils.host_local_array_to_global_array(
            np.full((1,), float(rank + 1), np.float32), mesh, P("x"))
        out = f(garr)            # replicated result: read the local shard
        val = float(np.asarray(out.addressable_data(0)))
        assert val == 3.0, val   # 1 + 2 summed across processes
        import pathlib
        pathlib.Path({str(tmp_path)!r}, f"ok{{rank}}").write_text(str(val))
    """))

    def start(rank):
        env = {**_children.env(), "JAX_PLATFORMS": "cpu",
               # the contract paddle_tpu.distributed.launch sets per host
               "JAX_COORDINATOR_ADDRESS": "127.0.0.1:19284",
               "JAX_NUM_PROCESSES": "2",
               "JAX_PROCESS_ID": str(rank),
               "PADDLE_TRAINER_ID": str(rank),
               "PADDLE_TRAINERS_NUM": "2"}
        env.pop("XLA_FLAGS", None)  # one real device per process
        return _children.spawn(
            [sys.executable, str(script)], env=env,
            stderr=subprocess.STDOUT)

    procs = []
    try:
        procs += [start(0), start(1)]
        (out0, _), (out1, _) = _children.outputs(
            procs, what="two federated processes")
    finally:
        _children.kill(*procs)
    p0, p1 = procs
    assert p0.returncode == 0, out0
    assert p1.returncode == 0, out1
    assert (tmp_path / "ok0").read_text() == "3.0"
    assert (tmp_path / "ok1").read_text() == "3.0"
