"""Launcher tests (reference pattern: test_launch_coverage / the
fleet elastic watchdog tests)."""
import sys
import textwrap

import _children


def _write(tmp_path, name, body):
    p = tmp_path / name
    p.write_text(textwrap.dedent(body))
    return str(p)


def test_launch_sets_env_contract(tmp_path):
    script = _write(tmp_path, "probe.py", f"""
        import os, pathlib
        r = os.environ["PADDLE_TRAINER_ID"]
        pathlib.Path({str(tmp_path)!r}, "out" + r).write_text(
            " ".join([r, os.environ["PADDLE_TRAINERS_NUM"],
                      os.environ["PADDLE_LOCAL_RANK"]]))
    """)
    out = _children.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", "2", script],
        env=_children.env())
    assert out.returncode == 0, out.stderr
    assert (tmp_path / "out0").read_text() == "0 2 0"
    assert (tmp_path / "out1").read_text() == "1 2 1"


def test_launch_elastic_restart(tmp_path):
    marker = tmp_path / "attempts"
    script = _write(tmp_path, "flaky.py", f"""
        import pathlib, sys
        m = pathlib.Path({str(marker)!r})
        n = int(m.read_text()) if m.exists() else 0
        m.write_text(str(n + 1))
        sys.exit(1 if n == 0 else 0)   # fail once, succeed on restart
    """)
    out = _children.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--max_restart_times", "2", script],
        env=_children.env())
    assert out.returncode == 0, (out.stdout, out.stderr)
    assert marker.read_text() == "2"  # initial failure + 1 restart
    assert "restart 1/2" in out.stderr


def test_launch_propagates_persistent_failure(tmp_path):
    script = _write(tmp_path, "dead.py", "import sys; sys.exit(3)")
    out = _children.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch", script],
        env=_children.env())
    assert out.returncode == 3


def test_multinode_env(tmp_path):
    script = _write(tmp_path, "probe.py", """
        import os
        print("R", os.environ["PADDLE_TRAINER_ID"],
              os.environ["JAX_COORDINATOR_ADDRESS"],
              os.environ["JAX_NUM_PROCESSES"],
              os.environ["JAX_PROCESS_ID"])
    """)
    out = _children.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nnodes", "4", "--node_rank", "2",
         "--master", "10.0.0.1:8476", script],
        env=_children.env())
    assert out.returncode == 0, out.stderr
    assert "R 2 10.0.0.1:8476 4 2" in out.stdout
