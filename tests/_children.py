"""Child processes of a test.  Every wait has an end of its own (there is
no per-test time limit here, so a case that waits for ever costs the whole
suite its limit), a missed deadline FAILS the case with what the children
printed, and no child, nor a child's child, outlives its case: each is
started in a process group of its own and the group is killed.
Standard library only."""
import contextlib
import os
import signal
import subprocess
import time

import pytest

LIMIT = 60.0    # seconds; the children of this suite finish in under 10
# the checkout these tests lie in: children run there and import from it
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def env(**more):
    """This process's environment with the checkout on ``PYTHONPATH``."""
    return {**os.environ, "PYTHONPATH": ROOT, **more}


def spawn(cmd, **kw):
    """``Popen`` with text pipes, in a new process group, run from the
    checkout's root."""
    kw.setdefault("cwd", ROOT)
    kw.setdefault("stdout", subprocess.PIPE)
    kw.setdefault("stderr", subprocess.PIPE)
    return subprocess.Popen(cmd, text=True, start_new_session=True, **kw)


def kill(*procs):
    """SIGKILL each child's whole group (a killed launcher cannot reap its
    workers) and reap the child."""
    for p in procs:
        if p is None:
            continue
        with contextlib.suppress(OSError):
            os.killpg(p.pid, signal.SIGKILL)
        with contextlib.suppress(OSError, subprocess.TimeoutExpired):
            p.wait(timeout=10)
        for pipe in (p.stdout, p.stderr):
            if pipe is not None:
                pipe.close()


def outputs(procs, seconds=LIMIT, what="children"):
    """``communicate()`` with every child under ONE deadline: the list of
    (stdout, stderr).  Past it, all are killed and the case fails."""
    deadline = time.monotonic() + seconds
    outs = []
    try:
        for p in procs:
            outs.append(p.communicate(
                timeout=max(deadline - time.monotonic(), 0.1)))
    except subprocess.TimeoutExpired:
        kill(*procs)
        pytest.fail(f"{what}: not finished after {seconds:.0f} s; "
                    f"killed. Output so far: {outs}")
    return outs


def run(cmd, seconds=LIMIT, **kw):
    """``subprocess.run(capture_output=True)`` with a deadline that fails
    the case, and the group killed whatever the outcome (a launcher's
    sleeping worker included)."""
    p = spawn(cmd, **kw)
    try:
        (out, err), = outputs([p], seconds, what=" ".join(map(str, cmd[-2:])))
        return subprocess.CompletedProcess(cmd, p.returncode, out, err)
    finally:
        kill(p)


def until(cond, seconds, what, every=0.1):
    """Poll ``cond()`` until it is true; fail the case with ``what`` when
    ``seconds`` have passed."""
    deadline = time.monotonic() + seconds
    while not cond():
        if time.monotonic() > deadline:
            pytest.fail(f"{what}: not after {seconds:.0f} s")
        time.sleep(every)
