"""``chip_smoke.py`` off the chip: what it must refuse, what importing
the package must leave alone, and a rehearsal of its one-chip phases at
a tiny width (the script reaches into the engine to read its programs'
text, so a refactor has to break here and not on the chip)."""
import json
import os
import subprocess
import sys

import pytest

_REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))
sys.path.insert(0, _REPO)


def _child(code, *argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=_REPO)
    return subprocess.run([sys.executable, "-c", code, *argv], cwd=_REPO,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_imports_initialise_no_backend():
    """``import paddle_tpu`` and the launcher's module leave JAX with no
    backend: a parent that only imports them does not hold the chip."""
    out = _child(
        "import paddle_tpu, paddle_tpu.distributed.launch.main\n"
        "from jax._src import xla_bridge\n"
        "print(xla_bridge.backends_are_initialized())")
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == "False"


def test_chip_smoke_help_touches_no_jax():
    out = _child(
        "import runpy, sys\n"
        "sys.argv = ['chip_smoke.py', '--help']\n"
        "try:\n"
        "    runpy.run_path('chip_smoke.py', run_name='__main__')\n"
        "except SystemExit as e:\n"
        "    assert e.code == 0, e.code\n"
        "print('jax' in sys.modules)")
    assert out.returncode == 0, out.stderr[-2000:]
    assert "--chips" in out.stdout
    assert out.stdout.strip().splitlines()[-1] == "False"


def test_chip_smoke_refuses_off_the_chip():
    """No accelerator: non-zero exit, the reason on stderr, and no
    result line — never a CPU run under the chip's name."""
    out = _child("import runpy; runpy.run_path('chip_smoke.py', "
                 "run_name='__main__')")
    assert out.returncode != 0
    assert "not 'tpu'" in out.stderr
    assert '"ok"' not in out.stdout


def test_chip_smoke_phases_rehearsal(capsys):
    """Both one-chip phases at a tiny width on the CPU, kernels in
    interpret mode: every check but the Mosaic custom calls."""
    import chip_smoke as cs
    width = dict(vocab_size=512, hidden_size=64, num_layers=2,
                 num_heads=4)
    compiles = cs._Compiles()
    cs._phase("train", compiles, lambda: cs.run_train(
        0, width=width, batch=2, seq=64, on_chip=False))
    # six requests of three lengths: each length of the dense reference
    # is a prefill and a decode program of its own (about 10 s a pair),
    # and what is asserted is six streams that agree
    cs._phase("serve", compiles, lambda: cs.run_serve(
        0, compiles, width=width, lens=(5, 33, 90, 5, 33, 90), new=18,
        on_chip=False))
    train, serve = (json.loads(line) for line in
                    capsys.readouterr().out.strip().splitlines())
    assert train["checked"]["step_programs"] == 1
    assert serve["checked"]["programs_after_warmup"] == 0
    assert serve["checked"]["agree_share"] == [1.0] * 6


def test_compile_cache_directory_rule(tmp_path):
    """Where JAX_COMPILATION_CACHE_DIR is set it is left alone and no
    directory is set in code; unset, the cache is <checkout>/.jax_cache
    — and importing the package alone turns nothing on."""
    code = ("import jax, paddle_tpu\n"
            "before = jax.config.jax_compilation_cache_dir\n"
            "from paddle_tpu.utils.compile_cache import "
            "enable_compile_cache\n"
            "print(before, enable_compile_cache(), "
            "jax.config.jax_compilation_cache_dir)")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=_REPO)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)

    def run(env):
        out = subprocess.run([sys.executable, "-c", code], cwd=_REPO,
                             env=env, capture_output=True, text=True,
                             timeout=300)
        assert out.returncode == 0, out.stderr[-2000:]
        return out.stdout.strip().splitlines()[-1].split()

    mine = os.path.join(_REPO, ".jax_cache")
    assert run(env) == ["None", mine, mine]
    theirs = str(tmp_path / "cache")
    assert run(dict(env, JAX_COMPILATION_CACHE_DIR=theirs)) == [theirs] * 3
