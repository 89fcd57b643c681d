"""Self-lint gate: the graph-lint CLI runs over ``paddle_tpu/`` itself
in ``--strict`` mode (all registered checks are warn/note severity, so
the default error-only gate could never fire) and must come back with
zero warn-or-worse findings — the analyzer gates the repo's own code
from here on. The subsystem dirs that grew after the gate first landed
(``inference/``, ``resilience/``, ``observability/``) are pinned
explicitly so a future package re-layout cannot silently drop them from
the walk, and representative compiled programs are audited clean at the
IR level too (the whole-program analog of the source gate)."""
import os

import numpy as np
import pytest

from paddle_tpu.analysis import Severity, analyze_file
from paddle_tpu.analysis.__main__ import main

_PKG = os.path.join(os.path.dirname(__file__), os.pardir, "paddle_tpu")


def test_selflint_cli_strict_exits_zero(capsys):
    rc = main([_PKG, "--strict"])
    out = capsys.readouterr().out
    assert rc == 0, f"graph lint gates the repo:\n{out}"
    # the walk actually covered the package, not an empty dir
    summary = out.strip().splitlines()[-1]
    n_files = int(summary.split(" in ")[1].split()[0])
    assert n_files > 100, summary
    assert "(0 error, 0 warn," in summary, summary


def test_selflint_no_warn_or_error_findings_per_file():
    bad = []
    for root, dirs, files in os.walk(_PKG):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in sorted(files):
            if not f.endswith(".py"):
                continue
            path = os.path.join(root, f)
            for d in analyze_file(path):
                if d.severity >= Severity.WARN:
                    bad.append(d.format())
    assert not bad, "\n".join(bad)


def test_readme_code_table_in_sync():
    """The README code table is generated from the registry — a stale
    block (new code registered, doc edited) fails here. Regenerate with
    ``python -m paddle_tpu.analysis --list-codes --format markdown``."""
    import re

    from paddle_tpu.analysis.__main__ import code_table_markdown
    readme = os.path.join(_PKG, os.pardir, "README.md")
    with open(readme) as f:
        text = f.read()
    m = re.search(r"<!-- BEGIN PDT CODE TABLE -->\n(.*?)\n"
                  r"<!-- END PDT CODE TABLE -->", text, re.S)
    assert m, "README PDT code-table markers missing"
    assert m.group(1) == code_table_markdown(), \
        "README code table is stale — regenerate from the registry"


@pytest.mark.parametrize("sub", ("inference", "resilience",
                                 "observability"))
def test_selflint_subsystem_dirs_covered_and_clean(sub, capsys):
    """The newer subsystem dirs stay under the strict gate in their own
    right — and the walk actually visits them (n_files > 0)."""
    rc = main([os.path.join(_PKG, sub), "--strict"])
    out = capsys.readouterr().out
    assert rc == 0, f"{sub}/ lint gates the repo:\n{out}"
    summary = out.strip().splitlines()[-1]
    assert int(summary.split(" in ")[1].split()[0]) > 0, summary
    assert "(0 error, 0 warn," in summary, summary


def test_program_audit_clean_on_representative_programs():
    """IR-level self-gate: a representative captured program (state
    capture + reduction, the train-step shape) audits with zero
    warn-or-worse whole-program findings."""
    import paddle_tpu as paddle
    from paddle_tpu import analysis

    w = paddle.to_tensor(np.ones((16,), np.float32))

    @paddle.jit.to_static
    def selflint_step(x):
        return (x * 2.0 + w.sum()).mean()

    with analysis.collect() as diags:
        selflint_step(paddle.to_tensor(np.ones((16,), np.float32)))
    bad = [d.format() for d in diags if d.severity >= Severity.WARN]
    assert not bad, "\n".join(bad)


def test_no_file_ordering_grows_back_under_tests():
    """Tier-1 runs on ``xdist`` workers by file (``--dist loadfile``), and
    ``xdist`` re-sorts the files by their number of cases whatever order
    collection gave: an ordering hook under ``tests/`` is dead code, and
    a list of file names in ``conftest.py`` is one waiting to be kept."""
    import re
    here = os.path.dirname(os.path.abspath(__file__))
    hooks = []
    for root, dirs, files in os.walk(here):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(root, f)) as fh:
                    if re.search(r"^\s*def pytest_collection_modifyitems\b",
                                 fh.read(), re.M):
                        hooks.append(os.path.relpath(
                            os.path.join(root, f), here))
    assert not hooks, f"collection is re-ordered in {hooks}"
    with open(os.path.join(here, "conftest.py")) as fh:
        named = re.findall(r"""["']test_\w+\.py["']""", fh.read())
    assert not named, f"tests/conftest.py lists test files: {named}"


def test_no_second_measurement_stack_grows_back():
    """Speed is measured in one place: ``perf/`` runs the cells of
    ``BENCHMARK.json`` and the driver keeps ``PERF_LEDGER.jsonl``
    (ROADMAP D4, closed by PR 47).  No code under ``paddle_tpu/`` or
    ``tests/`` (``tests/perf`` is the benchmark's own) or at the root,
    nor the two documents that describe the tree as it is, imports or
    names ``bench.py``, ``benchmarks/`` or ``observability.regress``,
    and the root holds no round record."""
    import re
    here = os.path.dirname(os.path.abspath(__file__))
    root = os.path.dirname(here)
    gone = re.compile(r"""(?<!\w)bench\.py|benchmarks/|["']benchmarks["']"""
                      r"""|observability\.regress|import regress\b""")
    paths = [os.path.join(root, f) for f in sorted(os.listdir(root))
             if f.endswith(".py") or f in ("README.md", "COVERAGE.md")]
    for top in (os.path.join(root, "paddle_tpu"), here):
        for d, dirs, files in os.walk(top):
            dirs[:] = [x for x in dirs if x != "__pycache__"
                       and os.path.join(d, x) != os.path.join(here, "perf")]
            paths += [os.path.join(d, f) for f in sorted(files)
                      if f.endswith(".py")]
    paths.remove(os.path.abspath(__file__))  # the guard names what it forbids
    named = []
    for path in paths:
        with open(path) as fh:
            for n, line in enumerate(fh, 1):
                if gone.search(line):
                    named.append(f"{os.path.relpath(path, root)}:{n}")
    assert not named, f"the old measurement stack is named in {named}"
    records = [f for f in os.listdir(root)
               if re.fullmatch(r"(BENCH|MULTICHIP)_r\d+\.json", f)]
    assert not records, f"round records at the root: {records}"
    assert not os.path.exists(os.path.join(root, "benchmarks"))
