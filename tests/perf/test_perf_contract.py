"""BENCHMARK.json against the rules the driver applies before any run,
and every name in it against a file of its own under perf/."""
import json
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perf import loader  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return loader.benchmark()


def _metrics(bench):
    return bench["end_to_end"] + bench["per_layer"]


def test_keys_and_sizes(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 << 10
    assert 1 <= bench["run_seconds"] <= 51
    cells = len(bench["workloads"])
    assert 1 <= cells <= 24 and 1 <= len(bench["per_layer"]) <= 128
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(
        1, cells // 4)
    # the full check of 24 cells fits the driver's 43200 seconds
    s = bench["run_seconds"]
    assert (2 + 14 * 24) * (s + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_names_units_and_lines(bench):
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in bench[k]]
    for n in names + [w["traffic"] for w in bench["workloads"]]:
        assert NAME.match(n), n
    for k in ("configs", "workloads"):
        got = [e["name"] for e in bench[k]]
        assert len(got) == len(set(got))
    got = [m["name"] for m in _metrics(bench)]
    assert len(got) == len(set(got))
    for m in _metrics(bench):
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for e in bench["end_to_end"]:
        assert e["source"] in ("host_clock", "device_trace")
        assert 0.01 <= e["bound"] <= 0.1
        assert set(e) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
    for e in bench["per_layer"]:
        assert set(e) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    lines = [e["why"] for k in ("configs", "workloads") for e in bench[k]]
    lines += [m["layer"] for m in bench["per_layer"]] + bench["command"]
    for text in lines:
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_every_moves_target_is_reported_by_the_same_cells(bench):
    cells = [w["name"] for w in bench["workloads"]]
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in bench["per_layer"]:
        target = e2e[m["moves"]]
        for cell in m.get("workloads", cells):
            assert cell in cells
            assert loader.applies(target, cell), (m["name"], cell)
    for cell in cells:
        assert sum(loader.applies(m, cell) for m in bench["end_to_end"]) >= 2
        assert any(loader.applies(m, cell) for m in bench["per_layer"])


def test_every_name_has_its_file(bench):
    paths = bench["paths"]
    configs = {c["name"]: c for c in bench["configs"]}
    files = [c["file"] for c in bench["configs"]]
    assert len(files) == len(set(files))
    used = set()
    for w in bench["workloads"]:
        cfg_entry = configs[w["config"]]
        used.add(w["config"])
        assert any(cfg_entry["file"].startswith(p + "/") for p in paths)
        with open(os.path.join(ROOT, cfg_entry["file"])) as f:
            cfg = json.load(f)
        assert cfg["source"] == cfg_entry["source"]
        assert cfg["reduced"] == cfg_entry["reduced"]
        traffic = loader.data("traffic", w["traffic"])
        for kind, name in (("drivers", traffic["driver"]),
                           ("models", cfg["family"]),
                           ("reference", cfg["family"])):
            assert hasattr(loader.module(kind, name),
                           {"drivers": "run", "models": "program_name",
                            "reference": "table"}[kind])
        limits = loader.data("limits", w["name"])
        for entry in limits.values():
            assert "limit" in entry and "set_from" in entry
    assert used == set(configs)
    for m in bench["per_layer"]:
        assert callable(loader.module("metrics", m["name"]).read)
    for word in bench["command"]:
        assert not word.startswith("/") and ".." not in word


# readers of a training cell that no entry declares, each with its
# reason: keep this list short
UNDECLARED = {
    "fused_optimizer_roofline.train": "every configuration sets the fused "
    "optimizer off, so no cell runs the kernel it reads (ROADMAP R0d)",
}
TRAIN_READERS = sorted(
    f[:-len(".py")] for f in os.listdir(os.path.join(ROOT, "perf", "metrics"))
    if f.endswith(".train.py"))


@pytest.mark.parametrize("reader", TRAIN_READERS)
def test_every_training_reader_is_declared_or_listed_with_its_reason(
        bench, reader):
    """A reader without an entry is read by no run and held by no
    ledger line: it ships with its entry, or stands in ``UNDECLARED``."""
    declared = reader in {m["name"] for m in bench["per_layer"]}
    assert declared != (reader in UNDECLARED), reader
    assert all(UNDECLARED.values())
    assert set(UNDECLARED) <= set(TRAIN_READERS)


def test_unknown_device_kind_is_an_error():
    assert loader.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert loader.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    for kind in ("TPU v9", "cpu", "_source"):
        with pytest.raises(KeyError):
            loader.peaks(kind)


def test_cli_refuses_a_machine_without_a_tpu(bench):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perf", "run.py"),
         "--workload", bench["workloads"][0]["name"], "--seed", "3",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert "not 'tpu'" in out.stderr
    assert not any(line.startswith('{"correct"')
                   for line in out.stdout.splitlines())


def test_result_line_holds_the_cells_metrics(bench):
    import types

    from perf.run import result_line
    every = {m["name"]: 1.5 for m in bench["end_to_end"]}
    for w in bench["workloads"]:
        run = types.SimpleNamespace(end_to_end=dict(every))
        got = result_line(bench, w, run, trace=False)
        want = {m["name"] for m in bench["end_to_end"]
                if loader.applies(m, w["name"])}
        assert set(got) == want and "setup_s" in got
        for m in bench["end_to_end"]:
            if m["name"] in got:
                assert got[m["name"]] == {"value": 1.5, "unit": m["unit"]}
        del run.end_to_end["setup_s"]
        with pytest.raises(KeyError):
            result_line(bench, w, run, trace=False)
