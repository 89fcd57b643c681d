"""Finding a piece of the benchmark by its name: a later PR adds a
configuration, a traffic mix, a driver, a metric reader, a kernel's
cost function, a reference or a family adapter as a file of its own,
and nothing here or in ``run.py`` is edited for it.
"""
from __future__ import annotations

import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PERF = os.path.join(ROOT, "perf")


def module(kind, name):
    """``perf/<kind>/<name>.py`` as a module (a metric's name may hold
    dots, so it is loaded by path)."""
    path = os.path.join(PERF, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(
            f"no {kind} named {name!r}: {os.path.relpath(path, ROOT)} "
            f"does not exist")
    plain = name.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(
        f"perf.{kind}.{plain}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def data(kind, name):
    """``perf/<kind>/<name>.json``."""
    path = os.path.join(PERF, kind, f"{name}.json")
    with open(path) as f:
        return json.load(f)


def benchmark():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def by_name(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"BENCHMARK.json has no {what} named {name!r}; it has "
                   f"{[e['name'] for e in entries]}")


def applies(metric, workload):
    """Whether a metric's entry covers a cell: its ``workloads`` key
    lists the cells, and without one it covers every cell."""
    return workload in metric.get("workloads", [workload])


def peaks(device_kind):
    with open(os.path.join(PERF, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(
            f"device_kind {device_kind!r} is not in perf/peaks.json "
            f"(it has {[k for k in table if not k.startswith('_')]}): "
            f"add its published peaks with their source; there is no "
            f"default")
    return table[device_kind]
