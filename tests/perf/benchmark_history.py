"""BENCHMARK.json as an earlier PR left it.

The benchmark only ever grows at the end: a PR may append cells,
configurations and metrics, and a cell's name to ``workloads`` lists,
and edit nothing that was there.  So the file as it stood when a given
cell was the last one is the file of today without what was appended
after that cell, and ``as_of`` computes it.  ``conftest.py`` shows that
view to the test files whose pins are on the END of a list."""
import copy

from perf import loader

# test file -> the cell that was last when its PR landed
AS_ITS_PR_LEFT_IT = {"test_perf_mellum.py": "mellum2-12b-a2.5b.pretrain_8k"}

whole = loader.benchmark        # the file itself, whatever a test patches


def as_of(cell):
    """The benchmark without the cells after ``cell``, their
    configurations, their names in every ``workloads`` list, and the
    metrics that only they report."""
    bench = copy.deepcopy(whole())
    names = [w["name"] for w in bench["workloads"]]
    later = set(names[names.index(cell) + 1:])
    bench["workloads"] = [w for w in bench["workloads"]
                          if w["name"] not in later]
    used = {w["config"] for w in bench["workloads"]}
    bench["configs"] = [c for c in bench["configs"] if c["name"] in used]
    for key in ("end_to_end", "per_layer"):
        kept = []
        for metric in bench[key]:
            if "workloads" in metric:
                if set(metric["workloads"]) <= later:
                    continue        # a metric the later cells brought
                metric["workloads"] = [c for c in metric["workloads"]
                                       if c not in later]
            kept.append(metric)
        bench[key] = kept
    return bench
