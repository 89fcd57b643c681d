"""A cell's test file may pin its entries to the END of BENCHMARK.json's
lists: the end as its PR left it.  The next cell moves that end, and
the earlier file, which no later PR may edit (it is a file of the
benchmark), would fail on a benchmark that kept every one of its rules.

A file named in ``benchmark_history.AS_ITS_PR_LEFT_IT`` is therefore
shown ``loader.benchmark()`` as it stood when its cell was the last.
Its pins go on holding every entry that was there to its place and its
content.  What came after is the later file's to pin, RELATIVE to what
was there (``test_perf_laguna.py``: the earlier cells first and in
their order, this cell after them, and
``test_the_benchmark_is_the_one_before_plus_this_cell`` joins the two
views), so the next cell needs no line here.
"""
import benchmark_history as H
import pytest

from perf import loader


@pytest.fixture(autouse=True)
def _the_benchmark_as_the_file_s_pr_left_it(request, monkeypatch):
    cell = H.AS_ITS_PR_LEFT_IT.get(request.node.path.name)
    if cell is not None:
        monkeypatch.setattr(loader, "benchmark", lambda: H.as_of(cell))
