"""The reader of a combined round's late prompt dispatch
(`perf/layers/prompt_dispatch_late_pct.py`) on hand-made samples, and
its entry in the manifest: no chip."""
import json
import os

import pytest

from perf import cells, loops
from perf import run as perf_run

ROOT = cells.ROOT
METRIC = "prompt_dispatch_late_pct.batch"
LATE = "aphrodite:dispatches_prompt_late_total"
PROMPT_ROUNDS = "aphrodite:rounds_ahead_prompt_total"
#: the cells the entry was added with (PR 45: every cell there was)
CELLS = ["mistral-7b-w4a8.batch", "smallthinker-21ba3b-bf16.batch-8k",
         "phi-4-mini-flash-bf16.reason-2k", "jamba2-3b-bf16.reason-512",
         "laguna-s-2.1-bf16.agent-4k"]


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _run(samples, seconds=10.0):
    """A hand-made `Run`: `/metrics` readings `seconds` apart."""
    window = loops.Window(t0=100.0, replies=[], t_end=0.0,
                          seconds=seconds * max(1, len(samples) - 1))
    cell = cells.load_cell(CELLS[2], ROOT)
    return perf_run.Run(
        cell=cell, window=window, t_start=0.0,
        samples=[(100.0 + i * seconds, s) for i, s in enumerate(samples)],
        steady_until=100.0 + window.seconds, log_setup="", log_window="",
        faults=[])


def _read(run):
    return cells.load_function(
        cells.reader_path(ROOT, "layers", METRIC), "read")(run)


#: (what the window saw, the counters at its two ends, the reading)
WINDOWS = [
    ("three_of_ten_late", {LATE: 2.0, PROMPT_ROUNDS: 100.0},
     {LATE: 5.0, PROMPT_ROUNDS: 110.0}, 30.0),
    ("none_late", {LATE: 2.0, PROMPT_ROUNDS: 100.0},
     {LATE: 2.0, PROMPT_ROUNDS: 110.0}, 0.0),
    ("no_prompt_round", {LATE: 2.0, PROMPT_ROUNDS: 100.0},
     {LATE: 2.0, PROMPT_ROUNDS: 100.0}, None),
    # the parent counts its prompt rounds and has no late counter
    ("a_program_without_the_counter", {PROMPT_ROUNDS: 100.0},
     {PROMPT_ROUNDS: 110.0}, None),
    ("the_late_counter_alone", {LATE: 2.0}, {LATE: 5.0}, None),
]


@pytest.mark.parametrize("case,first,last,want", WINDOWS,
                         ids=[w[0] for w in WINDOWS])
def test_the_reader_on_a_hand_made_window(case, first, last, want):
    got = _read(_run([first, last]))
    assert got == (want if want is None else pytest.approx(want))


def test_a_run_without_samples_reads_nothing():
    assert _read(_run([])) is None


def test_the_manifest_has_the_entry_and_every_cell_reports_it():
    """Found by its name, not by its place, and held to the five cells
    it came with at the head of its list: a later PR may append a
    metric behind it and a cell to it."""
    bench = _bench()
    (entry,) = [m for m in bench["per_layer"] if m["name"] == METRIC]
    assert entry["workloads"][:len(CELLS)] == CELLS
    assert set(entry["workloads"]) <= {w["name"] for w in bench["workloads"]}
    assert {k: v for k, v in entry.items() if k != "workloads"} == dict(
        name=METRIC, unit="%", better="lower", source="program_counter",
        layer="model runner (executor/model_runner.py)", moves="out_tok_s")
    # beside its twin's: the same layer, the same rounds underneath
    (twin,) = [m for m in bench["per_layer"]
               if m["name"] == "dispatch_starved_prompt_pct.batch"]
    for key in ("unit", "better", "source", "layer", "moves"):
        assert twin[key] == entry[key]
    path = cells.reader_path(ROOT, "layers", METRIC)
    assert os.path.basename(path) == "prompt_dispatch_late_pct.py" and \
        os.path.isfile(path)
    for cell in entry["workloads"]:
        assert METRIC in {m["name"] for m in
                          cells.load_cell(cell, ROOT).per_layer}


def test_the_counters_it_reads_are_ones_the_program_exports():
    from aphrodite_tpu.common import tracing
    from aphrodite_tpu.engine.metrics import _STAGE_COUNTERS
    exported = {name: total for name, _, total in _STAGE_COUNTERS}
    assert {LATE, PROMPT_ROUNDS} <= set(exported)
    # each from the accumulator of its name, and from no other
    tracer = tracing.Tracer()
    tracer.add("runner.prompt_late", count=3)
    assert exported[LATE](tracer.seconds, tracer.counts) == 3
    assert [name for name, total in exported.items()
            if total(tracer.seconds, tracer.counts)] == [LATE]
