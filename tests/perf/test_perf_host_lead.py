"""The six readers of the host's lead over the device
(`perf/layers/host_lead_ms.py` and its five neighbours) on hand-made
samples, and their entries in the manifest: no chip."""
import ast
import json
import os

import pytest

from perf import cells, loops
from perf import run as perf_run

ROOT = cells.ROOT
CELLS = ["mistral-7b-w4a8.batch", "smallthinker-21ba3b-bf16.batch-8k",
         "phi-4-mini-flash-bf16.reason-2k"]
ENGINE = "core engine (engine/aphrodite_engine.py)"
RUNNER = "model runner (executor/model_runner.py)"
FRONT = ("HTTP front end and async engine "
         "(endpoints/openai/api_server.py, engine/async_aphrodite.py)")
#: metric -> (unit, better, layer, the counters it reads)
METRICS = {
    "host_lead_ms.batch": ("ms", "higher", ENGINE, (
        "pull_blocked_seconds", "pulls_ahead")),
    "host_lead_decode_ms.batch": ("ms", "higher", ENGINE, (
        "pull_blocked_decode_seconds", "pulls_ahead_decode")),
    "dispatch_starved_pct.batch": ("%", "lower", RUNNER, (
        "dispatches_starved", "rounds_ahead")),
    "dispatch_starved_prompt_pct.batch": ("%", "lower", RUNNER, (
        "dispatches_starved_prompt", "rounds_ahead_prompt")),
    "host_dispatch_ms.batch": ("ms", "lower", RUNNER, (
        "host_dispatch_seconds", "engine_rounds")),
    "host_hops_ms.batch": ("ms", "lower", FRONT, (
        "step_call_seconds", "engine_step_seconds", "engine_rounds")),
}


def _run(samples, seconds=10.0):
    """A hand-made `Run`: `/metrics` readings `seconds` apart."""
    window = loops.Window(t0=100.0, replies=[], t_end=0.0,
                          seconds=seconds * max(1, len(samples) - 1))
    return perf_run.Run(
        cell=cells.load_cell(CELLS[2], ROOT), window=window, t_start=0.0,
        samples=[(100.0 + i * seconds, s) for i, s in enumerate(samples)],
        steady_until=100.0 + window.seconds, log_setup="", log_window="",
        faults=[])


def _counters(**totals):
    return {f"aphrodite:{k}_total": float(v) for k, v in totals.items()}


#: two readings 10 s apart: 320 rounds of 31.25 ms, 319 of them
#: dispatched with a round in flight, 40 of those with a prompt step;
#: 16 dispatches met a drained device, 12 of them a prompt round's; the
#: 319 pulls behind them blocked 4.785 s in all (15 ms each), the 250
#: of decode under decode 2.0 s (8 ms); 0.24 s in the jitted calls;
#: 6.4 s inside `engine.step` and 6.72 s in the loop's calls of it
WINDOW = [
    _counters(engine_rounds=1000, rounds_ahead=990, rounds_ahead_prompt=100,
              dispatches_starved=4, dispatches_starved_prompt=3,
              pull_blocked_seconds=10.0, pulls_ahead=990,
              pull_blocked_decode_seconds=5.0, pulls_ahead_decode=700,
              host_dispatch_seconds=1.0, engine_step_seconds=20.0,
              step_call_seconds=21.0),
    _counters(engine_rounds=1320, rounds_ahead=1309, rounds_ahead_prompt=140,
              dispatches_starved=20, dispatches_starved_prompt=15,
              pull_blocked_seconds=14.785, pulls_ahead=1309,
              pull_blocked_decode_seconds=7.0, pulls_ahead_decode=950,
              host_dispatch_seconds=1.24, engine_step_seconds=26.4,
              step_call_seconds=27.72)]
WANT = {
    "host_lead_ms.batch": 15.0, "host_lead_decode_ms.batch": 8.0,
    "dispatch_starved_pct.batch": 16 / 319 * 100,
    "dispatch_starved_prompt_pct.batch": 30.0,
    "host_dispatch_ms.batch": 0.75, "host_hops_ms.batch": 1.0}


def _read(metric, run):
    return cells.load_function(
        cells.reader_path(ROOT, "layers", metric), "read")(run)


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_a_reader_on_a_hand_made_window(metric):
    assert _read(metric, _run(WINDOW)) == pytest.approx(WANT[metric])


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_a_program_without_the_counters_reads_nothing(metric):
    """The parent has none of these counters: the reader returns None
    and the line leaves the metric out; it never raises."""
    new = {f"aphrodite:{c}_total" for _, _, _, counters in METRICS.values()
           for c in counters} - {"aphrodite:engine_rounds_total",
                                 "aphrodite:engine_step_seconds_total"}
    parent = [{k: v for k, v in s.items() if k not in new} for s in WINDOW]
    assert _read(metric, _run(parent)) is None
    assert _read(metric, _run([])) is None
    # and a run with one of its counters alone reads nothing either
    *_, counters = METRICS[metric]
    for counter in counters[:-1]:
        only = [{k: v for k, v in s.items()
                 if k == f"aphrodite:{counter}_total"} for s in WINDOW]
        assert _read(metric, _run(only)) is None


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_a_denominator_that_did_not_grow_reads_nothing(metric):
    """No round went out ahead in the window (an idle server, a synced
    path): no ratio, and no division by zero."""
    *_, counters = METRICS[metric]
    bottom = f"aphrodite:{counters[-1]}_total"
    still = [dict(s, **{bottom: WINDOW[0][bottom]}) for s in WINDOW]
    assert _read(metric, _run(still)) is None


def test_a_numerator_that_did_not_grow_reads_zero():
    """No dispatch was starved: 0, which is the reading, not None."""
    fed = [dict(s, **{"aphrodite:dispatches_starved_total": 4.0,
                      "aphrodite:dispatches_starved_prompt_total": 3.0})
           for s in WINDOW]
    assert _read("dispatch_starved_pct.batch", _run(fed)) == 0.0
    assert _read("dispatch_starved_prompt_pct.batch", _run(fed)) == 0.0


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_each_entry_has_its_file_and_every_cell_reports_it(metric):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    unit, better, layer, _ = METRICS[metric]
    (entry,) = [m for m in bench["per_layer"] if m["name"] == metric]
    assert entry == dict(name=metric, unit=unit, better=better,
                         source="program_counter", layer=layer,
                         moves="out_tok_s", workloads=CELLS)
    assert [w["name"] for w in bench["workloads"]] == CELLS
    path = cells.reader_path(ROOT, "layers", metric)
    assert os.path.isfile(path) and \
        os.path.basename(path) == metric.rsplit(".", 1)[0] + ".py"
    for cell in CELLS:
        assert metric in {m["name"] for m in
                          cells.load_cell(cell, ROOT).per_layer}


def test_the_six_are_appended_and_the_pinning_tests_are_spared():
    """The entries are the manifest's last six, in the issue's order,
    and the fixture that spares the two pinning tests names exactly
    them."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    order = ["host_lead_ms.batch", "host_lead_decode_ms.batch",
             "dispatch_starved_pct.batch",
             "dispatch_starved_prompt_pct.batch",
             "host_dispatch_ms.batch", "host_hops_ms.batch"]
    assert [m["name"] for m in bench["per_layer"]][-6:] == order
    assert set(order) == set(METRICS)
    with open(os.path.join(ROOT, "tests", "conftest.py")) as f:
        (later,) = [ast.literal_eval(node.value)
                    for node in ast.parse(f.read()).body
                    if isinstance(node, ast.Assign) and getattr(
                        node.targets[0], "id", None) == "LATER_METRICS"]
    assert list(later) == order
    # the counters the readers name are the ones the program exports
    from aphrodite_tpu.engine.metrics import _STAGE_COUNTERS
    exported = {name for name, _, _ in _STAGE_COUNTERS}
    for *_, counters in METRICS.values():
        for counter in counters:
            assert f"aphrodite:{counter}_total" in exported
