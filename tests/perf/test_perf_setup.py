"""The ten readers of the program's account of its own set-up (PR 54:
`perf/layers/startup_*.py`, `program_*.py`, `programs_built.py`, over
`perf/startup.py`) on hand-made samples, their entries in the manifest,
and the rehearsal's toy server printing all ten on the CPU: no chip."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from perf import cells, loops
from perf import run as perf_run

ROOT = cells.ROOT
A = "aphrodite:"
READY = A + "startup_seconds"
TRACE, LOWER, COMPILE = (A + f"program_{stage}_seconds_total"
                         for stage in ("trace", "lower", "compile"))
HITS, MISSES = A + "program_cache_hits_total", \
    A + "program_cache_misses_total"
BUILT = A + "programs_built_total"
IMPORT, BACKEND, WEIGHTS, KV_POOL = (
    A + f"setup_{phase}_seconds_total"
    for phase in ("import", "backend", "weights", "kv_pool"))
#: the layers' names as the manifest had them before these entries
LAYERS = dict(
    http="HTTP front end and async engine (endpoints/openai/api_server.py, "
         "engine/async_aphrodite.py)",
    core="core engine (engine/aphrodite_engine.py)",
    step="model step (modeling/models/llama.py)",
    pool="block manager and cache engine (processing/block_manager.py, "
         "executor/cache_engine.py)",
    runner="model runner (executor/model_runner.py)")
#: name -> (unit, better, moves, layer), in the manifest's order
ENTRIES = {
    "startup_ready_s": ("s", "lower", "setup_s", "http"),
    "startup_backend_s": ("s", "lower", "setup_s", "core"),
    "startup_weights_s": ("s", "lower", "setup_s", "step"),
    "startup_kv_pool_s": ("s", "lower", "setup_s", "pool"),
    "program_trace_s": ("s", "lower", "setup_s", "runner"),
    "program_lower_s": ("s", "lower", "setup_s", "runner"),
    "program_compile_s": ("s", "lower", "setup_s", "runner"),
    "program_cache_hit_pct": ("%", "higher", "setup_s", "runner"),
    "programs_built": ("programs", "lower", "setup_s", "runner"),
    "program_build_in_window_pct.batch": ("%", "lower", "out_tok_s",
                                          "runner"),
}
#: the cells the entries were added with (PR 54: every cell there was)
CELLS = ["mistral-7b-w4a8.batch", "smallthinker-21ba3b-bf16.batch-8k",
         "phi-4-mini-flash-bf16.reason-2k", "jamba2-3b-bf16.reason-512",
         "laguna-s-2.1-bf16.agent-4k", "evabyte-6.5b-bf16.doc-5k",
         "sarvam-105b-bf16.doc-8k"]


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _run(samples, seconds=10.0):
    """A hand-made `Run`: `/metrics` readings `seconds` apart."""
    window = loops.Window(t0=100.0, replies=[], t_end=0.0,
                          seconds=seconds * max(1, len(samples) - 1))
    return perf_run.Run(
        cell=cells.load_cell(CELLS[0], ROOT), window=window, t_start=0.0,
        samples=[(100.0 + i * seconds, s) for i, s in enumerate(samples)],
        steady_until=100.0 + window.seconds, log_setup="", log_window="",
        faults=[])


def _read(metric, run):
    return cells.load_function(
        cells.reader_path(ROOT, "layers", metric), "read")(run)


#: what the server had counted when the window opened, and when it
#: closed 10 s later: 0.05 s of a build inside the window
OPENING = {READY: 41.5, IMPORT: 9.0, BACKEND: 11.5, WEIGHTS: 12.25,
           KV_POOL: 0.75, TRACE: 130.0, LOWER: 21.0, COMPILE: 34.5,
           HITS: 210.0, MISSES: 30.0, BUILT: 240.0}
CLOSE = {**OPENING, TRACE: 130.03, LOWER: 21.01, COMPILE: 34.51,
         BUILT: 241.0, MISSES: 31.0}
#: metric -> its reading of that window
READINGS = {
    "startup_ready_s": 41.5,
    "startup_backend_s": 20.5,
    "startup_weights_s": 12.25,
    "startup_kv_pool_s": 0.75,
    "program_trace_s": 130.0,
    "program_lower_s": 21.0,
    "program_compile_s": 34.5,
    "program_cache_hit_pct": 87.5,
    "programs_built": 240.0,
    "program_build_in_window_pct.batch": 0.5,
}


@pytest.mark.parametrize("metric", sorted(ENTRIES))
def test_a_reader_reads_the_windows_first_sample(metric):
    """A set-up quantity is the counter's value when the window opens,
    whatever it grows to after; the share of the window spent building
    is the growth."""
    assert _read(metric, _run([OPENING, CLOSE])) == \
        pytest.approx(READINGS[metric])
    quiet = _read(metric, _run([OPENING, OPENING]))
    assert quiet == pytest.approx(
        0.0 if metric.endswith(".batch") else READINGS[metric])


@pytest.mark.parametrize("metric", sorted(ENTRIES))
def test_a_reader_finds_nothing_in_a_program_without_the_counters(metric):
    """The parent's `/metrics`: rounds and tokens, none of these. No
    reader raises, and the line leaves the metric out."""
    parent = {A + "engine_rounds_total": 1000.0,
              A + "host_dispatch_seconds_total": 3.0}
    assert _read(metric, _run([parent, parent])) is None
    assert _read(metric, _run([])) is None
    bench = _bench()
    entry = [m for m in bench["per_layer"] if m["name"] == metric]
    assert perf_run.read_metrics(_run([parent, parent]), entry,
                                 "layers") == {}


@pytest.mark.parametrize("missing", [IMPORT, BACKEND])
def test_a_sum_of_counters_needs_every_one(missing):
    first = {k: v for k, v in OPENING.items() if k != missing}
    assert _read("startup_backend_s", _run([first, first])) is None


@pytest.mark.parametrize("hits,misses,want", [
    (0.0, 0.0, None),               # no request: the cache is off
    (0.0, 12.0, 0.0),               # the first run of a checkout
    (12.0, 0.0, 100.0),             # a warm one
    (3.0, 1.0, 75.0)])
def test_the_cache_hit_share_by_requests(hits, misses, want):
    first = {HITS: hits, MISSES: misses}
    got = _read("program_cache_hit_pct", _run([first, first]))
    assert got == (want if want is None else pytest.approx(want))
    assert _read("program_cache_hit_pct", _run([{HITS: 3.0}] * 2)) is None


def test_the_manifest_has_the_ten_in_order_and_every_cell_reports_them():
    """Found by their names, wherever they stand: a later PR may append
    a metric behind them and a cell to their lists."""
    bench = _bench()
    names = [m["name"] for m in bench["per_layer"]]
    places = [names.index(name) for name in ENTRIES]
    assert places == sorted(places) and len(set(names)) == len(names)
    before = {m["layer"] for m in bench["per_layer"]
              if m["name"] not in ENTRIES}
    for name, (unit, better, moves, layer) in ENTRIES.items():
        (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
        assert entry["workloads"][:len(CELLS)] == CELLS
        assert set(entry["workloads"]) <= {
            w["name"] for w in bench["workloads"]}
        assert {k: v for k, v in entry.items() if k != "workloads"} == \
            dict(name=name, unit=unit, better=better,
                 source="program_counter", layer=LAYERS[layer],
                 moves=moves)
        # a layer the manifest already names, letter for letter
        assert entry["layer"] in before
        path = cells.reader_path(ROOT, "layers", name)
        assert os.path.isfile(path)
        assert os.path.basename(path) == name.split(".batch")[0] + ".py"
    for cell in CELLS:
        reported = {m["name"] for m in
                    cells.load_cell(cell, ROOT).per_layer}
        assert set(ENTRIES) <= reported
    # `setup_s` has more than the count of three names in a log under it
    assert {m["name"] for m in bench["per_layer"]
            if m["moves"] == "setup_s"} >= set(ENTRIES) - {
                "program_build_in_window_pct.batch"} | {"programs_warmed"}


def test_the_counters_they_read_are_ones_the_program_exports():
    from aphrodite_tpu.engine import metrics
    exported = {name for name, _, _ in metrics._STAGE_COUNTERS}
    assert set(OPENING) - {READY} <= exported
    assert READY == metrics.Metrics(["model_name"]).gauge_startup._name \
        .replace("aphrodite:", A)


@pytest.fixture(scope="module")
def rehearsed(tmp_path_factory):
    """One rehearsal of the real server at a toy size on the CPU, from
    a checkout of its own whose COPY of the rehearsal's manifest has
    the ten entries appended (the rehearsal's own manifest is the
    benchmark's file and stays as it is)."""
    root = tmp_path_factory.mktemp("checkout")
    os.symlink(os.path.join(ROOT, "aphrodite_tpu"), root / "aphrodite_tpu")
    shutil.copytree(os.path.join(ROOT, "perf"), root / "perf",
                    ignore=shutil.ignore_patterns(".cache", ".work",
                                                  "__pycache__"))
    path = root / "perf" / "rehearse" / "manifest.json"
    manifest = json.loads(path.read_text())
    real = {m["name"]: m for m in _bench()["per_layer"]}
    manifest["per_layer"] += [dict(real[name], workloads=["tiny.batch"])
                              for name in ENTRIES]
    path.write_text(json.dumps(manifest))
    log = root / "server.log"
    out = subprocess.run(
        [sys.executable, str(root / "perf" / "run.py"), "--rehearse",
         "--workload", "tiny.batch", "--seed", "3000000061", "--seconds",
         "3", "--trace", "2", "--keep-log", str(log)],
        cwd=root, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    (line,) = [ln for ln in out.stdout.splitlines() if ln.startswith("{")]
    return json.loads(line), log.read_text()


def test_the_rehearsal_prints_all_ten(rehearsed):
    line, _ = rehearsed
    metrics = line["metrics"]
    assert line["correct"] and set(ENTRIES) <= set(metrics)
    for name, (unit, _, _, _) in ENTRIES.items():
        assert metrics[name]["unit"] == unit
        assert metrics[name]["value"] is not None
    value = {name: metrics[name]["value"] for name in ENTRIES}
    # process start to ready lies inside the harness's own set-up
    assert 0 < value["startup_ready_s"] < metrics["setup_s"]["value"]
    assert 0 < value["startup_backend_s"] < value["startup_ready_s"]
    assert value["startup_weights_s"] > 0 and \
        value["startup_kv_pool_s"] > 0
    for stage in ("trace", "lower", "compile"):
        assert value[f"program_{stage}_s"] > 0
    # every program the process built, where the log's reader counts
    # three names
    assert value["programs_built"] >= \
        metrics["programs_warmed"]["value"] > 0
    assert 0 <= value["program_cache_hit_pct"] <= 100
    assert 0 <= value["program_build_in_window_pct.batch"] < 100


def test_the_rehearsals_server_logs_its_start_and_its_programs(rehearsed):
    line, log = rehearsed
    (startup,) = [ln for ln in log.splitlines() if "] startup: " in ln]
    phases = dict(part.split("=") for part in
                  startup.split("] startup: ")[1].split(" (")[0].split())
    assert list(phases) == ["import", "backend", "tokenizer", "weights",
                            "kv_pool", "runner", "frontend", "total"]
    ready = float(startup.split("process start to ready ")[1].rstrip(")"))
    assert ready == pytest.approx(
        line["metrics"]["startup_ready_s"]["value"], abs=2e-3)
    # the phases tile it
    assert float(phases["total"]) == pytest.approx(ready, rel=0.05)
    built = [ln for ln in log.splitlines() if "] program built: " in ln]
    steps = [ln for ln in built if "fun=jit(_step_sample)" in ln]
    assert steps and all(" round=-" not in ln for ln in steps)
    assert any(" path=setup.weights " in ln for ln in built)
    (summary,) = [ln for ln in log.splitlines() if "] programs: " in ln]
    assert f"programs: {len(built)} built, " in summary
    assert "jit(_step_sample) " in summary
    assert log.index("] programs: ") < log.index("Drain complete")
