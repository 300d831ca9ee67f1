"""Set-up, accounted for by the one `Tracer`
(`aphrodite_tpu/common/tracing.py`): the programs the process builds,
filed by listeners of `jax.monitoring` under the `program.*` names
(outermost traces alone, a build's round in its log line, its stages in
the profiler's trace), and the phases from process start to ready."""
import json
import logging
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from jax._src import monitoring as jax_monitoring

from aphrodite_tpu.common import tracing
from aphrodite_tpu.common.sampling_params import SamplingParams
from aphrodite_tpu.engine.metrics import _STAGE_COUNTERS, StatLogger
from tests.engine.test_tracing import (SETUP_COUNTERS, Recorder, _prompt,
                                       _stats, _value)

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
GREEDY = SamplingParams(temperature=0.0, max_tokens=3, ignore_eos=True)
TRACE = "/jax/core/compile/jaxpr_trace_duration"


def _totals():
    with tracing.BUILDS.lock:
        return (dict(tracing.BUILDS.seconds), dict(tracing.BUILDS.counts),
                tracing.BUILDS.nested_traces)


@pytest.fixture
def heard():
    """Every call `jax.monitoring` makes to a listener while the test
    runs: (kind, event, value or None, fun_name or None)."""
    calls = []

    def scalar(event, value, **kw):
        calls.append(("scalar", event, value, kw.get("fun_name")))

    def duration(event, secs, **kw):
        calls.append(("duration", event, secs, kw.get("fun_name")))

    def event(event, **kw):
        calls.append(("event", event, None, None))

    jax.monitoring.register_scalar_listener(scalar)
    jax.monitoring.register_event_duration_secs_listener(duration)
    jax.monitoring.register_event_listener(event)
    yield calls
    jax.monitoring.unregister_scalar_listener(scalar)
    jax.monitoring.unregister_event_duration_listener(duration)
    jax.monitoring.unregister_event_listener(event)


@pytest.fixture
def built_lines():
    """The `program built:` lines logged while the test runs."""
    lines = []

    class Keep(logging.Handler):
        def emit(self, record):
            lines.append(record.getMessage())

    handler, logger = Keep(), logging.getLogger(tracing.logger.name)
    logger.addHandler(handler)
    yield lines
    logger.removeHandler(handler)


def _llm(tiny_model_dir, **kw):
    from aphrodite_tpu.endpoints.llm import LLM
    return LLM(model=tiny_model_dir, load_format="dummy", dtype="float32",
               block_size=16, max_model_len=256, max_num_seqs=4,
               swap_space=0.01, **kw)


# ---- the builds ----

def test_a_nested_jits_trace_is_counted_once(heard):
    tracing.Tracer()                    # the listeners are in

    @jax.jit
    def inner(x):
        return jnp.sin(x) * 2 + jnp.cos(x)

    @jax.jit
    def outer(x):
        return inner(x) + inner(x * 3).sum()

    x = jnp.ones(5)
    del heard[:]
    seconds, counts, nested = _totals()
    outer(x)
    after_s, after_c, after_nested = _totals()
    traces = [(fun, secs) for kind, event, secs, fun in heard
              if kind == "duration" and event == TRACE]
    assert traces[-1][0] == "outer" and len(traces) > 4
    # the outermost function's own duration, not its callees' on top
    assert after_s["program.trace"] - seconds["program.trace"] == \
        pytest.approx(traces[-1][1], rel=1e-6)
    assert sum(secs for _, secs in traces) > traces[-1][1]
    assert after_c["program.trace"] - counts["program.trace"] == 1
    assert after_nested - nested == len(traces) - 1
    # lowering and compiling are the outermost function's alone
    for name in ("program.lower", "program.compile"):
        assert after_c[name] - counts[name] == 1
        assert after_s[name] > seconds[name]
    row = tracing.BUILDS.by_function["jit(outer)"]
    assert row[0] >= 1 and all(v > 0 for v in row[1:])


def test_a_hundred_calls_of_a_built_function_fire_no_listener(heard):
    tracing.Tracer()

    @jax.jit
    def built(x):
        return x * 2 + 1

    x = jnp.ones(7)
    built(x).block_until_ready()
    del heard[:]
    before = _totals()
    for _ in range(100):
        x = built(x)
    x.block_until_ready()
    assert heard == [] and _totals() == before


def test_a_kernels_nested_trace_is_kept_by_function(monkeypatch):
    tracing.Tracer()
    monkeypatch.setattr(tracing, "KERNEL_JITS", frozenset(["_a_kernel"]))

    @jax.jit
    def _a_kernel(x):
        return x - 1

    @jax.jit
    def step(x):
        return _a_kernel(x) * _a_kernel(x + 1.0)

    x = jnp.ones(3)
    before = _totals()[1]["program.trace"]
    step(x)
    assert "_a_kernel (nested) " in tracing.BUILDS.summary()
    traces, seconds = tracing.BUILDS.by_function.pop("_a_kernel")[:2]
    # (the second call of the same shapes finds the first's trace)
    assert traces >= 1 and seconds > 0
    assert _totals()[1]["program.trace"] == before + 1
    assert tracing.BUILDS.summary().startswith("programs: ")


_CACHE_SCRIPT = """
import json, sys, tempfile
import jax, jax.numpy as jnp
from aphrodite_tpu.common import tracing
jax.config.update("jax_compilation_cache_dir", tempfile.mkdtemp())
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
tracing.Tracer()

@jax.jit
def only_here(x):
    return jnp.tanh(x) @ x.T

x = jnp.ones((4, 4))
x.block_until_ready()
out = []
for _ in range(2):
    before = dict(tracing.BUILDS.counts), dict(tracing.BUILDS.seconds)
    only_here(x)
    out.append({k: tracing.BUILDS.counts[k] - before[0][k]
                for k in before[0]})
    out[-1]["load_s"] = tracing.BUILDS.seconds["program.cache_load"] - \\
        before[1]["program.cache_load"]
    jax.clear_caches()
print(json.dumps(out))
"""


def test_the_first_build_is_a_miss_and_the_second_process_like_a_hit():
    """With a persistent cache directory the first build of a function
    misses; after `jax.clear_caches()`, as in a second process, it is
    traced and lowered again and its executable loaded from the cache:
    a hit, and `program.cache_load` seconds."""
    done = subprocess.run(
        [sys.executable, "-c", _CACHE_SCRIPT], cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT},
        capture_output=True, text=True, timeout=120)
    assert done.returncode == 0, done.stderr[-2000:]
    first, second = json.loads(done.stdout.strip().splitlines()[-1])
    assert (first["program.cache_miss"], first["program.cache_hit"]) == \
        (1, 0) and first["load_s"] == 0
    assert (second["program.cache_miss"], second["program.cache_hit"]) \
        == (0, 1) and second["load_s"] > 0
    for run in (first, second):
        assert run["program.trace"] == run["program.lower"] == \
            run["program.compile"] == 1


def test_the_listeners_are_installed_once_and_put_back():
    def times():
        return (jax_monitoring.get_scalar_listeners().count(
                    tracing._stage_started),
                jax_monitoring.get_event_duration_listeners().count(
                    tracing._stage_ended),
                jax_monitoring.get_event_listeners().count(
                    tracing._cache_answered))
    tracing.Tracer()
    tracing.Tracer()
    assert times() == (1, 1, 1)
    generation = tracing._generation
    try:
        # (JAX 0.9.0's clears every list but the scalar listeners')
        jax.monitoring.clear_event_listeners()
        assert times()[1:] == (0, 0)
        # a trace that no listener closes leaves its thread deep
        tracing._stage_started(TRACE, 0.0, fun_name="lost")
        assert tracing._building().depth == 1
    finally:
        tracing.Tracer()                # the next engine's
    assert times() == (1, 1, 1) and tracing._generation > generation
    x = jnp.ones(2)
    before = _totals()[1]["program.trace"]
    jax.jit(lambda x: x + 2)(x)
    # the void depth did not make this outermost trace a nested one
    assert _totals()[1]["program.trace"] == before + 1
    assert tracing._building().depth == 0


def test_two_engines_in_one_process_share_the_listeners_and_the_account(
        tiny_llm, tiny_model_dir):
    other = _llm(tiny_model_dir)
    assert other.engine.tracer is not tiny_llm.engine.tracer
    assert jax_monitoring.get_scalar_listeners().count(
        tracing._stage_started) == 1
    assert jax_monitoring.get_event_duration_listeners().count(
        tracing._stage_ended) == 1
    # the seconds are the process's: each engine exports the same
    for engine in (tiny_llm.engine, other.engine):
        engine._get_stats(None)
        assert {n: engine.tracer.counts[n] for n in tracing.BUILD_NAMES} \
            == tracing.BUILDS.counts
        assert engine.tracer.seconds["program.compile"] > 0


def test_a_build_on_the_step_thread_is_logged_with_its_round(
        tiny_model_dir, built_lines, monkeypatch):
    monkeypatch.setenv("APHRODITE_SPEC", "0")
    engine = _llm(tiny_model_dir).engine
    # before the first round a build is its phase's (a second engine
    # of a process finds the loader's programs built)
    x = jnp.ones(3)
    with engine.tracer.phase("setup.weights"):
        jax.jit(lambda x: x * 5)(x)
    assert " round=- path=setup.weights rows=- prompt_tokens=- " in \
        built_lines[-1]
    del built_lines[:]
    engine.add_request("a", None, GREEDY, prompt_token_ids=_prompt(3))
    first = engine._round + 1
    while engine.has_unfinished_requests():
        engine.step()
    steps = [ln for ln in built_lines
             if ln.startswith("program built: fun=jit(_step")]
    assert len(steps) >= 2, built_lines
    assert f" round={first} path=prompt rows=1 prompt_tokens=20 " in \
        steps[0]
    assert f" round={first + 1} path=decode rows=1 prompt_tokens=0 " in \
        steps[1]
    for line in steps:
        assert " cache=" in line and " trace=" in line and \
            " lower=" in line and " compile=" in line


def test_under_the_profiler_a_build_is_named_inside_the_dispatch(
        tiny_model_dir, monkeypatch):
    rec = Recorder()
    monkeypatch.setattr(tracing, "TraceAnnotation", rec)
    monkeypatch.setenv("APHRODITE_SPEC", "0")
    engine = _llm(tiny_model_dir).engine
    assert rec.spans == []              # set-up ran with the profiler off
    monkeypatch.setattr(engine.tracer, "annotating", True)
    engine.add_request("a", None, GREEDY, prompt_token_ids=_prompt(4))
    engine.step()
    stages = [(name, parent, facts) for name, parent, facts in rec.spans
              if name.startswith("aph.program.")]
    steps = [s for s in stages if "_step" in s[2]["fun"]]
    assert [name for name, _, _ in steps[:3]] == [
        "aph.program.trace", "aph.program.lower", "aph.program.compile"]
    for name, parent, facts in steps[:3]:
        assert parent == "aph.runner.dispatch"
        assert facts["path"] == "prompt" and facts["round"] == engine._round
    assert steps[0][2]["fun"] == "_step_sample"
    assert steps[2][2]["fun"] == "jit(_step_sample)"
    # a nested trace opens nothing: a stage's parent is never a stage
    assert not [s for s in stages if s[1].startswith("aph.program.trace")]

    @jax.jit
    def raises(x):
        raise ValueError("in the trace")

    del rec.spans[:]
    with engine.tracer.span("runner.dispatch"):
        with pytest.raises(ValueError):
            raises(jnp.ones(2))
    assert rec._open == []              # none is left open
    assert ("aph.program.trace", "aph.runner.dispatch") in [
        (name, parent) for name, parent, _ in rec.spans]
    assert tracing._building().depth == 0 and \
        tracing._building().open == []


# ---- the phases ----

def test_the_phases_are_entered_once_in_order_and_tile_the_start(
        tiny_model_dir, monkeypatch, built_lines):
    entered, own = [], tracing.Tracer.phase

    def phase(self, name):
        entered.append(name)
        return own(self, name)
    monkeypatch.setattr(tracing.Tracer, "phase", phase)
    tracer = _llm(tiny_model_dir, disable_log_stats=False).engine.tracer
    assert tuple(entered) == tracing.SETUP_PHASES[1:]
    assert tracing.SETUP_PHASES[0] == "setup.import"
    assert all(tracer.counts[n] == 1 for n in tracing.SETUP_PHASES)
    assert all(tracer.seconds[n] > 0 for n in (
        "setup.import", "setup.backend", "setup.weights", "setup.kv_pool",
        "setup.runner"))
    total = sum(tracer.seconds[n] for n in tracing.SETUP_PHASES)
    assert 0 < tracer.startup_seconds < 86400
    assert total == pytest.approx(tracer.startup_seconds, rel=0.05)
    (line,) = [ln for ln in built_lines if ln.startswith("startup: ")]
    assert line.split()[1:8] == [
        f"{n[len('setup.'):]}={tracer.seconds[n]:.3f}"
        for n in tracing.SETUP_PHASES]
    assert f"(process start to ready {tracer.startup_seconds:.3f})" in line


def test_an_engine_built_alone_has_its_own_tracer_and_no_import_phase(
        tiny_model_dir):
    from aphrodite_tpu.engine.aphrodite_engine import AphroditeEngine
    from aphrodite_tpu.engine.args_tools import EngineArgs
    engine = AphroditeEngine.from_engine_args(EngineArgs(
        model=tiny_model_dir, load_format="dummy", dtype="float32",
        block_size=16, max_model_len=256, max_num_seqs=4,
        swap_space=0.01, disable_log_stats=True))
    counts = engine.tracer.counts
    assert counts["setup.import"] == counts["setup.frontend"] == 0
    assert counts["setup.backend"] == counts["setup.weights"] == 1
    assert engine.tracer.startup_seconds == 0.0
    assert engine.executor.tracer is engine.tracer


def test_process_age_reads_the_seconds_since_the_process_started():
    first = tracing.process_age()
    assert 0 < first < 86400
    assert 0 <= tracing.process_age() - first < 5
    tracer = tracing.Tracer.at_entry()
    assert tracer.counts["setup.import"] == 1
    assert tracer.seconds["setup.import"] >= first


# ---- the exporter ----

def test_the_start_up_gauge_is_exported_beside_the_other_gauges():
    labels = dict(model_name="tracing-test-startup")
    log = StatLogger(labels=labels)
    log.log(_stats())
    assert _value("aphrodite:startup_seconds", labels) == 0.0
    log.log(_stats(startup_seconds=41.5))
    assert _value("aphrodite:startup_seconds", labels) == 41.5


def test_every_set_up_name_is_exported_and_described_where_the_rest_are():
    """Each new name is one of `NAMES`, a row of `_STAGE_COUNTERS` with
    a description, and named in the README's operator section and in
    PERF.md; the three exporters nothing read are gone from all
    three."""
    table = {metric: doc for metric, doc, _ in _STAGE_COUNTERS}
    with open(os.path.join(ROOT, "README.md")) as f:
        readme = f.read()
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    for counter, (_, name) in SETUP_COUNTERS.items():
        assert name in tracing.NAMES
        assert len(table[counter]) > 20, counter
        assert counter in readme, counter
        assert counter.split(":")[1] in perf, counter
        assert name in readme and name in perf, name
    for text in (readme, perf):
        assert "aphrodite:startup_seconds" in text
        for stage in ("aph.program.trace", "aph.program.lower",
                      "aph.program.compile"):
            assert stage in text
    gone = ("aphrodite:window_release_seconds_total",
            "aphrodite:window_close_seconds_total",
            "aphrodite:summarise_seconds_total")
    for counter in gone:
        assert counter not in table and counter not in readme
    # their spans stay: they name idle gaps in a trace
    for name in ("cache.window_release", "cache.window_close",
                 "runner.summarise"):
        assert name in tracing.NAMES
