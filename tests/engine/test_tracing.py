"""The engine round's spans and per-stage counters
(`aphrodite_tpu/common/tracing.py`): every path of a round goes through
the same span names, nested as PERF.md's table says; with the profiler
off a span is two clock reads and no annotation; the counters ride
through `Stats` into Prometheus."""
import asyncio
import glob
import os
import time

import pytest
from prometheus_client import REGISTRY

from aphrodite_tpu.common import flags, tracing
from aphrodite_tpu.common.sampling_params import SamplingParams
from aphrodite_tpu.engine.metrics import Stats, StatLogger

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
GREEDY = SamplingParams(temperature=0.0, max_tokens=4, ignore_eos=True)
#: the stages a round's wall time is made of, as the counters sum them
STAGES = ("sched.schedule", "runner.prepare", "runner.dispatch",
          "runner.device_wait", "sampler.finalize", "engine.process")


class Recorder:
    """Stands in for `jax.profiler.TraceAnnotation`: keeps what the
    profiler's trace would hold, each span with its depth and the name
    of the span it opened under."""

    def __init__(self):
        self.spans, self._open = [], []

    def __call__(self, name, **facts):
        recorder = self

        class Annotation:
            def __enter__(self):
                parent = recorder._open[-1] if recorder._open else None
                recorder.spans.append((name, parent, dict(facts)))
                recorder._open.append(name)

            def __exit__(self, *exc):
                assert recorder._open.pop() == name

        return Annotation()

    def names(self):
        return [name for name, _, _ in self.spans]

    def rounds(self):
        """The spans of each `aph.engine.step`, in order."""
        out = []
        for span in self.spans:
            if span[0] == "aph.engine.step":
                out.append([])
            if out:
                out[-1].append(span)
        return out


@pytest.fixture
def recorder(monkeypatch):
    """What the profiler's trace would hold of the engines whose
    tracers a test switches on (`_annotating`)."""
    rec = Recorder()
    monkeypatch.setattr(tracing, "TraceAnnotation", rec)
    monkeypatch.setenv("APHRODITE_SPEC", "0")
    return rec


@pytest.fixture
def _annotating(monkeypatch):
    def on(engine):
        monkeypatch.setattr(engine.tracer, "annotating", True)
        return engine
    return on


def _prompt(i, n=20):
    return [(i * 7 + j * 3) % 90 + 5 for j in range(n)]


def _drain(engine):
    while engine.has_unfinished_requests():
        engine.step()


def _tree(round_spans):
    """[(name, parent)] of one round, without the `aph.` prefix: the
    round's own stages (a program that a stage meets for the first
    time is built inside it and named there, `aph.program.*`:
    `test_tracing_setup.py`)."""
    return [(name[4:], parent and parent[4:])
            for name, parent, _ in round_spans
            if not name.startswith("aph.program.")]


# ---- the module ----

def test_a_span_accumulates_and_an_unknown_name_raises():
    tracer = tracing.Tracer()
    with tracer.span("runner.prepare"):
        time.sleep(0.01)
    assert tracer.counts["runner.prepare"] == 1
    assert 0.01 <= tracer.seconds["runner.prepare"] < 0.5
    with pytest.raises(KeyError):
        with tracer.span("runner.prepair"):
            pass
    tracer.add("preemptions")
    assert tracer.counts["preemptions"] == 1
    assert set(tracer.seconds) == set(tracer.counts) == set(tracing.NAMES)
    # an engine's tracer is its own
    assert tracing.Tracer().counts["runner.prepare"] == 0


def test_profiler_off_enters_no_annotation_and_on_names_the_facts(
        monkeypatch):
    rec = Recorder()
    monkeypatch.setattr(tracing, "TraceAnnotation", rec)
    tracer = tracing.Tracer()
    tracer.set_round(round=7, path="decode", rows=3, prompt_tokens=0)
    with tracer.span("runner.dispatch"):
        pass
    assert rec.spans == []
    tracer.annotate(True)
    with tracer.span("engine.step"):
        with tracer.span("cache.kv_handoff", pages=5):
            tracer.annotate(False)      # a span ends as it began
    with tracer.span("engine.step"):
        pass
    assert rec.spans == [
        ("aph.engine.step", None,
         dict(round=7, path="decode", rows=3, prompt_tokens=0)),
        ("aph.cache.kv_handoff", "aph.engine.step",
         dict(round=7, path="decode", rows=3, prompt_tokens=0, pages=5))]
    assert rec._open == [] and tracer.counts["engine.step"] == 2


def test_spanned_wraps_the_whole_call_of_a_method():
    class Stage:
        tracer = tracing.Tracer()

        @tracing.spanned("sampler.plan")
        def plan(self, x, y=1):
            """doc"""
            return x + y
    assert Stage().plan(2, y=3) == 5 and Stage.plan.__doc__ == "doc"
    assert Stage.tracer.counts["sampler.plan"] == 1


# ---- the spans of a round, by path ----

def test_prompt_decode_and_combined_rounds_nest_as_the_table_says(
        tiny_llm, recorder, _annotating):
    engine = _annotating(tiny_llm.engine)
    late_before = engine.tracer.counts["runner.prompt_late"]
    engine.add_request("a", None, GREEDY, prompt_token_ids=_prompt(1))
    engine.step()                       # prompt
    engine.step()                       # decode
    engine.add_request("b", None, GREEDY, prompt_token_ids=_prompt(2))
    engine.step()                       # combined: b's prompt, a's decode
    _drain(engine)
    rounds = recorder.rounds()
    paths = [r[-1][2]["path"] for r in rounds]
    assert paths[:3] == ["prompt", "decode", "combined"]
    # the last call finds nothing to schedule and pulls the last round
    assert set(paths[3:-1]) == {"decode"} and paths[-1] == "empty"

    step = [("sched.schedule", "engine.step"),
            ("runner.prepare", "engine.step"),
            ("sampler.plan", "runner.prepare"),
            ("runner.dispatch", "engine.step"),
            ("runner.device_wait", "engine.step"),
            ("sampler.finalize", "engine.step")]
    # a decode round is dispatched, then the round before it is pulled
    # and processed: every span of the path exactly once, in the order
    # of a synced round
    for r in rounds[3:-1]:
        assert _tree(r) == [("engine.step", None)] + step + \
            [("engine.process", "engine.step")]
    # the first decode round has no round before it to pull (the
    # prompt round was pulled at once)
    assert _tree(rounds[1]) == [("engine.step", None)] + step[:4]
    # a prompt round is dispatched without a sync, and the scheduler is
    # asked for a further prompt-only round to chain behind it
    assert _tree(rounds[0]) == [("engine.step", None)] + step[:4] + \
        [("sched.schedule", "engine.step")] + step[4:] + \
        [("engine.process", "engine.step")]
    # a combined round is prepared and dispatched a step at a time, in
    # the device's order: the decode step is out before the prompt step
    # is prepared, both ahead of the pull of the round before
    assert _tree(rounds[2]) == [("engine.step", None)] + step[:4] + \
        step[1:] + [("engine.process", "engine.step")]
    # the last call: the pull, and the processing of both rounds
    assert _tree(rounds[-1]) == [("engine.step", None), step[0]] + \
        step[4:] + [("engine.process", "engine.step")] * 2
    # the facts: `round` counts up; the scheduled round's spans say what
    # ran (the step and the schedule open before that is known)
    numbers = [r[0][2]["round"] for r in rounds]
    assert numbers == list(range(numbers[0], numbers[0] + len(rounds)))
    assert rounds[2][0][2] == dict(round=numbers[2])
    # a round that goes out ahead of a round in flight says what that
    # one carries (`pulls`); the first decode round had none in flight
    assert rounds[2][-1][2] == dict(round=numbers[2], path="combined",
                                    rows=2, prompt_tokens=20,
                                    pulls="decode")
    assert "pulls" not in rounds[1][-1][2]
    assert rounds[3][-1][2]["pulls"] == "combined"
    assert {r[-1][2].get("pulls") for r in rounds[4:-1]} == {"decode"}
    # and its first program's dispatch whether the device had drained

    def says_starved(r):
        return ["starved" in facts for name, _, facts in r
                if name == "aph.runner.dispatch"]
    assert says_starved(rounds[1]) == [False]       # nothing in flight
    assert says_starved(rounds[2]) == [True, False]
    assert says_starved(rounds[3]) == [True]
    assert not any(says_starved(rounds[0]))         # a synced round
    assert all(facts["starved"] in (0, 1) for r in rounds
               for name, _, facts in r if "starved" in facts)
    # and its second program's whether the first had already finished

    def says_late(r):
        return [facts.get("late") for name, _, facts in r
                if name == "aph.runner.dispatch"]
    first, second = says_late(rounds[2])
    assert first is None and second in (0, 1)
    assert {late for r in rounds[:2] + rounds[3:]
            for late in says_late(r)} == {None}
    assert engine.tracer.counts["runner.prompt_late"] - late_before == \
        second


@pytest.fixture(scope="module")
def burst_llm(tiny_model_dir):
    from aphrodite_tpu.endpoints.llm import LLM
    return LLM(model=tiny_model_dir, load_format="dummy", dtype="float32",
               block_size=16, max_model_len=256, max_num_seqs=4,
               multi_step=4, swap_space=0.01)


def test_burst_and_fused_combined_rounds_use_the_same_names(
        burst_llm, recorder, _annotating):
    engine = _annotating(burst_llm.engine)
    sp = SamplingParams(temperature=0.0, max_tokens=9, ignore_eos=True)
    engine.add_request("a", None, sp, prompt_token_ids=_prompt(3))
    engine.step()                       # prompt
    engine.step()                       # burst of 4
    engine.add_request("b", None, sp, prompt_token_ids=_prompt(4))
    engine.step()                       # fused: prompt + burst, one sync
    _drain(engine)
    rounds = recorder.rounds()
    paths = [r[-1][2]["path"] for r in rounds]
    assert paths[:3] == ["prompt", "burst", "combined"]
    burst = _tree(rounds[1])
    # the page reservation of the burst is scheduler work
    assert burst.count(("sched.schedule", "engine.step")) == 2
    assert [n for n, _ in burst if n != "sched.schedule"] == [
        "engine.step", "runner.prepare", "sampler.plan",
        "runner.dispatch", "runner.device_wait", "sampler.finalize",
        "engine.process"]
    fused = [n for n, _ in _tree(rounds[2]) if n != "sched.schedule"]
    assert fused == ["engine.step",
                     "runner.prepare", "sampler.plan", "runner.dispatch",
                     "runner.prepare", "sampler.plan", "runner.dispatch",
                     "runner.device_wait", "sampler.finalize",
                     "engine.process"]
    assert {n for r in rounds for n in recorder.names()} <= {
        "aph." + n for n in tracing.NAMES}


def test_a_speculative_round_uses_the_same_names(
        tiny_llm, recorder, _annotating, monkeypatch):
    monkeypatch.setenv("APHRODITE_SPEC", "1")
    engine = _annotating(tiny_llm.engine)
    # a prompt that repeats itself, so that the n-gram drafter proposes
    sp = SamplingParams(temperature=0.0, max_tokens=12, ignore_eos=True)
    engine.add_request("s", None, sp,
                       prompt_token_ids=[5, 6, 7, 8, 9] * 8)
    _drain(engine)
    spec = [r for r in recorder.rounds()
            if r[-1][2].get("path") == "spec"]
    if not spec:
        pytest.skip("the dummy weights never repeated a token: no draft")
    assert [n for n, _ in _tree(spec[0]) if n != "sched.schedule"] == [
        "engine.step", "runner.prepare", "sampler.plan",
        "runner.dispatch", "runner.device_wait", "sampler.finalize",
        "engine.process"]


def test_a_rebuilt_engine_keeps_its_tracer_and_its_counts(
        tiny_model_dir, monkeypatch):
    from aphrodite_tpu.endpoints.llm import LLM
    monkeypatch.setenv("APHRODITE_SPEC", "0")
    engine = LLM(model=tiny_model_dir, load_format="dummy",
                 dtype="float32", block_size=16, max_model_len=256,
                 max_num_seqs=4, swap_space=0.01).engine
    engine.add_request("r", None, GREEDY, prompt_token_ids=_prompt(6))
    engine.step()
    rounds = engine.tracer.counts["engine.step"]
    assert rounds == 1 and engine.tracer.counts["queue_wait"] == 1
    engine.reincarnate()
    for part in (engine.scheduler, engine.executor,
                 engine.executor.model_runner):
        assert part.tracer is engine.tracer
    _drain(engine)
    assert engine.tracer.counts["engine.step"] > rounds
    # the restored request is admitted again, not scheduled for the
    # first time again
    assert engine.tracer.counts["queue_wait"] == 1


# ---- cost and completeness ----

def test_stage_seconds_sum_to_the_steps_wall_time(tiny_llm, monkeypatch):
    monkeypatch.setenv("APHRODITE_SPEC", "0")
    engine = tiny_llm.engine
    for i in range(4):
        engine.add_request(f"w{i}", None, GREEDY,
                           prompt_token_ids=_prompt(i))
    _drain(engine)                      # every shape is compiled now
    sp = SamplingParams(temperature=0.0, max_tokens=24, ignore_eos=True)
    for i in range(4):
        engine.add_request(f"m{i}", None, sp, prompt_token_ids=_prompt(i))
    before = dict(engine.tracer.seconds)
    t0 = time.perf_counter()
    _drain(engine)
    wall = time.perf_counter() - t0
    grew = {k: engine.tracer.seconds[k] - before[k] for k in before}
    stages = sum(grew[k] for k in STAGES)
    assert grew["engine.step"] <= wall
    # self times cover the step: what lies between the spans is glue
    assert 0.85 * grew["engine.step"] <= stages <= grew["engine.step"]
    # the plan is inside prepare, not beside it
    assert grew["sampler.plan"] < grew["runner.prepare"]


def test_profiler_off_a_round_costs_two_clock_reads_a_span(
        tiny_llm, monkeypatch):
    monkeypatch.setenv("APHRODITE_SPEC", "0")
    engine = tiny_llm.engine
    engine.add_request("c", None, GREEDY, prompt_token_ids=_prompt(5))
    engine.step()                       # the prompt round
    engine.step()                       # a decode round, now in flight
    reads = []

    def clock():
        reads.append(1)
        return time.perf_counter()

    monkeypatch.setattr(tracing, "_clock", clock)
    monkeypatch.setattr(
        tracing, "TraceAnnotation",
        lambda *a, **k: pytest.fail("annotation with the profiler off"))
    def spans():
        # (the round also counts events that are no spans: it was
        # dispatched ahead, to a device that had drained or had not,
        # and the pull behind it blocked; its plan was the round
        # before's; and the pages its attention copies and those that
        # are live, over one more decode step)
        return sum(n for name, n in engine.tracer.counts.items()
                   if name not in ("runner.ahead", "round.ahead",
                                   "runner.starved", "pull.blocked",
                                   "pull.blocked.decode",
                                   "sampler.plan_reuse",
                                   "attn.pages_fetched",
                                   "attn.pages_live",
                                   "attn.decode_steps"))
    before = spans()
    engine.step()                       # one decode round, one ahead
    spans = spans() - before
    monkeypatch.undo()
    _drain(engine)
    # eight spans on the step thread (the async loop adds two of its
    # own a round, `async.between_steps` and `async.step_call`: ten),
    # and one read each when the step is dispatched and when the one
    # before is pulled (`Tracer.flight`); the lead and the starved
    # dispatch read no clock: the pull's span is timed once, and
    # `is_ready()` is no clock read
    assert spans == 8 and len(reads) == 2 * spans + 2
    assert engine.tracer.counts["async.step_call"] == 0
    # the budget: under 50 us of added host time a round
    tracer = tracing.Tracer()

    def cost(n=2000):
        t0 = time.perf_counter()
        for _ in range(n):
            with tracer.span("runner.dispatch"):
                pass
        return (time.perf_counter() - t0) / n
    assert min(cost() for _ in range(5)) * spans < 50e-6


# ---- the counted events ----

def test_queue_wait_and_preemptions_count_on_a_forced_case():
    from aphrodite_tpu.common.config import CacheConfig, SchedulerConfig
    from aphrodite_tpu.common.sequence import (Sequence, SequenceGroup,
                                               SequenceStatus)
    from aphrodite_tpu.processing.scheduler import Scheduler
    cache_config = CacheConfig(block_size=4)
    # 4 pages hold two 7-token prompts; the next page of either evicts
    # the other
    cache_config.num_gpu_blocks, cache_config.num_cpu_blocks = 4, 16
    tracer = tracing.Tracer()
    sched = Scheduler(SchedulerConfig(
        max_num_batched_tokens=256, max_num_seqs=8, max_model_len=256,
        max_paddings=1024), cache_config, None, tracer=tracer)
    now = time.monotonic()

    def make_group(i, waited):
        seq = Sequence(900_000 + i, "x", list(range(7)), 4)
        return SequenceGroup(f"q{i}", [seq], SamplingParams(),
                             arrival_time=now - waited)

    def append_tokens(group, n):
        for seq in group.get_seqs(status=SequenceStatus.RUNNING):
            for _ in range(n):
                seq.append_token_id(seq.get_len(), {seq.get_len(): 0.0})

    g1, g2 = make_group(1, 2.0), make_group(2, 1.0)

    def grew(name):
        return tracer.seconds[name], tracer.counts[name]

    assert g1.first_scheduled_time is None
    sched.add_seq_group(g1)
    sched.add_seq_group(g2)
    sched.schedule()
    assert grew("queue_wait")[1] == 2
    assert 3.0 <= grew("queue_wait")[0] < 3.5
    assert now <= g1.first_scheduled_time <= time.monotonic()
    assert grew("preemptions") == (0.0, 0)
    append_tokens(g1, 2)
    append_tokens(g2, 2)
    sched.schedule()
    assert grew("preemptions") == (0.0, 1)
    (preempted,) = sched.waiting
    stamp = preempted.first_scheduled_time
    # the preempted group is admitted again once the other has gone: it
    # is not a request scheduled for the first time
    sched.abort_seq_group(g2.request_id if preempted is g1
                          else g1.request_id)
    _, out = sched.schedule()
    assert [c.group for c in out.prompt_chunks] == [preempted]
    assert grew("queue_wait")[1] == 2
    assert preempted.first_scheduled_time == stamp


# ---- the host's lead over the device ----

@pytest.mark.parametrize("facts, twin", [
    (dict(path="combined", pulls="decode"), ".prompt"),
    (dict(path="combined", pulls="combined"), ".prompt"),
    (dict(path="decode", pulls="decode"), ".decode"),
    (dict(path="decode", pulls="combined"), None),
    (dict(path="decode"), None),        # nothing was in flight
    (dict(path="prompt"), None),        # a synced round
    (dict(), None),
], ids=lambda v: "-".join(v.values()) or "bare" if isinstance(v, dict)
    else str(v))
def test_a_twin_moves_only_on_the_round_its_name_says(facts, twin):
    """`.prompt` on a round that carries a prompt step, `.decode` when
    the round dispatched and the round pulled are both decode-only;
    and only the twins that `NAMES` lists exist."""
    for name in ("pull.blocked", "runner.starved", "round.ahead"):
        tracer = tracing.Tracer()
        tracer.set_round(round=1, **facts)
        tracer.add_split(name, 0.5)
        moved = {k: v for k, v in tracer.counts.items() if v}
        want = {name: 1}
        if twin is not None and name + twin in tracing.NAMES:
            want[name + twin] = 1
        assert moved == want
        assert {k: v for k, v in tracer.seconds.items() if v} == \
            dict.fromkeys(want, 0.5)
    assert {n for n in tracing.NAMES if n.endswith((".prompt", ".decode"))
            } == {"pull.blocked.decode", "runner.starved.prompt",
                  "round.ahead.prompt"}


def _grew(tracer, names, before=None):
    now = {n: (tracer.counts[n], tracer.seconds[n]) for n in names}
    if before is None:
        return now
    return {n: (now[n][0] - before[n][0], now[n][1] - before[n][1])
            for n in names}


def test_only_pulls_behind_a_round_ahead_are_the_lead_and_by_kind(
        tiny_llm, monkeypatch):
    """Six calls: a's prompt (synced), a decode round with nothing in
    flight, a decode round ahead, b's prompt beside a's decode ahead,
    b's two decode rounds ahead (the first pulls the combined round),
    and the call that finds nothing to schedule and pulls synced."""
    monkeypatch.setenv("APHRODITE_SPEC", "0")
    engine = tiny_llm.engine
    names = ("pull.blocked", "pull.blocked.decode", "round.ahead",
             "round.ahead.prompt", "runner.starved",
             "runner.starved.prompt", "runner.device_wait",
             "runner.ahead")
    before = _grew(engine.tracer, names)
    sp = SamplingParams(temperature=0.0, max_tokens=4, ignore_eos=True)
    engine.add_request("lead-a", None, sp, prompt_token_ids=_prompt(1))
    for _ in range(3):
        engine.step()
    engine.add_request("lead-b", None, sp, prompt_token_ids=_prompt(2))
    _drain(engine)
    grew = _grew(engine.tracer, names, before)
    # rounds 3 to 7 went out with a round in flight; one carried b's
    # prompt step, and had two programs
    assert grew["round.ahead"][0] == 5
    assert grew["round.ahead.prompt"][0] == 1
    assert grew["runner.ahead"][0] == 6
    # each was followed by the pull of the round before it; the prompt
    # round's pull and the last call's are a synced round's
    assert grew["pull.blocked"][0] == 5
    assert grew["runner.device_wait"][0] == 7
    assert 0 < grew["pull.blocked"][1] < grew["runner.device_wait"][1]
    # decode under decode: round 3 and rounds 6 and 7; round 4 carries
    # a prompt step and round 5 pulls one
    assert grew["pull.blocked.decode"][0] == 3
    assert 0 < grew["pull.blocked.decode"][1] < grew["pull.blocked"][1]
    assert grew["runner.starved.prompt"][0] <= \
        grew["round.ahead.prompt"][0]
    assert grew["runner.starved"][0] <= grew["round.ahead"][0]


@pytest.mark.parametrize("ready", [True, False],
                         ids=["drained", "busy"])
def test_a_dispatch_to_a_drained_device_is_starved(
        tiny_llm, recorder, _annotating, ready):
    """Whether the round in flight had finished when the next was
    dispatched is read off its handles, without blocking: here
    stand-ins whose `is_ready()` the test sets."""
    from aphrodite_tpu.executor.model_runner import StepHandle

    class SetHandle(StepHandle):
        __slots__ = ()

        def is_ready(self):
            return ready

    engine = _annotating(tiny_llm.engine)
    sp = SamplingParams(temperature=0.0, max_tokens=8, ignore_eos=True)
    engine.add_request(f"starved-{ready}", None, sp,
                       prompt_token_ids=_prompt(3))
    engine.step()                       # the prompt round
    engine.step()                       # a decode round, now in flight
    engine._ahead.handles = tuple(
        SetHandle(h.packed, h.sampling, h.plan, counts=h.counts,
                  is_prompt=h.is_prompt) for h in engine._ahead.handles)
    names = ("runner.starved", "runner.starved.prompt", "round.ahead")
    before = _grew(engine.tracer, names)
    engine.step()                       # dispatched behind the stand-in
    grew = _grew(engine.tracer, names, before)
    assert grew["round.ahead"][0] == 1
    assert grew["runner.starved"] == (int(ready), 0.0)
    assert grew["runner.starved.prompt"][0] == 0    # a decode round
    dispatches = [facts for name, _, facts in recorder.rounds()[2]
                  if name == "aph.runner.dispatch"]
    assert [f["starved"] for f in dispatches] == [int(ready)]
    assert dispatches[0]["pulls"] == "decode"
    _drain(engine)


# ---- the control ----

def test_start_stop_start_in_one_process_and_the_spans_are_in_the_trace(
        tiny_llm, tmp_path, monkeypatch):
    from jax.profiler import ProfileData
    monkeypatch.setenv("APHRODITE_SPEC", "0")
    engine = tiny_llm.engine
    with pytest.raises(RuntimeError):
        engine.stop_profile()
    for i, python_tracer in enumerate((False, True)):
        # (a directory each: the profiler names a trace by the second)
        engine.start_profile(str(tmp_path / str(i)),
                             python_tracer=python_tracer)
        with pytest.raises(RuntimeError):
            engine.start_profile(str(tmp_path))
        engine.add_request(f"p{i}", None, GREEDY,
                           prompt_token_ids=_prompt(i))
        _drain(engine)
        engine.stop_profile()
        engine.add_request(f"o{i}", None, GREEDY,
                           prompt_token_ids=_prompt(i))
        _drain(engine)                  # off again: nothing is recorded
    paths = sorted(glob.glob(str(
        tmp_path / "*" / "plugins" / "profile" / "*" / "*.xplane.pb")))
    assert len(paths) == 2
    python_frames = []
    for path in paths:
        events = [e.name for plane in ProfileData.from_file(path).planes
                  if plane.name.startswith("/host:")
                  for line in plane.lines for e in line.events]
        steps = events.count("aph.engine.step")
        # p's rounds, not o's: the prompt, three decode rounds, and
        # the call that pulls the last of them
        assert steps == 5
        for name in STAGES:
            assert events.count("aph." + name) >= steps - 1, name
        python_frames.append(sum(1 for n in events if n.startswith("$")))
    # Python frames only when asked for
    assert python_frames[0] == 0 and python_frames[1] > 0


def test_the_two_print_flags_are_gone():
    for name in ("APHRODITE_BURST_TIMING", "APHRODITE_DISAGG_TIMING"):
        assert name not in flags.registry()
        with pytest.raises(flags.FlagError):
            flags.get_bool(name)
    with open(os.path.join(ROOT, "README.md")) as f:
        readme = f.read()
    assert "BURST_TIMING" not in readme and "DISAGG_TIMING" not in readme
    # and no print on the step path
    for rel in ("engine/aphrodite_engine.py", "executor/executor.py",
                "executor/model_runner.py", "processing/scheduler.py",
                "common/tracing.py"):
        with open(os.path.join(ROOT, "aphrodite_tpu", rel)) as f:
            assert "print(" not in f.read(), rel


# ---- the exporter ----

def _value(name, labels):
    return REGISTRY.get_sample_value(name, labels)


def _stats(**kw):
    return Stats(now=0.0, num_running=0, num_waiting=0, num_swapped=0,
                 gpu_cache_usage=0.0, cpu_cache_usage=0.0,
                 num_prompt_tokens=0, num_generation_tokens=0,
                 time_to_first_tokens=[], time_per_output_tokens=[],
                 time_e2e_requests=[], **kw)


def test_cumulative_totals_are_exported_as_deltas_by_one_helper():
    labels = dict(model_name="tracing-test-a")
    log = StatLogger(labels=labels)
    shed = "aphrodite:num_requests_shed_total"
    base = _value(shed, labels) or 0.0
    for total, want in ((3, 3), (5, 5), (4, 5), (6, 6)):
        # a total that fell (a rebuilt engine) exports nothing until it
        # has passed what was exported
        log.log(_stats(sheds_total=total, reincarnations_total=total))
        assert _value(shed, labels) == base + want
        assert _value("aphrodite:reincarnations_total", labels) == want


def test_stage_counters_are_exported_from_the_tracers_totals():
    tracer = tracing.Tracer()
    labels = dict(model_name="tracing-test-b")
    log = StatLogger(labels=labels)
    names = {
        "aphrodite:engine_rounds_total", "aphrodite:host_syncs_total",
        "aphrodite:host_schedule_seconds_total",
        "aphrodite:host_prepare_seconds_total",
        "aphrodite:device_wait_seconds_total",
        "aphrodite:host_process_seconds_total",
        "aphrodite:host_between_steps_seconds_total",
        "aphrodite:queue_wait_seconds_total",
        "aphrodite:requests_first_scheduled_total",
        "aphrodite:preemptions_total",
        "aphrodite:engine_step_seconds_total",
        "aphrodite:steps_ahead_total"}
    # every one reads 0 from the start, not "absent"
    assert {n: _value(n, labels) for n in names} == dict.fromkeys(names,
                                                                 0.0)
    tracer.add("runner.dispatch", 0.25)
    tracer.add("runner.device_wait", 0.5)
    tracer.add("runner.in_flight", 0.75)
    tracer.add("runner.ahead")
    tracer.add("runner.ahead")
    tracer.add("sampler.finalize", 0.125)
    tracer.add("engine.process", 0.125)
    tracer.add("queue_wait", 2.0)
    tracer.add("preemptions")
    log.log(_stats(stage_seconds=tracer.seconds,
                   stage_counts=tracer.counts))
    assert _value("aphrodite:device_wait_seconds_total", labels) == \
        pytest.approx(0.75)
    assert _value("aphrodite:host_process_seconds_total", labels) == \
        pytest.approx(0.25)
    assert _value("aphrodite:host_syncs_total", labels) == 1
    assert _value("aphrodite:steps_ahead_total", labels) == 2
    assert _value("aphrodite:queue_wait_seconds_total", labels) == \
        pytest.approx(2.0)
    assert _value("aphrodite:requests_first_scheduled_total", labels) == 1
    assert _value("aphrodite:preemptions_total", labels) == 1
    log.log(_stats())                   # a Stats without them: no-op
    assert _value("aphrodite:preemptions_total", labels) == 1


#: PR 38's counters and the late prompt dispatch's (PR 45), each with
#: the accumulator it exports
LEAD_COUNTERS = {
    "aphrodite:dispatches_prompt_late_total": ("c", "runner.prompt_late"),
    "aphrodite:pull_blocked_seconds_total": ("s", "pull.blocked"),
    "aphrodite:pulls_ahead_total": ("c", "pull.blocked"),
    "aphrodite:pull_blocked_decode_seconds_total":
        ("s", "pull.blocked.decode"),
    "aphrodite:pulls_ahead_decode_total": ("c", "pull.blocked.decode"),
    "aphrodite:dispatches_starved_total": ("c", "runner.starved"),
    "aphrodite:dispatches_starved_prompt_total":
        ("c", "runner.starved.prompt"),
    "aphrodite:rounds_ahead_total": ("c", "round.ahead"),
    "aphrodite:rounds_ahead_prompt_total": ("c", "round.ahead.prompt"),
    "aphrodite:host_dispatch_seconds_total": ("s", "runner.dispatch"),
    "aphrodite:step_call_seconds_total": ("s", "async.step_call"),
}


#: PR 54's: the stages of the programs the process builds and the
#: phases from process start to ready
SETUP_COUNTERS = {
    "aphrodite:program_trace_seconds_total": ("s", "program.trace"),
    "aphrodite:program_lower_seconds_total": ("s", "program.lower"),
    "aphrodite:program_compile_seconds_total": ("s", "program.compile"),
    "aphrodite:program_cache_load_seconds_total":
        ("s", "program.cache_load"),
    "aphrodite:programs_built_total": ("c", "program.compile"),
    "aphrodite:program_cache_hits_total": ("c", "program.cache_hit"),
    "aphrodite:program_cache_misses_total": ("c", "program.cache_miss"),
    **{f"aphrodite:setup_{phase[len('setup.'):]}_seconds_total":
       ("s", phase) for phase in tracing.SETUP_PHASES},
}
#: the accumulators of which two counters export a side each
TWO_SIDED = ("pull.blocked", "pull.blocked.decode", "program.compile")


@pytest.mark.parametrize("counter",
                         sorted({**LEAD_COUNTERS, **SETUP_COUNTERS}))
def test_a_lead_counter_reads_zero_before_its_first_event(counter):
    from aphrodite_tpu.engine.metrics import _STAGE_COUNTERS
    kind, name = {**LEAD_COUNTERS, **SETUP_COUNTERS}[counter]
    labels = dict(model_name="tracing-test-" + counter.split(":")[1])
    log = StatLogger(labels=labels)
    assert _value(counter, labels) == 0.0
    tracer = tracing.Tracer()
    tracer.add(name, 0.75, count=3)
    log.log(_stats(stage_seconds=tracer.seconds,
                   stage_counts=tracer.counts))
    assert _value(counter, labels) == (0.75 if kind == "s" else 3.0)
    # one name each: no two counters export the same accumulator
    reads = [doc for metric, doc, total in _STAGE_COUNTERS
             if total(tracer.seconds, tracer.counts)]
    # (the lead's seconds and its pulls are one accumulator's two
    # sides, as a compile stage's seconds and the programs built are)
    assert len(reads) == (2 if name in TWO_SIDED else 1)


#: the prompt attention's dispatch, counted where a prompt step is
#: built (`ModelRunner._prepare_prompt`)
PROMPT_KERNEL_COUNTERS = {
    "aphrodite:prefill_attn_steps_total": "attn.prefill_steps",
    "aphrodite:prefill_attn_kernel_steps_total":
    "attn.prefill_kernel_steps",
}


#: the counts among `SETUP_COUNTERS`
BUILD_COUNTS = {counter: name for counter, (kind, name)
                in SETUP_COUNTERS.items() if kind == "c"}


@pytest.mark.parametrize(
    "counter", sorted({**PROMPT_KERNEL_COUNTERS, **BUILD_COUNTS}))
def test_a_prompt_kernel_counter_is_exported_and_described(counter):
    """Each of the pair reads 0 before a prompt step, exports its own
    accumulator and no other, and goes by one name in `NAMES`, the
    README's operator section and PERF.md; the counts of the programs
    built and of the persistent cache's answers the same."""
    from aphrodite_tpu.engine.metrics import _STAGE_COUNTERS
    name = {**PROMPT_KERNEL_COUNTERS, **BUILD_COUNTS}[counter]
    assert name in tracing.NAMES
    # (the counts are cases of the test above too: a label of its own)
    labels = dict(model_name="tracing-test-described-" +
                  counter.split(":")[1])
    log = StatLogger(labels=labels)
    assert _value(counter, labels) == 0.0
    tracer = tracing.Tracer()
    tracer.add(name, count=7)
    log.log(_stats(stage_seconds=tracer.seconds,
                   stage_counts=tracer.counts))
    assert _value(counter, labels) == 7.0
    assert [metric for metric, _, total in _STAGE_COUNTERS
            if total(tracer.seconds, tracer.counts)] == [counter]
    for text in ("README.md", "PERF.md"):
        with open(os.path.join(ROOT, text)) as f:
            assert counter in f.read(), text


def test_steps_ahead_says_not_pulled_wherever_it_is_described():
    """`runner.ahead` counts a round that had not been pulled, not one
    that was still on the device; the help text, the comment in
    `NAMES`, the README and PERF.md say so and point at the counter
    that does."""
    from aphrodite_tpu.engine.metrics import _STAGE_COUNTERS
    (doc,) = [doc for metric, doc, _ in _STAGE_COUNTERS
              if metric == "aphrodite:steps_ahead_total"]
    assert "had not been pulled" in doc
    assert "aphrodite:dispatches_starved_total" in doc
    texts = {"tracing.py": os.path.join(ROOT, "aphrodite_tpu", "common",
                                        "tracing.py"),
             "README.md": os.path.join(ROOT, "README.md"),
             "PERF.md": os.path.join(ROOT, "PERF.md")}
    for name, path in texts.items():
        with open(path) as f:
            text = " ".join(f.read().split())
        assert "was still on the device" not in text, name
        assert "had not been pulled" in text, name
    # every new span and counter under one name in each of the four
    with open(texts["README.md"]) as f:
        readme = f.read()
    with open(texts["PERF.md"]) as f:
        perf = f.read()
    for counter, (_, name) in LEAD_COUNTERS.items():
        assert name in tracing.NAMES
        assert counter in readme, counter
        assert counter.split(":")[1] in perf, counter


def test_device_wait_seconds_are_the_union_of_the_steps_in_flight(
        monkeypatch):
    """A step is in flight from its dispatch to its pull; with the
    engine a round ahead two overlap, and the seconds in which a step
    was in flight are counted once. The total is current at every
    dispatch and pull, however long a step stays out."""
    now = [100.0]
    monkeypatch.setattr(tracing, "_clock", lambda: now[0])
    tracer = tracing.Tracer()

    def at(t, steps):
        now[0] = 100.0 + t
        tracer.flight(steps)
        return tracer.seconds["runner.in_flight"], tracer.in_flight

    assert at(0.0, 1) == (0.0, 1)       # round 1 dispatched
    assert at(1.0, 2) == (1.0, 3)       # round 2, two steps, ahead
    assert at(1.5, -1) == (1.5, 2)      # round 1 pulled
    assert at(4.0, -2) == (4.0, 0)      # round 2 pulled: 4 s, not 5.5
    assert at(6.0, 1) == (4.0, 1)       # nothing was in flight for 2 s
    now[0] = 107.0
    tracer.grounded()                   # a failed round: abandoned
    assert (tracer.seconds["runner.in_flight"], tracer.in_flight) == \
        (5.0, 0)
    labels = dict(model_name="tracing-test-d")
    log = StatLogger(labels=labels)
    log.log(_stats(stage_seconds=tracer.seconds,
                   stage_counts=tracer.counts))
    assert _value("aphrodite:device_wait_seconds_total", labels) == 5.0


def test_a_synced_step_is_in_flight_from_its_dispatch_to_its_pull(
        tiny_llm, monkeypatch):
    """At depth 0 the in-flight seconds are what they were: the
    dispatch span, the blocking pull and the glue between them."""
    monkeypatch.setenv("APHRODITE_SPEC", "1")       # keeps it synced
    engine = tiny_llm.engine
    seconds, counts = engine.tracer.seconds, engine.tracer.counts
    before = dict(seconds), counts["runner.ahead"]
    engine.add_request("sync", None, GREEDY, prompt_token_ids=_prompt(8))
    _drain(engine)
    grew = {k: seconds[k] - before[0][k] for k in seconds}
    spans = grew["runner.dispatch"] + grew["runner.device_wait"]
    assert spans <= grew["runner.in_flight"] <= spans + 0.05
    assert counts["runner.ahead"] == before[1]
    assert engine.tracer.in_flight == 0


def test_the_sampling_plan_counters_are_exported_and_a_zero_reads_zero():
    tracer = tracing.Tracer()
    labels = dict(model_name="tracing-test-c")
    log = StatLogger(labels=labels)
    names = ("aphrodite:sampler_plan_seconds_total",
             "aphrodite:sampler_plans_total",
             "aphrodite:sampler_plan_reuses_total")
    assert [_value(n, labels) for n in names] == [0.0, 0.0, 0.0]
    tracer.add("sampler.plan", 0.004)
    tracer.add("sampler.plan", 0.002)
    log.log(_stats(stage_seconds=tracer.seconds,
                   stage_counts=tracer.counts))
    # two plans, none reused: the reuse counter reads 0, not nothing
    assert [_value(n, labels) for n in names] == [
        pytest.approx(0.006), 2.0, 0.0]
    tracer.add("sampler.plan", 0.00001)
    tracer.add("sampler.plan_reuse")
    log.log(_stats(stage_seconds=tracer.seconds,
                   stage_counts=tracer.counts))
    assert [_value(n, labels) for n in names][1:] == [3.0, 1.0]


@pytest.mark.parametrize("sampled", [False, True],
                         ids=["greedy", "sampled"])
def test_a_steady_decode_batch_reuses_its_plan_and_a_sampled_one_never(
        tiny_llm, monkeypatch, sampled):
    """Reuse is decided by what the plan observes: the same rows and
    the same `SamplingParams` objects, and no row that draws."""
    monkeypatch.setenv("APHRODITE_SPEC", "0")
    engine = tiny_llm.engine
    counts = engine.tracer.counts
    before = dict(counts)
    params = SamplingParams(temperature=0.8 if sampled else 0.0,
                            max_tokens=6, ignore_eos=True)
    for i in range(2):
        engine.add_request(f"plan-{sampled}-{i}", None, params,
                           prompt_token_ids=_prompt(i))
    _drain(engine)
    plans = counts["sampler.plan"] - before["sampler.plan"]
    reuses = counts["sampler.plan_reuse"] - before["sampler.plan_reuse"]
    assert plans >= 6
    # the first decode step builds the plan, the four after it reuse it
    assert reuses == (0 if sampled else 4)


# ---- the async loop ----

def test_between_steps_is_counted_once_a_round_and_never_while_idle(
        tiny_model_dir, monkeypatch):
    from aphrodite_tpu.engine.args_tools import AsyncEngineArgs
    from aphrodite_tpu.engine.async_aphrodite import AsyncAphrodite
    monkeypatch.setenv("APHRODITE_SPEC", "0")
    labels = dict(model_name=tiny_model_dir)
    sp = SamplingParams(temperature=0.0, max_tokens=6, ignore_eos=True)

    async def _generate_all(engine, prompts, tag):
        async def one(i, p):
            final = None
            async for out in engine.generate(None, sp, f"{tag}-{i}",
                                             prompt_token_ids=p):
                final = out
            return final
        return await asyncio.gather(*(one(i, p)
                                      for i, p in enumerate(prompts)))

    async def go():
        engine = AsyncAphrodite.from_engine_args(AsyncEngineArgs(
            model=tiny_model_dir, load_format="dummy", dtype="float32",
            block_size=16, max_model_len=256, max_num_seqs=8,
            swap_space=0.01, disable_log_requests=True))
        await _generate_all(engine, [_prompt(0)], "warm")
        tracer = engine.engine.tracer
        before = {k: (tracer.seconds[k], tracer.counts[k])
                  for k in ("async.between_steps", "engine.step")}
        await asyncio.sleep(0.4)        # idle: the loop waits
        outs = await _generate_all(engine, [_prompt(1), _prompt(2)], "m")
        assert all(len(o.outputs[0].token_ids) == 6 for o in outs)
        return {k: (tracer.seconds[k] - s, tracer.counts[k] - c)
                for k, (s, c) in before.items()}

    grew = asyncio.run(go())
    rounds = grew["engine.step"][1]
    assert rounds >= 6
    # one span between two steps, and one for the intake that ends an
    # idle wait; the 0.4 s of idling are in none of them
    assert rounds <= grew["async.between_steps"][1] <= rounds + 2
    assert grew["async.between_steps"][0] < 0.3
    # the engine's own exporter carried them to Prometheus
    assert _value("aphrodite:engine_rounds_total", labels) >= rounds - 1
    assert _value("aphrodite:host_between_steps_seconds_total",
                  labels) > 0


def test_step_call_spans_the_hops_on_the_loop_thread(
        tiny_model_dir, monkeypatch):
    """`async.step_call` is entered and left on the event-loop thread
    once a round, around the hop to the step thread, `engine.step` and
    the hop back; never open while the loop idles; a span that is open
    when the profiler starts or stops ends as it began; and a step the
    watchdog abandons closes it."""
    import threading
    from aphrodite_tpu.engine.args_tools import AsyncEngineArgs
    from aphrodite_tpu.engine.async_aphrodite import AsyncAphrodite
    from aphrodite_tpu.engine.supervisor import StepTimeoutError
    monkeypatch.setenv("APHRODITE_SPEC", "0")
    sp = SamplingParams(temperature=0.0, max_tokens=8, ignore_eos=True)
    events, open_calls = [], []

    class Annotation:
        def __init__(self, name, **facts):
            self.name = name

        def __enter__(self):
            events.append(("enter", self.name, threading.get_ident()))
            if self.name == "aph.async.step_call":
                open_calls.append(self)

        def __exit__(self, *exc):
            events.append(("exit", self.name, threading.get_ident()))
            if self.name == "aph.async.step_call":
                open_calls.remove(self)

    monkeypatch.setattr(tracing, "TraceAnnotation", Annotation)
    names = ("async.step_call", "engine.step", "async.between_steps")

    async def go():
        engine = AsyncAphrodite.from_engine_args(AsyncEngineArgs(
            model=tiny_model_dir, load_format="dummy", dtype="float32",
            block_size=16, max_model_len=256, max_num_seqs=8,
            swap_space=0.01, disable_log_requests=True))
        tracer = engine.engine.tracer
        loop_thread = threading.get_ident()

        async def one(tag):
            n = 0
            async for out in engine.generate(None, sp, tag,
                                             prompt_token_ids=_prompt(1)):
                n += 1
                # the profiler starts and stops while a call is open
                # or between two: a span ends the way it began
                tracer.annotate(n % 4 < 2)
            return out

        await one("warm")
        await asyncio.sleep(0.2)        # the call that pulls the last round
        tracer.annotate(False)
        events.clear()
        before = _grew(tracer, names)
        await asyncio.sleep(0.4)        # idle: no call is open
        assert open_calls == [] and events == []
        idle = _grew(tracer, names, before)
        out = await one("hops")
        assert len(out.outputs[0].token_ids) == 8
        await asyncio.sleep(0.2)        # the loop idles again
        grew = _grew(tracer, names, before)
        # a step the watchdog abandons: the call's span closes with it
        monkeypatch.setenv("APHRODITE_STEP_TIMEOUT_S", "0.05")
        monkeypatch.setattr(engine.engine, "step",
                            lambda: time.sleep(0.3))
        with pytest.raises(StepTimeoutError):
            await engine._step_with_watchdog()
        abandoned = _grew(tracer, names, before)
        await asyncio.sleep(0.35)       # the wedged thread ends
        return loop_thread, idle, grew, abandoned

    loop_thread, idle, grew, abandoned = asyncio.run(go())
    assert idle["async.step_call"] == (0, 0.0)
    rounds = grew["engine.step"][0]
    assert rounds >= 8 and grew["async.step_call"][0] == rounds
    # the call holds the step and the two hops, and none of the idling
    assert grew["engine.step"][1] <= grew["async.step_call"][1] < \
        grew["engine.step"][1] + 0.3
    # entered and left on the loop's thread, balanced, whatever the
    # profiler did meanwhile; the step's own spans are another thread's
    calls = [e for e in events if e[1] == "aph.async.step_call"]
    assert calls and open_calls == []
    assert {thread for _, _, thread in calls} == {loop_thread}
    assert [kind for kind, _, _ in calls] == \
        ["enter", "exit"] * (len(calls) // 2)
    assert 0 < len(calls) // 2 < rounds     # some began with it off
    assert loop_thread not in {thread for _, name, thread in events
                               if name == "aph.engine.step"}
    # nothing else of the program's opens on the loop's thread inside it
    inside = False
    for kind, name, thread in events:
        if name == "aph.async.step_call":
            inside = kind == "enter"
        elif thread == loop_thread:
            assert not inside, name
    assert abandoned["async.step_call"][0] == rounds + 1
    assert 0.05 <= abandoned["async.step_call"][1] - \
        grew["async.step_call"][1] < 0.3


def test_decode_attention_pages_are_counted_a_step_and_exported(
        tiny_llm, monkeypatch):
    """Where the runner builds a decode step's work list it counts the
    pages the step's attention copies and those below the rows' context
    lengths (the kernel copies live pages only, so the two agree); the
    totals ride through `Stats` onto what `/metrics` serves."""
    from prometheus_client import generate_latest
    monkeypatch.setenv("APHRODITE_SPEC", "0")
    engine = tiny_llm.engine
    runner = engine.executor.model_runner
    counts = engine.tracer.counts
    before = counts["attn.pages_fetched"], counts["attn.pages_live"]
    steps = []
    real = runner._send_decode_batch

    def spy(tokens, positions, slots, ctx_list, tables, **kw):
        steps.append(list(ctx_list))
        return real(tokens, positions, slots, ctx_list, tables, **kw)
    monkeypatch.setattr(runner, "_send_decode_batch", spy)
    # 20 and 40 prompt tokens, pages of 16: a row's live pages grow
    # from 2 to 3 and stay 3 over the steps
    sp = SamplingParams(temperature=0.0, max_tokens=14, ignore_eos=True)
    engine.add_request("pages-a", None, sp, prompt_token_ids=_prompt(1))
    engine.add_request("pages-b", None, sp,
                       prompt_token_ids=_prompt(2, n=40))
    _drain(engine)
    assert len(steps) >= 13
    pages = sum(-(-ctx // 16) for step in steps for ctx in step)
    assert {-(-ctx // 16) for step in steps for ctx in step} == {2, 3, 4}
    assert counts["attn.pages_live"] - before[1] == pages
    assert counts["attn.pages_fetched"] - before[0] == pages
    labels = dict(model_name="tracing-test-pages")
    names = ("aphrodite:decode_attn_pages_fetched_total",
             "aphrodite:decode_attn_pages_live_total")
    log = StatLogger(labels=labels)
    assert [_value(n, labels) for n in names] == [0.0, 0.0]
    log.log(_stats(stage_seconds=engine.tracer.seconds,
                   stage_counts=counts))
    assert [_value(n, labels) for n in names] == [
        float(counts["attn.pages_fetched"]),
        float(counts["attn.pages_live"])]
    served = generate_latest().decode()
    for name in names:
        assert name.replace(":", "_") in served or name in served
