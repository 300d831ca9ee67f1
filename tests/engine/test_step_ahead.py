"""One decode step ahead (`AphroditeEngine.step`): round n is scheduled,
prepared and dispatched before round n-1 is pulled, its tokens fed on
the device. Nothing a client can see differs from the synced path,
which is the same code at depth 0 (`_runs_ahead` false): tokens, text,
finish reasons and pages are held to it here, on the CPU toy model."""
import contextlib

import numpy as np
import pytest

from aphrodite_tpu.common import faultinject
from aphrodite_tpu.common.sampling_params import SamplingParams
from aphrodite_tpu.common.sequence import (Sequence, SequenceData,
                                           SequenceGroup,
                                           SequenceGroupMetadata)
from aphrodite_tpu.executor.model_runner import ModelRunner
from aphrodite_tpu.processing.admission import RequestTimeoutError


@pytest.fixture(autouse=True)
def _no_speculation(monkeypatch):
    """A speculative engine drafts from the last token's id and keeps
    the synced path (`_runs_ahead`)."""
    monkeypatch.setenv("APHRODITE_SPEC", "0")
    monkeypatch.delenv("APHRODITE_FAULT", raising=False)
    faultinject.reset()
    yield
    faultinject.reset()


def _engine(tiny_model_dir, num_blocks=None, **kw):
    from aphrodite_tpu.engine.aphrodite_engine import AphroditeEngine
    from aphrodite_tpu.engine.args_tools import EngineArgs
    args = dict(model=tiny_model_dir, load_format="dummy", dtype="float32",
                block_size=16, max_model_len=256, max_num_seqs=8,
                swap_space=0.01, disable_log_stats=True)
    args.update(kw)
    configs = EngineArgs(**args).create_engine_configs()
    if num_blocks is not None:
        configs[1].num_gpu_blocks = num_blocks
    return AphroditeEngine(*configs)


@pytest.fixture(scope="module")
def engine(tiny_model_dir):
    return _engine(tiny_model_dir)


def _prompt(i, n=20):
    return [(i * 7 + j * 3) % 90 + 5 for j in range(n)]


@contextlib.contextmanager
def synced(engine):
    """The engine at depth 0: no round is dispatched ahead."""
    engine._runs_ahead = lambda *a: False
    try:
        yield
    finally:
        del engine._runs_ahead


_RUN = [0]


def run(engine, requests, on_step=None, retry=()):
    """Serve `requests` ((prompt ids, params, the step call before
    which it arrives)) to the end. Returns what each request's client
    saw last (token ids, text, finish reason), and how many step
    programs were dispatched ahead."""
    _RUN[0] += 1
    ahead0 = engine.tracer.counts["runner.ahead"]
    last, calls = {}, 0
    pending = sorted(enumerate(requests), key=lambda r: r[1][2])
    while pending or engine.has_unfinished_requests():
        while pending and pending[0][1][2] <= calls:
            i, (prompt, params, _) = pending.pop(0)
            engine.add_request(f"run{_RUN[0]}-{i}", None, params,
                               prompt_token_ids=list(prompt))
        if on_step is not None:
            on_step(calls)
        calls += 1
        try:
            outputs = engine.step()
        except retry:
            continue
        for out in outputs:
            i = int(out.request_id.rsplit("-", 1)[1])
            assert not (i in last and last[i][2] is not None), \
                "an output after the finished one"
            c = out.outputs[0]
            last[i] = (list(c.token_ids), c.text, c.finish_reason) \
                if len(out.outputs) == 1 else \
                ([list(c.token_ids) for c in out.outputs], None,
                 out.outputs[0].finish_reason)
    assert engine._ahead is None and engine.tracer.in_flight == 0
    return last, engine.tracer.counts["runner.ahead"] - ahead0


def no_leak(engine):
    manager = engine.scheduler.block_manager
    return manager.get_num_free_gpu_blocks() == \
        engine.cache_config.num_gpu_blocks - \
        engine.scheduler.prefix_pinned_pages()


def greedy(n, **kw):
    return SamplingParams(temperature=0.0, max_tokens=n, ignore_eos=True,
                          **kw)


def seeded(n, seed, **kw):
    return SamplingParams(temperature=1.0, seed=seed, max_tokens=n,
                          ignore_eos=True, **kw)


#: rows that leave at different steps and join while others run: a
#: prompt's first token is fed from the prompt step, a leaver's page
#: comes back, the batch crosses its buckets
def _mix(kind):
    params = {
        "greedy": [greedy(5), greedy(9), greedy(17), greedy(12),
                   greedy(7), greedy(2)],
        "seeded": [seeded(5, 1), seeded(9, 2), seeded(17, 3),
                   seeded(12, 4), seeded(7, 5), seeded(2, 6)],
        "mixed": [greedy(5), seeded(9, 2, top_p=0.9), greedy(17),
                  seeded(12, 4, top_k=20), greedy(7), seeded(2, 6)],
    }[kind]
    arrivals = [0, 0, 0, 3, 6, 8]
    return [(_prompt(i, 14 + 5 * i), p, at)
            for i, (p, at) in enumerate(zip(params, arrivals))]


@pytest.mark.parametrize("kind", ["greedy", "seeded", "mixed"])
def test_tokens_are_those_of_the_synced_path(engine, kind):
    requests = _mix(kind)
    with synced(engine):
        want, none_ahead = run(engine, requests)
    got, ahead = run(engine, requests)
    assert none_ahead == 0 and ahead >= 12
    assert got == want
    assert [len(got[i][0]) for i in range(6)] == [5, 9, 17, 12, 7, 2]
    assert no_leak(engine)


def _greedy_reference(engine, prompt, n):
    """The tokens and the text after each token of one greedy row."""
    texts = []
    engine.add_request("ref", None, greedy(n), prompt_token_ids=prompt)
    with synced(engine):
        while engine.has_unfinished_requests():
            for out in engine.step():
                texts.append(out.outputs[0].text)
                tokens = list(out.outputs[0].token_ids)
    return tokens, texts


class _EosIs:
    """The tokenizer, with another end-of-sequence id."""

    def __init__(self, tokenizer, eos):
        self._tokenizer, self.eos_token_id = tokenizer, eos

    def __getattr__(self, name):
        return getattr(self._tokenizer, name)


@pytest.mark.parametrize("how", ["eos", "stop_token_ids", "stop_string"])
def test_a_stop_that_only_the_token_says_drops_the_token_in_flight(
        engine, monkeypatch, how):
    prompt = _prompt(3, 23)
    tokens, texts = _greedy_reference(engine, prompt, 14)
    # stop at the first token from the fifth on that no earlier
    # position holds (and, for the string, that adds text of its own)
    k = next(i for i in range(4, 14) if tokens[i] not in tokens[:i]
             and texts[i] != texts[i - 1]
             and not any(t.endswith(texts[i][len(texts[i - 1]):])
                         for t in texts[:i]))
    params = dict(temperature=0.0, max_tokens=14)
    if how == "eos":
        tokenizer = engine.tokenizer.get_lora_tokenizer()
        monkeypatch.setattr(
            engine.tokenizer, "get_lora_tokenizer",
            lambda *a, **kw: _EosIs(tokenizer, tokens[k]))
    elif how == "stop_token_ids":
        params.update(ignore_eos=True, stop_token_ids=[tokens[k]])
    else:
        params.update(ignore_eos=True,
                      stop=[texts[k][len(texts[k - 1]):]])
    # beside it a row that goes on, and one that joins at the stop
    requests = [(prompt, SamplingParams(**params), 0),
                (_prompt(5), greedy(k + 6), 0),
                (_prompt(6), greedy(5), k)]
    steps = []
    dispatch = engine.executor.dispatch_steps

    def spy(rnd):
        steps.append([md.request_id.rsplit("-", 1)[1]
                      for md in rnd.decode])
        return dispatch(rnd)

    with synced(engine):
        want, _ = run(engine, requests)
    monkeypatch.setattr(engine.executor, "dispatch_steps", spy)
    got, ahead = run(engine, requests)
    assert got == want and ahead > 0
    assert got[0][0] == tokens[:k + 1] and got[0][2] == "stop"
    if how == "stop_string":
        assert got[0][1] == texts[k - 1]        # the stop is cut off
    # the stopped row had one step too many in flight: k decode steps
    # make its k + 1 tokens (the prompt step the first)
    assert sum("0" in rows for rows in steps) == k + 1
    assert no_leak(engine)


def test_a_row_is_never_scheduled_past_its_last_token_by_length(
        engine, monkeypatch):
    # the third row ends at the model's length, not at its max_tokens
    requests = [(_prompt(1), greedy(6), 0), (_prompt(2), greedy(11), 0),
                (_prompt(3, 250), greedy(20), 2)]
    rows = []
    dispatch = engine.executor.dispatch_steps

    def spy(rnd):
        rows.extend(md.request_id.rsplit("-", 1)[1] for md in rnd.decode)
        return dispatch(rnd)

    monkeypatch.setattr(engine.executor, "dispatch_steps", spy)
    got, ahead = run(engine, requests)
    assert ahead > 0
    assert [len(got[i][0]) for i in range(3)] == [6, 11, 7]
    assert {got[i][2] for i in range(3)} == {"length"}
    # a decode step for every token but the prompt step's, and no more
    assert [rows.count(str(i)) for i in range(3)] == [5, 10, 6]
    with synced(engine):
        want, _ = run(engine, requests)
    assert got == want and no_leak(engine)


def test_an_abort_with_a_step_in_flight_drops_its_token(engine):
    requests = [(_prompt(1), greedy(12), 0), (_prompt(2), greedy(12), 0),
                (_prompt(3), greedy(12), 0)]
    with synced(engine):
        want, _ = run(engine, requests)
    seen = []

    def abort_the_second(call):
        if call == 5:
            assert engine._ahead is not None
            (rid,) = [g.request_id for g in engine.scheduler.running
                      if g.request_id.endswith("-1")]
            engine.abort_request(rid)
            seen.append(rid)

    got, ahead = run(engine, requests, on_step=abort_the_second)
    assert seen and ahead > 0
    assert got[0] == want[0] and got[2] == want[2]
    # nothing reached its client after the abort: the fifth call's
    # round was still in flight and its token is dropped
    assert got[1][2] is None and len(got[1][0]) < 5
    assert no_leak(engine)


def test_all_rows_aborted_with_a_step_in_flight_is_still_pulled(engine):
    engine.add_request("gone", None, greedy(12),
                       prompt_token_ids=_prompt(4))
    for _ in range(3):
        engine.step()
    assert engine._ahead is not None
    engine.abort_request("gone")
    assert engine.has_unfinished_requests()     # the step in flight
    assert engine.step() == []
    assert not engine.has_unfinished_requests()
    assert engine.tracer.in_flight == 0 and no_leak(engine)


def test_a_deadline_expires_in_the_queue_while_steps_are_in_flight(
        tiny_model_dir):
    engine = _engine(tiny_model_dir, max_num_seqs=2)
    requests = [(_prompt(1), greedy(10), 0), (_prompt(2), greedy(10), 0)]
    with synced(engine):
        want, _ = run(engine, requests)
    late = SamplingParams(temperature=0.0, max_tokens=4, ignore_eos=True,
                          ttft_slo_s=1e-4)
    got, ahead = run(engine, requests + [(_prompt(3), late, 3)])
    assert ahead > 0 and 2 not in got
    assert {i: got[i] for i in (0, 1)} == want
    ((rid, exc),) = engine.drain_step_faults()
    assert rid.endswith("-2") and isinstance(exc, RequestTimeoutError)
    assert no_leak(engine)


def test_a_preemption_round_recomputes_the_token_it_dropped(
        tiny_model_dir):
    # 9 pages of 16: three rows of 33-40 tokens fill them, and the
    # next page of any evicts another (recompute)
    engine = _engine(tiny_model_dir, num_blocks=9)
    requests = [(_prompt(i, 33 + 2 * i), greedy(30), 0) for i in range(3)]
    with synced(engine):
        want, _ = run(engine, requests)
    before = engine.tracer.counts["preemptions"]
    got, ahead = run(engine, requests)
    assert engine.tracer.counts["preemptions"] > before and ahead > 0
    assert got == want
    assert no_leak(engine)


def _bias(token_ids, logits):
    logits = np.array(logits)
    logits[7] += 2.0
    return logits


INELIGIBLE = {
    "penalty": dict(temperature=0.0, presence_penalty=0.5),
    "repetition": dict(temperature=0.0, repetition_penalty=1.3),
    "mirostat": dict(temperature=1.0, seed=3, mirostat_mode=2,
                     mirostat_tau=3.0, mirostat_eta=0.1),
    "logprobs": dict(temperature=0.0, logprobs=2),
    "prompt_logprobs": dict(temperature=0.0, prompt_logprobs=1),
    "best_of": dict(temperature=1.0, seed=5, n=2, best_of=2),
    "beam": dict(temperature=0.0, n=2, best_of=2, use_beam_search=True),
    "logits_processor": dict(temperature=0.0, logits_processors=[_bias]),
}


@pytest.mark.parametrize("what", sorted(INELIGIBLE))
def test_a_row_off_the_fused_path_keeps_the_whole_batch_synced(
        engine, what):
    odd = SamplingParams(max_tokens=8, ignore_eos=True,
                         **INELIGIBLE[what])
    requests = [(_prompt(1), greedy(8), 0), (_prompt(2), odd, 0),
                (_prompt(3), seeded(8, 9), 0)]
    with synced(engine):
        want, _ = run(engine, requests)
    got, ahead = run(engine, requests)
    assert ahead == 0 and got == want and no_leak(engine)


def test_a_row_off_the_fused_path_that_joins_drains_the_step_in_flight(
        engine):
    odd = SamplingParams(temperature=0.0, max_tokens=4, ignore_eos=True,
                         presence_penalty=0.5)
    requests = [(_prompt(1), greedy(16), 0), (_prompt(2), seeded(16, 2), 0),
                (_prompt(3), odd, 5)]
    with synced(engine):
        want, _ = run(engine, requests)
    counts = []
    got, ahead = run(engine, requests, on_step=lambda call: counts.append(
        engine.tracer.counts["runner.ahead"]))
    assert got == want and no_leak(engine)
    # ahead before it joins and after it has gone, never while it runs:
    # its prompt step (call 5) and its three decode steps
    assert counts[5] > counts[1] and counts[-1] > counts[10]
    assert counts[6] == counts[7] == counts[8] == counts[9]


def _row(seq_id, params, pages, is_prompt):
    """One hand-made row for the model runner: a prompt of 20 tokens,
    and one token on if it is a decode row."""
    data = SequenceData(_prompt(seq_id))
    if not is_prompt:
        data.append_token_id(33, 0.0)
    return SequenceGroupMetadata(
        request_id=f"row{seq_id}", is_prompt=is_prompt,
        seq_data={seq_id: data}, sampling_params=params,
        block_tables={seq_id: list(pages)},
        persistent_data={seq_id: {}})


@pytest.mark.parametrize("what", ["logprobs", "prompt_logprobs", "best_of",
                                  "beam", "logits_processor"])
def test_a_prompt_row_off_the_fused_path_sends_out_nothing_of_its_round(
        engine, what):
    """`[decode, prompt]` goes out a batch at a time, so all or none
    is decided before the first: a prompt row that needs the raw
    logits leaves the decode program unsent, nothing prepared and the
    pool untouched. The same round with a plain prompt row goes out,
    decode step first."""
    runner = engine.executor.model_runner
    kv_caches = engine.executor.cache_engine.kv_caches
    tracer = engine.tracer
    decode = [_row(1, greedy(4), [0, 1], False)]
    odd = SamplingParams(max_tokens=4, ignore_eos=True, **INELIGIBLE[what])
    before = (tracer.in_flight, tracer.counts["runner.prepare"],
              tracer.counts["runner.dispatch"])
    handles, same = runner.dispatch_steps(
        [decode, [_row(2, odd, [2, 3], True)]], kv_caches)
    assert handles is None and same is kv_caches
    assert (tracer.in_flight, tracer.counts["runner.prepare"],
            tracer.counts["runner.dispatch"]) == before
    handles, kv_caches = runner.dispatch_steps(
        [decode, [_row(2, greedy(4), [2, 3], True)]], kv_caches)
    engine.executor.cache_engine.kv_caches = kv_caches
    assert [h.is_prompt for h in handles] == [False, True]
    assert tracer.in_flight == before[0] + 2
    assert tracer.counts["runner.prepare"] == before[1] + 2
    runner.pull(handles)
    assert tracer.in_flight == before[0]


@pytest.mark.parametrize("point", ["engine.step", "scheduler.schedule",
                                   "executor.execute_model"])
def test_a_fault_with_a_step_in_flight_rolls_back_and_a_retry_gives_the_tokens(
        engine, monkeypatch, point):
    requests = [(_prompt(1), greedy(10), 0), (_prompt(2), seeded(10, 4), 0),
                (_prompt(3), greedy(6), 2)]
    want, _ = run(engine, requests)
    state = []

    def fault_at_the_fifth(call):
        if call == 5 and not state:
            assert engine._ahead is not None
            monkeypatch.setenv("APHRODITE_FAULT", f"{point}:transient:1:1")
        if call == 6:
            # the entry of a step is before anything is scheduled, and
            # the round in flight lives on; a later fault abandons it
            # with the round that failed, rows back in the queue
            state.append((engine._ahead is not None,
                          len(engine.scheduler.waiting)))

    got, ahead = run(engine, requests, on_step=fault_at_the_fifth,
                     retry=(faultinject.InjectedTransientFault,))
    assert state[0] == ((True, 0) if point == "engine.step" else (False, 3))
    assert got == want and ahead > 0 and no_leak(engine)
    assert engine.drain_step_faults() == []


def test_a_rebuild_with_a_step_in_flight_samples_its_tokens_again(
        tiny_model_dir):
    engine = _engine(tiny_model_dir)
    requests = [(_prompt(1), greedy(10), 0), (_prompt(2), seeded(10, 4), 0)]
    want, _ = run(engine, requests)

    def rebuild(call):
        if call == 4:
            assert engine._ahead is not None
            outcome = engine.reincarnate()
            assert outcome.restored == 2 and not outcome.lost
            assert engine._ahead is None and engine.tracer.in_flight == 0

    got, ahead = run(engine, requests, on_step=rebuild)
    assert got == want and ahead > 0 and no_leak(engine)


def test_generate_drains_the_last_step(tiny_llm):
    engine = tiny_llm.engine
    before = engine.tracer.counts["runner.ahead"]
    outputs = tiny_llm.generate(
        ["the quick brown fox", "paged key value cache"],
        SamplingParams(temperature=0.0, max_tokens=7, ignore_eos=True),
        use_tqdm=False)
    assert [len(o.outputs[0].token_ids) for o in outputs] == [7, 7]
    assert all(o.finished for o in outputs)
    assert engine.tracer.counts["runner.ahead"] > before
    assert engine._ahead is None and not engine.has_unfinished_requests()
    assert no_leak(engine)


@pytest.mark.parametrize("weights", ["made_by_a_program", "put_by_a_loader"])
def test_a_decode_batch_is_committed_as_the_results_are_fed_or_not(
        tiny_model_dir, weights):
    """A step program is lowered again for an operand that changes
    between committed and not. The packed decode batch reaches it from
    the host (no token in flight) or as the feed program's result,
    which is committed iff the weights are: both ways the same."""
    import jax
    engine = _engine(tiny_model_dir)
    runner = engine.executor.model_runner
    if weights == "put_by_a_loader":
        runner.params = jax.device_put(runner.params, jax.devices()[0])
        runner._results_committed = True
    assert runner._results_committed == (weights == "put_by_a_loader")
    seen = []
    enqueue = runner._enqueue

    def spy(inputs, sampling, params, plan, kv_caches, **facts):
        if not inputs["is_prompt"]:
            seen.append((inputs["metadata"].block_tables.committed,
                         any(d.in_flight
                             for d in sampling.seq_data.values())))
        return enqueue(inputs, sampling, params, plan, kv_caches, **facts)

    runner._enqueue = spy
    # a prompt round alone, so that the first decode step is not fed
    run(engine, [(_prompt(1), greedy(6), 0), (_prompt(2), greedy(4), 3)])
    assert {fed for _, fed in seen} == {False, True}
    assert {committed for committed, _ in seen} == \
        {runner._results_committed}


def test_the_feed_takes_each_rows_token_from_its_cell():
    import jax.numpy as jnp
    # [token, position, slot, context length, table...] of five rows:
    # two tokens the host knows, three still on the device
    rows = np.array([[41, 7, 70, 8, 3], [-1 - 5, 9, 90, 10, 4],
                     [42, 3, 30, 4, 5], [-1 - 0, 5, 50, 6, 6],
                     [-1 - (2 * 4 + 2), 20, 200, 21, 7]], dtype=np.int32)
    # results of the round in flight: greedy token, draw, then floats
    decode = np.array([[100, 101, 0, 0, 0], [102, 103, 0, 0, 0],
                       [104, 105, 0, 0, 0], [106, 107, 0, 0, 0]],
                      dtype=np.int32)
    prompt = np.array([[200, 201, 0, 0, 0], [202, 203, 0, 0, 0]],
                      dtype=np.int32)
    fed = np.asarray(ModelRunner._feed(
        jnp.asarray(rows), jnp.asarray(decode), jnp.asarray(prompt)))
    # cell 5: row 2's draw; cell 0: row 0's greedy token; cell 10: the
    # second prompt's greedy token
    assert fed[:, 0].tolist() == [41, 105, 42, 100, 202]
    assert (fed[:, 1:] == rows[:, 1:]).all()


def test_a_token_in_flight_counts_for_the_slot_and_for_the_last_token():
    from aphrodite_tpu.common.config import CacheConfig, SchedulerConfig
    from aphrodite_tpu.processing.scheduler import Scheduler
    cache_config = CacheConfig(block_size=4)
    cache_config.num_gpu_blocks, cache_config.num_cpu_blocks = 8, 0
    sched = Scheduler(SchedulerConfig(
        max_num_batched_tokens=64, max_num_seqs=4, max_model_len=64,
        max_paddings=64), cache_config, None)
    seq = Sequence(1, "x", list(range(8)), 4)       # two full pages
    group = SequenceGroup("g", [seq], SamplingParams(max_tokens=3), 0.0)
    sched.add_seq_group(group)
    _, out = sched.schedule()                       # the prompt
    assert out.prompt_chunks and len(
        sched.block_manager.get_block_table(seq)) == 2
    # its first token is still on the device: the decode step writes
    # position 8, in a third page
    seq.data.in_flight = 1
    mds, out = sched.schedule()
    assert out.decode_groups == [group]
    assert len(mds[0].block_tables[1]) == 3
    # which is no look-ahead for page pressure to take back
    assert sched.block_manager.trim_reserved(seq) == 0
    # once one token is known, the one in flight is the third and last
    # by `max_tokens`... not yet: it is the second
    seq.append_token_id(5, {5: 0.0})
    assert not sched._last_token_in_flight(group)
    seq.append_token_id(6, {6: 0.0})
    assert sched._last_token_in_flight(group)
    _, out = sched.schedule()
    assert out.decode_groups == [] and list(sched.running) == [group]
    # a preemption or a rollback takes the token out of flight
    sched._preempt_by_recompute(group)
    assert seq.data.in_flight == 0
