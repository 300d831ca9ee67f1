"""Which rows keep the fused step program, a case a kind of request.

Four places ask it of a request's `SamplingParams`: the engine before
it pipelines prompt rounds (`_prompt_fast_path_ok`), before a decode
burst (`_burst_steps`), before a speculative verify round
(`_spec_eligible`, and through it `_runs_ahead`), and the model runner
of a prepared step (`ModelRunner._fused(plan)`, the authority). They
differ on purpose: the burst scan compiles its own sampler statics, so
`best_of` and per-token log-probabilities keep it; a prompt step reads
no history yet, so penalties keep the pipelined prompt rounds. This
table is what each answers, row by row."""
import types

import pytest

from aphrodite_tpu.common import tracing
from aphrodite_tpu.common.config import PageGroups
from aphrodite_tpu.common.sampling_params import SamplingParams
from aphrodite_tpu.common.sequence import (SequenceData,
                                           SequenceGroupMetadata)
from aphrodite_tpu.engine.aphrodite_engine import AphroditeEngine
from aphrodite_tpu.executor.model_runner import ModelRunner
from aphrodite_tpu.modeling.layers.sampler import Sampler
from aphrodite_tpu.modeling.sampling_metadata import SamplingMetadata

VOCAB = 128


def _bias(token_ids, logits):
    return logits


# (case, SamplingParams fields, sequences in the group) ->
# (pipelined prompt rounds, burst, verify round, round ahead,
#  fused prompt step, fused decode step)
TABLE = [
    ("greedy", dict(temperature=0.0), 1,
     (True, True, True, True, True, True)),
    ("temperature", dict(temperature=0.8, top_p=0.9, seed=3), 1,
     (True, True, True, True, True, True)),
    ("presence_penalty", dict(presence_penalty=0.5), 1,
     (True, False, False, False, True, True)),
    ("frequency_penalty", dict(frequency_penalty=-0.5), 1,
     (True, False, False, False, True, True)),
    ("repetition_penalty", dict(repetition_penalty=1.2), 1,
     (True, False, False, False, True, True)),
    ("mirostat_2", dict(mirostat_mode=2, mirostat_tau=5.0,
                        mirostat_eta=0.1), 1,
     (True, False, False, False, True, True)),
    ("logprobs_0", dict(logprobs=0), 1,
     (True, True, True, True, True, True)),
    ("logprobs_3", dict(logprobs=3), 1,
     (False, True, False, False, False, False)),
    # the fused program has no log-softmax rows to hand over; a decode
    # step needs none of a prompt's, and the metadata-level checks do
    # not ask which step it is
    ("prompt_logprobs_0", dict(prompt_logprobs=0), 1,
     (False, False, False, False, False, True)),
    ("prompt_logprobs_2", dict(prompt_logprobs=2), 1,
     (False, False, False, False, False, False)),
    # one sequence of two is left: the group still draws best_of ways
    ("best_of_2", dict(n=1, best_of=2, temperature=0.8), 1,
     (False, True, False, False, False, False)),
    ("n_2_both_running", dict(n=2, temperature=0.8), 2,
     (False, False, False, False, False, False)),
    ("beam", dict(use_beam_search=True, best_of=2, temperature=0.0), 1,
     (False, False, False, False, False, False)),
    ("logits_processor", dict(logits_processors=[_bias]), 1,
     (False, False, False, False, False, False)),
]


def _metadata(params: SamplingParams, n_seqs: int, is_prompt: bool):
    seq_data = {}
    for seq_id in range(n_seqs):
        data = SequenceData(list(range(5, 25)))
        if not is_prompt:
            data.append_token_id(30 + seq_id, 0.0)
        seq_data[seq_id] = data
    return SequenceGroupMetadata(
        request_id="r", is_prompt=is_prompt, seq_data=seq_data,
        sampling_params=params,
        block_tables={s: [0, 1] for s in seq_data},
        persistent_data={s: {} for s in seq_data})


def _engine_of(multi_step: int):
    """What the engine's eligibility methods read of it, and no more:
    a colocated engine without a sliding window or speculation, whose
    scheduler grants every page a burst asks for."""
    engine = types.SimpleNamespace(
        scheduler_config=types.SimpleNamespace(multi_step=multi_step,
                                               max_model_len=256),
        model_config=types.SimpleNamespace(
            get_sliding_window=lambda: None),
        cache_config=types.SimpleNamespace(
            page_groups=PageGroups.of([False], None)),
        executor=types.SimpleNamespace(disagg=False),
        scheduler=types.SimpleNamespace(
            reserve_decode_burst=lambda mds, want, cap, groups: want),
        tracer=tracing.Tracer(),
        _check_epoch=lambda: None,
        _speculates=lambda: False)
    for name in ("_spec_eligible", "_burst_steps", "_runs_ahead"):
        setattr(engine, name, types.MethodType(
            getattr(AphroditeEngine, name), engine))
    return engine


def _fused(*mds: SequenceGroupMetadata) -> bool:
    """`ModelRunner._fused` of the plan the runner makes for a step of
    these groups, all prompts or all decode rows (`_prepare_step`: no
    plan when a row has host logits processors)."""
    is_prompt = mds[0].is_prompt
    seq_groups, seq_data = [], {}
    for i, md in enumerate(mds):
        # (each group's sequences under ids of their own)
        ids = [8 * i + seq_id for seq_id in md.seq_data]
        seq_data.update(zip(ids, md.seq_data.values()))
        seq_groups.append((ids[:1] if is_prompt else ids,
                           md.sampling_params))
    sampling = SamplingMetadata(
        seq_groups=seq_groups, seq_data=seq_data,
        prompt_lens=[20] * len(mds) if is_prompt else [])
    if any(md.sampling_params.logits_processors for md in mds):
        return ModelRunner._fused(None)
    return ModelRunner._fused(Sampler(VOCAB).plan(sampling, pad_to=64))


@pytest.mark.parametrize("case,fields,n_seqs,want", TABLE,
                         ids=[row[0] for row in TABLE])
def test_what_each_check_answers_for_a_kind_of_request(
        case, fields, n_seqs, want):
    fields = dict(dict(max_tokens=64, ignore_eos=True), **fields)
    prompt = _metadata(SamplingParams(**fields), 1, is_prompt=True)
    decode = _metadata(SamplingParams(**fields), n_seqs, is_prompt=False)
    outputs = types.SimpleNamespace(
        decode_groups=[None], blocks_to_swap_in={}, blocks_to_swap_out={},
        blocks_to_copy={})
    burst, _ = _engine_of(multi_step=4)._burst_steps([decode], outputs)
    got = (AphroditeEngine._prompt_fast_path_ok([prompt]),
           burst > 1,
           _engine_of(1)._spec_eligible([decode]),
           _engine_of(1)._runs_ahead([], [decode], outputs),
           _fused(prompt), _fused(decode))
    assert got == want


def test_one_row_off_the_fused_program_takes_its_round_with_it():
    plain = _metadata(SamplingParams(temperature=0.0), 1, False)
    logprobs = _metadata(SamplingParams(logprobs=3), 1, False)
    penalised = _metadata(SamplingParams(presence_penalty=0.5), 1, False)
    engine = _engine_of(1)
    assert engine._spec_eligible([plain, plain])
    assert not engine._spec_eligible([plain, logprobs])
    assert not engine._spec_eligible([plain, penalised])
    assert AphroditeEngine._prompt_fast_path_ok([plain, penalised])
    assert not AphroditeEngine._prompt_fast_path_ok([plain, logprobs])


# What `ModelRunner.dispatch_steps` decides a list of batches by before
# any of them is prepared: (case, SamplingParams fields)
MIRROR = [
    ("greedy", dict(temperature=0.0)),
    ("seeded", dict(temperature=0.8, top_p=0.9, seed=3)),
    ("logprobs_0", dict(logprobs=0)),
    ("logprobs_3", dict(logprobs=3)),
    ("prompt_logprobs", dict(prompt_logprobs=2)),
    ("best_of_2", dict(n=1, best_of=2, temperature=0.8)),
    ("logits_processor", dict(logits_processors=[_bias])),
    ("beam", dict(use_beam_search=True, best_of=2, temperature=0.0)),
]


@pytest.mark.parametrize("case,fields", MIRROR,
                         ids=[row[0] for row in MIRROR])
def test_the_rows_parameters_say_what_the_prepared_plan_will(case, fields):
    """`needs_raw_logits` of a batch's rows is `_fused` of the plan
    prepared for them, the row alone and beside a plain one: a round
    of two batches goes out or not by the first, one program before
    the second batch's plan exists. Of a decode step the plan may know
    better (no prompt log-probabilities are owed any more): the rows
    never let through what the plan refuses."""
    fields = dict(dict(max_tokens=64, ignore_eos=True), **fields)
    plain = SamplingParams(temperature=0.0, max_tokens=64)
    for is_prompt in (True, False):
        row = _metadata(SamplingParams(**fields), 1, is_prompt)
        for mds in ([row], [_metadata(plain, 1, is_prompt), row]):
            raw = any(md.sampling_params.needs_raw_logits for md in mds)
            if is_prompt:
                assert _fused(*mds) == (not raw)
            else:
                assert _fused(*mds) or raw
