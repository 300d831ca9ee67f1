"""Sampler unit tests with injected logits — no model, pure CPU
(reference strategy: `tests/samplers/test_samplers.py` with
MockLogitsSampler)."""
from typing import List

import numpy as np
import pytest

import jax.numpy as jnp

from aphrodite_tpu.common.sampling_params import SamplingParams
from aphrodite_tpu.common.sequence import SequenceData
from aphrodite_tpu.modeling.layers.sampler import Sampler
from aphrodite_tpu.modeling.sampling_metadata import (OutputMetadata,
                                                      PersistentMetadata,
                                                      SamplingMetadata)

VOCAB = 32


def make_metadata(groups, seq_data, prompt_lens=None,
                  persistent=None) -> SamplingMetadata:
    return SamplingMetadata(
        seq_groups=groups,
        seq_data=seq_data,
        prompt_lens=prompt_lens or [],
        persistent_metadata=persistent or PersistentMetadata(),
        output_metadata=OutputMetadata())


def uniform_logits(rows: int) -> jnp.ndarray:
    return jnp.zeros((rows, VOCAB), dtype=jnp.float32)


def peaked_logits(rows: int, peak: int, height: float = 10.0):
    logits = np.zeros((rows, VOCAB), dtype=np.float32)
    logits[:, peak] = height
    return jnp.asarray(logits)


def test_greedy_picks_argmax():
    sampler = Sampler(VOCAB)
    params = SamplingParams(temperature=0.0)
    meta = make_metadata([([0], params)], {0: SequenceData([1, 2])})
    out = sampler(peaked_logits(1, peak=7), meta)
    assert out[0].samples[0].output_token == 7


def test_greedy_batch_mixed_peaks():
    sampler = Sampler(VOCAB)
    groups, seq_data = [], {}
    logits = np.zeros((4, VOCAB), dtype=np.float32)
    for i in range(4):
        groups.append(([i], SamplingParams(temperature=0.0)))
        seq_data[i] = SequenceData([1])
        logits[i, i + 3] = 5.0
    out = sampler(jnp.asarray(logits), make_metadata(groups, seq_data))
    for i in range(4):
        assert out[i].samples[0].output_token == i + 3


def test_top_k_one_is_greedy():
    sampler = Sampler(VOCAB)
    params = SamplingParams(temperature=1.0, top_k=1)
    meta = make_metadata([([0], params)], {0: SequenceData([1])})
    out = sampler(peaked_logits(1, peak=11, height=0.5), meta)
    assert out[0].samples[0].output_token == 11


def test_top_p_masks_tail():
    sampler = Sampler(VOCAB)
    # Two dominant tokens hold ~all mass; top_p=0.5 keeps only argmax.
    logits = np.full((1, VOCAB), -20.0, dtype=np.float32)
    logits[0, 3] = 10.0
    logits[0, 4] = 9.0
    params = SamplingParams(temperature=1.0, top_p=0.5, seed=1)
    for trial in range(5):
        meta = make_metadata([([0], params)], {0: SequenceData([1])})
        out = sampler(jnp.asarray(logits), meta)
        assert out[0].samples[0].output_token == 3


def test_repetition_penalty_discourages_repeats():
    sampler = Sampler(VOCAB)
    seq = SequenceData([5])
    seq.output_token_ids = [7, 7, 7]
    logits = np.zeros((1, VOCAB), dtype=np.float32)
    logits[0, 7] = 1.0     # would win without penalty
    logits[0, 9] = 0.99
    params = SamplingParams(temperature=0.0, repetition_penalty=2.0)
    out = sampler(jnp.asarray(logits), make_metadata([([0], params)],
                                                     {0: seq}))
    assert out[0].samples[0].output_token == 9


def test_presence_frequency_penalties():
    sampler = Sampler(VOCAB)
    seq = SequenceData([2])
    seq.output_token_ids = [4, 4]
    logits = np.zeros((1, VOCAB), dtype=np.float32)
    logits[0, 4] = 1.5
    logits[0, 6] = 0.5
    params = SamplingParams(temperature=0.0, presence_penalty=1.0,
                            frequency_penalty=0.5)
    # token 4: 1.5 - 1.0 - 0.5*2 = -0.5 < 0.5 (token 6)
    out = sampler(jnp.asarray(logits), make_metadata([([0], params)],
                                                     {0: seq}))
    assert out[0].samples[0].output_token == 6


def test_seeded_sampling_reproducible():
    def run():
        sampler = Sampler(VOCAB)
        params = SamplingParams(temperature=1.0, seed=1234)
        meta = make_metadata([([0], params)], {0: SequenceData([1])})
        return sampler(uniform_logits(1), meta)[0].samples[0].output_token

    assert run() == run()


def test_random_sampling_covers_support():
    sampler = Sampler(VOCAB)
    tokens = set()
    for i in range(20):
        params = SamplingParams(temperature=1.0)
        meta = make_metadata([([0], params)], {0: SequenceData([1])})
        tokens.add(sampler(uniform_logits(1), meta)[0].samples[0]
                   .output_token)
    assert len(tokens) > 3


def test_best_of_prompt_draws_n():
    sampler = Sampler(VOCAB)
    params = SamplingParams(temperature=1.0, n=3, best_of=3)
    meta = make_metadata([([0], params)], {0: SequenceData([1])},
                         prompt_lens=[2])
    out = sampler(uniform_logits(1), meta)
    assert len(out[0].samples) == 3


def test_beam_search_prompt_returns_2x():
    sampler = Sampler(VOCAB)
    params = SamplingParams(temperature=0.0, use_beam_search=True, n=2,
                            best_of=2)
    logits = np.zeros((1, VOCAB), dtype=np.float32)
    logits[0, 1] = 3.0
    logits[0, 2] = 2.0
    logits[0, 3] = 1.0
    meta = make_metadata([([0], params)], {0: SequenceData([1])},
                         prompt_lens=[2])
    out = sampler(jnp.asarray(logits), meta)
    assert len(out[0].samples) == 4
    assert [s.output_token for s in out[0].samples[:2]] == [1, 2]


def test_mirostat_updates_mu():
    sampler = Sampler(VOCAB)
    params = SamplingParams(temperature=1.0, mirostat_mode=2,
                            mirostat_tau=2.0, mirostat_eta=0.1)
    meta = make_metadata([([0], params)], {0: SequenceData([1])})
    # Uniform over 32 tokens -> every surprise is 5 bits; tau=2 so
    # mu moves from 2*tau=4.0 by eta*(5-2)=0.3.
    out = sampler(uniform_logits(1), meta)
    assert "miro_mu" in out[0].samples[0].persistent_data
    mu = out[0].samples[0].persistent_data["miro_mu"]
    assert mu == pytest.approx(3.7, abs=1e-3)


def test_logprobs_include_sampled_and_topn():
    sampler = Sampler(VOCAB)
    params = SamplingParams(temperature=0.0, logprobs=3)
    meta = make_metadata([([0], params)], {0: SequenceData([1])})
    out = sampler(peaked_logits(1, peak=5), meta)
    lp = out[0].samples[0].logprobs
    assert 5 in lp
    assert len(lp) >= 3
    assert lp[5] == pytest.approx(max(lp.values()))


def test_typical_and_tfs_smoke():
    sampler = Sampler(VOCAB)
    for kwargs in ({"tfs": 0.9}, {"typical_p": 0.8}, {"eta_cutoff": 10.0},
                   {"epsilon_cutoff": 10.0}, {"smoothing_factor": 0.5},
                   {"dynatemp_range": 0.3}, {"top_a": 0.2},
                   {"min_p": 0.1}):
        params = SamplingParams(temperature=0.8, seed=7, **kwargs)
        meta = make_metadata([([0], params)], {0: SequenceData([1])})
        out = sampler(peaked_logits(1, peak=9, height=8.0), meta)
        # Strongly peaked logits survive every filter.
        assert out[0].samples[0].output_token == 9


def test_logits_processor_bias():
    from aphrodite_tpu.common.logits_processor import BiasLogitsProcessor
    sampler = Sampler(VOCAB)
    proc = BiasLogitsProcessor({12: 100.0})
    params = SamplingParams(temperature=0.0, logits_processors=[proc])
    meta = make_metadata([([0], params)], {0: SequenceData([1])})
    out = sampler(peaked_logits(1, peak=3), meta)
    assert out[0].samples[0].output_token == 12


def test_quadratic_does_not_corrupt_cobatched_greedy():
    """smoothing_factor=0 rows must be untouched when batched with a
    quadratic-sampling request (regression: where-guard in the stage)."""
    sampler = Sampler(VOCAB)
    logits = np.zeros((2, VOCAB), dtype=np.float32)
    logits[0, 7] = 5.0     # greedy row
    logits[1, 9] = 5.0     # quadratic row
    groups = [([0], SamplingParams(temperature=0.0)),
              ([1], SamplingParams(temperature=0.0, smoothing_factor=0.5))]
    seq_data = {0: SequenceData([1]), 1: SequenceData([1])}
    out = sampler(jnp.asarray(logits), make_metadata(groups, seq_data))
    assert out[0].samples[0].output_token == 7
    assert out[1].samples[0].output_token == 9


def test_mirostat_mode0_with_tau_set_is_ignored():
    """mirostat_tau set but mode=0 must NOT trigger mirostat masking
    (regression: device gate now derives from mode==2)."""
    sampler = Sampler(VOCAB)
    logits = np.zeros((2, VOCAB), dtype=np.float32)
    logits[0, 5] = 6.0
    groups = [([0], SamplingParams(temperature=0.0, mirostat_mode=0,
                                   mirostat_tau=1.0)),
              ([1], SamplingParams(temperature=1.0, mirostat_mode=2,
                                   mirostat_tau=2.0, mirostat_eta=0.1))]
    seq_data = {0: SequenceData([1]), 1: SequenceData([1])}
    out = sampler(jnp.asarray(logits), make_metadata(groups, seq_data))
    assert out[0].samples[0].output_token == 5
    assert "miro_mu" not in out[0].samples[0].persistent_data
    assert "miro_mu" in out[1].samples[0].persistent_data
