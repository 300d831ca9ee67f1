"""The sampling plan of a step (`Sampler.plan`): the packed knobs hold
exactly what the per-field builder gave, the device copy is kept while
the batch does not change, and a plan costs at most two transfers."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from aphrodite_tpu.common.sampling_params import SamplingParams
from aphrodite_tpu.common.sequence import SequenceData
from aphrodite_tpu.modeling.layers.sampler import (Sampler,
                                                   _fused_sample_jit)
from aphrodite_tpu.modeling.sampling_metadata import (
    GATES, KNOB_COLUMNS, PersistentMetadata, SamplingMetadata,
    SamplingTensors, knob_row)

VOCAB = 64
EPS = 1e-5
BASE_SEED, STEP = 0x1234567, 5


# ---- the plain reference: the per-field builder this PR replaced ----

def _pad_2d(rows, pad_value, width):
    out = np.full((len(rows), width), pad_value, dtype=np.int32)
    for i, r in enumerate(rows):
        n = min(len(r), width)
        out[i, :n] = r[:n]
    return out


def _pow2_width(rows, lo):
    need = max((len(r) for r in rows), default=1)
    w = lo
    while w < need:
        w *= 2
    return w


def reference_tensors(metadata, vocab_size, pad_to=None):
    """`build_sampling_tensors` as it was: 22 lists appended row by
    row, the twelve gates, the row -> sequence map. Arrays stay numpy."""
    names = list(KNOB_COLUMNS)
    lists = {name: [] for name in names}
    prompt_tokens, output_tokens, banned_tokens = [], [], []
    row_to_seq = {}
    do = dict.fromkeys(GATES, False)
    for group_idx, (seq_ids, p) in enumerate(metadata.seq_groups):
        temperature = p.temperature
        if temperature < EPS:
            temperature = 1.0
        else:
            if temperature != 1.0 or p.dynatemp_range > 0:
                do["do_temperatures"] = True
        if p.dynatemp_range > 0:
            do["do_temperatures"] = True
        if p.top_p < 1.0 - EPS or p.top_k not in (-1, vocab_size):
            do["do_top_p_top_k"] = True
        if p.top_a > 0.0:
            do["do_top_as"] = True
        if p.min_p > EPS:
            do["do_min_p"] = True
        if p.tfs < 1.0 - EPS:
            do["do_tfss"] = True
        if p.eta_cutoff > EPS:
            do["do_eta_cutoffs"] = True
        if p.epsilon_cutoff > EPS:
            do["do_epsilon_cutoffs"] = True
        if p.typical_p < 1.0 - EPS:
            do["do_typical_ps"] = True
        if p.smoothing_factor > EPS:
            do["do_quadratic"] = True
        if p.mirostat_mode == 2:
            do["do_mirostat"] = True
        if p.custom_token_bans:
            do["do_token_bans"] = True
        if abs(p.presence_penalty) >= EPS or \
                abs(p.frequency_penalty) >= EPS or \
                abs(p.repetition_penalty - 1.0) >= EPS:
            do["do_penalties"] = True
        is_prompt = group_idx < len(metadata.prompt_lens)
        rows = []
        if is_prompt and p.prompt_logprobs is not None:
            rows.extend([seq_ids[0]] * (metadata.prompt_lens[group_idx] - 1))
        rows.extend(seq_ids)
        for seq_id in rows:
            data = metadata.seq_data[seq_id]
            lists["temperatures"].append(temperature)
            lists["dynatemp_mins"].append(
                max(temperature - p.dynatemp_range, 0.0))
            lists["dynatemp_maxs"].append(temperature + p.dynatemp_range)
            lists["dynatemp_exps"].append(p.dynatemp_exponent)
            lists["top_ps"].append(p.top_p)
            lists["top_ks"].append(vocab_size if p.top_k == -1
                                   else min(p.top_k, vocab_size))
            lists["top_as"].append(p.top_a)
            lists["min_ps"].append(p.min_p)
            lists["tfss"].append(p.tfs)
            lists["eta_cutoffs"].append(p.eta_cutoff)
            lists["epsilon_cutoffs"].append(p.epsilon_cutoff)
            lists["typical_ps"].append(p.typical_p)
            lists["smoothing_factors"].append(p.smoothing_factor)
            is_miro = p.mirostat_mode == 2
            lists["miro_taus"].append(p.mirostat_tau if is_miro else 0.0)
            lists["miro_etas"].append(p.mirostat_eta if is_miro else 0.0)
            lists["miro_mus"].append(
                metadata.persistent_metadata.get(seq_id).get(
                    "miro_mu", 2.0 * p.mirostat_tau) if is_miro else 0.0)
            lists["presence_penalties"].append(p.presence_penalty)
            lists["frequency_penalties"].append(p.frequency_penalty)
            lists["repetition_penalties"].append(p.repetition_penalty)
            prompt_tokens.append(list(data.prompt_token_ids))
            output_tokens.append(list(data.output_token_ids))
            banned_tokens.append(list(p.custom_token_bans))
            row_to_seq[len(lists["temperatures"]) - 1] = seq_id
    n_pad = max(0, (pad_to or 0) - len(lists["temperatures"]))
    neutral = dict.fromkeys(names, 0.0)
    neutral.update(temperatures=1.0, dynatemp_exps=1.0, top_ps=1.0,
                   top_ks=vocab_size, tfss=1.0, typical_ps=1.0,
                   repetition_penalties=1.0)
    for name in names:
        lists[name] += [neutral[name]] * n_pad
    prompt_tokens += [[]] * n_pad
    output_tokens += [[]] * n_pad
    banned_tokens += [[]] * n_pad
    hist_width = _pow2_width(prompt_tokens + output_tokens, 32) \
        if do["do_penalties"] else 0
    bans_width = _pow2_width(banned_tokens, 8) if do["do_token_bans"] \
        else 0
    arrays = {name: np.asarray(lists[name], dtype=np.int32
                               if name == "top_ks" else np.float32)
              for name in names}
    arrays["prompt_tokens"] = _pad_2d(prompt_tokens, vocab_size, hist_width)
    arrays["output_tokens"] = _pad_2d(output_tokens, vocab_size, hist_width)
    arrays["banned_tokens"] = _pad_2d(banned_tokens, vocab_size, bans_width)
    return arrays, do, row_to_seq


def reference_key_parts(metadata, rows, row_to_seq, base_seed, step):
    """`Sampler._key_parts` as it was: a Python loop over every row."""
    group_of = {seq_id: (seq_ids, params)
                for seq_ids, params in metadata.seq_groups
                for seq_id in seq_ids}
    parts = np.empty((rows, 3), dtype=np.int64)
    step_mix = (base_seed ^ (step * 0x9E3779B1)) & 0x7FFFFFFF
    for row in range(rows):
        seq_id = row_to_seq.get(row)
        entry = group_of.get(seq_id) if seq_id is not None else None
        if entry is not None and entry[1].seed is not None:
            seq_ids, params = entry
            parts[row] = (params.seed,
                          len(metadata.seq_data[seq_id].output_token_ids),
                          seq_ids.index(seq_id))
        else:
            parts[row] = ((step_mix ^ (row * 0x85EBCA77)) & 0x7FFFFFFF,
                          0, 0)
    return parts.astype(np.int32)      # as a 32-bit default sent it


# ---- batches ----

def _data(seq_id, n_out=3):
    data = SequenceData([1 + seq_id % 5, 7, 9, 11 + seq_id % 3])
    data.output_token_ids = [(13 * seq_id + 5 * i) % VOCAB
                             for i in range(n_out)]
    return data


def _metadata(groups, prompt_lens=(), persistent=None):
    seq_data = {s: _data(s) for seq_ids, _ in groups for s in seq_ids}
    return SamplingMetadata(
        seq_groups=groups, seq_data=seq_data, prompt_lens=list(prompt_lens),
        persistent_metadata=PersistentMetadata(persistent or {}))


#: one batch a gate: a greedy row, a row that turns the gate on (seeded,
#: so that its draw can be compared), a plain sampled row
GATE_PARAMS = {
    "do_penalties": dict(presence_penalty=0.5, frequency_penalty=0.25,
                         repetition_penalty=1.3),
    "do_temperatures": dict(temperature=0.7, dynatemp_range=0.2,
                            dynatemp_exponent=1.5),
    "do_top_p_top_k": dict(top_p=0.8, top_k=5),
    "do_top_as": dict(top_a=0.2),
    "do_min_p": dict(min_p=0.1),
    "do_tfss": dict(tfs=0.9),
    "do_eta_cutoffs": dict(eta_cutoff=10.0),
    "do_epsilon_cutoffs": dict(epsilon_cutoff=10.0),
    "do_typical_ps": dict(typical_p=0.8),
    "do_quadratic": dict(smoothing_factor=0.5),
    "do_mirostat": dict(mirostat_mode=2, mirostat_tau=2.0,
                        mirostat_eta=0.1),
    "do_token_bans": dict(custom_token_bans=[3, 4, 60]),
}


def _gate_batch(gate):
    knobs = dict(temperature=1.0, seed=77)
    knobs.update(GATE_PARAMS[gate])
    groups = [([0], SamplingParams(temperature=0.0)),
              ([1], SamplingParams(**knobs)),
              ([2], SamplingParams(temperature=1.0))]
    return _metadata(groups, persistent={1: {"miro_mu": 3.25}}), 4


def _mixed_batch():
    """Prompt groups: one with prompt-logprobs row expansion (3 rows
    before its own), a best-of group, a greedy one; padded to 8."""
    groups = [
        ([0], SamplingParams(temperature=0.8, top_k=7, prompt_logprobs=2,
                             seed=5)),
        ([1], SamplingParams(temperature=1.0, n=2, best_of=3, min_p=0.05)),
        ([2], SamplingParams(temperature=0.0, repetition_penalty=1.2)),
    ]
    return _metadata(groups, prompt_lens=[4, 4, 4]), 8


def _decode_best_of_batch():
    """A decode step of a best-of group of three sequences, seeded: the
    sibling index salts each."""
    groups = [([0, 1, 2], SamplingParams(temperature=1.0, n=3, best_of=3,
                                         seed=11, top_p=0.9)),
              ([3], SamplingParams(temperature=0.0))]
    return _metadata(groups), 8


BATCHES = {gate: (lambda gate=gate: _gate_batch(gate)) for gate in GATES}
BATCHES["mixed_prompt"] = _mixed_batch
BATCHES["decode_best_of"] = _decode_best_of_batch


def _pinned_sampler(vocab=VOCAB, **kw):
    sampler = Sampler(vocab, **kw)
    sampler._base_seed, sampler._step = BASE_SEED, STEP - 1
    return sampler


@pytest.mark.parametrize("batch", sorted(BATCHES))
def test_packed_plan_unpacks_to_the_per_field_arrays(batch):
    metadata, pad_to = BATCHES[batch]()
    want, gates, row_to_seq = reference_tensors(metadata, VOCAB, pad_to)
    plan = _pinned_sampler().plan(metadata, pad_to=pad_to)
    t = plan.tensors
    assert t.knobs.dtype == jnp.float32
    assert t.knobs.shape == (pad_to, len(KNOB_COLUMNS))
    for name in KNOB_COLUMNS:
        got = np.asarray(getattr(t, name))
        if name == "top_ks":
            got = got.astype(np.int32)
        np.testing.assert_array_equal(got, want[name], err_msg=name)
    assert {g: getattr(t, g) for g in GATES} == gates
    assert plan.num_rows == len(row_to_seq)
    if batch in GATES:
        assert gates[batch] and gates == dict(
            dict.fromkeys(GATES, False), **{batch: True})
    for name, gate in (("prompt_tokens", "do_penalties"),
                       ("output_tokens", "do_penalties"),
                       ("banned_tokens", "do_token_bans")):
        if gates[gate]:
            np.testing.assert_array_equal(np.asarray(getattr(t, name)),
                                          want[name])
        else:       # not an argument of the program at all
            assert getattr(t, name) is None
    # every row of this batch's key parts, a row that draws in it
    np.testing.assert_array_equal(
        np.asarray(plan.key_parts),
        reference_key_parts(metadata, pad_to, row_to_seq, BASE_SEED, STEP))


@pytest.mark.parametrize("batch", sorted(BATCHES))
def test_fused_sample_is_identical_on_the_packed_plan(batch):
    """The program reads columns of one array where it read 19 arrays:
    the same packed result, greedy and drawn, bit for bit."""
    metadata, pad_to = BATCHES[batch]()
    want, gates, row_to_seq = reference_tensors(metadata, VOCAB, pad_to)
    plan = _pinned_sampler().plan(metadata, pad_to=pad_to)
    by_field = SamplingTensors(
        knobs=jnp.stack([jnp.asarray(want[c], dtype=jnp.float32)
                         for c in KNOB_COLUMNS], axis=1),
        prompt_tokens=jnp.asarray(want["prompt_tokens"])
        if gates["do_penalties"] else None,
        output_tokens=jnp.asarray(want["output_tokens"])
        if gates["do_penalties"] else None,
        banned_tokens=jnp.asarray(want["banned_tokens"])
        if gates["do_token_bans"] else None, **gates)
    keys = jnp.asarray(reference_key_parts(metadata, pad_to, row_to_seq,
                                           BASE_SEED, STEP))
    logits = jax.random.normal(jax.random.PRNGKey(3), (pad_to, VOCAB)) * 3
    statics = dict(max_best_of=plan.max_best_of, num_topk=plan.num_topk,
                   need_logprobs=plan.need_logprobs)
    got, got_lp = _fused_sample_jit(logits, plan.tensors, plan.key_parts,
                                    **statics)
    ref, ref_lp = _fused_sample_jit(logits, by_field, keys, **statics)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    assert (got_lp is None) == (ref_lp is None)
    assert plan.max_best_of == (3 if batch in ("mixed_prompt",
                                               "decode_best_of") else 1)


def test_an_all_greedy_batch_has_one_constant_key_array():
    groups = [([i], SamplingParams(temperature=0.0)) for i in range(3)]
    sampler = Sampler(VOCAB)
    a = sampler.plan(_metadata(groups), pad_to=4)
    b = sampler.plan(_metadata(groups[:2]), pad_to=4)
    assert a.key_parts is b.key_parts and not np.asarray(a.key_parts).any()


# ---- the row cache ----

def test_a_knob_row_is_computed_once_per_object_and_a_clone_has_its_own():
    p = SamplingParams(temperature=0.7, top_k=5)
    row, mask = knob_row(p, VOCAB)
    assert knob_row(p, VOCAB)[0] is row
    # another vocabulary is another row (top_k -1 reads as the vocabulary)
    q = SamplingParams(temperature=1.0)
    assert knob_row(q, VOCAB)[0][KNOB_COLUMNS.index("top_ks")] == VOCAB
    assert knob_row(q, 2 * VOCAB)[0][KNOB_COLUMNS.index("top_ks")] == \
        2 * VOCAB
    clone = p.clone()
    assert "_knob_row" not in clone.__dict__ and clone == p
    clone.top_k = 9
    assert knob_row(clone, VOCAB)[0][KNOB_COLUMNS.index("top_ks")] == 9
    assert knob_row(p, VOCAB)[0] is row


@pytest.mark.parametrize("vocab", [32_000, 128_256, 256_000])
def test_top_k_is_exact_for_every_vocabulary_served(vocab):
    groups = [([0], SamplingParams(temperature=1.0, top_k=vocab - 1)),
              ([1], SamplingParams(temperature=1.0))]
    plan = Sampler(vocab).plan(_metadata(groups), pad_to=4)
    top_ks = np.asarray(plan.tensors.top_ks).astype(np.int64)
    assert top_ks.tolist() == [vocab - 1, vocab, vocab, vocab]
    with pytest.raises(ValueError):
        Sampler(1 << 24)


# ---- reuse ----

def _greedy_groups(n=3):
    return [([i], SamplingParams(temperature=0.0)) for i in range(n)]


def test_an_unchanged_all_greedy_batch_reuses_the_device_copy():
    sampler = Sampler(VOCAB)
    groups = _greedy_groups()
    first = sampler.plan(_metadata(groups), pad_to=4)
    # the engine hands new lists and grown histories, the same objects
    again = sampler.plan(_metadata([(list(s), p) for s, p in groups]),
                         pad_to=4)
    assert not first.reused and again.reused
    assert again.tensors is first.tensors
    assert again.key_parts is first.key_parts


def _leaves(groups):
    return groups[:-1]


def _joins(groups):
    return groups + [([9], SamplingParams(temperature=0.0))]


def _equal_valued_other_object(groups):
    assert SamplingParams(temperature=0.0) == groups[1][1]
    return [groups[0], (groups[1][0], SamplingParams(temperature=0.0)),
            groups[2]]


def _other_sequence(groups):
    return [groups[0], ([8], groups[1][1]), groups[2]]


@pytest.mark.parametrize("change", [_leaves, _joins,
                                    _equal_valued_other_object,
                                    _other_sequence])
def test_reuse_is_refused_when_the_rows_change(change):
    sampler = Sampler(VOCAB)
    groups = _greedy_groups()
    first = sampler.plan(_metadata(groups), pad_to=4)
    groups = change(groups)
    changed = sampler.plan(_metadata(groups), pad_to=4)
    assert not changed.reused and changed.tensors is not first.tensors
    # and taken again once the changed batch stands
    assert sampler.plan(_metadata(groups), pad_to=4).reused


def test_reuse_is_refused_when_pad_to_changes():
    sampler = Sampler(VOCAB)
    groups = _greedy_groups()
    sampler.plan(_metadata(groups), pad_to=4)
    wider = sampler.plan(_metadata(groups), pad_to=8)
    assert not wider.reused and wider.tensors.knobs.shape[0] == 8


def test_a_prompt_step_and_a_decode_step_of_one_sequence_differ():
    sampler = Sampler(VOCAB)
    groups = [([0], SamplingParams(temperature=0.0, prompt_logprobs=1))]
    prompt = sampler.plan(_metadata(groups, prompt_lens=[4]), pad_to=4)
    decode = sampler.plan(_metadata(groups), pad_to=4)
    assert prompt.num_rows == 4 and decode.num_rows == 1
    assert not decode.reused


PER_STEP = {
    "mirostat": dict(temperature=1.0, mirostat_mode=2, mirostat_tau=2.0,
                     mirostat_eta=0.1),
    "penalty": dict(temperature=0.0, repetition_penalty=1.2),
    "ban": dict(temperature=0.0, custom_token_bans=[5]),
    "seed": dict(temperature=1.0, seed=3),
    "unseeded_draw": dict(temperature=1.0),
}


@pytest.mark.parametrize("state", sorted(PER_STEP))
def test_a_row_with_state_of_the_step_is_never_a_reused_plan(state):
    sampler = Sampler(VOCAB)
    groups = _greedy_groups(2) + [([2], SamplingParams(**PER_STEP[state]))]
    metadata = _metadata(groups, persistent={2: {"miro_mu": 3.0}})
    first = sampler.plan(metadata, pad_to=4)
    metadata.seq_data[2].output_token_ids.append(5)
    metadata.persistent_metadata._metadata[2]["miro_mu"] = 2.5
    second = sampler.plan(metadata, pad_to=4)
    assert not first.reused and not second.reused
    if state == "mirostat":     # the mu column is the step's
        mus = np.asarray(second.tensors.miro_mus)
        assert mus.tolist() == [0.0, 0.0, 2.5, 0.0]
        assert np.asarray(first.tensors.miro_mus)[2] == 3.0
    else:                       # the knobs stay on the device
        assert second.tensors.knobs is first.tensors.knobs
    if state == "penalty":      # the history grew
        assert np.asarray(second.tensors.output_tokens)[2, 3] == 5
    if state in ("seed", "unseeded_draw", "mirostat"):
        a, b = np.asarray(first.key_parts), np.asarray(second.key_parts)
        assert second.key_parts is not first.key_parts
        if state == "seed":
            assert a[2].tolist() == [3, 3, 0] and b[2].tolist() == [3, 4, 0]
        else:                   # the per-step mix keeps rows and steps apart
            assert a[2, 0] != b[2, 0] and len(set(b[:, 0].tolist())) == 4
    else:
        assert second.key_parts is first.key_parts


def test_seeded_rows_draw_the_tokens_of_the_position_salted_keys():
    """Eight steps of a batch whose greedy rows are reused and whose
    seeded rows are not: every seeded token is the one that
    fold_in(fold_in(PRNGKey(seed), output_len), sibling) draws, as
    before this PR, whatever the batch around it."""
    sampler = Sampler(VOCAB)
    seeded = {1: SamplingParams(temperature=1.0, seed=1234),
              3: SamplingParams(temperature=1.0, seed=2 ** 31 + 9)}
    groups = [([0], SamplingParams(temperature=0.0)), ([1], seeded[1]),
              ([2], SamplingParams(temperature=0.0)), ([3], seeded[3])]
    metadata = _metadata(groups)
    for s in metadata.seq_data.values():
        s.output_token_ids = []
    for step in range(8):
        logits = jax.random.normal(jax.random.PRNGKey(100 + step),
                                   (4, VOCAB))
        out = sampler(logits, metadata)
        assert out[0].samples[0].output_token == int(jnp.argmax(logits[0]))
        for seq_id, p in seeded.items():
            base = np.asarray(p.seed, dtype=np.int64).astype(np.int32)
            key = jax.random.fold_in(jax.random.fold_in(
                jax.random.PRNGKey(base), step), 0)
            want = int(jax.random.categorical(key, logits[seq_id],
                                              shape=(1,))[0])
            assert out[seq_id].samples[0].output_token == want
        for seq_id, group in enumerate(out):
            metadata.seq_data[seq_id].append_token_id(
                group.samples[0].output_token, 0.0)


def test_salt_offsets_move_the_position_of_the_rows_that_draw():
    """Speculative verify: row j of a sequence samples for output
    position len + j."""
    p = SamplingParams(temperature=1.0, seed=21)
    groups = [([0], p), ([0], p), ([0], p)]
    plan = Sampler(VOCAB).plan(_metadata(groups), pad_to=4,
                               salt_offsets=np.arange(3, dtype=np.int32))
    assert np.asarray(plan.key_parts)[:3].tolist() == [
        [21, 3, 0], [21, 4, 0], [21, 5, 0]]


# ---- the budget ----

@pytest.fixture
def transfers(monkeypatch):
    """Counts every `jnp.asarray` and `jax.device_put` call made while
    it is armed."""
    calls = []
    real_asarray, real_put = jnp.asarray, jax.device_put

    def asarray(*a, **kw):
        calls.append("asarray")
        return real_asarray(*a, **kw)

    def device_put(*a, **kw):
        calls.append("device_put")
        return real_put(*a, **kw)
    monkeypatch.setattr(jnp, "asarray", asarray)
    monkeypatch.setattr(jax, "device_put", device_put)
    return calls


class NoHistory:
    """A sequence whose token lists may not be touched."""

    def __getattr__(self, name):
        raise AssertionError(f"the plan read SequenceData.{name}")


BUDGET = {
    "greedy": (lambda i: SamplingParams(temperature=0.0), 1, 0),
    "sampled": (lambda i: SamplingParams(temperature=0.7, top_p=0.9,
                                         top_k=40 + i), 2, 1),
}


@pytest.mark.parametrize("kind", sorted(BUDGET))
def test_a_plan_costs_at_most_two_transfers_and_a_reused_one_none(
        kind, transfers):
    params_of, rebuilt, steady = BUDGET[kind]
    # the default `put` is looked up when the sampler is made
    sampler = Sampler(VOCAB, put=lambda x: jnp.asarray(x))
    groups = [([i], params_of(i)) for i in range(47)]
    metadata = SamplingMetadata(
        seq_groups=groups, seq_data={i: NoHistory() for i in range(47)},
        prompt_lens=[])
    sampler.plan(metadata, pad_to=64)       # a first plan at this width
    del transfers[:]
    plan = sampler.plan(SamplingMetadata(
        seq_groups=groups[1:], seq_data=metadata.seq_data, prompt_lens=[]),
        pad_to=64)
    assert not plan.reused and len(transfers) <= rebuilt <= 2, transfers
    del transfers[:]
    plan = sampler.plan(SamplingMetadata(
        seq_groups=groups[1:], seq_data=metadata.seq_data, prompt_lens=[]),
        pad_to=64)
    assert len(transfers) == steady, transfers
    assert plan.reused is (steady == 0)


def test_histories_are_read_only_for_a_batch_with_a_penalty(transfers):
    sampler = Sampler(VOCAB, put=lambda x: jnp.asarray(x))
    groups = [([0], SamplingParams(temperature=0.0)),
              ([1], SamplingParams(temperature=0.0, presence_penalty=0.5))]
    with pytest.raises(AssertionError, match="prompt_token_ids"):
        sampler.plan(SamplingMetadata(
            seq_groups=groups, seq_data={0: NoHistory(), 1: NoHistory()},
            prompt_lens=[]), pad_to=4)
    del transfers[:]
    plan = sampler.plan(_metadata(groups), pad_to=4)
    # knobs, two histories; the constant key array of 4 rows
    assert len(transfers) <= 4 and plan.tensors.prompt_tokens.shape == (4, 32)


def test_the_runner_counts_a_reused_plan():
    """`ModelRunner._plan` is the one place a plan is made for a step:
    it times the span and counts the reuse."""
    from aphrodite_tpu.common import tracing
    from aphrodite_tpu.executor.model_runner import ModelRunner

    class Runner:
        tracer = tracing.Tracer()
        sampler = Sampler(VOCAB)
    groups = _greedy_groups()
    for _ in range(3):
        ModelRunner._plan(Runner, _metadata(groups), 4)
    assert Runner.tracer.counts["sampler.plan"] == 3
    assert Runner.tracer.counts["sampler.plan_reuse"] == 2
