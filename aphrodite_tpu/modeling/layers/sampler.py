"""The sampler: full logits-processing pipeline + token selection.

Reference: `aphrodite/modeling/layers/sampler.py` (pipeline order `:53-138`,
penalties `:207`, alphabet soup `:239`, TFS `:282`, eta/epsilon cutoff
`:312,335`, typical `:354`, temperature+dynatemp `:379`, quadratic `:408`,
mirostat v2 `:754,805`, categorized sampling `:545`, logprobs `:607`).

TPU-native structure: every stage is dense vectorized jnp over a
[rows, vocab] logits matrix with per-row knob vectors; the whole pipeline
jits into ONE program whose shape is selected by the SamplingTensors'
static `do_*` flags (stages used by nobody in the batch are absent from
the compiled program — the reference elides them dynamically, we elide at
trace time). Sampling uses per-row PRNG keys so seeded requests are
reproducible regardless of batch composition. The only host work is
ragged per-group assembly of SequenceGroupOutputs (beam search included),
as in the reference.

A step's plan (`Sampler.plan`) is what reaches the device for it: the
packed knobs (`SamplingTensors.knobs`, one transfer), the PRNG key parts
(`[rows, 3]` int32: base, output-position salt, sibling salt; one
transfer), and token histories and bans only when a gate reads them.
The sampler keeps the last plan. While the next step has the same rows
(sequence ids, the same `SamplingParams` objects, prompt lengths) under
the same `pad_to`, the knobs on the device are handed to the program
again, and only what changes from step to step is built and sent: the
`miro_mus` column of a batch with mirostat rows (so such a batch sends
its knobs each step), histories and bans, and the key parts of a batch
with rows that draw. A batch with none of these (all greedy: a greedy
row's draw is discarded, so its key is one constant) sends nothing.

Numerical notes: the pipeline runs in float32; stage formulas match the
reference exactly (mirostat surprise in bits, eta/epsilon scaled by 1e-4,
dynatemp entropy normalization).
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from aphrodite_tpu.common.sampling_params import (SamplingParams,
                                                  SamplingType)
from aphrodite_tpu.common.sequence import (SamplerOutput,
                                           SequenceGroupOutput,
                                           SequenceOutput)
from aphrodite_tpu.modeling.sampling_metadata import (MU_COLUMN,
                                                      SamplingMetadata,
                                                      SamplingTensors,
                                                      build_knobs,
                                                      build_token_lists,
                                                      gates_of)

_NEG_INF = float("-inf")


# ---------------------------------------------------------------- stages --

def _bin_counts_and_mask(tokens: jax.Array,
                         vocab_size: int) -> Tuple[jax.Array, jax.Array]:
    """tokens [rows, width] padded with vocab_size -> (counts, mask) over
    [rows, vocab]. The pad id lands in an extra column that is sliced off
    (reference `_get_bin_counts_and_mask`)."""
    rows = tokens.shape[0]
    counts = jnp.zeros((rows, vocab_size + 1), dtype=jnp.int32)
    row_idx = jnp.arange(rows)[:, None]
    counts = counts.at[row_idx, tokens].add(1, mode="drop")
    counts = counts[:, :vocab_size]
    return counts, counts > 0


def _apply_penalties(logits, t: SamplingTensors) -> jax.Array:
    vocab = logits.shape[-1]
    _, prompt_mask = _bin_counts_and_mask(t.prompt_tokens, vocab)
    out_counts, out_mask = _bin_counts_and_mask(t.output_tokens, vocab)

    rep = jnp.where(prompt_mask | out_mask,
                    t.repetition_penalties[:, None], 1.0)
    logits = jnp.where(logits > 0, logits / rep, logits * rep)
    logits -= t.frequency_penalties[:, None] * out_counts
    logits -= t.presence_penalties[:, None] * out_mask
    return logits


def _apply_temperatures(logits, t: SamplingTensors) -> jax.Array:
    """Plain temperature + dynatemp (reference `:379-407`): rows with a
    dynatemp range get an entropy-interpolated temperature."""
    dyn_mask = (t.dynatemp_maxs - t.dynatemp_mins) > 0
    shifted = jax.nn.log_softmax(logits, axis=-1)
    probs = jnp.exp(shifted)
    entropies = -jnp.nansum(probs * shifted, axis=-1)
    num_valid = jnp.sum(logits > _NEG_INF, axis=-1).astype(jnp.float32)
    max_entropies = jnp.log(num_valid)
    normalized = jnp.where(max_entropies > 0, entropies / max_entropies,
                           0.0)
    dyn_temps = (t.dynatemp_mins + (t.dynatemp_maxs - t.dynatemp_mins) *
                 jnp.power(normalized, t.dynatemp_exps))
    temps = jnp.where(dyn_mask, dyn_temps, t.temperatures)
    temps = jnp.where(temps == 0.0, 1.0, temps)
    return logits / temps[:, None]


def _apply_alphabet_soup(logits, t: SamplingTensors) -> jax.Array:
    """Fused top-p / top-k / top-a / min-p on one sort (reference `:239`)."""
    sorted_logits = jnp.sort(logits, axis=-1)[:, ::-1]
    order = jnp.argsort(logits, axis=-1)[:, ::-1]
    probs_sort = jax.nn.softmax(sorted_logits, axis=-1)
    # Exclusive cumsum: top-p keeps tokens whose *preceding* mass <= p.
    probs_cum = jnp.cumsum(probs_sort, axis=-1) - probs_sort

    top_probs = probs_sort[:, :1]
    threshold = jnp.maximum(top_probs * t.min_ps[:, None],
                            (top_probs ** 2) * t.top_as[:, None])
    mask = probs_sort < threshold
    mask |= probs_cum > t.top_ps[:, None]
    positions = jnp.arange(logits.shape[-1])[None, :]
    mask |= positions >= t.top_ks.astype(jnp.int32)[:, None]
    mask = mask.at[:, 0].set(False)     # always keep the argmax

    sorted_logits = jnp.where(mask, _NEG_INF, sorted_logits)
    # Undo the sort.
    inv = jnp.argsort(order, axis=-1)
    return jnp.take_along_axis(sorted_logits, inv, axis=-1)


def _apply_tfs(logits, t: SamplingTensors) -> jax.Array:
    """Tail-free sampling (reference `:282`): cull the low-curvature tail
    of the sorted prob distribution."""
    sorted_logits = jnp.sort(logits, axis=-1)[:, ::-1]
    order = jnp.argsort(logits, axis=-1)[:, ::-1]
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    d2 = jnp.abs(jnp.diff(jnp.diff(probs, axis=-1), axis=-1))
    d2_sum = jnp.sum(d2, axis=-1, keepdims=True)
    norm_d2 = jnp.where(d2_sum > 0, d2 / d2_sum, 0.0)
    cdf = jnp.cumsum(norm_d2, axis=-1)
    tail = cdf > t.tfss[:, None]
    rows = logits.shape[0]
    mask = jnp.concatenate([
        jnp.zeros((rows, 1), dtype=bool), tail,
        jnp.ones((rows, 1), dtype=bool)
    ], axis=-1)
    sorted_logits = jnp.where(mask, _NEG_INF, sorted_logits)
    inv = jnp.argsort(order, axis=-1)
    return jnp.take_along_axis(sorted_logits, inv, axis=-1)


def _entropy_cutoff_mask(probs, eps):
    """Shared guard: never mask the max-probability token."""
    top = jnp.max(probs, axis=-1, keepdims=True)
    return (probs < eps) & (probs < top)


def _apply_eta_cutoff(logits, t: SamplingTensors) -> jax.Array:
    eta = t.eta_cutoffs * 1e-4
    shifted = jax.nn.log_softmax(logits, axis=-1)
    probs = jnp.exp(shifted)
    neg_entropy = jnp.nansum(probs * shifted, axis=-1)
    eps = jnp.minimum(eta, jnp.sqrt(eta) * jnp.exp(neg_entropy))[:, None]
    return jnp.where(_entropy_cutoff_mask(probs, eps), _NEG_INF, logits)


def _apply_epsilon_cutoff(logits, t: SamplingTensors) -> jax.Array:
    probs = jax.nn.softmax(logits, axis=-1)
    eps = (t.epsilon_cutoffs * 1e-4)[:, None]
    return jnp.where(_entropy_cutoff_mask(probs, eps), _NEG_INF, logits)


def _apply_typical_sampling(logits, t: SamplingTensors) -> jax.Array:
    """Locally-typical sampling (reference `:354`): keep tokens whose
    surprisal is closest to the distribution entropy, up to mass
    typical_p."""
    shifted = jax.nn.log_softmax(logits, axis=-1)
    probs = jnp.exp(shifted)
    neg_entropy = jnp.nansum(probs * shifted, axis=-1, keepdims=True)
    deviations = jnp.abs(neg_entropy - shifted)
    order = jnp.argsort(deviations, axis=-1)
    reordered = jnp.take_along_axis(probs, order, axis=-1)
    mask_sorted = jnp.cumsum(reordered, axis=-1) >= t.typical_ps[:, None]
    mask_sorted = mask_sorted.at[:, 0].set(False)
    rows = jnp.arange(logits.shape[0])[:, None]
    mask = jnp.zeros_like(mask_sorted).at[rows, order].set(mask_sorted)
    return jnp.where(mask, _NEG_INF, logits)


def _apply_token_bans(logits, t: SamplingTensors) -> jax.Array:
    """custom_token_bans -> -inf (reference `:230`); pad id (vocab) is
    scatter-dropped."""
    rows = jnp.arange(logits.shape[0])[:, None]
    return logits.at[rows, t.banned_tokens].set(_NEG_INF, mode="drop")


def _apply_quadratic(logits, t: SamplingTensors) -> jax.Array:
    max_logits = jnp.max(logits, axis=-1, keepdims=True)
    transformed = -(t.smoothing_factors[:, None] *
                    (logits - max_logits) ** 2) + max_logits
    # factor==0 must be a no-op: the formula would flatten the whole row
    # to max_logits (every co-batched request corrupted).
    return jnp.where(t.smoothing_factors[:, None] > 0, transformed, logits)


def _apply_mirostat_v2(logits, t: SamplingTensors,
                       keys) -> Tuple[jax.Array, jax.Array]:
    """Mirostat v2 (reference `:754-805`): mask tokens above the surprise
    target mu, sample, and one-hot the logits; returns updated mus.
    Rows without mirostat (tau == 0 gate handled by caller's where)."""
    surprise = -jnp.log2(jax.nn.softmax(logits, axis=-1))
    mask = surprise > t.miro_mus[:, None]
    min_idx = jnp.argmin(surprise, axis=-1)
    rows = jnp.arange(logits.shape[0])
    mask = mask.at[rows, min_idx].set(False)
    masked = jnp.where(mask, _NEG_INF, logits)

    sampled = jax.vmap(
        lambda k, lg: jax.random.categorical(k, lg))(keys, masked)
    picked = surprise[rows, sampled]
    new_mus = t.miro_mus - t.miro_etas * (picked - t.miro_taus)

    onehot = jnp.full_like(logits, _NEG_INF).at[rows, sampled].set(1.0)
    return onehot, new_mus


# ----------------------------------------------------------- jitted core --

@jax.jit
def _process_logits(logits: jax.Array, t: SamplingTensors,
                    miro_keys: jax.Array
                    ) -> Tuple[jax.Array, jax.Array]:
    """Run the pipeline in reference order (`sampler.py:84-122`);
    static do_* flags prune stages at trace time."""
    logits = logits.astype(jnp.float32)
    if t.do_penalties:
        logits = _apply_penalties(logits, t)
    if t.do_temperatures:
        logits = _apply_temperatures(logits, t)
    if t.do_top_p_top_k or t.do_top_as or t.do_min_p:
        logits = _apply_alphabet_soup(logits, t)
    if t.do_tfss:
        logits = _apply_tfs(logits, t)
    if t.do_eta_cutoffs:
        logits = _apply_eta_cutoff(logits, t)
    if t.do_epsilon_cutoffs:
        logits = _apply_epsilon_cutoff(logits, t)
    if t.do_typical_ps:
        logits = _apply_typical_sampling(logits, t)
    if t.do_quadratic:
        logits = _apply_quadratic(logits, t)
    if t.do_token_bans:
        logits = _apply_token_bans(logits, t)

    new_mus = t.miro_mus
    if t.do_mirostat:
        miro_logits, new_mus_all = _apply_mirostat_v2(logits, t, miro_keys)
        is_miro = t.miro_taus > 0
        logits = jnp.where(is_miro[:, None], miro_logits, logits)
        new_mus = jnp.where(is_miro, new_mus_all, t.miro_mus)
    return logits, new_mus


@functools.partial(jax.jit,
                   static_argnames=("max_best_of", "num_topk"))
def _sample_tokens(logits: jax.Array, keys: jax.Array, max_best_of: int,
                   num_topk: int):
    """Device-side token selection + small result tensors.

    Returns (greedy [rows], multinomial [rows, max_best_of], lp_greedy
    [rows], lp_random [rows, max_best_of], topk_vals/topk_idx
    [rows, num_topk], logprobs [rows, vocab]). Only the small tensors are
    pulled to the host; the full logprobs stay on device and are sliced
    per-row for the rare beam/prompt-logprobs paths (the reference
    transfers top-k only as well, sampler.py:607-650).
    """
    greedy = jnp.argmax(logits, axis=-1)
    draw = jax.vmap(
        lambda k, lg: jax.random.categorical(k, lg, shape=(max_best_of,)))
    random = draw(keys, logits)
    logprobs = jax.nn.log_softmax(logits, axis=-1)
    rows = jnp.arange(logits.shape[0])
    lp_greedy = logprobs[rows, greedy]
    lp_random = jnp.take_along_axis(logprobs, random, axis=-1)
    if num_topk > 0:
        topk_vals, topk_idx = jax.lax.top_k(logprobs, num_topk)
    else:
        topk_vals = jnp.zeros((logits.shape[0], 0), logprobs.dtype)
        topk_idx = jnp.zeros((logits.shape[0], 0), jnp.int32)
    return greedy, random, lp_greedy, lp_random, topk_vals, topk_idx, \
        logprobs


@jax.jit
def _make_row_keys(bases: jax.Array, salt1: jax.Array,
                   salt2: jax.Array) -> jax.Array:
    """Vectorized per-row PRNG keys: one dispatch for the whole batch."""
    make = jax.vmap(
        lambda b, s1, s2: jax.random.fold_in(
            jax.random.fold_in(jax.random.PRNGKey(b), s1), s2))
    return make(bases, salt1, salt2)


def fused_sample(logits: jax.Array, t: SamplingTensors, key_parts: jax.Array,
                 *, max_best_of: int, num_topk: int, need_logprobs: bool):
    """The whole device-side sampling step — key building, the logits
    pipeline, and token selection — packed into ONE int32 result array so
    the host needs exactly one blocking transfer per engine step (the
    dominant cost on a high-latency device link; floats ride along
    bitcast to int32). Columns:

      [0]                greedy token
      [1 : 1+B]          multinomial draws (B = max_best_of)
      [1+B : 1+B+K]      top-k logprob token ids (K = num_topk)
      [W : W+1]          lp(greedy)      } float32 bitcast
      [W+1 : W+1+B]      lp(draws)       }
      [W+1+B : W+1+B+K]  top-k logprob values }
      [-1]               updated mirostat mu  }

    with W = 1+B+K. `key_parts` [rows, 3] holds each row's (base, salt1,
    salt2). Full [rows, vocab] logprobs are returned only when
    `need_logprobs` (beam search / prompt_logprobs), and stay on device.
    Callable inside an outer jit or via `_fused_sample_jit`.
    """
    keys = _make_row_keys(key_parts[:, 0], key_parts[:, 1], key_parts[:, 2])
    processed, new_mus = _process_logits(logits, t, keys)
    greedy, random, lp_greedy, lp_random, topk_vals, topk_idx, logprobs = \
        _sample_tokens(processed, keys, max_best_of, num_topk)
    ints = jnp.concatenate([
        greedy[:, None].astype(jnp.int32),
        random.astype(jnp.int32),
        topk_idx.astype(jnp.int32),
    ], axis=1)
    floats = jnp.concatenate([
        lp_greedy[:, None], lp_random, topk_vals, new_mus[:, None]
    ], axis=1).astype(jnp.float32)
    packed = jnp.concatenate(
        [ints, jax.lax.bitcast_convert_type(floats, jnp.int32)], axis=1)
    return packed, (logprobs if need_logprobs else None)


_fused_sample_jit = jax.jit(
    fused_sample,
    static_argnames=("max_best_of", "num_topk", "need_logprobs"))


# ------------------------------------------------------------- host side --

class SamplePlan:
    """Host-side bookkeeping for one sampling step, shared between the
    device dispatch (`fused_sample` args: `tensors` and `key_parts`, on
    the device already) and `finalize`. `reused` says that the step
    built and sent nothing."""

    __slots__ = ("tensors", "key_parts", "max_best_of", "num_topk",
                 "need_logprobs", "num_rows", "miro_rows", "reused")

    def __init__(self, tensors, key_parts, max_best_of, num_topk,
                 need_logprobs, num_rows, miro_rows, reused):
        self.tensors = tensors
        self.key_parts = key_parts
        self.max_best_of = max_best_of
        self.num_topk = num_topk
        self.need_logprobs = need_logprobs
        self.num_rows = num_rows
        self.miro_rows = miro_rows
        self.reused = reused


class _RowsPlan:
    """What `Sampler.plan` keeps of the last step: all of a plan that
    is a function of the step's rows alone, on the host and on the
    device. `signature` says which rows; `groups` keeps their
    `SamplingParams` alive, so that an identity in the signature stays
    that object's."""

    __slots__ = ("signature", "groups", "knobs", "mask", "row_info",
                 "tensors", "key_parts", "draws", "seeded", "miro_rows",
                 "max_best_of", "num_topk", "need_logprobs")


class Sampler:
    """Host orchestrator: tensorize knobs, run the jitted pipeline, and
    assemble per-group outputs (greedy/random/beam) like the reference
    `_sample` + `_get_logprobs` (`sampler.py:545-650`). `put` is the
    host-to-device transfer (the model runner's, which commits to its
    mesh)."""

    def __init__(self, vocab_size: int, put=jnp.asarray) -> None:
        if vocab_size >= 1 << 24:
            raise ValueError("top_k rides in a float32 knob column, exact "
                             f"below 2**24; vocabulary {vocab_size}")
        self.vocab_size = vocab_size
        self._put = put
        self._step = 0
        self._last: Optional[_RowsPlan] = None
        # The key parts of a batch in which no row draws, by row count.
        self._zero_keys: Dict[int, jax.Array] = {}
        # Process entropy so unseeded sampling differs across restarts
        # (seeded requests are unaffected: their keys derive from the
        # request seed only).
        import os as _os
        self._base_seed = int.from_bytes(_os.urandom(4), "little") \
            & 0x7FFFFFFF

    def __call__(self, logits: jax.Array,
                 metadata: SamplingMetadata) -> SamplerOutput:
        assert logits.ndim == 2
        logits = self._apply_logits_processors(logits, metadata)
        plan = self.plan(metadata)
        packed, logprobs = _fused_sample_jit(
            logits, plan.tensors, plan.key_parts,
            max_best_of=plan.max_best_of, num_topk=plan.num_topk,
            need_logprobs=plan.need_logprobs)
        return self.finalize(metadata, plan, np.asarray(packed), logprobs)

    def plan(self, metadata: SamplingMetadata,
             pad_to: Optional[int] = None,
             salt_offsets: Optional[np.ndarray] = None) -> SamplePlan:
        """Build the step plan: the knob tensors (padded to the
        program's row bucket) and PRNG key parts on the device, and the
        static shapes. What the rows alone decide is kept from the step
        before while the rows are the same; see the module docstring
        for what is sent when. `salt_offsets` [n] is added to the
        output-position salt of the first n rows (speculative verify:
        row j of a sequence samples for position output_len + j)."""
        self._step += 1
        signature = (pad_to, tuple(metadata.prompt_lens), tuple(
            (tuple(seq_ids), id(p)) for seq_ids, p in metadata.seq_groups))
        last = self._last
        same_rows = last is not None and last.signature == signature
        if not same_rows:
            last = self._last = self._rows_plan(metadata, pad_to,
                                                signature)
        tensors, key_parts = last.tensors, last.key_parts
        if last.miro_rows:
            knobs = last.knobs.copy()
            for row, seq_id, first_mu in last.miro_rows:
                knobs[row, MU_COLUMN] = metadata.persistent_metadata.get(
                    seq_id).get("miro_mu", first_mu)
            tensors = tensors.replace(knobs=self._put(knobs))
        lists = build_token_lists(metadata, self.vocab_size, last.mask,
                                  len(last.knobs), last.row_info)
        if lists:
            tensors = tensors.replace(
                **{name: self._put(arr) for name, arr in lists.items()})
        if last.draws:
            key_parts = self._put(
                self._key_parts(metadata, last, salt_offsets))
        reused = same_rows and tensors is last.tensors and \
            key_parts is last.key_parts
        return SamplePlan(tensors, key_parts, last.max_best_of,
                          last.num_topk, last.need_logprobs,
                          len(last.row_info), last.miro_rows, reused)

    def _rows_plan(self, metadata: SamplingMetadata,
                   pad_to: Optional[int], signature: tuple) -> _RowsPlan:
        """The part of a plan that the rows decide, built from the
        cached knob rows of their `SamplingParams`; the knobs go to the
        device here unless a mirostat row makes them the step's."""
        last = _RowsPlan()
        last.signature, last.groups = signature, metadata.seq_groups
        last.knobs, last.mask, last.row_info = build_knobs(
            metadata, self.vocab_size, pad_to)
        rows = len(last.knobs)
        params = [p for _, p in metadata.seq_groups]
        # tau/eta/mu are per row; a first step starts mu at 2 tau.
        last.miro_rows = [
            (row, seq_id, 2.0 * p.mirostat_tau)
            for row, (seq_id, p, _) in enumerate(last.row_info)
            if p.mirostat_mode == 2]
        # A greedy or beam row's draw is discarded by `_assemble`; a
        # mirostat row samples inside the pipeline whatever its type.
        last.draws = bool(last.miro_rows) or any(
            p.sampling_type == SamplingType.RANDOM for p in params)
        seeded = [(row, seq_id, p.seed, sibling)
                  for row, (seq_id, p, sibling) in enumerate(last.row_info)
                  if p.seed is not None]
        last.seeded = None
        if seeded and last.draws:
            at, seq_ids, seeds, siblings = zip(*seeded)
            # wrapped to 32 bits, as a 64-bit seed always reached the
            # device
            last.seeded = (np.asarray(at), seq_ids, np.asarray(
                seeds, dtype=np.int64).astype(np.int32), siblings)
        last.tensors = SamplingTensors(
            knobs=None if last.miro_rows else self._put(last.knobs),
            **gates_of(last.mask))
        last.key_parts = None
        if not last.draws:
            if rows not in self._zero_keys:
                self._zero_keys[rows] = self._put(
                    np.zeros((rows, 3), dtype=np.int32))
            last.key_parts = self._zero_keys[rows]
        last.max_best_of = max([1] + [
            p.best_of for p in params
            if p.sampling_type == SamplingType.RANDOM
        ])
        last.num_topk = max([0] + [
            min(p.logprobs or 0, self.vocab_size - 1) for p in params
        ] + [
            min(p.prompt_logprobs or 0, self.vocab_size - 1)
            for p in params
        ])
        last.need_logprobs = any(
            p.sampling_type == SamplingType.BEAM or
            (p.prompt_logprobs is not None and metadata.prompt_lens)
            for p in params)
        return last

    def finalize(self, metadata: SamplingMetadata, plan: SamplePlan,
                 packed: np.ndarray,
                 logprobs_dev: Optional[jax.Array]) -> SamplerOutput:
        """Unpack the single transferred result array and assemble
        per-group outputs; device logprobs are touched only by the rare
        beam / prompt-logprobs paths."""
        B, K = plan.max_best_of, plan.num_topk
        w_int = 1 + B + K
        packed = packed[:plan.num_rows]
        ints = packed[:, :w_int]
        floats = packed[:, w_int:].view(np.float32)
        greedy = ints[:, 0]
        random = ints[:, 1:1 + B]
        topk_idx = ints[:, 1 + B:w_int]
        lp_greedy = floats[:, 0]
        lp_random = floats[:, 1:1 + B]
        topk_vals = floats[:, 1 + B:1 + B + K]
        if plan.miro_rows:
            new_mus = floats[:, 1 + B + K]
            for row, seq_id, _ in plan.miro_rows:
                metadata.output_metadata.add(seq_id, "miro_mu",
                                             float(new_mus[row]))
        return self._assemble(metadata, greedy, random, lp_greedy,
                              lp_random, topk_vals, topk_idx, logprobs_dev)

    # -- helpers --

    def _key_parts(self, metadata: SamplingMetadata, last: _RowsPlan,
                   salt_offsets: Optional[np.ndarray]) -> np.ndarray:
        """Per-row PRNG key ingredients [rows, 3] (folded together on
        device by `_make_row_keys`).

        Seeded rows: base=request seed, salts=(output_len, sibling index)
        — reproducible regardless of batch composition or restarts.
        Unseeded rows: base mixes process entropy, step, and row so that
        the per-step salt1 offset added by decode bursts (+t) never
        collides across (row, step) diagonals.
        """
        rows = len(last.knobs)
        step_mix = (self._base_seed ^ (self._step * 0x9E3779B1)) \
            & 0x7FFFFFFF
        parts = np.zeros((rows, 3), dtype=np.int32)
        parts[:, 0] = (step_mix ^ (np.arange(rows, dtype=np.int64) *
                                   0x85EBCA77)) & 0x7FFFFFFF
        if last.seeded is not None:
            at, seq_ids, seeds, siblings = last.seeded
            parts[at, 0] = seeds
            # The output position sampled for: a token still on the
            # device (`SequenceData.in_flight`) counts.
            parts[at, 1] = [len(metadata.seq_data[s].output_token_ids) +
                            metadata.seq_data[s].in_flight
                            for s in seq_ids]
            parts[at, 2] = siblings
        if salt_offsets is not None:
            parts[:len(salt_offsets), 1] += salt_offsets
        return parts

    def _apply_logits_processors(self, logits, metadata):
        """Host-side per-request callables (logit_bias, grammar, min-tokens
        EOS ban; reference `sampler.py:180-204`)."""
        has_any = any(p.logits_processors
                      for _, p in metadata.seq_groups)
        if not has_any:
            return logits
        arr = np.array(logits, dtype=np.float32)  # writable copy
        offset = 0
        for i, (seq_ids, params) in enumerate(metadata.seq_groups):
            # Prompt-logprob rows are never processed (reference
            # `_apply_logits_processors` advances past them).
            if i < len(metadata.prompt_lens) and \
                    params.prompt_logprobs is not None:
                offset += metadata.prompt_lens[i] - 1
            if params.logits_processors:
                for j, sid in enumerate(seq_ids):
                    toks = metadata.seq_data[sid].output_token_ids
                    row = arr[offset + j]
                    for proc in params.logits_processors:
                        row = proc(toks, row)
                    arr[offset + j] = row
            offset += len(seq_ids)
        return jnp.asarray(arr)

    def _assemble(self, metadata: SamplingMetadata, greedy: np.ndarray,
                  random: np.ndarray, lp_greedy: np.ndarray,
                  lp_random: np.ndarray, topk_vals: np.ndarray,
                  topk_idx: np.ndarray,
                  logprobs_dev: jax.Array) -> SamplerOutput:
        """Per-group output assembly. Fast paths (greedy/random) touch
        only the small host tensors; beam and prompt-logprobs groups
        transfer just their own logprob rows from device."""
        outputs: List[SequenceGroupOutput] = []
        row = 0
        # plain numbers once a step, not a numpy scalar a row
        greedy, lp_greedy = greedy.tolist(), lp_greedy.tolist()
        for group_idx, (seq_ids, params) in enumerate(metadata.seq_groups):
            is_prompt = group_idx < len(metadata.prompt_lens)

            # Prompt-logprobs rows (one per prompt position before last).
            group_prompt_logprobs = None
            if is_prompt and params.prompt_logprobs is not None:
                n = metadata.prompt_lens[group_idx] - 1
                ctx = metadata.prompt_offsets[group_idx] \
                    if metadata.prompt_offsets else 0
                group_prompt_logprobs = [None] if ctx == 0 else []
                prompt_token_ids = \
                    metadata.seq_data[seq_ids[0]].prompt_token_ids
                rows_np = np.asarray(logprobs_dev[row:row + n])
                for j in range(n):
                    tok = prompt_token_ids[ctx + j + 1]
                    group_prompt_logprobs.append(
                        self._full_top_logprobs(rows_np[j],
                                                params.prompt_logprobs,
                                                tok))
                row += n

            samples: List[SequenceOutput] = []
            if params.sampling_type == SamplingType.GREEDY:
                token = greedy[row]
                lp = self._topk_logprobs(topk_vals, topk_idx, row, params,
                                         token, lp_greedy[row])
                samples.append(SequenceOutput(
                    seq_ids[0], token, lp,
                    metadata.output_metadata.get(seq_ids[0])))
            elif params.sampling_type == SamplingType.BEAM:
                samples = self._beam_sample(metadata, seq_ids, params,
                                            logprobs_dev, row, is_prompt)
            else:
                if is_prompt:
                    for i in range(params.best_of):
                        token = int(random[row, i])
                        lp = self._topk_logprobs(
                            topk_vals, topk_idx, row, params, token,
                            float(lp_random[row, i]))
                        samples.append(SequenceOutput(
                            seq_ids[0], token, lp,
                            metadata.output_metadata.get(seq_ids[0])))
                else:
                    for offset, seq_id in enumerate(seq_ids):
                        token = int(random[row + offset, 0])
                        lp = self._topk_logprobs(
                            topk_vals, topk_idx, row + offset, params,
                            token, float(lp_random[row + offset, 0]))
                        samples.append(SequenceOutput(
                            seq_id, token, lp,
                            metadata.output_metadata.get(seq_id)))
            row += len(seq_ids)
            outputs.append(SequenceGroupOutput(samples,
                                               group_prompt_logprobs))
        return outputs

    def _beam_sample(self, metadata, seq_ids, params, logprobs_dev, row,
                     is_prompt) -> List[SequenceOutput]:
        """Beam search select (reference `_beam_search_sample`,
        `sampler.py:462-527`): 2*best_of candidates. Transfers only this
        group's logprob rows."""
        beam_width = params.best_of
        out_meta = metadata.output_metadata

        def mk(seq_id, token, row_np):
            lp = self._full_top_logprobs(row_np, params.logprobs, token)
            return SequenceOutput(seq_id, token, lp, out_meta.get(seq_id))

        if is_prompt:
            lp = np.asarray(logprobs_dev[row])
            top_idx = np.argpartition(-lp, 2 * beam_width)[:2 * beam_width]
            top_idx = top_idx[np.argsort(-lp[top_idx])]
            return [mk(seq_ids[0], int(tok), lp) for tok in top_idx]

        seq_lp = np.asarray(logprobs_dev[row:row + len(seq_ids)])
        cum = np.asarray([
            metadata.seq_data[sid].cumulative_logprob for sid in seq_ids
        ])
        flat = (seq_lp + cum[:, None]).reshape(-1)
        top_idx = np.argpartition(-flat, 2 * beam_width)[:2 * beam_width]
        top_idx = top_idx[np.argsort(-flat[top_idx])]
        vocab = seq_lp.shape[-1]
        return [
            mk(seq_ids[int(i) // vocab], int(i) % vocab,
               seq_lp[int(i) // vocab]) for i in top_idx
        ]

    @staticmethod
    def _topk_logprobs(topk_vals: np.ndarray, topk_idx: np.ndarray,
                       row: int, params, sampled_token: int,
                       sampled_lp: float) -> Dict[int, float]:
        """Top-n logprobs dict from the device-side top-k, always
        including the sampled token (reference `_get_logprobs`)."""
        result = {sampled_token: sampled_lp}
        n = params.logprobs or 0
        for k in range(min(n, topk_idx.shape[-1])):
            result[int(topk_idx[row, k])] = float(topk_vals[row, k])
        return result

    @staticmethod
    def _full_top_logprobs(row: np.ndarray, num_logprobs: Optional[int],
                           sampled_token: int) -> Dict[int, float]:
        """Top-n over a full host row (beam / prompt-logprobs paths)."""
        result = {sampled_token: float(row[sampled_token])}
        if num_logprobs:
            num_logprobs = min(num_logprobs, row.shape[-1] - 1)
            top_idx = np.argpartition(-row, num_logprobs)[:num_logprobs]
            for tok in top_idx:
                result[int(tok)] = float(row[tok])
        return result
