"""Per-batch sampling bookkeeping and the packed sampler knobs.

Reference: `aphrodite/modeling/sampling_metadata.py` (SamplingMetadata
`:30`, SamplingTensors.from_sampling_metadata `:108`, Persistent/Output
metadata `:13-28`).

The host builds `SamplingMetadata` (Python lists, ragged). What the
jitted sampler consumes is `SamplingTensors`: ONE `[rows, 19]` float32
array of scalar knobs (a column per name of `KNOB_COLUMNS`, rows padded
to the program's row bucket with `_NEUTRAL_ROW`) and the static `do_*`
gates, each of which removes a whole pipeline stage at trace time when
no sequence of the batch uses it. A row of knobs and its gates are a
function of one `SamplingParams` and the vocabulary size: `knob_row`
computes them once per object and keeps them on it, so a step's knobs
are a stack of cached rows (`build_knobs`). Token histories and bans
are built (`build_token_lists`) only for a batch whose gate reads them;
otherwise they are `None`, not arguments of the program at all.
`Sampler.plan` (layers/sampler.py) sends the arrays and keeps the
device copy while the batch does not change.

Mirostat state (`mu`) persists across steps host-side in
`PersistentMetadata`, round-tripping through `OutputMetadata` exactly as
the reference (`sampling_metadata.py:13-28`).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax
from flax import struct

from aphrodite_tpu.common.sampling_params import SamplingParams
from aphrodite_tpu.common.sequence import SequenceData

_SAMPLING_EPS = 1e-5


class PersistentMetadata:
    """Per-seq state that survives across steps (mirostat mu)."""

    def __init__(self, data: Optional[Dict[int, dict]] = None) -> None:
        self._metadata: Dict[int, dict] = data or {}

    def get(self, seq_id: int) -> dict:
        return self._metadata.get(seq_id, {})


class OutputMetadata(PersistentMetadata):
    """Mutable variant the sampler writes back into."""

    def add(self, seq_id: int, key: str, val) -> None:
        self._metadata.setdefault(seq_id, {})[key] = val


@dataclass
class SamplingMetadata:
    """Ragged per-group sampling info for one step.

    seq_groups: per scheduled group, (seq_ids, sampling_params).
    seq_data: seq id -> SequenceData (for penalties' token histories).
    prompt_lens: per prompt group, the prompt length (empty for decode).
    """
    seq_groups: List[Tuple[List[int], SamplingParams]]
    seq_data: Dict[int, SequenceData]
    prompt_lens: List[int]
    persistent_metadata: PersistentMetadata = field(
        default_factory=PersistentMetadata)
    output_metadata: OutputMetadata = field(default_factory=OutputMetadata)
    # Per prompt group: tokens already in cache before this chunk (prefix
    # caching / chunked prefill). Aligns prompt-logprobs attribution.
    prompt_offsets: List[int] = field(default_factory=list)


#: The columns of `SamplingTensors.knobs`, in order. `top_ks` rides as
#: exact small integers: float32 holds every integer up to 2**24, far
#: past any vocabulary (`Sampler` refuses a larger one).
KNOB_COLUMNS = (
    "temperatures", "dynatemp_mins", "dynatemp_maxs", "dynatemp_exps",
    "top_ps", "top_ks", "top_as", "min_ps", "tfss", "eta_cutoffs",
    "epsilon_cutoffs", "typical_ps", "miro_taus", "miro_etas", "miro_mus",
    "smoothing_factors", "presence_penalties", "frequency_penalties",
    "repetition_penalties")
MU_COLUMN = KNOB_COLUMNS.index("miro_mus")
#: The static gates; bit i of a gate mask is GATES[i].
GATES = ("do_penalties", "do_temperatures", "do_top_p_top_k", "do_top_as",
         "do_min_p", "do_tfss", "do_eta_cutoffs", "do_epsilon_cutoffs",
         "do_typical_ps", "do_quadratic", "do_mirostat", "do_token_bans")


@struct.dataclass
class SamplingTensors:
    """Fixed-shape device-side sampler knobs, one row per sampled token.

    `knobs` is [rows, len(KNOB_COLUMNS)]; each column reads as an
    attribute of its name (`t.top_ps`: a slice inside the program). The
    token-history tensors are [rows, k], padded with vocab_size (an
    out-of-range id scatter-dropped by the stage that reads them), and
    None unless their gate is on.
    """
    knobs: jax.Array
    prompt_tokens: Optional[jax.Array] = None     # do_penalties
    output_tokens: Optional[jax.Array] = None     # do_penalties
    banned_tokens: Optional[jax.Array] = None     # do_token_bans
    # Static gates (trace-time):
    do_penalties: bool = struct.field(pytree_node=False, default=False)
    do_temperatures: bool = struct.field(pytree_node=False, default=False)
    do_top_p_top_k: bool = struct.field(pytree_node=False, default=False)
    do_top_as: bool = struct.field(pytree_node=False, default=False)
    do_min_p: bool = struct.field(pytree_node=False, default=False)
    do_tfss: bool = struct.field(pytree_node=False, default=False)
    do_eta_cutoffs: bool = struct.field(pytree_node=False, default=False)
    do_epsilon_cutoffs: bool = struct.field(pytree_node=False,
                                            default=False)
    do_typical_ps: bool = struct.field(pytree_node=False, default=False)
    do_quadratic: bool = struct.field(pytree_node=False, default=False)
    do_mirostat: bool = struct.field(pytree_node=False, default=False)
    do_token_bans: bool = struct.field(pytree_node=False, default=False)


for _i, _name in enumerate(KNOB_COLUMNS):
    setattr(SamplingTensors, _name,
            property(lambda self, _i=_i: self.knobs[:, _i]))


def gates_of(mask: int) -> Dict[str, bool]:
    return {name: bool(mask >> i & 1) for i, name in enumerate(GATES)}


def knob_row(p: SamplingParams, vocab_size: int) -> Tuple[np.ndarray, int]:
    """The knobs of one request as the device sees them, and the gates
    it turns on: computed once per `SamplingParams` object and kept on
    it (`clone()` drops the copy; the knob fields of a request are not
    written after it is made)."""
    cached = p.__dict__.get("_knob_row")
    if cached is not None and cached[0] == vocab_size:
        return cached[1], cached[2]
    temperature = p.temperature
    if temperature < _SAMPLING_EPS:
        temperature = 1.0      # zero temp == greedy: no-op scaling
    # tau/eta are zeroed unless mode==2 so the device row gate (tau > 0)
    # agrees with the host mu write-back gate; mu is the step's.
    is_miro = p.mirostat_mode == 2
    row = np.array([
        temperature, max(temperature - p.dynatemp_range, 0.0),
        temperature + p.dynatemp_range, p.dynatemp_exponent, p.top_p,
        vocab_size if p.top_k == -1 else min(p.top_k, vocab_size),
        p.top_a, p.min_p, p.tfs, p.eta_cutoff, p.epsilon_cutoff,
        p.typical_p, p.mirostat_tau if is_miro else 0.0,
        p.mirostat_eta if is_miro else 0.0, 0.0, p.smoothing_factor,
        p.presence_penalty, p.frequency_penalty, p.repetition_penalty,
    ], dtype=np.float32)
    on = dict(
        do_penalties=p.has_penalties,
        do_temperatures=p.dynatemp_range > 0 or (
            p.temperature >= _SAMPLING_EPS and p.temperature != 1.0),
        do_top_p_top_k=p.top_p < 1.0 - _SAMPLING_EPS or
        p.top_k not in (-1, vocab_size),
        do_top_as=p.top_a > 0.0,
        do_min_p=p.min_p > _SAMPLING_EPS,
        do_tfss=p.tfs < 1.0 - _SAMPLING_EPS,
        do_eta_cutoffs=p.eta_cutoff > _SAMPLING_EPS,
        do_epsilon_cutoffs=p.epsilon_cutoff > _SAMPLING_EPS,
        do_typical_ps=p.typical_p < 1.0 - _SAMPLING_EPS,
        do_quadratic=p.smoothing_factor > _SAMPLING_EPS,
        do_mirostat=is_miro,
        do_token_bans=bool(p.custom_token_bans))
    mask = sum(1 << i for i, name in enumerate(GATES) if on[name])
    p.__dict__["_knob_row"] = (vocab_size, row, mask)
    return row, mask


@functools.lru_cache(maxsize=None)
def _neutral_row(vocab_size: int) -> np.ndarray:
    """A padding row: no stage changes its logits (its sampled result is
    sliced off host-side)."""
    neutral = dict.fromkeys(KNOB_COLUMNS, 0.0)
    neutral.update(temperatures=1.0, dynatemp_exps=1.0, top_ps=1.0,
                   top_ks=vocab_size, tfss=1.0, typical_ps=1.0,
                   repetition_penalties=1.0)
    return np.array([neutral[c] for c in KNOB_COLUMNS], dtype=np.float32)


def build_knobs(
    metadata: SamplingMetadata, vocab_size: int,
    pad_to: Optional[int] = None,
) -> Tuple[np.ndarray, int, List[Tuple[int, SamplingParams, int]]]:
    """Stack the cached knob rows of a step's sampled rows.

    Mirrors `SamplingTensors.from_sampling_metadata`
    (`sampling_metadata.py:108-261`) incl. the prompt-logprobs row
    expansion: when a prompt group requests prompt_logprobs, its row is
    replicated for every prompt position.

    Returns (knobs [max(rows, pad_to), K] with `miro_mus` zero, the
    batch's gate mask, and per real row its (sequence id,
    SamplingParams, index among its group's sequences)).
    """
    stack, row_info, mask = [], [], 0
    for group_idx, (seq_ids, p) in enumerate(metadata.seq_groups):
        row, gates = knob_row(p, vocab_size)
        mask |= gates
        if group_idx < len(metadata.prompt_lens) and \
                p.prompt_logprobs is not None:
            row_info.extend([(seq_ids[0], p, 0)] *
                            (metadata.prompt_lens[group_idx] - 1))
        row_info.extend((seq_id, p, sibling)
                        for sibling, seq_id in enumerate(seq_ids))
        stack.extend([row] * (len(row_info) - len(stack)))
    stack.extend([_neutral_row(vocab_size)] *
                 max(0, (pad_to or 0) - len(stack)))
    knobs = np.stack(stack) if stack else \
        np.zeros((0, len(KNOB_COLUMNS)), np.float32)
    return knobs, mask, row_info


def _pow2_width(lists: Sequence[Sequence[int]], lo: int) -> int:
    """Bucket the ragged width to a power of two so the compiled sampler
    program's shape is stable as histories grow step to step."""
    need = max((len(r) for r in lists), default=1)
    w = lo
    while w < need:
        w *= 2
    return w


def _pad_2d(lists: Sequence[Sequence[int]], pad_value: int, width: int,
            rows: int) -> np.ndarray:
    out = np.full((rows, width), pad_value, dtype=np.int32)
    for i, r in enumerate(lists):
        out[i, :len(r)] = r
    return out


def build_token_lists(
    metadata: SamplingMetadata, vocab_size: int, mask: int, rows: int,
    row_info: List[Tuple[int, SamplingParams, int]],
) -> Dict[str, np.ndarray]:
    """The token-history and ban arrays of a step, for the gates of
    `mask` that read them (nothing is read or built for a gate that is
    off)."""
    out = {}
    if mask & 1 << GATES.index("do_penalties"):
        data = [metadata.seq_data[s] for s, _, _ in row_info]
        prompts = [d.prompt_token_ids for d in data]
        outputs = [d.output_token_ids for d in data]
        width = _pow2_width(prompts + outputs, 32)
        out["prompt_tokens"] = _pad_2d(prompts, vocab_size, width, rows)
        out["output_tokens"] = _pad_2d(outputs, vocab_size, width, rows)
    if mask & 1 << GATES.index("do_token_bans"):
        bans = [p.custom_token_bans for _, p, _ in row_info]
        out["banned_tokens"] = _pad_2d(bans, vocab_size,
                                       _pow2_width(bans, 8), rows)
    return out
