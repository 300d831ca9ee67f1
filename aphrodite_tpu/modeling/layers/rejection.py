"""Modified rejection sampling for speculative decoding.

Reference: `aphrodite/modeling/layers/rejection.py:9-352` (torch
implementation of "Accelerating Large Language Model Decoding with
Speculative Sampling", arXiv:2302.01318). TPU-native rewrite: a pure
jittable function over [batch, k, vocab] probability tensors — no
module state, no device bookkeeping; acceptance, recovered-distribution
sampling, and the after-first-rejection masking are all dense vector
ops. The engine's self-drafting path (processing/drafter.py +
ModelRunner.finalize_step) uses the DELTA-PROPOSAL
specialization below: an n-gram drafter is a point-mass proposal
q = one-hot(draft), for which the general accept/recover machinery
collapses to `target-sample == draft` (`delta_rejection_length`) —
provably the same emitted distribution, and bit-equal to classic
decode for greedy and seeded sampling. The general tensor form stays
for model-drafted proposals; the statistical test
(tests/samplers/test_rejection.py) pins the output distribution to
the target model's.
"""
from __future__ import annotations

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp


def delta_rejection_length(sampled: Sequence[int],
                           drafted: Sequence[int]) -> int:
    """Accepted-prefix length for a POINT-MASS draft distribution.

    With q = one-hot(d_j), the acceptance test of
    `rejection_sample` — u * q(d_j) < p(d_j), i.e. accept d_j with
    probability p(d_j) — and its recovered distribution
    norm(max(0, p - q)) = p restricted to tokens != d_j are together
    equivalent to: sample s_j ~ p and accept iff s_j == d_j
    (P[emit d] = p(d); P[emit x != d] = (1 - p(d)) * p(x)/(1 - p(d))
    = p(x)). The verify step therefore samples every row from the
    TARGET with the row's own positional PRNG salt and this helper
    computes the accepted prefix host-side; emitted tokens are the
    accepted drafts plus the first-mismatch target sample (or the
    bonus sample on full acceptance) — bit-equal to classic decode
    for greedy and seeded rows by construction."""
    n = 0
    for s, d in zip(sampled, drafted):
        if int(s) != int(d):
            break
        n += 1
    return n


def _categorical(key: jax.Array, probs: jax.Array) -> jax.Array:
    """Sample from the trailing-axis distribution via the Gumbel trick
    (probs may contain zeros; log is masked)."""
    logits = jnp.log(jnp.maximum(probs, 1e-38))
    gumbel = jax.random.gumbel(key, probs.shape, dtype=jnp.float32)
    return jnp.argmax(logits + gumbel, axis=-1)


def rejection_sample(
    key: jax.Array,
    target_probs: jax.Array,      # [batch, k, vocab] f32
    bonus_token_ids: jax.Array,   # [batch] int32
    draft_probs: jax.Array,       # [batch, k, vocab] f32
    draft_token_ids: jax.Array,   # [batch, k] int32
) -> Tuple[jax.Array, jax.Array]:
    """Accept/reject k speculative tokens per sequence.

    Returns (output_token_ids [batch, k+1], num_accepted [batch]).
    Position j emits: the draft token while all previous drafts were
    accepted; the token re-sampled from the RECOVERED distribution
    norm(max(0, p_target - p_draft)) at the first rejection; -1 after
    it. If every draft is accepted, the bonus token fills slot k
    (reference forward `:42-102`, _get_accepted `:133`,
    _get_recovered_probs `:179`)."""
    batch, k, vocab = target_probs.shape
    key_u, key_r = jax.random.split(key)

    # Acceptance: u < p_target(tok) / p_draft(tok).
    p_t = jnp.take_along_axis(target_probs,
                              draft_token_ids[..., None], axis=-1)[..., 0]
    p_d = jnp.take_along_axis(draft_probs,
                              draft_token_ids[..., None], axis=-1)[..., 0]
    u = jax.random.uniform(key_u, (batch, k), dtype=jnp.float32)
    accepted = u * jnp.maximum(p_d, 1e-38) < p_t      # [batch, k]

    # Recovered distribution at each position (used only at the first
    # rejection): norm(max(0, p_t - p_d)).
    diff = jnp.maximum(target_probs - draft_probs, 0.0)
    denom = jnp.sum(diff, axis=-1, keepdims=True)
    # All-zero diff (distributions identical): fall back to the target.
    recovered = jnp.where(denom > 0, diff / jnp.maximum(denom, 1e-38),
                          target_probs)
    recovered_ids = _categorical(key_r, recovered)    # [batch, k]

    # Prefix-accept logic: position j is a kept draft iff all drafts
    # <= j accepted; the first rejection emits the recovered token.
    all_prev = jnp.cumprod(accepted.astype(jnp.int32), axis=-1)  # [b,k]
    num_accepted = jnp.sum(all_prev, axis=-1)                    # [b]
    idx = jnp.arange(k)[None, :]
    keep_draft = idx < num_accepted[:, None]
    is_first_reject = idx == num_accepted[:, None]
    tokens_k = jnp.where(
        keep_draft, draft_token_ids,
        jnp.where(is_first_reject, recovered_ids, -1)).astype(jnp.int32)

    # Slot k: bonus token iff everything accepted.
    bonus = jnp.where(num_accepted == k, bonus_token_ids,
                      -1).astype(jnp.int32)
    out = jnp.concatenate([tokens_k, bonus[:, None]], axis=1)
    return out, num_accepted
