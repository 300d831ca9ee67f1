"""Rotary position embeddings with long-context scaling.

Reference semantics: `aphrodite/modeling/layers/rotary_embedding.py`
(RotaryEmbedding `:49`, linear scaling `:151`, dynamic-NTK `:187`, YaRN
`:268`, `get_rope` factory `:330`), CUDA kernel
`kernels/pos_encoding_kernels.cu`. TPU-first: the cos/sin cache is a jnp
array gathered by position ids inside the jitted step — a fused kernel buys
nothing here because XLA fuses the gather+mul+add chain into the
surrounding matmuls.

Both 'neox' (rotate-half) and 'gptj' (interleaved) styles are supported,
selected by `is_neox_style` exactly as the reference.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def _apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array,
                is_neox_style: bool) -> jax.Array:
    """x: [..., heads, rot_dim]; cos/sin: [..., 1, rot_dim // 2]."""
    if is_neox_style:
        x1, x2 = jnp.split(x, 2, axis=-1)
        o1 = x1 * cos - x2 * sin
        o2 = x2 * cos + x1 * sin
        return jnp.concatenate([o1, o2], axis=-1)
    x1 = x[..., ::2]
    x2 = x[..., 1::2]
    o1 = x1 * cos - x2 * sin
    o2 = x2 * cos + x1 * sin
    # Re-interleave.
    return jnp.stack([o1, o2], axis=-1).reshape(x.shape)


class RotaryEmbedding:
    """Plain RoPE with a precomputed cos/sin cache (float32).

    The cache is a numpy array captured as a jit constant; shape
    [max_positions, rot_dim] storing [cos | sin] halves. `max_len`
    caps its rows at the longest sequence the server admits: every
    step program holds the table, and a model built for a million
    positions would hold half a gigabyte of it (None: the model's own
    range, as ever).
    """

    def __init__(
        self,
        head_size: int,
        rotary_dim: int,
        max_position_embeddings: int,
        base: float,
        is_neox_style: bool,
        max_len: Optional[int] = None,
    ) -> None:
        self.head_size = head_size
        self.rotary_dim = rotary_dim
        self.max_position_embeddings = max_position_embeddings
        self.base = base
        self.is_neox_style = is_neox_style
        self.max_len = max_len
        self.cos_sin_cache = self._compute_cos_sin_cache()

    def _rows(self, positions: int) -> int:
        """The table's rows for a range of `positions`."""
        return positions if self.max_len is None \
            else min(positions, self.max_len)

    def _compute_inv_freq(self, base: float) -> np.ndarray:
        return 1.0 / (base ** (np.arange(0, self.rotary_dim, 2,
                                         dtype=np.float32) /
                               self.rotary_dim))

    def _compute_cos_sin_cache(self) -> np.ndarray:
        inv_freq = self._compute_inv_freq(self.base)
        t = np.arange(self._rows(self.max_position_embeddings),
                      dtype=np.float32)
        freqs = np.einsum("i,j->ij", t, inv_freq)
        return np.concatenate([np.cos(freqs), np.sin(freqs)],
                              axis=-1).astype(np.float32)

    def __call__(self, positions: jax.Array, query: jax.Array,
                 key: jax.Array) -> Tuple[jax.Array, jax.Array]:
        """positions: [...]; query/key: [..., num_heads, head_size].

        Only the first rotary_dim dims of each head are rotated (partial
        rotary, reference `rotary_embedding.py:112-125`).
        """
        cache = jnp.asarray(self.cos_sin_cache)
        cos_sin = cache[positions]                    # [..., rot_dim]
        cos, sin = jnp.split(cos_sin, 2, axis=-1)
        cos = cos[..., None, :].astype(query.dtype)   # [..., 1, rot/2]
        sin = sin[..., None, :].astype(query.dtype)

        if self.rotary_dim == self.head_size:
            return (_apply_rope(query, cos, sin, self.is_neox_style),
                    _apply_rope(key, cos, sin, self.is_neox_style))
        q_rot = _apply_rope(query[..., :self.rotary_dim], cos, sin,
                            self.is_neox_style)
        k_rot = _apply_rope(key[..., :self.rotary_dim], cos, sin,
                            self.is_neox_style)
        return (jnp.concatenate([q_rot, query[..., self.rotary_dim:]], -1),
                jnp.concatenate([k_rot, key[..., self.rotary_dim:]], -1))


class LinearScalingRotaryEmbedding(RotaryEmbedding):
    """Positions divided by a constant factor (reference `:151`)."""

    def __init__(self, head_size, rotary_dim, max_position_embeddings, base,
                 is_neox_style, scaling_factor: float) -> None:
        self.scaling_factor = scaling_factor
        super().__init__(head_size, rotary_dim, max_position_embeddings,
                         base, is_neox_style)

    def _compute_cos_sin_cache(self) -> np.ndarray:
        inv_freq = self._compute_inv_freq(self.base)
        max_len = int(self.max_position_embeddings * self.scaling_factor)
        t = np.arange(max_len, dtype=np.float32) / self.scaling_factor
        freqs = np.einsum("i,j->ij", t, inv_freq)
        return np.concatenate([np.cos(freqs), np.sin(freqs)],
                              axis=-1).astype(np.float32)


class DynamicNTKScalingRotaryEmbedding(RotaryEmbedding):
    """NTK-aware base rescaling for the extended range (reference `:186-223`).

    Matches the reference exactly: one static cache for the full extended
    window built with the max-length base (reference `_compute_cos_sin_cache`
    `:205-215` does the same). Note this diverges from HF transformers'
    truly-dynamic variant, which recomputes the base from the running
    seq_len and so uses the ORIGINAL base while seq_len <= original
    max_position_embeddings; serving with a paged KV cache can't re-rotate
    cached keys when the base changes, so the static choice is the only
    coherent one (and is what the reference ships).
    """

    def __init__(self, head_size, rotary_dim, max_position_embeddings, base,
                 is_neox_style, scaling_factor: float) -> None:
        self.scaling_factor = scaling_factor
        super().__init__(head_size, rotary_dim, max_position_embeddings,
                         base, is_neox_style)

    def _compute_cos_sin_cache(self) -> np.ndarray:
        max_len = int(self.max_position_embeddings * self.scaling_factor)
        base = self.base * (
            (self.scaling_factor * max_len / self.max_position_embeddings) -
            (self.scaling_factor - 1)) ** (self.rotary_dim /
                                           (self.rotary_dim - 2))
        inv_freq = self._compute_inv_freq(base)
        t = np.arange(max_len, dtype=np.float32)
        freqs = np.einsum("i,j->ij", t, inv_freq)
        return np.concatenate([np.cos(freqs), np.sin(freqs)],
                              axis=-1).astype(np.float32)


def _yarn_find_correction_dim(num_rotations: float, dim: int, base: float,
                              max_position_embeddings: int) -> float:
    return (dim * math.log(max_position_embeddings /
                           (num_rotations * 2 * math.pi))) / \
        (2 * math.log(base))


def _yarn_find_correction_range(low_rot: float, high_rot: float, dim: int,
                                base: float,
                                max_position_embeddings: int
                                ) -> Tuple[int, int]:
    low = math.floor(_yarn_find_correction_dim(low_rot, dim, base,
                                               max_position_embeddings))
    high = math.ceil(_yarn_find_correction_dim(high_rot, dim, base,
                                               max_position_embeddings))
    return max(low, 0), min(high, dim - 1)


def _yarn_linear_ramp_mask(low: float, high: float,
                           dim: int) -> np.ndarray:
    if low == high:
        high += 0.001
    ramp = (np.arange(dim, dtype=np.float32) - low) / (high - low)
    return np.clip(ramp, 0, 1)


def _yarn_get_mscale(scale: float = 1.0, mscale: float = 1.0) -> float:
    if scale <= 1:
        return 1.0
    return 0.1 * mscale * math.log(scale) + 1.0


def deepseek_yarn_softmax_mscale(rope_scaling: Dict[str, Any]) -> float:
    """What DeepSeek-V2's YaRN multiplies the softmax scale by, SQUARED
    by the caller (`mscale_all_dim` over the whole head; 1 without the
    key): the other of its two mscales, the one on cos and sin, is
    `get_rope`'s."""
    all_dim = rope_scaling.get("mscale_all_dim", 0)
    return _yarn_get_mscale(rope_scaling["factor"], all_dim) \
        if all_dim else 1.0


class YaRNScalingRotaryEmbedding(RotaryEmbedding):
    """YaRN: NTK-by-parts interpolation + attention mscale (reference
    `rotary_embedding.py:268-328`). `attention_factor` is HF's key:
    the multiplier of cos and sin itself, where `attn_factor` multiplies
    what `_yarn_get_mscale` gives. With `rotary_dim` under the head
    size only the rotated dimensions meet cos and sin, so only they
    carry the multiplier."""

    def __init__(self, head_size, rotary_dim, max_position_embeddings, base,
                 is_neox_style, scaling_factor: float, *,
                 extrapolation_factor: float = 1.0,
                 attn_factor: float = 1.0, beta_fast: int = 32,
                 beta_slow: int = 1,
                 attention_factor: Optional[float] = None,
                 max_len: Optional[int] = None) -> None:
        self.scaling_factor = scaling_factor
        self.extrapolation_factor = extrapolation_factor
        self.attn_factor = attn_factor
        self.beta_fast = beta_fast
        self.beta_slow = beta_slow
        self.mscale = float(
            _yarn_get_mscale(scaling_factor) * attn_factor
            if attention_factor is None else attention_factor)
        super().__init__(head_size, rotary_dim, max_position_embeddings,
                         base, is_neox_style, max_len)

    def _compute_inv_freq(self, scaling_factor: float) -> np.ndarray:
        pos_freqs = self.base ** (np.arange(0, self.rotary_dim, 2,
                                            dtype=np.float32) /
                                  self.rotary_dim)
        inv_freq_extrapolation = 1.0 / pos_freqs
        inv_freq_interpolation = 1.0 / (scaling_factor * pos_freqs)
        low, high = _yarn_find_correction_range(
            self.beta_fast, self.beta_slow, self.rotary_dim, self.base,
            self.max_position_embeddings)
        inv_freq_mask = (1 - _yarn_linear_ramp_mask(
            low, high, self.rotary_dim // 2)) * self.extrapolation_factor
        return (inv_freq_interpolation * (1 - inv_freq_mask) +
                inv_freq_extrapolation * inv_freq_mask)

    def _compute_cos_sin_cache(self) -> np.ndarray:
        inv_freq = self._compute_inv_freq(self.scaling_factor)
        max_len = self._rows(
            int(self.max_position_embeddings * self.scaling_factor))
        t = np.arange(max_len, dtype=np.float32)
        freqs = np.einsum("i,j->ij", t, inv_freq)
        return np.concatenate(
            [np.cos(freqs) * self.mscale, np.sin(freqs) * self.mscale],
            axis=-1).astype(np.float32)


_ROPE_CACHE: Dict[Any, RotaryEmbedding] = {}


def get_rope(
    head_size: int,
    rotary_dim: int,
    max_position: int,
    base: float,
    is_neox_style: bool = True,
    rope_scaling: Optional[Dict[str, Any]] = None,
    max_len: Optional[int] = None,
) -> RotaryEmbedding:
    """Factory + cache (reference `rotary_embedding.py:333-379`).
    `max_len`: the longest sequence the server admits, where the
    caller knows it; the table then has no more rows (plain and YaRN
    embeddings take it)."""
    key = (head_size, rotary_dim, max_position, base, is_neox_style,
           tuple(sorted(rope_scaling.items())) if rope_scaling else None,
           max_len)
    if key in _ROPE_CACHE:
        return _ROPE_CACHE[key]

    if rope_scaling is None:
        rope = RotaryEmbedding(head_size, rotary_dim, max_position, base,
                               is_neox_style, max_len)
    else:
        scaling_type = rope_scaling.get("type",
                                        rope_scaling.get("rope_type"))
        factor = rope_scaling.get("factor", 1.0)
        if scaling_type == "linear":
            rope = LinearScalingRotaryEmbedding(head_size, rotary_dim,
                                                max_position, base,
                                                is_neox_style, factor)
        elif scaling_type == "dynamic":
            rope = DynamicNTKScalingRotaryEmbedding(head_size, rotary_dim,
                                                    max_position, base,
                                                    is_neox_style, factor)
        elif scaling_type in ("yarn", "deepseek_yarn"):
            original_max = rope_scaling.get(
                "original_max_position_embeddings", max_position)
            extra = {
                k: v for k, v in rope_scaling.items()
                if k in ("extrapolation_factor", "attn_factor", "beta_fast",
                         "beta_slow", "attention_factor")
            }
            if scaling_type == "deepseek_yarn":
                # DeepSeek-V2's two mscales: cos and sin carry the
                # ratio of `mscale` to `mscale_all_dim` (1 where they
                # are equal), the softmax scale the latter squared
                # (`deepseek_yarn_softmax_mscale`)
                extra["attention_factor"] = _yarn_get_mscale(
                    factor, rope_scaling.get("mscale", 1)) / \
                    _yarn_get_mscale(
                        factor, rope_scaling.get("mscale_all_dim", 0)) * \
                    rope_scaling.get("attn_factor", 1.0)
                extra.pop("attn_factor", None)
            rope = YaRNScalingRotaryEmbedding(head_size, rotary_dim,
                                              original_max, base,
                                              is_neox_style, factor, **extra,
                                              max_len=max_len)
        else:
            raise ValueError(f"Unknown RoPE scaling type {scaling_type}")
    _ROPE_CACHE[key] = rope
    return rope
