"""The Kimi Delta Attention mixer (KDA; Kimi Linear, arXiv:2510.26692):
a gated delta rule with a decay for every channel of the key. For
token `t` with input `x_t`, `H` heads of `d` x `d`:

    q_t = l2norm_head(silu(conv_q(W_q x)_t)) * d^-0.5        [H, d]
    k_t = l2norm_head(silu(conv_k(W_k x)_t))                 [H, d]
    v_t =             silu(conv_v(W_v x)_t)                  [H, d]
    g_t = -exp(A_log[h]) * softplus(W_fb (W_fa x_t) + dt_bias)   [H, d]
    b_t = sigmoid(W_b x_t)                                   [H]
    S'  = diag(exp(g_t)) S_{t-1};  S_t = S' + b_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t
    out = W_o (rmsnorm_head(o_t; gain[d]) * sigmoid(W_gb (W_ga x_t)))

`conv_*` is a causal depthwise convolution over the last `taps` inputs
of each channel, no bias; no projection has one. The three
projections are ONE matrix here (`qkv_proj`) and so are the three
convolutions' weights and their tail; `W_fa`, `W_ga` and `W_b` are one
matrix too (`fgb_proj`).

The heads' matrices `S` (float32) and the last `taps - 1` inputs of
the convolutions live in the sequence's STATE SLOT, beside the KV
pages of the model's other layers (`common/config.py::StateSpec`), as
a Mamba layer's do (`layers/mamba.py`): a prompt chunk starts from the
slot (from zeros at position 0) and leaves its last token's state
there (`ops/pallas/kda.py::kda_chunk`), a decode step moves it on by
one token in place (`kda_update`). A decode step's one token a row
runs through the layer as `[rows, width]` arrays.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from aphrodite_tpu.modeling.input_metadata import InputMetadata
from aphrodite_tpu.modeling.layers.linear import (
    ColumnParallelLinear, LinearMethod, MergedColumnParallelLinear,
    RowParallelLinear)
from aphrodite_tpu.ops.pallas import kda

StateCache = Tuple[jax.Array, jax.Array]
Params = Dict[str, Dict[str, jax.Array]]

#: the L2 norm's eps (ASSUMED: the flash-linear-attention project's)
L2_EPS = 1e-6


def l2norm(x: jax.Array) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True)
                             + L2_EPS)


class KimiDeltaAttention:
    """Returns the output and the layer's state arrays as it leaves
    them."""

    def __init__(self, hidden: int, heads: int, dim: int, taps: int,
                 eps: float, prefix: str, dtype,
                 linear_method: Optional[LinearMethod]) -> None:
        self.prefix = prefix
        self.dtype = dtype
        self.heads, self.dim, self.taps = heads, dim, taps
        self.eps = eps
        self.width = width = heads * dim
        kw = dict(bias=False, dtype=dtype, linear_method=linear_method)
        self.qkv_proj = MergedColumnParallelLinear(hidden, [width] * 3, **kw)
        # the two gates' inner projections (width `dim`: ASSUMED) and
        # the write strength's
        self.fgb_proj = MergedColumnParallelLinear(
            hidden, [dim, dim, heads], **kw)
        self.f_b_proj = ColumnParallelLinear(dim, width, **kw)
        self.g_b_proj = ColumnParallelLinear(dim, width, **kw)
        self.o_proj = RowParallelLinear(width, hidden, **kw)

    def linears(self) -> Dict[str, object]:
        p = self.prefix
        return {f"{p}.qkv_proj": self.qkv_proj,
                f"{p}.fgb_proj": self.fgb_proj,
                f"{p}.f_b_proj": self.f_b_proj,
                f"{p}.g_b_proj": self.g_b_proj,
                f"{p}.o_proj": self.o_proj}

    def init(self) -> Params:
        p = self.prefix
        params = {key: layer.init() for key, layer in self.linears().items()}
        params[f"{p}.conv1d"] = {"weight": jnp.zeros(
            (self.taps, 3 * self.width), dtype=self.dtype)}
        # one `A_log` a head, one `dt_bias` a channel (ASSUMED), float32
        params[f"{p}.kda"] = {
            "A_log": jnp.zeros((self.heads,), dtype=jnp.float32),
            "dt_bias": jnp.zeros((self.width,), dtype=jnp.float32)}
        # the output norm's gain: a head-width vector shared by the
        # heads (ASSUMED)
        params[f"{p}.o_norm"] = {"weight": jnp.ones((self.dim,),
                                                    dtype=self.dtype)}
        return params

    def __call__(self, params: Params, h: jax.Array, positions: jax.Array,
                 cache: Optional[StateCache], metadata: InputMetadata,
                 layer: int):
        """`cache`: the model's `(tail, state)` arrays, `[state layers,
        slots + 1, kept | heads, dim, dim]` with `kept >= taps - 1`
        inputs a slot, and `layer` which of the state layers this is;
        None runs a prompt from zeros and keeps nothing."""
        p = self.prefix
        batch, seq = h.shape[:2]
        heads, dim = self.heads, self.dim
        decode = not metadata.is_prompt
        if decode:
            h = h[:, 0]                 # `layers/mamba.py` says why
        lead = h.shape[:-1]
        x = self.qkv_proj(params[f"{p}.qkv_proj"], h)
        fgb = self.fgb_proj(params[f"{p}.fgb_proj"], h)
        f_a, g_a, b = jnp.split(fgb, [dim, 2 * dim], axis=-1)
        taps = self.taps - 1
        slots = metadata.state_slots
        if cache is None:
            tail = jnp.zeros((1, 1, taps, 3 * self.width), self.dtype)
            state = jnp.zeros((1, 1, heads, dim, dim), jnp.float32)
            slots, layer = jnp.zeros((batch,), jnp.int32), 0
        else:
            tail, state = cache
        # the inputs a slot keeps, of which the last `taps` are read
        kept = tail.shape[2]
        first = kept - taps

        # the convolutions over [the slot's tail ; this step's inputs]
        fresh = positions[:, 0] == 0
        before = tail[layer, slots]
        if metadata.is_prompt:
            before = jnp.where(fresh[:, None, None], 0, before)
        window = jnp.concatenate(
            [before, x[:, None] if decode else x], axis=1).astype(jnp.float32)
        conv_w = params[f"{p}.conv1d"]["weight"].astype(jnp.float32)
        conv = sum(conv_w[i] * window[:, first + i:first + i + seq]
                   for i in range(self.taps))
        if decode:
            conv = conv[:, 0]
        q, k, v = (part.reshape(lead + (heads, dim)) for part in jnp.split(
            jax.nn.silu(conv), 3, axis=-1))                 # float32
        q, k = l2norm(q) * dim ** -0.5, l2norm(k)
        gate = params[f"{p}.kda"]
        g = -jnp.exp(gate["A_log"])[:, None] * jax.nn.softplus(
            self.f_b_proj(params[f"{p}.f_b_proj"], f_a).astype(jnp.float32)
            + gate["dt_bias"]).reshape(lead + (heads, dim))
        b = jax.nn.sigmoid(b.astype(jnp.float32))

        if metadata.is_prompt:
            lens = metadata.prompt_lens if metadata.prompt_lens is not None \
                else jnp.full((batch,), seq, jnp.int32)
            # padding is passed over: no decay and nothing written
            live = jnp.arange(seq)[None, :] < lens[:, None]
            g = jnp.where(live[..., None, None], g, 0.0)
            b = jnp.where(live[..., None], b, 0.0)
            # (the kernels are one chip's programs; the state arrays
            # are too: `CacheEngine._allocate_state`)
            chunk = kda.kda_chunk if metadata.tp == 1 else kda.kda_chunk_ref
            o, state = chunk(q, k, v, g, b, state, slots, fresh, layer)
            # the tail after the row's last live token
            moved = jax.vmap(lambda w, n: jax.lax.dynamic_slice_in_dim(
                w, n, kept, axis=0))(window, lens).astype(tail.dtype)
            tail = tail.at[layer, slots].set(moved)
        else:
            update = kda.kda_update if metadata.tp == 1 \
                else kda.kda_update_ref
            o, state, tail = update(x, q, k, v, g, b, state, tail, slots,
                                    layer)
        # the output norm a head, in float32 as the state is
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                              + self.eps) * \
            params[f"{p}.o_norm"]["weight"].astype(jnp.float32)
        out_gate = jax.nn.sigmoid(self.g_b_proj(
            params[f"{p}.g_b_proj"], g_a).astype(jnp.float32))
        out = self.o_proj(
            params[f"{p}.o_proj"],
            (o.reshape(lead + (self.width,)) * out_gate).astype(self.dtype))
        if decode:
            out = out[:, None]
        return out, (None if cache is None else (tail, state))
