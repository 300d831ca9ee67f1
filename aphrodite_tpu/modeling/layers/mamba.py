"""The Mamba mixer (a selective state-space layer; Mamba,
arXiv:2312.00752), shared by the models that have one
(`models/phi4flash.py`, `models/jamba.py`):

    [u ; z] = W_in h;   u = silu(conv1d_causal(u) + b_conv)
    [dt ; B ; C] = W_x u
    (with `inner_norms`: dt, B and C each through an RMSNorm of its own)
    delta = softplus(W_dt dt + b_dt);   A = -exp(A_log)
    s_t = exp(delta_t A) s_{t-1} + (delta_t u_t) B_t^T
    y_t = s_t C_t + D u_t;   out = W_out (y * silu(z))

The state `s` `[d_state, d_inner]` (float32) and the last `d_conv - 1`
inputs of the causal convolution live in the sequence's STATE SLOT,
beside the KV pages (`common/config.py::StateSpec`): a prompt chunk
starts from the slot (from zeros at position 0) and leaves its last
token's state there, a decode step moves it on by one token in place
(`ops/pallas/ssm_scan.py`). Every Mamba layer of a model reads and
writes its own layer of the model's ONE pair of state arrays
(`executor/cache_engine.py`); a slot of the tail array keeps the last
`d_conv` inputs, one more than the convolution reads, because that is
the shape the device lays out as the update kernel takes it. A decode
step's one token a row runs through the layer as `[rows, width]`
arrays, the token axis taken off at the door and put back at the exit.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from aphrodite_tpu.modeling.input_metadata import InputMetadata
from aphrodite_tpu.modeling.layers.layernorm import rms_norm
from aphrodite_tpu.modeling.layers.linear import (ColumnParallelLinear,
                                                  LinearMethod,
                                                  RowParallelLinear)
from aphrodite_tpu.ops.pallas import ssm_scan

StateCache = Tuple[jax.Array, jax.Array]
Params = Dict[str, Dict[str, jax.Array]]

#: the gains of the three inner norms, by the part of `x_proj`'s output
#: each one normalises
INNER_NORMS = ("dt_layernorm", "b_layernorm", "c_layernorm")


class MambaMixer:
    """Returns the output, `y` (the scan's result before the gate) and
    the layer's state arrays as it leaves them. `inner_norms`: an
    RMSNorm (eps `eps`) on each of dt, B and C between `x_proj` and
    `dt_proj` / the scan, gains under `{prefix}.dt_layernorm`,
    `.b_layernorm`, `.c_layernorm`. The convolution and `dt_proj`
    have a bias, the other projections none."""

    def __init__(self, config, prefix: str, dtype,
                 linear_method: Optional[LinearMethod], *,
                 inner_norms: bool = False, eps: float = 1e-6) -> None:
        self.prefix = prefix
        self.dtype = dtype
        self.inner_norms = inner_norms
        self.eps = eps
        self.d_inner = config.mamba_d_inner
        self.d_state = config.mamba_d_state
        self.d_conv = config.mamba_d_conv
        self.dt_rank = config.mamba_dt_rank
        kw = dict(dtype=dtype, linear_method=linear_method)
        self.in_proj = ColumnParallelLinear(
            config.hidden_size, 2 * self.d_inner, bias=False, **kw)
        self.x_proj = RowParallelLinear(
            self.d_inner, self.dt_rank + 2 * self.d_state, bias=False, **kw)
        self.dt_proj = ColumnParallelLinear(
            self.dt_rank, self.d_inner, bias=True, **kw)
        self.out_proj = RowParallelLinear(
            self.d_inner, config.hidden_size, bias=False, **kw)

    def init(self) -> Params:
        p, d = self.prefix, self.d_inner
        params = {
            f"{p}.in_proj": self.in_proj.init(),
            f"{p}.conv1d": {
                "weight": jnp.zeros((self.d_conv, d), dtype=self.dtype),
                "bias": jnp.zeros((d,), dtype=self.dtype)},
            f"{p}.x_proj": self.x_proj.init(),
            f"{p}.dt_proj": self.dt_proj.init(),
            f"{p}.ssm": {
                "A_log": jnp.zeros((self.d_state, d), dtype=self.dtype),
                "D": jnp.ones((d,), dtype=self.dtype)},
            f"{p}.out_proj": self.out_proj.init(),
        }
        if self.inner_norms:
            for name, size in zip(INNER_NORMS, (self.dt_rank, self.d_state,
                                                self.d_state)):
                params[f"{p}.{name}"] = {
                    "weight": jnp.ones((size,), dtype=self.dtype)}
        return params

    def __call__(self, params: Params, h: jax.Array, positions: jax.Array,
                 cache: Optional[StateCache], metadata: InputMetadata,
                 layer: int):
        """`cache`: the model's `(tail, state)` arrays, `[state layers,
        slots + 1, kept | d_state, d_inner]` with `kept >= d_conv - 1`
        inputs a slot, and `layer` which of the state layers this is;
        None runs a prompt from zeros and keeps nothing."""
        p = self.prefix
        batch, seq = h.shape[:2]
        decode = not metadata.is_prompt
        if decode:
            # a decode step's one token a row, as `[rows, width]` arrays
            # from here to the output: a `[rows, 1, width]` array beside
            # the update kernel takes the kernel's row-major layout, a
            # tile a row with one sublane in eight used, and every
            # elementwise fusion over it costs eight times its work
            h = h[:, 0]
        x, z = jnp.split(self.in_proj(params[f"{p}.in_proj"], h), 2, axis=-1)
        conv_w = params[f"{p}.conv1d"]["weight"].astype(jnp.float32)
        conv_b = params[f"{p}.conv1d"]["bias"].astype(jnp.float32)
        a = -jnp.exp(params[f"{p}.ssm"]["A_log"].astype(jnp.float32))
        d = params[f"{p}.ssm"]["D"].astype(jnp.float32)
        taps = self.d_conv - 1
        slots = metadata.state_slots
        if cache is None:
            tail = jnp.zeros((1, 1, taps, self.d_inner), self.dtype)
            state = jnp.zeros((1, 1, self.d_state, self.d_inner),
                              jnp.float32)
            slots, layer = jnp.zeros((batch,), jnp.int32), 0
        else:
            tail, state = cache
        # the inputs a slot keeps, of which the last `taps` are read
        kept = tail.shape[2]
        lead = kept - taps

        # the convolution over [the slot's tail ; this step's inputs]
        fresh = positions[:, 0] == 0
        before = tail[layer, slots]
        if metadata.is_prompt:
            before = jnp.where(fresh[:, None, None], 0, before)
        window = jnp.concatenate(
            [before, x[:, None] if decode else x], axis=1).astype(jnp.float32)
        conv = conv_b + sum(
            conv_w[k] * window[:, lead + k:lead + k + seq]
            for k in range(self.d_conv))
        if decode:
            conv = conv[:, 0]
        u = jax.nn.silu(conv)                       # float32
        dbc = self.x_proj(params[f"{p}.x_proj"], u.astype(self.dtype))
        dt, b, c = jnp.split(
            dbc, [self.dt_rank, self.dt_rank + self.d_state], axis=-1)
        if self.inner_norms:
            dt, b, c = (rms_norm(part, params[f"{p}.{name}"]["weight"],
                                 self.eps)
                        for part, name in zip((dt, b, c), INNER_NORMS))
        delta = jax.nn.softplus(self.dt_proj(
            params[f"{p}.dt_proj"], dt).astype(jnp.float32))
        b, c = b.astype(jnp.float32), c.astype(jnp.float32)

        if metadata.is_prompt:
            lens = metadata.prompt_lens if metadata.prompt_lens is not None \
                else jnp.full((batch,), seq, jnp.int32)
            # padding is passed over: delta 0 leaves the state as it is
            live = jnp.arange(seq)[None, :] < lens[:, None]
            delta = jnp.where(live[..., None], delta, 0.0)
            # (the kernels are one chip's programs; the state arrays
            # are too: `CacheEngine._allocate_state`)
            scan = ssm_scan.selective_scan if metadata.tp == 1 \
                else ssm_scan.ssm_scan_ref
            y, state = scan(u, delta, b, c, a, d, state, slots, fresh,
                            layer)
            # the tail after the row's last live token
            moved = jax.vmap(lambda w, n: jax.lax.dynamic_slice_in_dim(
                w, n, kept, axis=0))(window, lens).astype(tail.dtype)
            tail = tail.at[layer, slots].set(moved)
        else:
            update = ssm_scan.selective_update if metadata.tp == 1 \
                else ssm_scan.ssm_update_ref
            y, state, tail = update(x, u, delta, b, c, a, d, state, tail,
                                    slots, layer)
        y = y.astype(self.dtype)
        out = self.out_proj(params[f"{p}.out_proj"],
                            y * jax.nn.silu(z.astype(jnp.float32)).astype(
                                self.dtype))
        if decode:
            out, y = out[:, None], y[:, None]
        return out, y, (None if cache is None else (tail, state))
