"""Jamba in plain `jax.numpy`, float32 (ai21labs, `model_type` "jamba";
written from AI21-Jamba2-3B's config.json and the published
descriptions: Jamba, arXiv:2403.19887; Mamba, arXiv:2312.00752; with no
import of the program).

`x_0 = E[ids]`. For layer `l` of `n`, with `RMS_g(v) = v / sqrt(mean(v^2)
+ eps) * g` and `eps = rms_norm_eps`:

    x = x + mixer_l(RMS_in(x))
    x = x + W_down (silu(W_gate h) * (W_up h)),   h = RMS_ff(x)

(`input_layernorm`, `pre_ff_layernorm`; no bias anywhere but where
stated). Logits: `RMS_final(x_n) E^T` over the tied embedding `E`.

- **Layer kinds** (`kinds`): attention where `l % attn_layer_period ==
  attn_layer_offset`, Mamba elsewhere. The feed-forward of layer `l`
  would be an expert layer where `l % expert_layer_period ==
  expert_layer_offset` and `num_experts > 1`; `num_experts` is 1, so
  every layer's is the dense MLP above, and more experts are refused.
- **Mamba mixer**: `[u ; z] = W_in h`; `u = silu(conv1d_causal(u) +
  b_conv)` (depthwise, `mamba_d_conv` taps); `[dt ; B ; C] = W_x u`;
  `dt = RMS_dt(dt)`, `B = RMS_B(B)`, `C = RMS_C(C)` (gains of
  `mamba_dt_rank`, `mamba_d_state`, `mamba_d_state`);
  `delta = softplus(W_dt dt + b_dt)`; `A = -exp(A_log)`;
  `s_t = exp(delta_t A) s_{t-1} + (delta_t u_t) B_t^T`;
  `y_t = s_t C_t + D u_t`; out `= W_out (y * silu(z))`. The state is
  float32 (as everything here).
- **Attention**: `q = W_q h` (`num_attention_heads` heads of hidden /
  heads), `k = W_k h`, `v = W_v h` (`num_key_value_heads` heads), NO
  rotary or other positional encoding, causal
  `softmax(q k^T / sqrt(d)) v`, `W_o`; `sliding_window` null.

No kernel, no cache, no batching beyond a leading axis: the recurrence
is a sequential `lax.scan` over time, attention a block of queries at
a time. The contract with the harness is stated at the top of
`perf/references/llama.py`; a stage maps the stream to itself, so
nothing rides beside it.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp

VOCAB_PAD = 64          # the server pads its vocabulary rows to this
GAIN = [0.75, 1.25]     # a norm's gains
BIAS = [-0.1, 0.1]      # the convolution's bias
QUERY_BLOCK = 256
#: the embedding's spread. The head is the embedding: the logit of the
#: token a position was given carries |E_tok|^2, sqrt(hidden) standard
#: deviations of the logits over the others were the stream still its
#: embedding. The layers' outputs have a spread near 1 each, so at a
#: quarter the embedding is a small part of the final stream and that
#: term is a deviation or two, under the largest of 65,536 draws
#: (`perf/references/phi4flash.py` has the same head and the argument).
EMBED = 0.25
#: the spread of a projection's output for an input of spread 1 (1 by
#: default). Queries and keys at 1.6 give scores a spread of 2.5 under
#: the model's own scale, so that a query looks at a few keys
#: (`perf/references/llama.py` has the argument); `dt_proj` at 0.5
#: moves `delta` by its input without drowning its bias. `x_proj` needs
#: no entry: the inner norms undo whatever spread it has.
SPREAD = {"self_attn.qkv_proj": 1.6, "mamba.dt_proj": 0.5}
#: `delta = softplus(b_dt + ...)` with `b_dt` in [-6, -3] is 0.0025 to
#: 0.05, and `A = -exp(A_log)` with `A_log` in [-1.5, 1.5] is -0.22 to
#: -4.5: `exp(delta A)` forgets in 4 steps at one end and in 1,800 at
#: the other, so over the cell's 1,536 tokens `delta A` neither freezes
#: the state (some of it turns over every few tokens) nor erases it
#: (some of it still carries the prompt's first tokens at the reply's
#: last): a Mamba layer's output depends on inputs 64 and 512 positions
#: back (`tests/models/test_jamba.py` holds that).
DT_BIAS = [-6.0, -3.0]
A_LOG = [-1.5, 1.5]
SKIP = [0.0, 0.5]       # D
CONV = 0.5              # a tap's spread: four of them give 1
#: the inner norms' gains. `dt` leaves its norm at a spread of 1, which
#: `dt_proj` halves (above). B and C leave theirs at a spread of 2, so
#: that what the state carries (`s C`, a sum over `d_state` products of
#: B and C) is the larger part of `y` beside the skip `D u`, as Phi's
#: `x_proj` at 2 makes it there.
GAIN_BC = [1.5, 2.5]


@dataclasses.dataclass(frozen=True)
class Precision:
    """Where a control's lower precision enters: `kv` rounds keys and
    values as a cache of fewer bits would hold them, `act` rounds what
    goes into every matmul of a layer."""
    kv: Callable = staticmethod(lambda x: x)
    act: Callable = staticmethod(lambda x: x)


def kinds(config: dict) -> List[str]:
    if config.get("num_experts", 1) > 1:
        raise ValueError("num_experts > 1: the expert layers of a Jamba "
                         "stack are not written here")
    if config.get("sliding_window") is not None:
        raise ValueError("sliding_window: full attention alone is "
                         "written here")
    period, offset = config["attn_layer_period"], config["attn_layer_offset"]
    return ["attention" if l % period == offset else "mamba"
            for l in range(config["num_hidden_layers"])]


def _sizes(config: dict) -> dict:
    hidden, heads = config["hidden_size"], config["num_attention_heads"]
    return dict(
        hidden=hidden, heads=heads, kv_heads=config["num_key_value_heads"],
        head=hidden // heads, inter=config["intermediate_size"],
        d_inner=config["mamba_expand"] * hidden,
        d_state=config["mamba_d_state"], d_conv=config["mamba_d_conv"],
        dt_rank=config["mamba_dt_rank"], eps=config["rms_norm_eps"])


def _uniform(spread: float, fan_in: int) -> List[float]:
    a = spread * (3 / fan_in) ** 0.5
    return [-a, a]


def tree(config: dict) -> Dict[str, Dict[str, tuple]]:
    z = _sizes(config)
    dtype = config["torch_dtype"]
    hidden, d_inner, head = z["hidden"], z["d_inner"], z["head"]
    rows = -(-config["vocab_size"] // VOCAB_PAD) * VOCAB_PAD

    def linear(name, n_in, n_out):
        return {"weight": ((n_in, n_out), dtype,
                           _uniform(SPREAD.get(name, 1.0), n_in))}

    def gain(size, draw=GAIN):
        return {"weight": ((size,), dtype, draw)}

    out = {"model.embed_tokens": {"weight": (
               (rows, hidden), dtype,
               [-EMBED * 3 ** 0.5, EMBED * 3 ** 0.5])},
           "model.final_layernorm": gain(hidden)}
    for l, kind in enumerate(kinds(config)):
        at = f"model.layers.{l}."
        out[at + "input_layernorm"] = gain(hidden)
        out[at + "pre_ff_layernorm"] = gain(hidden)
        out[at + "feed_forward.gate_up_proj"] = linear(
            "feed_forward.gate_up_proj", hidden, 2 * z["inter"])
        out[at + "feed_forward.down_proj"] = linear(
            "feed_forward.down_proj", z["inter"], hidden)
        if kind == "mamba":
            out[at + "mamba.in_proj"] = linear(
                "mamba.in_proj", hidden, 2 * d_inner)
            out[at + "mamba.conv1d"] = {
                "weight": ((z["d_conv"], d_inner), dtype,
                           [-CONV * 3 ** 0.5, CONV * 3 ** 0.5]),
                "bias": ((d_inner,), dtype, BIAS)}
            out[at + "mamba.x_proj"] = linear(
                "mamba.x_proj", d_inner, z["dt_rank"] + 2 * z["d_state"])
            out[at + "mamba.dt_layernorm"] = gain(z["dt_rank"])
            out[at + "mamba.b_layernorm"] = gain(z["d_state"], GAIN_BC)
            out[at + "mamba.c_layernorm"] = gain(z["d_state"], GAIN_BC)
            out[at + "mamba.dt_proj"] = {
                **linear("mamba.dt_proj", z["dt_rank"], d_inner),
                "bias": ((d_inner,), dtype, DT_BIAS)}
            out[at + "mamba.ssm"] = {
                "A_log": ((z["d_state"], d_inner), dtype, A_LOG),
                "D": ((d_inner,), dtype, SKIP)}
            out[at + "mamba.out_proj"] = linear(
                "mamba.out_proj", d_inner, hidden)
        else:
            out[at + "self_attn.qkv_proj"] = linear(
                "self_attn.qkv_proj", hidden,
                (z["heads"] + 2 * z["kv_heads"]) * head)
            out[at + "self_attn.o_proj"] = linear(
                "self_attn.o_proj", z["heads"] * head, hidden)
    return out


_COMMON = ("input_layernorm", "pre_ff_layernorm",
           "feed_forward.gate_up_proj", "feed_forward.down_proj")
_MAMBA = ("mamba.in_proj", "mamba.conv1d", "mamba.x_proj",
          "mamba.dt_layernorm", "mamba.b_layernorm", "mamba.c_layernorm",
          "mamba.dt_proj", "mamba.ssm", "mamba.out_proj")
_BUCKETS = {"mamba": _MAMBA,
            "attention": ("self_attn.qkv_proj", "self_attn.o_proj")}


def stages(config: dict) -> List[Tuple[str, Dict[str, str]]]:
    out = [("embed", {"embed": "model.embed_tokens"})]
    for l, kind in enumerate(kinds(config)):
        out.append(("layer_" + kind, {b: f"model.layers.{l}.{b}"
                                      for b in _COMMON + _BUCKETS[kind]}))
    out.append(("logits", {"norm": "model.final_layernorm",
                           "head": "model.embed_tokens"}))
    return out


# ---- the arithmetic ----

def _f32(x) -> jax.Array:
    return x.astype(jnp.float32)


def rms_norm(x: jax.Array, w: dict, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) +
                             eps) * _f32(w["weight"])


def linear(w: dict, x: jax.Array, p: Precision) -> jax.Array:
    y = p.act(x) @ _f32(w["weight"])
    return y + _f32(w["bias"]) if "bias" in w else y


def attention(q: jax.Array, k: jax.Array, v: jax.Array,
              scale: float) -> jax.Array:
    """Causal attention of `q` `[b, t, kv_heads, group, d]` over `k`
    and `v` `[b, t, kv_heads, d]`: a block of `QUERY_BLOCK` queries at
    a time against every key, those behind a query masked."""
    b, t, kv_heads, group, _ = q.shape
    block = min(QUERY_BLOCK, t)
    pad = -t % block
    q = jnp.pad(q, ((0, 0), (0, pad)) + ((0, 0),) * 3)

    def one(first):
        qb = jax.lax.dynamic_slice_in_dim(q, first, block, axis=1)
        scores = jnp.einsum("btkgd,bskd->bkgts", qb, k) * scale
        seen = jnp.arange(t)[None, :] <= first + jnp.arange(block)[:, None]
        weights = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bkgts,bskd->btkgd", weights, v)

    blocks = jax.lax.map(one, jnp.arange(0, t + pad, block))
    out = jnp.moveaxis(blocks, 0, 1).reshape((b, t + pad) + blocks.shape[3:])
    return out[:, :t]


def self_attention(config: dict, w: dict, h: jax.Array,
                   p: Precision) -> jax.Array:
    z = _sizes(config)
    b, t, _ = h.shape
    d, heads, kv_heads = z["head"], z["heads"], z["kv_heads"]
    q, k, v = jnp.split(linear(w["self_attn.qkv_proj"], h, p),
                        [heads * d, (heads + kv_heads) * d], axis=-1)
    k, v = p.kv(k), p.kv(v)             # as a cache holds them
    out = attention(q.reshape(b, t, kv_heads, heads // kv_heads, d),
                    k.reshape(b, t, kv_heads, d),
                    v.reshape(b, t, kv_heads, d), d ** -0.5)
    return linear(w["self_attn.o_proj"], out.reshape(b, t, -1), p)


def mamba(config: dict, w: dict, h: jax.Array, p: Precision
          ) -> Tuple[jax.Array, jax.Array]:
    """The mixer's output and `y`, the scan's result before the gate."""
    z = _sizes(config)
    n, rank, taps, eps = z["d_state"], z["dt_rank"], z["d_conv"], z["eps"]
    t = h.shape[1]
    x, gate = jnp.split(linear(w["mamba.in_proj"], h, p), 2, axis=-1)
    conv_w = _f32(w["mamba.conv1d"]["weight"])
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    u = jax.nn.silu(_f32(w["mamba.conv1d"]["bias"]) + sum(
        conv_w[k] * padded[:, k:k + t] for k in range(taps)))
    dt, b_in, c_in = jnp.split(linear(w["mamba.x_proj"], u, p),
                               [rank, rank + n], axis=-1)
    dt = rms_norm(dt, w["mamba.dt_layernorm"], eps)
    b_in = rms_norm(b_in, w["mamba.b_layernorm"], eps)
    c_in = rms_norm(c_in, w["mamba.c_layernorm"], eps)
    delta = jax.nn.softplus(linear(w["mamba.dt_proj"], dt, p))
    a = -jnp.exp(_f32(w["mamba.ssm"]["A_log"]))         # [n, d_inner]
    skip = _f32(w["mamba.ssm"]["D"])

    def step(s, xs):
        u_t, dl_t, b_t, c_t = xs
        s = jnp.exp(dl_t[:, None, :] * a) * s + \
            (dl_t * u_t)[:, None, :] * b_t[:, :, None]
        return s, jnp.sum(s * c_t[:, :, None], axis=1) + skip * u_t

    s0 = jnp.zeros((h.shape[0], n, z["d_inner"]), jnp.float32)
    _, y = jax.lax.scan(step, s0, tuple(
        jnp.moveaxis(a_, 1, 0) for a_ in (u, delta, b_in, c_in)))
    y = jnp.moveaxis(y, 0, 1)
    return linear(w["mamba.out_proj"], y * jax.nn.silu(gate), p), y


def _feed_forward(config: dict, w: dict, x: jax.Array,
                  p: Precision) -> jax.Array:
    h = rms_norm(x, w["pre_ff_layernorm"], config["rms_norm_eps"])
    gate, up = jnp.split(linear(w["feed_forward.gate_up_proj"], h, p), 2,
                         axis=-1)
    return x + linear(w["feed_forward.down_proj"],
                      jax.nn.silu(gate) * up, p)


def _normed(config: dict, w: dict, x: jax.Array) -> jax.Array:
    return rms_norm(x, w["input_layernorm"], config["rms_norm_eps"])


def embed(config: dict, w: dict, ids: jax.Array,
          p: Precision) -> jax.Array:
    return _f32(w["embed"]["weight"])[ids]


def layer_mamba(config: dict, w: dict, x: jax.Array,
                p: Precision) -> jax.Array:
    out, _ = mamba(config, w, _normed(config, w, x), p)
    return _feed_forward(config, w, x + out, p)


def layer_attention(config: dict, w: dict, x: jax.Array,
                    p: Precision) -> jax.Array:
    out = self_attention(config, w, _normed(config, w, x), p)
    return _feed_forward(config, w, x + out, p)


def logits(config: dict, w: dict, x: jax.Array,
           p: Precision) -> jax.Array:
    x = rms_norm(x, w["norm"], config["rms_norm_eps"])
    return (x @ _f32(w["head"]["weight"]).T)[..., :config["vocab_size"]]
