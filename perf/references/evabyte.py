"""The EvaByte block in plain `jax.numpy`, float32 (EvaByte/EvaByte
6.5B, 2025-01: a byte-level decoder with EVA's chunked attention).
Written from the checkpoint's config.json and from EVA's paper (Zheng,
Yuan, Wang, Kong: "Efficient Attention via Control Variates", ICLR
2023) in the simplified form EvaByte's release describes, with no
import of the program, no cache and no pages.

A layer with input `x` (the float32 stream), position `t`, head `h` of
32, `s = 128 ** -0.5`:

    n(x)    = x / rms(x) * (1 + g)              eps 1e-5, unit offset
    q, k, v = n(x) W_q, n(x) W_k, n(x) W_v      [32, 128] each, no bias
    q, k    = rope(q, k, t)                     theta 1e5, half-split
    chunk c = positions 16 c .. 16 c + 15:
        p_j    = softmax_j( s * <k_j, phi_h> )  over the chunk's 16 keys
        kbar_c = sum_j p_j k_j + mu_h
        vbar_c = sum_j p_j v_j
    query t, in window w = t // 2048, sees
        exact  (k_j, v_j)        for 2048 w <= j <= t
        pooled (kbar_c, vbar_c)  for c < 128 w
    a_t = ONE softmax over both kinds of s * <q_t, .>
    y   = x + a W_o
    z   = y + W_down( silu(W_gate n'(y)) * W_up n'(y) )
    logits = n''(z) W_head[:320]^T        head 0 of `num_pred_heads`

What config.json leaves open is listed, with reasons, under
`perf.assumed` in `perf/configs/evabyte-6.5b-bf16.json`: the form of
the pooling, that keys are pooled after the rotary embedding, that a
query sees no summary of its own window, that the next-byte head is
rows 0-319, that the unit offset is `1 + weight`.

No kernel, no batching beyond a leading axis. Attention goes a block
of queries at a time, every block against its own window's keys under
the causal mask and against every chunk's pooled key under the mask
`c < 128 w`, so that all blocks share one shape; all layers are alike,
so there is ONE stage function for them.

**The ranges** go by `perf/references/llama.py`'s (matrices at a
fan-in scale, the MLP's output half of it, the embedding at spread 1)
but for two things. A norm's gain is drawn about 0, since the norm
multiplies by `1 + g`: 0.75-1.25 as the other files' norms. And
**queries, keys and values at 1.8, not 1.6**: a stack of 8 dense
layers amplifies nothing at 1.6 (on the chip, this file alone over
5,632 random ids: float8 K and V moved the logits by 0.36 of their
spread and the first token at one position in five, which `gap_mean`
read as 0.022, and int8 activations by 0.09, read as 0.002; the
cell's sound run read 0 at every one of 1,328 positions and `kv8`
0.003: a check that nothing could fail). The stack turns chaotic
between 1.6 and 2.0, where a query comes to look at one key: at 2.0
the cell's sound run read `gap_mean` 0.065 and `act8` 0.20, a factor
of three apart, and at 2.4 operands rounded to bfloat16 alone read
0.31. At 1.8 the same sweep reads bfloat16 operands 0.0012, `act8`
0.021 and `kv8` 0.25: each a factor of ten from the next, as the
32-layer stacks are at 1.6 (`PERF.md` section 6, PR 48, has the sweep
and the cell's readings). `phi` in +-2.2 gives a chunk's pooling
scores a spread of 2.3 (keys of spread 1.8 against a vector of spread
1.27), so that a chunk's largest weight is about a half of the 16:
neither flat nor one-hot. `mu` in +-3.5 is what lets the summaries
carry weight: a pooled key is an average and so shorter than an exact
one, and 256 of them beside 1,300-2,000 exact keys would draw a
hundredth of the softmax's mass; `<q, mu>` moves all of a head's
pooled scores together by a spread of 3.6, so a query in eight puts
more than half its mass on the summaries and the mean is a tenth or
more (`tests/perf/test_perf_evabyte.py` holds both at the published
head size).

The contract with the harness (`tree`, `stages`, `Precision`, `embed`,
`layer`, `logits`) is stated at the top of `perf/references/llama.py`.
"""
from __future__ import annotations

import dataclasses
from math import gcd
from typing import Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp

GAIN = [-0.25, 0.25]    # a norm's gains, under the unit offset
SPREAD = {"self_attn.qkv_proj": 1.8, "mlp.down_proj": 0.5}
PHI = [-2.2, 2.2]
MU = [-3.5, 3.5]
QUERY_BLOCK = 256


@dataclasses.dataclass(frozen=True)
class Precision:
    """Where a control's lower precision enters: `kv` rounds keys,
    values and the pooled keys and values as a cache of fewer bits
    would hold them, `act` rounds what goes into every matmul of a
    layer."""
    kv: Callable = staticmethod(lambda x: x)
    act: Callable = staticmethod(lambda x: x)


def _sizes(config: dict) -> Tuple[int, int, int, int]:
    hidden, heads = config["hidden_size"], config["num_attention_heads"]
    return hidden, heads, hidden // heads, config["intermediate_size"]


def _uniform(spread: float, fan_in: int) -> List[float]:
    a = spread * (3 / fan_in) ** 0.5
    return [-a, a]


def tree(config: dict) -> Dict[str, Dict[str, tuple]]:
    hidden, heads, head, inter = _sizes(config)
    dtype, vocab = config["torch_dtype"], config["vocab_size"]
    gain = {"weight": ((hidden,), dtype, GAIN)}
    out = {"model.embed_tokens": {
               "weight": ((vocab, hidden), dtype, [-3 ** 0.5, 3 ** 0.5])},
           "model.norm": gain,
           "lm_head": {"weight": (
               (config["num_pred_heads"] * vocab, hidden), dtype,
               _uniform(1.0, hidden))}}
    for i in range(config["num_hidden_layers"]):
        at = f"model.layers.{i}."
        out[at + "input_layernorm"] = gain
        out[at + "post_attention_layernorm"] = gain
        out[at + "self_attn"] = {
            "adaptive_phi": ((heads, head), dtype, PHI),
            "adaptive_mu_k": ((heads, head), dtype, MU)}
        for name, n_in, n_out in (
                ("self_attn.qkv_proj", hidden, 3 * hidden),
                ("self_attn.o_proj", hidden, hidden),
                ("mlp.gate_up_proj", hidden, 2 * inter),
                ("mlp.down_proj", inter, hidden)):
            out[at + name] = {"weight": (
                (n_in, n_out), dtype,
                _uniform(SPREAD.get(name, 1.0), n_in))}
    return out


LAYER_BUCKETS = ("input_layernorm", "post_attention_layernorm",
                 "self_attn", "self_attn.qkv_proj", "self_attn.o_proj",
                 "mlp.gate_up_proj", "mlp.down_proj")


def stages(config: dict) -> List[Tuple[str, Dict[str, str]]]:
    out = [("embed", {"embed": "model.embed_tokens"})]
    for i in range(config["num_hidden_layers"]):
        out.append(("layer", {b: f"model.layers.{i}.{b}"
                              for b in LAYER_BUCKETS}))
    out.append(("logits", {"norm": "model.norm", "head": "lm_head"}))
    return out


def _matmul(w: Dict[str, jax.Array], x: jax.Array,
            p: Precision) -> jax.Array:
    return p.act(x) @ w["weight"].astype(jnp.float32)


def offset_rms_norm(x: jax.Array, gain: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * (1.0 + gain.astype(jnp.float32))


def rotary(x: jax.Array, theta: float) -> jax.Array:
    """`x` is `[batch, tokens, heads, head]`, positions 0..tokens-1;
    the pair of a dimension is the one half a head away."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def pool(k: jax.Array, v: jax.Array, phi: jax.Array, mu: jax.Array,
         chunk: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """`k`, `v`: `[batch, tokens, heads, head]`, `tokens` a multiple of
    `chunk`. Returns `(kbar, vbar, weights)`: a pooled key and value
    for every chunk, `[batch, tokens / chunk, heads, head]`, and the
    pooling weights `[batch, tokens / chunk, chunk, heads]`."""
    b, t, heads, head = k.shape
    kc = k.reshape(b, t // chunk, chunk, heads, head)
    vc = v.reshape(b, t // chunk, chunk, heads, head)
    scores = jnp.einsum("bcjhd,hd->bcjh", kc, phi) * head ** -0.5
    weights = jax.nn.softmax(scores, axis=2)
    kbar = jnp.einsum("bcjh,bcjhd->bchd", weights, kc) + mu
    vbar = jnp.einsum("bcjh,bcjhd->bchd", weights, vc)
    return kbar, vbar, weights


def attend(q: jax.Array, k: jax.Array, v: jax.Array, kbar: jax.Array,
           vbar: jax.Array, window: int, chunk: int
           ) -> Tuple[jax.Array, jax.Array]:
    """EVA's attention over `[batch, tokens, heads, head]` queries,
    keys and values and the chunks' pooled keys and values. Returns
    the mixed values and, `[batch, tokens, heads]`, the share of each
    query's softmax that fell on pooled keys. A block of queries lies
    inside one window (the block divides it), so every block scores
    its own window's keys, all `window` of them under the causal mask,
    and every chunk's pooled key under the mask `c < chunks behind`."""
    b, t, heads, head = q.shape
    block = gcd(gcd(QUERY_BLOCK, window), t)
    # keys past the last token, so that a window's slice never clamps
    short = -t % window
    pad = ((0, 0), (0, short), (0, 0), (0, 0))
    k, v = jnp.pad(k, pad), jnp.pad(v, pad)
    chunks, scale = kbar.shape[1], head ** -0.5

    def one(i):
        t0 = i * block
        first = t0 // window * window       # the window's first key
        qb = jax.lax.dynamic_slice_in_dim(q, t0, block, axis=1)
        kw = jax.lax.dynamic_slice_in_dim(k, first, window, axis=1)
        vw = jax.lax.dynamic_slice_in_dim(v, first, window, axis=1)
        exact = jnp.einsum("bthd,bshd->bhts", qb, kw) * scale
        pooled = jnp.einsum("bthd,bchd->bhtc", qb, kbar) * scale
        seen = first + jnp.arange(window)[None, :] <= \
            t0 + jnp.arange(block)[:, None]
        behind = jnp.arange(chunks)[None, :] < first // chunk
        scores = jnp.concatenate(
            [jnp.where(behind, pooled, -jnp.inf),
             jnp.where(seen, exact, -jnp.inf)], axis=-1)
        weights = jax.nn.softmax(scores, axis=-1)
        mixed = jnp.einsum("bhtc,bchd->bthd", weights[..., :chunks], vbar) \
            + jnp.einsum("bhts,bshd->bthd", weights[..., chunks:], vw)
        return mixed, weights[..., :chunks].sum(-1).transpose(0, 2, 1)

    mixed, mass = jax.lax.map(one, jnp.arange(t // block))
    return (jnp.moveaxis(mixed, 0, 1).reshape(b, t, heads, head),
            jnp.moveaxis(mass, 0, 1).reshape(b, t, heads))


def embed(config: dict, w: dict, ids: jax.Array,
          p: Precision) -> jax.Array:
    return w["embed"]["weight"].astype(jnp.float32)[ids]


def attention(config: dict, w: dict, x: jax.Array, p: Precision
              ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The attention block's mixed values of the normed input `x`,
    `[batch, tokens, hidden]`, before `W_o`; with them each query's
    pooled share and the pooling weights (what the tests read)."""
    _, heads, head, _ = _sizes(config)
    window, chunk = config["window_size"], config["chunk_size"]
    theta = config["rope_theta"]
    b, t, _ = x.shape
    qkv = _matmul(w["self_attn.qkv_proj"], x, p).reshape(b, t, 3, heads,
                                                         head)
    q = rotary(qkv[:, :, 0], theta)
    k = p.kv(rotary(qkv[:, :, 1], theta))
    v = p.kv(qkv[:, :, 2])
    vectors = w["self_attn"]
    kbar, vbar, weights = pool(
        k, v, vectors["adaptive_phi"].astype(jnp.float32),
        vectors["adaptive_mu_k"].astype(jnp.float32), chunk)
    mixed, mass = attend(q, k, v, p.kv(kbar), p.kv(vbar), window, chunk)
    return mixed.reshape(b, t, -1), mass, weights


def layer(config: dict, w: dict, hidden: jax.Array,
          p: Precision) -> jax.Array:
    """`hidden` is `[batch, tokens, hidden]`, every sequence from its
    position 0, `tokens` a multiple of the chunk; a sequence padded at
    its end is right up to its own last token, since the mask is
    causal and a chunk's pooled key is seen only from the windows
    after it."""
    eps = config["rms_norm_eps"]
    x = offset_rms_norm(hidden, w["input_layernorm"]["weight"], eps)
    mixed, _, _ = attention(config, w, x, p)
    hidden = hidden + _matmul(w["self_attn.o_proj"], mixed, p)
    x = offset_rms_norm(hidden, w["post_attention_layernorm"]["weight"],
                        eps)
    gate, up = jnp.split(_matmul(w["mlp.gate_up_proj"], x, p), 2, -1)
    return hidden + _matmul(w["mlp.down_proj"], jax.nn.silu(gate) * up, p)


def logits(config: dict, w: dict, hidden: jax.Array,
           p: Precision) -> jax.Array:
    """The next-byte head: rows 0 to `vocab_size` of the head matrix."""
    x = offset_rms_norm(hidden, w["norm"]["weight"], config["rms_norm_eps"])
    head = w["head"]["weight"].astype(jnp.float32)[:config["vocab_size"]]
    return x @ head.T
