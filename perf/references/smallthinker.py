"""The SmallThinker block in plain `jax.numpy`, float32 (PowerInfer,
SmallThinker-21BA3B-Instruct; written from the model's config.json and
its card's description, with no import of the program).

A layer `l` with input `x`:

    r = x W_r                      the 64 router logits, taken from the
                                   layer's INPUT, before the input norm
    a = Attn_l(RMSNorm(x))         28 query heads, 4 KV heads of 128
    y = x + a ;  m = RMSNorm(y)
    z = y + sum_k w_k W_down,k (relu(W_gate,k m) * W_up,k m)

over the 6 experts `k` with the largest `r`, `w` the softmax over those
6 logits. Where `sliding_window_layout[l]` is 1 the attention is causal
over a window of `sliding_window_size` keys (the query's own among
them) with rotary embedding in its half-split form (Su et al. 2021);
where it is 0 the attention is causal over every key and there is no
positional encoding at all (`rope_layout[l]` says which layers rotate).
Then a final RMSNorm and an untied head.

Assumed, because config.json does not say: the router reads the
layer's input as it arrives (the card: "router placed before
attention"), and the attention projections have no bias.

No kernel, no cache, no batching beyond a leading axis. What would not
fit is computed in blocks of the same arithmetic: attention a block of
queries at a time (a window layer against the keys its block can see),
the experts one after the other, each for every token, under the
router's mask.

The contract with the harness (`tree`, `stages`, `Precision`, `embed`,
the layer functions, `logits`) is stated at the top of
`perf/references/llama.py`. The two kinds of layer are two stage
functions, `layer_full` and `layer_window`, because a stage function is
told nothing of its place in the stack.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp

VOCAB_PAD = 64          # the server pads its vocabulary rows to this
GAIN = [0.75, 1.25]     # a norm's gains
#: the spread of a projection's output for an input of spread 1 (1 by
#: default). Queries and keys at 1.6 give scores a spread of 2.5, so
#: that a query looks at a few keys and not at the mean of thousands
#: (`perf/references/llama.py` has the argument; it holds the more for
#: a full layer without positions over 8k keys). The router at 3 puts
#: the sixth of 64 logits some 3.3 under the first, so that the sixth
#: expert carries a few hundredths of the weight: a near-tie of the
#: sixth and the seventh, which bfloat16 rounding of the stream
#: decides one way or the other at a share of the tokens that no
#: spread changes (the gap and the rounding both grow with it), then
#: swaps two experts of little weight (PERF.md section 6, PR 33, has
#: the share read). The experts' down projection at 1 makes their sum
#: about what attention adds.
SPREAD = {"self_attn.qkv_proj": 1.6, "router": 3.0}
QUERY_BLOCK = 256


@dataclasses.dataclass(frozen=True)
class Precision:
    """Where a control's lower precision enters: `kv` rounds keys and
    values as a cache of fewer bits would hold them, `act` rounds what
    goes into every matmul of a layer."""
    kv: Callable = staticmethod(lambda x: x)
    act: Callable = staticmethod(lambda x: x)


def _sizes(config: dict) -> Tuple[int, int, int, int, int, int, int]:
    hidden, heads = config["hidden_size"], config["num_attention_heads"]
    return (hidden, heads, config["num_key_value_heads"],
            config.get("head_dim") or hidden // heads,
            config["moe_num_primary_experts"],
            config["moe_num_active_primary_experts"],
            config["moe_ffn_hidden_size"])


def _uniform(spread: float, fan_in: int) -> List[float]:
    a = spread * (3 / fan_in) ** 0.5
    return [-a, a]


def tree(config: dict) -> Dict[str, Dict[str, tuple]]:
    hidden, heads, kv_heads, head, experts, _, inter = _sizes(config)
    dtype = config["torch_dtype"]
    rows = -(-config["vocab_size"] // VOCAB_PAD) * VOCAB_PAD
    gain = {"weight": ((hidden,), dtype, GAIN)}
    out = {"model.embed_tokens": {
               "weight": ((rows, hidden), dtype, [-3 ** 0.5, 3 ** 0.5])},
           "model.norm": gain,
           "lm_head": {"weight": ((rows, hidden), dtype,
                                  _uniform(1.0, hidden))}}
    for i in range(config["num_hidden_layers"]):
        at = f"model.layers.{i}."
        out[at + "input_layernorm"] = gain
        out[at + "post_attention_layernorm"] = gain
        out[at + "self_attn.qkv_proj"] = {"weight": (
            (hidden, (heads + 2 * kv_heads) * head), dtype,
            _uniform(SPREAD["self_attn.qkv_proj"], hidden))}
        out[at + "self_attn.o_proj"] = {"weight": (
            (heads * head, hidden), dtype, _uniform(1.0, heads * head))}
        out[at + "block_sparse_moe.primary_router"] = {"weight": (
            (hidden, experts), dtype, _uniform(SPREAD["router"], hidden))}
        out[at + "block_sparse_moe.experts"] = {
            "w_gate": ((experts, hidden, inter), dtype,
                       _uniform(1.0, hidden)),
            "w_up": ((experts, hidden, inter), dtype,
                     _uniform(1.0, hidden)),
            "w_down": ((experts, inter, hidden), dtype,
                       _uniform(1.0, inter))}
    return out


LAYER_BUCKETS = ("input_layernorm", "post_attention_layernorm",
                 "self_attn.qkv_proj", "self_attn.o_proj",
                 "block_sparse_moe.primary_router",
                 "block_sparse_moe.experts")


def stages(config: dict) -> List[Tuple[str, Dict[str, str]]]:
    out = [("embed", {"embed": "model.embed_tokens"})]
    for i in range(config["num_hidden_layers"]):
        windowed = bool(config["sliding_window_layout"][i])
        if bool(config["rope_layout"][i]) != windowed:
            raise ValueError(
                f"layer {i}: this reference rotates exactly the window "
                "layers, as the published layouts do")
        out.append(("layer_window" if windowed else "layer_full",
                    {b: f"model.layers.{i}.{b}" for b in LAYER_BUCKETS}))
    out.append(("logits", {"norm": "model.norm", "head": "lm_head"}))
    return out


def rms_norm(x: jax.Array, gain: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * gain.astype(jnp.float32)


def rotary(x: jax.Array, theta: float) -> jax.Array:
    """`x` is `[batch, tokens, heads, head]`, positions 0..tokens-1;
    the pair of a dimension is the one half a head away."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(q: jax.Array, k: jax.Array, v: jax.Array,
              window: int = None) -> jax.Array:
    """Causal attention of `q` `[b, t, kv_heads, group, head]` over `k`
    and `v` `[b, t, kv_heads, head]`, a query attending over the
    `window` newest keys, its own among them (all of them for None).
    A block of `QUERY_BLOCK` queries at a time, against the keys that
    block can see: `[.., QUERY_BLOCK, span]` scores, not `[.., t, t]`."""
    b, t, kv_heads, group, head = q.shape
    block = min(QUERY_BLOCK, t)
    pad = -t % block
    q = jnp.pad(q, ((0, 0), (0, pad)) + ((0, 0),) * 3)
    span = t if window is None else min(t, window + block - 1)

    def one(first):
        # the block's queries first..first+block-1 see keys from
        # first-window+1 to first+block-1
        at = jnp.clip(first + block - span, 0, t - span)
        qb = jax.lax.dynamic_slice_in_dim(q, first, block, axis=1)
        kb = jax.lax.dynamic_slice_in_dim(k, at, span, axis=1)
        vb = jax.lax.dynamic_slice_in_dim(v, at, span, axis=1)
        scores = jnp.einsum("btkgd,bskd->bkgts", qb, kb) * head ** -0.5
        q_pos = first + jnp.arange(block)[:, None]
        k_pos = at + jnp.arange(span)[None, :]
        seen = k_pos <= q_pos
        if window is not None:
            seen &= k_pos > q_pos - window
        weights = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bkgts,bskd->btkgd", weights, vb)

    blocks = jax.lax.map(one, jnp.arange(0, t + pad, block))
    # [blocks, b, block, ...] -> [b, t, ...]
    out = jnp.moveaxis(blocks, 0, 1).reshape((b, t + pad) + blocks.shape[3:])
    return out[:, :t].reshape(b, t, -1)


def experts(config: dict, w: dict, m: jax.Array, router_logits: jax.Array,
            p: Precision) -> jax.Array:
    """`sum_k w_k W_down,k (relu(W_gate,k m) * W_up,k m)`: every expert
    for every token, kept where the router chose it."""
    *_, top_k, _ = _sizes(config)
    top, chosen = jax.lax.top_k(router_logits, top_k)
    weight = jax.nn.softmax(top, axis=-1)           # over the top_k alone
    m = p.act(m)

    def add(total, expert):
        w_gate, w_up, w_down, e = expert
        mine = jnp.sum(jnp.where(chosen == e, weight, 0.0), axis=-1)
        act = jax.nn.relu(m @ w_gate.astype(jnp.float32)) * \
            (m @ w_up.astype(jnp.float32))
        return total + mine[..., None] * (
            p.act(act) @ w_down.astype(jnp.float32)), None

    total, _ = jax.lax.scan(
        add, jnp.zeros_like(m),
        (w["w_gate"], w["w_up"], w["w_down"],
         jnp.arange(w["w_gate"].shape[0])))
    return total


def embed(config: dict, w: dict, ids: jax.Array,
          p: Precision) -> jax.Array:
    return w["embed"]["weight"].astype(jnp.float32)[ids]


def _layer(config: dict, w: dict, hidden: jax.Array, p: Precision,
           windowed: bool) -> jax.Array:
    """`hidden` is `[batch, tokens, hidden]`, every sequence from its
    position 0; a sequence padded at its end is right up to its own
    last token, since the mask is causal."""
    _, heads, kv_heads, head, *_ = _sizes(config)
    eps = config["rms_norm_eps"]
    b, t, _ = hidden.shape
    router_logits = p.act(hidden) @ w["block_sparse_moe.primary_router"][
        "weight"].astype(jnp.float32)
    x = rms_norm(hidden, w["input_layernorm"]["weight"], eps)
    qkv = p.act(x) @ w["self_attn.qkv_proj"]["weight"].astype(jnp.float32)
    q, k, v = jnp.split(qkv, [heads * head, (heads + kv_heads) * head], -1)
    q = q.reshape(b, t, heads, head)
    k = k.reshape(b, t, kv_heads, head)
    if windowed:
        theta = float(config["rope_theta"])
        q, k = rotary(q, theta), rotary(k, theta)
    mixed = attention(
        q.reshape(b, t, kv_heads, heads // kv_heads, head), p.kv(k),
        p.kv(v.reshape(b, t, kv_heads, head)),
        int(config["sliding_window_size"]) if windowed else None)
    hidden = hidden + p.act(mixed) @ w["self_attn.o_proj"]["weight"].astype(
        jnp.float32)
    m = rms_norm(hidden, w["post_attention_layernorm"]["weight"], eps)
    return hidden + experts(config, w["block_sparse_moe.experts"], m,
                            router_logits, p)


def layer_full(config: dict, w: dict, hidden: jax.Array,
               p: Precision) -> jax.Array:
    return _layer(config, w, hidden, p, windowed=False)


def layer_window(config: dict, w: dict, hidden: jax.Array,
                 p: Precision) -> jax.Array:
    return _layer(config, w, hidden, p, windowed=True)


def logits(config: dict, w: dict, hidden: jax.Array,
           p: Precision) -> jax.Array:
    x = rms_norm(hidden, w["norm"]["weight"], config["rms_norm_eps"])
    head = w["head"]["weight"].astype(jnp.float32)
    return (x @ head.T)[..., :config["vocab_size"]]
