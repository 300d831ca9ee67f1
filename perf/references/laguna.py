"""The Laguna block in plain `jax.numpy`, float32 (poolside,
Laguna-S-2.1; written from the model's config.json, with no import of
the program and without the publisher's code, which is not on this
machine).

A layer `l` with input `x`, `H_l` query heads (48 in a full layer, 72
in a window layer; `num_attention_heads_per_layer`) over 8 KV heads of
128:

    h = RMSNorm(x)
    q, k, v = h W_q, h W_k, h W_v
    q, k = rope_l(q, k, position)
    a = softmax(q k^T / sqrt(128) + mask_l) v     head j reads KV head
                                                  j // (H_l / 8)
    g = sigmoid(h W_g)                            [H_l]: a gate a head
    y = x + (g[:, None] * a).reshape(-1) W_o
    m = RMSNorm(y)
    dense:   z = y + W_down (silu(W_gate m) * W_up m)
    sparse:  p = softmax(m W_r) over all routed experts;  S = the
             `num_experts_per_tok` largest;  w_e = p_e / sum_S p
             z = y + s * sum_{e in S, e held} w_e E_e(m) + E_shared(m)

`rope_l` and `mask_l` by the layer's kind (`layer_types`,
`rope_parameters`): a full layer is causal over every key and rotates
the first `partial_rotary_factor` of each head by YaRN (Peng et al.
2023: the frequencies blended between theta's own and theta's divided
by `factor`, by a ramp between the dimensions that turn `beta_fast`
and `beta_slow` times over the original range; cos and sin times
`attention_factor`), the rest of the head passing through; a window
layer sees the newest `sliding_window` keys, the query's own among
them, and rotates the whole head by the plain embedding, both in the
half-split form (Su et al. 2021). `E` is SwiGLU, `s`
`moe_routed_scaling_factor`. Then a final RMSNorm and an untied head.

**One chip's share.** The configuration may state that this chip holds
`num_experts` of the `num_routed_experts` the router scores, those
from `first_held_expert` on (and `vocab_size` of the vocabulary's
rows). The router and the top-k are over all routed experts; only the
held ones' terms are summed. What the other experts would add is left
out here as in the program: nothing stands in for the other chip.

ASSUMED, because config.json does not say, and not checked against the
publisher's code (each is one place here, and one in the program):
(a) the gate is a sigmoid of a linear map of the attention block's
normed input, a scalar a head, applied to that head's output before
`W_o` (`_gate`); (b) the router scores by softmax over all its logits
before the top-k (`moe_router_logit_softcapping` 0 is no cap; the key
names are the Qwen-MoE family's, whose router does so) (`experts`);
(c) the shared expert is added ungated and unscaled (`layer`);
(d) no bias anywhere and no norm on queries or keys; (e) the class
name `LagunaForCausalLM` and the checkpoint's tensor names (the
program's loader; the tree below is the program's own).

No kernel, no cache, no batching beyond a leading axis. What would not
fit is computed in blocks of the same arithmetic: attention a block of
queries at a time (a window layer against the keys its block can see),
the held experts one after the other, each for every token, under the
router's mask.

The contract with the harness (`tree`, `stages`, `Precision`, `embed`,
the layer functions, `logits`) is stated at the top of
`perf/references/llama.py`. A stage function is told nothing of its
place in the stack, so the three kinds of layer the stack has are
three functions: `layer_full_dense`, `layer_window_sparse`,
`layer_full_sparse`.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

VOCAB_PAD = 64          # the server pads its vocabulary rows to this
GAIN = [0.75, 1.25]     # a norm's gains
#: the spread of a projection's output for an input of spread 1 (1 by
#: default). Queries and keys at 1.6 give scores a spread of 2.5, so
#: that a query looks at a few keys and not at the mean of thousands
#: (`perf/references/llama.py` has the argument). The gate at 1.5 puts
#: a head's gate between 0.18 and 0.82 two times in three: neither
#: shut nor open, and what moves it moves the stream; `o_proj` at 2
#: gives back what a gate of a half takes. The router at 3 puts the
#: tenth of 256 logits some 3 under the first, so that the tenth
#: expert carries a few hundredths of the weight (a near-tie of the
#: tenth and the eleventh, which bfloat16 rounding of the stream
#: decides one way or the other, then adds or drops a term of little
#: weight where it moves a pair across the share's edge). The experts'
#: and the shared expert's down projections at 1: half the routed
#: weight is held, times `moe_routed_scaling_factor`, so that the
#: routed sum and the shared expert each add about what attention adds.
SPREAD = {"self_attn.qkv_proj": 1.6, "self_attn.g_proj": 1.5,
          "self_attn.o_proj": 2.0, "router": 3.0}
QUERY_BLOCK = 256


@dataclasses.dataclass(frozen=True)
class Precision:
    """Where a control's lower precision enters: `kv` rounds keys and
    values as a cache of fewer bits would hold them, `act` rounds what
    goes into every matmul of a layer."""
    kv: Callable = staticmethod(lambda x: x)
    act: Callable = staticmethod(lambda x: x)


def _uniform(spread: float, fan_in: int) -> List[float]:
    a = spread * (3 / fan_in) ** 0.5
    return [-a, a]


def _kinds(config: dict, i: int) -> Tuple[bool, bool]:
    """`(under a window, sparse)` of layer `i`."""
    return (config["layer_types"][i] == "sliding_attention",
            config["mlp_layer_types"][i] == "sparse")


def tree(config: dict) -> Dict[str, Dict[str, tuple]]:
    hidden, head = config["hidden_size"], config["head_dim"]
    kv_heads = config["num_key_value_heads"]
    held = config["num_experts"]
    routed = config.get("num_routed_experts") or held
    inter = config["moe_intermediate_size"]
    dtype = config["torch_dtype"]
    rows = -(-config["vocab_size"] // VOCAB_PAD) * VOCAB_PAD
    gain = {"weight": ((hidden,), dtype, GAIN)}

    def linear(name, n_in, n_out):
        return {"weight": ((n_in, n_out), dtype,
                           _uniform(SPREAD.get(name, 1.0), n_in))}

    def mlp(at, width):
        return {at + "gate_up_proj": linear("", hidden, 2 * width),
                at + "down_proj": linear("", width, hidden)}

    out = {"model.embed_tokens": {
               "weight": ((rows, hidden), dtype, [-3 ** 0.5, 3 ** 0.5])},
           "model.norm": gain,
           "lm_head": {"weight": ((rows, hidden), dtype,
                                  _uniform(1.0, hidden))}}
    for i in range(config["num_hidden_layers"]):
        at = f"model.layers.{i}."
        heads = config["num_attention_heads_per_layer"][i]
        out[at + "input_layernorm"] = gain
        out[at + "post_attention_layernorm"] = gain
        for name, n_in, n_out in (
                ("self_attn.qkv_proj", hidden,
                 (heads + 2 * kv_heads) * head),
                ("self_attn.g_proj", hidden, heads),
                ("self_attn.o_proj", heads * head, hidden)):
            out[at + name] = linear(name, n_in, n_out)
        if not _kinds(config, i)[1]:
            out.update(mlp(at + "mlp.", config["intermediate_size"]))
            continue
        out[at + "mlp.experts"] = {
            "gate": ((hidden, routed), dtype,
                     _uniform(SPREAD["router"], hidden)),
            "w_gate": ((held, hidden, inter), dtype, _uniform(1.0, hidden)),
            "w_up": ((held, hidden, inter), dtype, _uniform(1.0, hidden)),
            "w_down": ((held, inter, hidden), dtype, _uniform(1.0, inter))}
        out.update(mlp(at + "mlp.shared_expert.",
                       config["shared_expert_intermediate_size"]))
    return out


_ATTN = ("input_layernorm", "post_attention_layernorm",
         "self_attn.qkv_proj", "self_attn.g_proj", "self_attn.o_proj")
DENSE_BUCKETS = _ATTN + ("mlp.gate_up_proj", "mlp.down_proj")
SPARSE_BUCKETS = _ATTN + ("mlp.experts", "mlp.shared_expert.gate_up_proj",
                          "mlp.shared_expert.down_proj")


def stages(config: dict) -> List[Tuple[str, Dict[str, str]]]:
    out = [("embed", {"embed": "model.embed_tokens"})]
    for i in range(config["num_hidden_layers"]):
        if config["gating_types"][i] != "per_head":
            raise ValueError(f"layer {i}: this reference gates every "
                             "head, as the published lists do")
        windowed, sparse = _kinds(config, i)
        if windowed and not sparse:
            raise ValueError(f"layer {i}: a dense layer under a window "
                             "is in no published stack")
        name = "layer_full_dense" if not sparse else \
            "layer_window_sparse" if windowed else "layer_full_sparse"
        out.append((name, {b: f"model.layers.{i}.{b}" for b in
                           (SPARSE_BUCKETS if sparse else DENSE_BUCKETS)}))
    out.append(("logits", {"norm": "model.norm", "head": "lm_head"}))
    return out


def rms_norm(x: jax.Array, gain: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * gain.astype(jnp.float32)


def inverse_frequencies(stated: dict, dim: int) -> Tuple[jax.Array, float]:
    """The `dim / 2` angular frequencies of a rotary embedding over
    `dim` dimensions as `rope_parameters` states it, and what cos and
    sin are multiplied by."""
    theta = float(stated["rope_theta"])
    index = jnp.arange(dim // 2, dtype=jnp.float32)
    own = 1.0 / theta ** (2.0 * index / dim)
    if stated.get("rope_type", "default") == "default":
        return own, 1.0
    if stated["rope_type"] != "yarn":
        raise ValueError(f"no rotary embedding {stated['rope_type']!r}")
    factor = float(stated["factor"])
    span = stated["original_max_position_embeddings"]

    def dimension_of(turns):
        # the dimension whose wavelength fits `turns` times into `span`
        return dim * math.log(span / (turns * 2 * math.pi)) / \
            (2 * math.log(theta))
    low = max(math.floor(dimension_of(stated["beta_fast"])), 0)
    high = min(math.ceil(dimension_of(stated["beta_slow"])), dim - 1)
    ramp = jnp.clip((index - low) / max(high - low, 1e-3), 0.0, 1.0)
    # below `low` theta's own frequency, past `high` it divided by
    # `factor`, between them a blend
    return own * (1.0 - ramp) + own / factor * ramp, \
        float(stated["attention_factor"])


def rotary(x: jax.Array, stated: dict) -> jax.Array:
    """`x` is `[batch, tokens, heads, head]`, positions 0..tokens-1.
    The first `partial_rotary_factor` of the head is rotated, the pair
    of a dimension the one half of the rotated part away; the rest of
    the head passes through."""
    dim = int(x.shape[-1] * stated.get("partial_rotary_factor", 1))
    inv, scale = inverse_frequencies(stated, dim)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos = (jnp.cos(angle) * scale)[:, None, :]
    sin = (jnp.sin(angle) * scale)[:, None, :]
    a, b, rest = x[..., :dim // 2], x[..., dim // 2:dim], x[..., dim:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest],
                           axis=-1)


def attention(q: jax.Array, k: jax.Array, v: jax.Array,
              window: Optional[int] = None) -> jax.Array:
    """Causal attention of `q` `[b, t, kv_heads, group, head]` over `k`
    and `v` `[b, t, kv_heads, head]`, a query attending over the
    `window` newest keys, its own among them (all of them for None).
    A block of `QUERY_BLOCK` queries at a time, against the keys that
    block can see: `[.., QUERY_BLOCK, span]` scores, not `[.., t, t]`.
    Returns `[b, t, kv_heads * group, head]`."""
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
    return out[:, :t].reshape(b, t, kv_heads * group, head)


def swiglu(m: jax.Array, gate_up: jax.Array, down: jax.Array,
           p: Precision) -> jax.Array:
    """`W_down (silu(W_gate m) * W_up m)`, `gate_up` the two matrices
    side by side; `m` is rounded by the caller."""
    gate, up = jnp.split(m @ gate_up.astype(jnp.float32), 2, axis=-1)
    return p.act(jax.nn.silu(gate) * up) @ down.astype(jnp.float32)


def experts(config: dict, w: dict, m: jax.Array, p: Precision) -> jax.Array:
    """`sum_{e in S, e held} w_e E_e(m)`: (b) the softmax over ALL
    routed experts, its `num_experts_per_tok` largest, renormalised;
    then every HELD expert for every token, kept where the router chose
    it. A chosen expert that is held elsewhere adds nothing here."""
    m = p.act(m)
    probs = jax.nn.softmax(m @ w["gate"].astype(jnp.float32), axis=-1)
    top, chosen = jax.lax.top_k(probs, config["num_experts_per_tok"])
    if config.get("norm_topk_prob", True):
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    first = config.get("first_held_expert", 0)

    def add(total, expert):
        w_gate, w_up, w_down, e = expert
        mine = jnp.sum(jnp.where(chosen == e, top, 0.0), axis=-1)
        act = jax.nn.silu(m @ w_gate.astype(jnp.float32)) * \
            (m @ w_up.astype(jnp.float32))
        return total + mine[..., None] * (
            p.act(act) @ w_down.astype(jnp.float32)), None

    total, _ = jax.lax.scan(
        add, jnp.zeros_like(m),
        (w["w_gate"], w["w_up"], w["w_down"],
         first + jnp.arange(w["w_gate"].shape[0])))
    return total


def embed(config: dict, w: dict, ids: jax.Array,
          p: Precision) -> jax.Array:
    return w["embed"]["weight"].astype(jnp.float32)[ids]


def _gate(w: dict, h: jax.Array, mixed: jax.Array) -> jax.Array:
    """(a) `mixed` `[b, t, heads, head]`, each head's times the sigmoid
    of its gate, a linear map of the block's normed input `h`."""
    gate = jax.nn.sigmoid(h @ w["self_attn.g_proj"]["weight"].astype(
        jnp.float32))
    return mixed * gate[..., None]


def layer(config: dict, w: dict, hidden: jax.Array, p: Precision,
          windowed: bool, sparse: bool) -> jax.Array:
    """`hidden` is `[batch, tokens, hidden]`, every sequence from its
    position 0; a sequence padded at its end is right up to its own
    last token, since the mask is causal."""
    kv_heads, head = config["num_key_value_heads"], config["head_dim"]
    eps = config["rms_norm_eps"]
    b, t, _ = hidden.shape
    h = p.act(rms_norm(hidden, w["input_layernorm"]["weight"], eps))
    qkv = h @ w["self_attn.qkv_proj"]["weight"].astype(jnp.float32)
    heads = qkv.shape[-1] // head - 2 * kv_heads
    q, k, v = jnp.split(qkv, [heads * head, (heads + kv_heads) * head], -1)
    stated = config["rope_parameters"][
        "sliding_attention" if windowed else "full_attention"]
    q = rotary(q.reshape(b, t, heads, head), stated)
    k = rotary(k.reshape(b, t, kv_heads, head), stated)
    mixed = attention(
        q.reshape(b, t, kv_heads, heads // kv_heads, head), p.kv(k),
        p.kv(v.reshape(b, t, kv_heads, head)),
        int(config["sliding_window"]) if windowed else None)
    mixed = _gate(w, h, mixed).reshape(b, t, heads * head)
    hidden = hidden + p.act(mixed) @ w["self_attn.o_proj"][
        "weight"].astype(jnp.float32)
    m = rms_norm(hidden, w["post_attention_layernorm"]["weight"], eps)
    if not sparse:
        return hidden + swiglu(p.act(m), w["mlp.gate_up_proj"]["weight"],
                               w["mlp.down_proj"]["weight"], p)
    # (c) the shared expert ungated and unscaled, beside the routed sum
    # times the model's factor
    return hidden + config["moe_routed_scaling_factor"] * experts(
        config, w["mlp.experts"], m, p) + swiglu(
            p.act(m), w["mlp.shared_expert.gate_up_proj"]["weight"],
            w["mlp.shared_expert.down_proj"]["weight"], p)


def layer_full_dense(config: dict, w: dict, hidden: jax.Array,
                     p: Precision) -> jax.Array:
    return layer(config, w, hidden, p, windowed=False, sparse=False)


def layer_window_sparse(config: dict, w: dict, hidden: jax.Array,
                        p: Precision) -> jax.Array:
    return layer(config, w, hidden, p, windowed=True, sparse=True)


def layer_full_sparse(config: dict, w: dict, hidden: jax.Array,
                      p: Precision) -> jax.Array:
    return layer(config, w, hidden, p, windowed=False, sparse=True)


def logits(config: dict, w: dict, hidden: jax.Array,
           p: Precision) -> jax.Array:
    x = rms_norm(hidden, w["norm"]["weight"], config["rms_norm_eps"])
    head = w["head"]["weight"].astype(jnp.float32)
    return (x @ head.T)[..., :config["vocab_size"]]
