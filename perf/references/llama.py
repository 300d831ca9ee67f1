"""The Llama block in plain `jax.numpy`, float32: RMSNorm, rotary
grouped-query attention under a causal mask, SwiGLU, an untied head.
Written from the published description (Touvron et al. 2023, "LLaMA";
Su et al. 2021 for the rotary embedding in its half-split form; the
Mistral 7B paper for grouped-query attention), and for GPTQ weights
from the AutoGPTQ v1 format's definition. No kernel, no cache, no
batching beyond a leading axis, and no import of the program.

What a reference file gives the harness:

- `tree(config)`: the server's parameter tree as it is served,
  `{bucket: {leaf: (shape, dtype name, draw)}}`. The weights are data
  made from the seed (`perf/weights.py`) for the server and for the
  reference, leaf by leaf in the sorted order of this tree, so the
  tree has to name every leaf the server has (`perf/serve_child.py`
  refuses a tree that differs from the program's). `draw` is the range
  a leaf is drawn from; the ranges make every layer count.
- `stages(config)`: the forward pass in order, one `(function name,
  {local bucket name: bucket})` a stage; a stage's weights are made,
  used and dropped before the next one's.
- `embed(config, w, ids, p)`, `layer(config, w, hidden, p)`,
  `logits(config, w, hidden, p)`: `w` is the stage's buckets under
  their local names, `p` a `Precision`. The reference itself runs with
  `Precision()` (nothing rounded); a control lowers one part of it
  (`perf/reference_child.py`).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp

VOCAB_PAD = 64          # the server pads its vocabulary rows to this
GAIN = [0.75, 1.25]     # a norm's gains
#: the spread of a projection's output for an input of spread 1.
#: Queries and keys at 1.6 give scores a spread of 2.5, so that a
#: query looks at a few keys and not at the mean of a thousand: with
#: scores of spread 1 attention's output is the mean of some 400
#: values, a twentieth of the MLP's, and the cache hardly counts
#: (at 2.0 a query looks at one key and the stack turns chaotic: int8
#: activations alone then move the logits by a quarter of their
#: spread). The MLP's output at half of attention's keeps the rounding
#: of its two int8 matmuls, which a sound run has, under what a cache
#: of fewer bits adds (PERF.md section 6, PR 27, has the readings).
SPREAD = {"self_attn.qkv_proj": 1.6, "mlp.down_proj": 0.5}


@dataclasses.dataclass(frozen=True)
class Precision:
    """Where a control's lower precision enters: `kv` rounds keys and
    values as a cache of fewer bits would hold them, `act` rounds what
    goes into a matmul whose weights are quantised."""
    kv: Callable = staticmethod(lambda x: x)
    act: Callable = staticmethod(lambda x: x)


def _quant(config: dict):
    return config["perf"].get("reference_quant")


def _linear(config: dict, name: str, n_in: int,
            n_out: int) -> Dict[str, tuple]:
    """A projection whose output has the spread `SPREAD` gives it
    (1 by default) for an input of spread 1. GPTQ: the codes and the
    zero points are random words, so `code - (zero + 1)` has the mean
    -1 and the mean square `(levels**2 - 1) / 6 + 1`; the scales are
    drawn about 0, a sign a group and column, so that the weights have
    the mean 0 (under positive scales every column would carry the
    same share of its input's sum, which the next matmul multiplies
    by its fan-in: a layer's output was 700 times its input)."""
    q, dtype = _quant(config), config["torch_dtype"]
    spread = SPREAD.get(name, 1.0) * n_in ** -0.5
    if q is None:
        a = spread * 3 ** 0.5
        return {"weight": ((n_in, n_out), dtype, [-a, a])}
    pack, groups = 32 // q["bits"], n_in // q["group_size"]
    levels = 2 ** q["bits"]
    a = spread * (3 / ((levels ** 2 - 1) / 6 + 1)) ** 0.5
    return {"qweight": ((n_in // pack, n_out), "int32", "bits"),
            "qzeros": ((groups, n_out // pack), "int32", "bits"),
            "scales": ((groups, n_out), dtype, [-a, a]),
            "g_idx": ((n_in,), "int32", "zeros")}


def _sizes(config: dict) -> Tuple[int, int, int, int, int]:
    hidden, heads = config["hidden_size"], config["num_attention_heads"]
    return (hidden, heads, config.get("num_key_value_heads", heads),
            config.get("head_dim") or hidden // heads,
            config["intermediate_size"])


def tree(config: dict) -> Dict[str, Dict[str, tuple]]:
    hidden, heads, kv_heads, head, inter = _sizes(config)
    dtype = config["torch_dtype"]
    rows = -(-config["vocab_size"] // VOCAB_PAD) * VOCAB_PAD
    gain = {"weight": ((hidden,), dtype, GAIN)}
    a = (3 / hidden) ** 0.5
    out = {"model.embed_tokens": {
               "weight": ((rows, hidden), dtype, [-3 ** 0.5, 3 ** 0.5])},
           "model.norm": gain,
           "lm_head": {"weight": ((rows, hidden), dtype, [-a, a])}}
    for i in range(config["num_hidden_layers"]):
        at = f"model.layers.{i}."
        out[at + "input_layernorm"] = gain
        out[at + "post_attention_layernorm"] = gain
        for name, n_in, n_out in (
                ("self_attn.qkv_proj", hidden,
                 (heads + 2 * kv_heads) * head),
                ("self_attn.o_proj", heads * head, hidden),
                ("mlp.gate_up_proj", hidden, 2 * inter),
                ("mlp.down_proj", inter, hidden)):
            out[at + name] = _linear(config, name, n_in, n_out)
    return out


LAYER_BUCKETS = ("input_layernorm", "post_attention_layernorm",
                 "self_attn.qkv_proj", "self_attn.o_proj",
                 "mlp.gate_up_proj", "mlp.down_proj")


def stages(config: dict) -> List[Tuple[str, Dict[str, str]]]:
    out = [("embed", {"embed": "model.embed_tokens"})]
    for i in range(config["num_hidden_layers"]):
        out.append(("layer", {b: f"model.layers.{i}.{b}"
                              for b in LAYER_BUCKETS}))
    out.append(("logits", {"norm": "model.norm", "head": "lm_head"}))
    return out


def dequantize(w: Dict[str, jax.Array], bits: int,
               group_size: int) -> jax.Array:
    """AutoGPTQ v1, no act-order: `qweight` int32 `[in / pack, out]`
    holds `pack = 32 / bits` codes a word along the input rows, lowest
    bits first; `qzeros` `[groups, out / pack]` holds the zero points
    minus one, packed the same way along the output columns; input row
    `i` belongs to group `i // group_size`;
    `w[i, j] = scales[g, j] * (code[i, j] - (zero[g, j] + 1))`."""
    pack, mask = 32 // bits, (1 << bits) - 1
    shifts = jnp.arange(pack, dtype=jnp.uint32) * bits
    words = w["qweight"].astype(jnp.uint32)
    codes = ((words[:, None, :] >> shifts[None, :, None]) & mask).reshape(
        -1, words.shape[1])
    words = w["qzeros"].astype(jnp.uint32)
    zeros = ((words[:, :, None] >> shifts[None, None, :]) & mask).reshape(
        words.shape[0], -1) + 1
    group = jnp.arange(codes.shape[0]) // group_size
    return (codes.astype(jnp.float32) - zeros[group].astype(jnp.float32)) \
        * w["scales"].astype(jnp.float32)[group]


def _matmul(config: dict, w: Dict[str, jax.Array], x: jax.Array,
            p: Precision) -> jax.Array:
    q = _quant(config)
    if q is None:
        return x @ w["weight"].astype(jnp.float32)
    return p.act(x) @ dequantize(w, q["bits"], q["group_size"])


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


def embed(config: dict, w: dict, ids: jax.Array,
          p: Precision) -> jax.Array:
    return w["embed"]["weight"].astype(jnp.float32)[ids]


def layer(config: dict, w: dict, hidden: jax.Array,
          p: Precision) -> jax.Array:
    """`hidden` is `[batch, tokens, hidden]`, every sequence from its
    position 0; a sequence padded at its end is right up to its own
    last token, since the mask is causal."""
    _, heads, kv_heads, head, _ = _sizes(config)
    eps, theta = config["rms_norm_eps"], config.get("rope_theta", 10000.0)
    b, t, _ = hidden.shape
    x = rms_norm(hidden, w["input_layernorm"]["weight"], eps)
    qkv = _matmul(config, w["self_attn.qkv_proj"], x, p)
    q, k, v = jnp.split(qkv, [heads * head, (heads + kv_heads) * head], -1)
    q = rotary(q.reshape(b, t, heads, head), theta)
    k = p.kv(rotary(k.reshape(b, t, kv_heads, head), theta))
    v = p.kv(v.reshape(b, t, kv_heads, head))
    q = q.reshape(b, t, kv_heads, heads // kv_heads, head)
    scores = jnp.einsum("btkgd,bskd->bkgts", q, k) * head ** -0.5
    causal = jnp.tril(jnp.ones((t, t), dtype=bool))
    weights = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    mixed = jnp.einsum("bkgts,bskd->btkgd", weights, v).reshape(b, t, -1)
    hidden = hidden + _matmul(config, w["self_attn.o_proj"], mixed, p)
    x = rms_norm(hidden, w["post_attention_layernorm"]["weight"], eps)
    gate, up = jnp.split(_matmul(config, w["mlp.gate_up_proj"], x, p), 2, -1)
    return hidden + _matmul(config, w["mlp.down_proj"],
                            jax.nn.silu(gate) * up, p)


def logits(config: dict, w: dict, hidden: jax.Array,
           p: Precision) -> jax.Array:
    x = rms_norm(hidden, w["norm"]["weight"], config["rms_norm_eps"])
    head = w["head"]["weight"].astype(jnp.float32)
    return (x @ head.T)[..., :config["vocab_size"]]
