"""The Sarvam MLA block in plain `jax.numpy`, float32 (sarvamai,
sarvam-105b, `model_type` "sarvam_mla"; written from the model's
config.json, whose keys are DeepSeek-V2's multi-head latent attention
one for one, with no import of the program and without the publisher's
code, which is not on this machine).

NOT absorbed: keys and values of every head are made from the latent,
as the equations have them. A layer with input `x`, 64 heads:

    h = RMSNorm(x)
    q = h W_q                          a head [q_nope 128 | q_rope 64]
    [c | k_r] = h W_kva                512 + 64
    c = RMSNorm_512(c)
    q_rope, k_r = rope(q_rope, k_r)    k_r ONE vector for all heads
    [k_nope_j | v_j] = c W_kvb         a head 128 + 128
    s_j = [q_nope_j | q_rope_j] . [k_nope_j | k_r] * 192^-0.5 * m^2
    a_j = softmax(s_j + causal mask) v_j
    y = x + concat_j(a_j) W_o
    z = RMSNorm(y)
    dense:   y + W_down (silu(W_gate z) * W_up z)
    sparse:  g = sigmoid(z W_r) over all routed experts;  S = the
             `num_experts_per_tok` largest of g + b;  w_e = g_e / sum_S g
             y + E_shared(z) + s * sum_{e in S, e held} w_e E_e(z)

`rope` is YaRN over the 64 rotary lanes (Peng et al. 2023: the
frequencies blended between theta's own and theta's divided by
`factor`, by a ramp between the dimensions that turn `beta_fast` and
`beta_slow` times over the original range), in the half-split form (Su
et al. 2021), with DeepSeek-V2's two mscales: cos and sin times
`mscale(factor, mscale) / mscale(factor, mscale_all_dim)` (1 here) and
the softmax scale times `m^2`, `m = mscale(factor, mscale_all_dim) =
0.1 * mscale_all_dim * ln(factor) + 1`. `E` is SwiGLU, `s`
`routed_scaling_factor`. What a token leaves in a cache is `[c | k_r]`:
a control that lowers the cache's precision rounds those two
(`Precision.kv`). Then a final RMSNorm and an untied head.

**One chip's share.** The configuration may state that this chip holds
`num_experts` of the `num_routed_experts` the router scores, those from
`first_held_expert` on (and `vocab_size` of the vocabulary's rows). The
router and the top-k are over all routed experts; only the held ones'
terms are summed. What the other experts would add is left out here as
in the program: nothing stands in for the other chips.

ASSUMED, because config.json does not say, and not checked against the
publisher's code (each is one place here, and one in the program):
(a) `use_qk_norm` is the RMSNorm on the latent (`layer`); (b) sigmoid
scores, the bias in the selection alone, renormalised top-k, no expert
groups (`experts`); (c) the shared expert added ungated (`layer`);
(d) half-split rotary pairs and `m^2` on the softmax scale; (e) no bias
in any projection; (f) the class name and the checkpoint's tensor names
(the program's loader; the tree below is the program's own).

No kernel, no cache, no batching beyond a leading axis. What would not
fit is computed in blocks of the same arithmetic: attention a block of
queries at a time, the held experts one after the other, each for
every token, under the router's mask.

The contract with the harness (`tree`, `stages`, `Precision`, `embed`,
the layer functions, `logits`) is stated at the top of
`perf/references/llama.py`.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp

VOCAB_PAD = 64          # the server pads its vocabulary rows to this
GAIN = [0.75, 1.25]     # a norm's gains
#: the spread of a projection's output for an input of spread 1 (1 by
#: default). Queries at 1.37 against keys of spread 1 (the latent is
#: normed, its up-projection and the rotary key's projection at 1),
#: under the softmax scale's `m^2` = 1.87, give scores a spread of
#: 2.5, so that a query looks at a few keys and not at the mean of
#: thousands (`perf/references/llama.py` has the argument); `o_proj`
#: at 1.5 gives back what averaging values takes. The router at 1 puts
#: the eight chosen scores between 0.82 and 0.92, a hundredth or two
#: apart, and the selection bias is drawn in +-0.02: small against
#: the sigmoid's spread, large enough to reorder neighbours, so that
#: a bias left out or added to the weights reads. An expert's down
#: projection at 1, as the shared expert's: under a sigmoid the eight
#: chosen weights are nearly equal, so the eighth expert swapped for
#: the ninth (which the stream's bfloat16 rounding decides where their
#: scores lie within it) adds or drops an eighth of the routed sum
#: where it crosses the share's edge; at 2 such a swap read gaps of
#: 1.5 spreads in a sound run (PERF.md section 6, PR 52, call 1).
SPREAD = {"self_attn.q_proj": 1.37, "self_attn.o_proj": 1.5,
          "router": 1.0, "expert_down": 1.0}
BIAS = [-0.02, 0.02]
QUERY_BLOCK = 256


@dataclasses.dataclass(frozen=True)
class Precision:
    """Where a control's lower precision enters: `kv` rounds what a
    cache of fewer bits would hold (the latent and the rotary key),
    `act` rounds what goes into every matmul of a layer."""
    kv: Callable = staticmethod(lambda x: x)
    act: Callable = staticmethod(lambda x: x)


def _uniform(spread: float, fan_in: int) -> List[float]:
    a = spread * (3 / fan_in) ** 0.5
    return [-a, a]


def _sparse(config: dict, i: int) -> bool:
    return i >= config["first_k_dense_replace"]


def tree(config: dict) -> Dict[str, Dict[str, tuple]]:
    hidden, heads = config["hidden_size"], config["num_attention_heads"]
    latent, rope = config["kv_lora_rank"], config["qk_rope_head_dim"]
    nope, v_dim = config["qk_nope_head_dim"], config["v_head_dim"]
    held = config["num_experts"]
    routed = config.get("num_routed_experts") or held
    inter = config["moe_intermediate_size"]
    dtype = config["torch_dtype"]
    rows = -(-config["vocab_size"] // VOCAB_PAD) * VOCAB_PAD

    def gain(width):
        return {"weight": ((width,), dtype, GAIN)}

    def linear(name, n_in, n_out):
        return {"weight": ((n_in, n_out), dtype,
                           _uniform(SPREAD.get(name, 1.0), n_in))}

    def mlp(at, width):
        return {at + "gate_up_proj": linear("", hidden, 2 * width),
                at + "down_proj": linear("", width, hidden)}

    out = {"model.embed_tokens": {
               "weight": ((rows, hidden), dtype, [-3 ** 0.5, 3 ** 0.5])},
           "model.norm": gain(hidden),
           "lm_head": {"weight": ((rows, hidden), dtype,
                                  _uniform(1.0, hidden))}}
    for i in range(config["num_hidden_layers"]):
        at = f"model.layers.{i}."
        out[at + "input_layernorm"] = gain(hidden)
        out[at + "post_attention_layernorm"] = gain(hidden)
        out[at + "self_attn.kv_a_layernorm"] = gain(latent)
        for name, n_in, n_out in (
                ("self_attn.q_proj", hidden, heads * (nope + rope)),
                ("self_attn.kv_a_proj_with_mqa", hidden, latent + rope),
                ("self_attn.kv_b_proj", latent, heads * (nope + v_dim)),
                ("self_attn.o_proj", heads * v_dim, hidden)):
            out[at + name] = linear(name, n_in, n_out)
        if not _sparse(config, i):
            out.update(mlp(at + "mlp.", config["intermediate_size"]))
            continue
        out[at + "mlp.experts"] = {
            "gate": ((hidden, routed), dtype,
                     _uniform(SPREAD["router"], hidden)),
            "e_bias": ((routed,), "float32", BIAS),
            "w_gate": ((held, hidden, inter), dtype, _uniform(1.0, hidden)),
            "w_up": ((held, hidden, inter), dtype, _uniform(1.0, hidden)),
            "w_down": ((held, inter, hidden), dtype,
                       _uniform(SPREAD["expert_down"], inter))}
        out.update(mlp(at + "mlp.shared_experts.",
                       inter * config["num_shared_experts"]))
    if not config.get("moe_router_enable_expert_bias", True):
        for bucket in out.values():
            bucket.pop("e_bias", None)
    return out


_ATTN = ("input_layernorm", "post_attention_layernorm",
         "self_attn.kv_a_layernorm", "self_attn.q_proj",
         "self_attn.kv_a_proj_with_mqa", "self_attn.kv_b_proj",
         "self_attn.o_proj")
DENSE_BUCKETS = _ATTN + ("mlp.gate_up_proj", "mlp.down_proj")
SPARSE_BUCKETS = _ATTN + ("mlp.experts", "mlp.shared_experts.gate_up_proj",
                          "mlp.shared_experts.down_proj")


def stages(config: dict) -> List[Tuple[str, Dict[str, str]]]:
    out = [("embed", {"embed": "model.embed_tokens"})]
    for i in range(config["num_hidden_layers"]):
        sparse = _sparse(config, i)
        out.append(("layer_sparse" if sparse else "layer_dense",
                    {b: f"model.layers.{i}.{b}" for b in
                     (SPARSE_BUCKETS if sparse else DENSE_BUCKETS)}))
    out.append(("logits", {"norm": "model.norm", "head": "lm_head"}))
    return out


def rms_norm(x: jax.Array, gain: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * gain.astype(jnp.float32)


def _mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def softmax_scale(config: dict) -> float:
    """`q_head_dim^-0.5 * m^2`: (d) DeepSeek-V2's `mscale_all_dim` on
    the whole head, squared (once for the query, once for the key)."""
    stated = config["rope_scaling"]
    m = _mscale(stated["factor"], stated["mscale_all_dim"]) \
        if stated.get("mscale_all_dim") else 1.0
    head = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    return head ** -0.5 * m * m


def inverse_frequencies(config: dict) -> Tuple[jax.Array, float]:
    """The angular frequencies of the rotary embedding over the
    `qk_rope_head_dim` rotary lanes as `rope_scaling` states it, and
    what cos and sin are multiplied by."""
    stated, dim = config["rope_scaling"], config["qk_rope_head_dim"]
    if stated["type"] != "deepseek_yarn":
        raise ValueError(f"no rotary embedding {stated['type']!r}")
    theta = float(config["rope_theta"])
    index = jnp.arange(dim // 2, dtype=jnp.float32)
    own = 1.0 / theta ** (2.0 * index / dim)
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
        _mscale(factor, stated.get("mscale", 1)) / \
        _mscale(factor, stated.get("mscale_all_dim", 0))


def rotary(x: jax.Array, config: dict) -> jax.Array:
    """`x` is `[batch, tokens, heads, rotary lanes]`, positions
    0..tokens-1; (d) the pair of a lane is the one half the rotary
    lanes away."""
    inv, scale = inverse_frequencies(config)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos = (jnp.cos(angle) * scale)[:, None, :]
    sin = (jnp.sin(angle) * scale)[:, None, :]
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(q: jax.Array, k: jax.Array, v: jax.Array,
              scale: float) -> jax.Array:
    """Causal attention of `q` `[b, t, heads, dk]` over `k` `[b, t,
    heads, dk]` and `v` `[b, t, heads, dv]`, a block of `QUERY_BLOCK`
    queries at a time: `[.., QUERY_BLOCK, t]` scores, not `[.., t,
    t]`. Returns `[b, t, heads, dv]`."""
    b, t = q.shape[:2]
    block = min(QUERY_BLOCK, t)
    pad = -t % block
    q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    k_pos = jnp.arange(t)[None, :]

    def one(first):
        qb = jax.lax.dynamic_slice_in_dim(q, first, block, axis=1)
        scores = jnp.einsum("bthd,bshd->bhts", qb, k) * scale
        seen = k_pos <= first + jnp.arange(block)[:, None]
        weights = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhts,bshd->bthd", weights, v)

    blocks = jax.lax.map(one, jnp.arange(0, t + pad, block))
    # [blocks, b, block, ...] -> [b, t, ...]
    out = jnp.moveaxis(blocks, 0, 1).reshape((b, t + pad) + blocks.shape[3:])
    return out[:, :t]


def swiglu(z: jax.Array, gate_up: jax.Array, down: jax.Array,
           p: Precision) -> jax.Array:
    """`W_down (silu(W_gate z) * W_up z)`, `gate_up` the two matrices
    side by side; `z` is rounded by the caller."""
    gate, up = jnp.split(z @ gate_up.astype(jnp.float32), 2, axis=-1)
    return p.act(jax.nn.silu(gate) * up) @ down.astype(jnp.float32)


def route(config: dict, w: dict, z: jax.Array
          ) -> Tuple[jax.Array, jax.Array]:
    """(b) `(weights [.., k], chosen [.., k])`: sigmoid scores of ALL
    routed experts; the `num_experts_per_tok` largest of score + bias
    are chosen; their weights are the scores WITHOUT the bias,
    renormalised over the chosen."""
    scores = jax.nn.sigmoid(z @ w["gate"].astype(jnp.float32))
    biased = scores + w["e_bias"] if "e_bias" in w else scores
    _, chosen = jax.lax.top_k(biased, config["num_experts_per_tok"])
    top = jnp.take_along_axis(scores, chosen, axis=-1)
    return top / jnp.sum(top, axis=-1, keepdims=True), chosen


def experts(config: dict, w: dict, z: jax.Array, p: Precision) -> jax.Array:
    """`sum_{e in S, e held} w_e E_e(z)`: every HELD expert for every
    token, kept where the router chose it. A chosen expert that is held
    elsewhere adds nothing here."""
    z = p.act(z)
    top, chosen = route(config, w, z)
    first = config.get("first_held_expert", 0)

    def add(total, expert):
        w_gate, w_up, w_down, e = expert
        mine = jnp.sum(jnp.where(chosen == e, top, 0.0), axis=-1)
        act = jax.nn.silu(z @ w_gate.astype(jnp.float32)) * \
            (z @ w_up.astype(jnp.float32))
        return total + mine[..., None] * (
            p.act(act) @ w_down.astype(jnp.float32)), None

    total, _ = jax.lax.scan(
        add, jnp.zeros_like(z),
        (w["w_gate"], w["w_up"], w["w_down"],
         first + jnp.arange(w["w_gate"].shape[0])))
    return total


def embed(config: dict, w: dict, ids: jax.Array,
          p: Precision) -> jax.Array:
    return w["embed"]["weight"].astype(jnp.float32)[ids]


def _heads(x: jax.Array, heads: int, first: int, second: int) -> jax.Array:
    """`x` `[.., heads * (first + second)]` as `[.., heads, first +
    second]`. The columns of `q_proj` and `kv_b_proj` lie as the
    served tree has them (`tree` states the program's own): every
    head's `first` lanes (nope lanes; keys), then every head's `second`
    (rotary lanes; values), where a checkpoint has a head's two parts
    side by side; the program's loader permutes."""
    lead = x.shape[:-1]
    return jnp.concatenate(
        [x[..., :heads * first].reshape(lead + (heads, first)),
         x[..., heads * first:].reshape(lead + (heads, second))], axis=-1)


def attend(config: dict, w: dict, hidden: jax.Array,
           p: Precision) -> jax.Array:
    """The attention block's output before the residual add; `hidden`
    is `[batch, tokens, hidden]`, every sequence from its position 0."""
    heads = config["num_attention_heads"]
    latent, rope = config["kv_lora_rank"], config["qk_rope_head_dim"]
    nope, v_dim = config["qk_nope_head_dim"], config["v_head_dim"]
    eps = config["rms_norm_eps"]
    b, t, _ = hidden.shape

    def weight(name):
        return w["self_attn." + name]["weight"].astype(jnp.float32)
    h = p.act(rms_norm(hidden, w["input_layernorm"]["weight"], eps))
    q = _heads(h @ weight("q_proj"), heads, nope, rope)
    kva = h @ weight("kv_a_proj_with_mqa")
    # (a) the norm of `use_qk_norm`: on the latent. What a cache holds
    # is the normed latent and the rotated key.
    c = p.kv(rms_norm(kva[..., :latent],
                      w["self_attn.kv_a_layernorm"]["weight"], eps))
    k_r = p.kv(rotary(kva[..., None, latent:], config))
    q = jnp.concatenate([q[..., :nope], rotary(q[..., nope:], config)], -1)
    kv = _heads(p.act(c) @ weight("kv_b_proj"), heads, nope, v_dim)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_r, (b, t, heads, rope))], -1)
    mixed = attention(q, k, kv[..., nope:], softmax_scale(config))
    return p.act(mixed.reshape(b, t, heads * v_dim)) @ weight("o_proj")


def layer(config: dict, w: dict, hidden: jax.Array, p: Precision,
          sparse: bool) -> jax.Array:
    hidden = hidden + attend(config, w, hidden, p)
    z = rms_norm(hidden, w["post_attention_layernorm"]["weight"],
                 config["rms_norm_eps"])
    if not sparse:
        return hidden + swiglu(p.act(z), w["mlp.gate_up_proj"]["weight"],
                               w["mlp.down_proj"]["weight"], p)
    # (c) the shared expert ungated, beside the routed sum times the
    # model's factor
    return hidden + config["routed_scaling_factor"] * experts(
        config, w["mlp.experts"], z, p) + swiglu(
            p.act(z), w["mlp.shared_experts.gate_up_proj"]["weight"],
            w["mlp.shared_experts.down_proj"]["weight"], p)


def layer_dense(config: dict, w: dict, hidden: jax.Array,
                p: Precision) -> jax.Array:
    return layer(config, w, hidden, p, sparse=False)


def layer_sparse(config: dict, w: dict, hidden: jax.Array,
                 p: Precision) -> jax.Array:
    return layer(config, w, hidden, p, sparse=True)


def logits(config: dict, w: dict, hidden: jax.Array,
           p: Precision) -> jax.Array:
    x = rms_norm(hidden, w["norm"]["weight"], config["rms_norm_eps"])
    head = w["head"]["weight"].astype(jnp.float32)
    return (x @ head.T)[..., :config["vocab_size"]]
