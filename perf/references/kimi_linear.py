"""The Kimi Linear block in plain `jax.numpy`, float32 (moonshotai,
Kimi-Linear-48B-A3B-Instruct, `model_type` "kimi_linear"; written from
the model's config.json and the equations of Kimi Linear,
arXiv:2510.26692, with no import of the program and without the
publisher's code, which is not on this machine).

A block is `h = h + mixer(RMSNorm(h))`, `h = h + mlp(RMSNorm(h))`,
eps `rms_norm_eps`; then a final RMSNorm and an untied head. The mixer
of layer `l` (counted from ONE) is by `linear_attn_config`:

**KDA** where `kda_layers` names it: a gated delta rule, computed here
a token at a time (a `lax.scan` over the tokens: no chunks, no WY
form), `H` heads of `d` x `d`, `x_t` the normed input:

    q_t = l2norm_head(silu(conv_q(W_q x)_t)) * d^-0.5        [H, d]
    k_t = l2norm_head(silu(conv_k(W_k x)_t))                 [H, d]
    v_t =             silu(conv_v(W_v x)_t)                  [H, d]
    g_t = -exp(A_log[h]) * softplus(W_fb (W_fa x_t) + dt_bias)   [H, d]
    b_t = sigmoid(W_b x_t)                                   [H]
    S'  = diag(exp(g_t)) S_{t-1}                             [d, d] a head
    S_t = S' + b_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t
    out = W_o (rmsnorm_head(o_t; gain[d]) * sigmoid(W_gb (W_ga x_t)))

`conv_*` is a causal depthwise convolution over the last
`short_conv_kernel_size` inputs of each channel, `l2norm(x) = x /
sqrt(sum x^2 + 1e-6)`.

**MLA** where `full_attn_layers` names it: DeepSeek-V2's multi-head
latent attention NOT absorbed, `q_lora_rank` null, and NO rotation
anywhere (`mla_use_nope`):

    q = x W_q                          a head [q_nope 128 | q_rest 64]
    [c | k_r] = x W_kva                512 + 64;  c = RMSNorm_512(c)
    [k_nope_j | v_j] = c W_kvb         a head 128 + 128
    s_j = [q_nope_j | q_rest_j] . [k_nope_j | k_r] * 192^-0.5
    a_j = softmax(s_j + causal mask) v_j;   out = concat_j(a_j) W_o

What a token leaves in a cache of such a layer is `[c | k_r]`: a control
that lowers the cache's precision rounds those two (`Precision.kv`).
A KDA layer's state has no control here: the harness has two kinds
(`kv`, `act_bits`) and neither is a state's.

**MLP**: layer 1 to `first_k_dense_replace` `W_down (silu(W_gate z) *
W_up z)`; later ones `g = sigmoid(z W_r)` over all routed experts, `S`
the `num_experts_per_token` largest of `g + bias`, `w_e = g_e / sum_S
g`, `E_shared(z) + s * sum_{e in S, e held} w_e E_e(z)` with `s`
`routed_scaling_factor` and `E` SwiGLU.

**One chip's share**, as `perf/references/sarvam_mla.py` has it: the
configuration may state that this chip holds `num_experts` of the
`num_routed_experts` the router scores, those from `first_held_expert`
on, `vocab_size` of the vocabulary's rows, and `num_hidden_layers` of
the layers the two lists name. The router and the top-k are over all
routed experts; only the held ones' terms are summed. Nothing stands
in for the other chips.

ASSUMED, because config.json does not say, and not checked against the
publisher's code (each is one place here, and one in the program):
(a) `mla_use_nope` keeps the `qk_rope_head_dim` lanes as an unrotated
part of q and of the one shared key; (b) the selection bias exists and
enters the top-k alone, the shared expert is added ungated; (c) in a
KDA layer the two gates' inner width is `head_dim`, no projection or
convolution has a bias, one `A_log` a head and one `dt_bias` a channel,
the L2 norm's eps 1e-6, q scaled by `d^-0.5` after the norm, the output
norm's gain one head-width vector shared by the heads, the state in
float32; (d) the class name and the checkpoint's tensor names (the
program's loader; the tree below is the program's own: q, k and v
projections, their convolutions, and `W_fa | W_ga | W_b` each as ONE
matrix).

No kernel, no cache, no batching beyond a leading axis. What would not
fit is computed in blocks of the same arithmetic: attention a block of
queries at a time, the held experts one after the other.

The contract with the harness (`tree`, `stages`, `Precision`, `embed`,
the layer functions, `logits`) is stated at the top of
`perf/references/llama.py`.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp

VOCAB_PAD = 64          # the server pads its vocabulary rows to this
GAIN = [0.75, 1.25]     # a norm's gains
#: the spread of a projection's output for an input of spread 1 (1 by
#: default). MLA: queries at 2.5 against keys of spread 1 (the latent
#: is normed, its up-projection and the shared key part's projection
#: at 1) give scores a spread of 2.5, so that a query looks at a few
#: keys and not at the mean of a thousand (`perf/references/llama.py`
#: has the argument; Sarvam's 1.37 had the rotary embedding's m^2 =
#: 1.87 beside it, which this model has not); `o_proj` at 1.5 gives
#: back what averaging values takes. KDA: `fgb_proj` (`W_fa | W_ga |
#: W_b`, one leaf and so one range) at 1.5 spreads `b = sigmoid(.)`
#: over 0.18-0.82 at one deviation, `f_b_proj` at 0.67 brings the
#: decay gate's argument back to spread 1, `o_proj` at 2 gives back
#: what the output gate (a sigmoid, 0.5 on average) takes. The router
#: and the experts as Sarvam's (`perf/references/sarvam_mla.py`).
SPREAD = {"self_attn.q_proj": 2.5, "self_attn.o_proj": 1.5,
          "self_attn.fgb_proj": 1.5, "self_attn.f_b_proj": 0.67,
          "kda.o_proj": 2.0, "router": 1.0, "expert_down": 1.0}
BIAS = [-0.02, 0.02]
#: the decay: `g = -exp(A_log) softplus(f + dt_bias)` with `f` of spread
#: 1. `dt_bias` in -3.5..-1.5 puts the softplus at 0.01-0.47 (0.08 at
#: the middle), `A_log` in -2.5..-1 multiplies by 0.08-0.37: `-g` lies
#: in 0.001-0.17 and `exp(g)` in 0.84-0.999 a token, 0.986 at the
#: middle: neither dead (a state that forgets within a few tokens
#: would hide a fault in what it carried) nor absent (`exp(g) = 1`
#: would hide one in the decay). A 1,024-token prompt's first token
#: is still 1e-6 of a channel at the middle decay, and whole at the
#: slowest.
A_LOG = [-2.5, -1.0]
DT_BIAS = [-3.5, -1.5]
#: a convolution's four taps: the sum of four inputs of spread 1 has
#: spread 1
CONV = 1.0
L2_EPS = 1e-6
QUERY_BLOCK = 256


@dataclasses.dataclass(frozen=True)
class Precision:
    """Where a control's lower precision enters: `kv` rounds what a
    cache of fewer bits would hold (an MLA layer's latent and shared
    key part), `act` rounds what goes into every matmul of a layer."""
    kv: Callable = staticmethod(lambda x: x)
    act: Callable = staticmethod(lambda x: x)


def _uniform(spread: float, fan_in: int) -> List[float]:
    a = spread * (3 / fan_in) ** 0.5
    return [-a, a]


def layer_kinds(config: dict) -> List[str]:
    """"kda" or "mla" for each layer held (the lists count from one)."""
    stated = config["linear_attn_config"]
    kda, mla = set(stated["kda_layers"]), set(stated["full_attn_layers"])
    kinds = []
    for l in range(1, config["num_hidden_layers"] + 1):
        if (l in kda) == (l in mla):
            raise ValueError(f"layer {l} is in both or neither of "
                             "kda_layers and full_attn_layers")
        kinds.append("kda" if l in kda else "mla")
    return kinds


def _sparse(config: dict, i: int) -> bool:
    return i >= config["first_k_dense_replace"]


def tree(config: dict) -> Dict[str, Dict[str, tuple]]:
    hidden, heads = config["hidden_size"], config["num_attention_heads"]
    latent, rope = config["kv_lora_rank"], config["qk_rope_head_dim"]
    nope, v_dim = config["qk_nope_head_dim"], config["v_head_dim"]
    stated = config["linear_attn_config"]
    k_heads, k_dim = stated["num_heads"], stated["head_dim"]
    taps, width = stated["short_conv_kernel_size"], k_heads * k_dim
    held = config["num_experts"]
    routed = config.get("num_routed_experts") or held
    inter = config["moe_intermediate_size"]
    dtype = config["torch_dtype"]
    rows = -(-config["vocab_size"] // VOCAB_PAD) * VOCAB_PAD

    def gain(size):
        return {"weight": ((size,), dtype, GAIN)}

    def linear(name, n_in, n_out):
        return {"weight": ((n_in, n_out), dtype,
                           _uniform(SPREAD.get(name, 1.0), n_in))}

    def mlp(at, size):
        return {at + "gate_up_proj": linear("", hidden, 2 * size),
                at + "down_proj": linear("", size, hidden)}

    out = {"model.embed_tokens": {
               "weight": ((rows, hidden), dtype, [-3 ** 0.5, 3 ** 0.5])},
           "model.norm": gain(hidden),
           "lm_head": {"weight": ((rows, hidden), dtype,
                                  _uniform(1.0, hidden))}}
    for i, kind in enumerate(layer_kinds(config)):
        at = f"model.layers.{i}."
        out[at + "input_layernorm"] = gain(hidden)
        out[at + "post_attention_layernorm"] = gain(hidden)
        if kind == "kda":
            for name, n_in, n_out in (
                    ("self_attn.qkv_proj", hidden, 3 * width),
                    ("self_attn.fgb_proj", hidden, 2 * k_dim + k_heads),
                    ("self_attn.f_b_proj", k_dim, width),
                    ("self_attn.g_b_proj", k_dim, width)):
                out[at + name] = linear(name, n_in, n_out)
            out[at + "self_attn.o_proj"] = linear("kda.o_proj", width,
                                                  hidden)
            out[at + "self_attn.conv1d"] = {"weight": (
                (taps, 3 * width), dtype, _uniform(CONV, taps))}
            out[at + "self_attn.kda"] = {
                "A_log": ((k_heads,), "float32", A_LOG),
                "dt_bias": ((width,), "float32", DT_BIAS)}
            out[at + "self_attn.o_norm"] = gain(k_dim)
        else:
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
    return out


_NORMS = ("input_layernorm", "post_attention_layernorm")
MIXER_BUCKETS = {
    "kda": _NORMS + tuple("self_attn." + b for b in (
        "qkv_proj", "fgb_proj", "f_b_proj", "g_b_proj", "o_proj", "conv1d",
        "kda", "o_norm")),
    "mla": _NORMS + tuple("self_attn." + b for b in (
        "kv_a_layernorm", "q_proj", "kv_a_proj_with_mqa", "kv_b_proj",
        "o_proj"))}
MLP_BUCKETS = {
    False: ("mlp.gate_up_proj", "mlp.down_proj"),
    True: ("mlp.experts", "mlp.shared_experts.gate_up_proj",
           "mlp.shared_experts.down_proj")}


def stages(config: dict) -> List[Tuple[str, Dict[str, str]]]:
    out = [("embed", {"embed": "model.embed_tokens"})]
    for i, kind in enumerate(layer_kinds(config)):
        sparse = _sparse(config, i)
        out.append((f"layer_{kind}_{'sparse' if sparse else 'dense'}",
                    {b: f"model.layers.{i}.{b}"
                     for b in MIXER_BUCKETS[kind] + MLP_BUCKETS[sparse]}))
    out.append(("logits", {"norm": "model.norm", "head": "lm_head"}))
    return out


def rms_norm(x: jax.Array, gain: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * gain.astype(jnp.float32)


def l2norm(x: jax.Array) -> jax.Array:
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def _weight(w: dict, name: str) -> jax.Array:
    return w["self_attn." + name]["weight"].astype(jnp.float32)


# ---- KDA ----

def causal_conv(x: jax.Array, taps: jax.Array) -> jax.Array:
    """`x` `[b, t, channels]`, `taps` `[n, channels]`: channel `c` of
    token `t` is `sum_i taps[i, c] x[t - (n - 1) + i, c]`, zeros before
    the sequence's first token."""
    n, t = taps.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (n - 1, 0), (0, 0)))
    return sum(taps[i] * padded[:, i:i + t] for i in range(n))


def delta_rule(q, k, v, g, b) -> jax.Array:
    """The recurrence, a token at a time from a zero state: `q`, `k`,
    `g` `[b, t, H, d]`, `v` `[b, t, H, d]`, `b` `[b, t, H]`; returns
    `o` `[b, t, H, d]`."""
    batch, _, heads, d = q.shape

    def step(s, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        s = jnp.exp(g_t)[..., None] * s                     # S'
        kept = jnp.einsum("bhkv,bhk->bhv", s, k_t)          # S'^T k
        s = s + k_t[..., None] * (b_t[..., None] * (v_t - kept)
                                  )[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t)

    _, o = jax.lax.scan(
        step, jnp.zeros((batch, heads, d, v.shape[-1]), jnp.float32),
        tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, b)))
    return jnp.moveaxis(o, 0, 1)


def kda(config: dict, w: dict, hidden: jax.Array, p: Precision) -> jax.Array:
    """The KDA mixer's output before the residual add."""
    stated = config["linear_attn_config"]
    heads, d = stated["num_heads"], stated["head_dim"]
    lead = hidden.shape[:2]
    h = p.act(rms_norm(hidden, w["input_layernorm"]["weight"],
                       config["rms_norm_eps"]))
    mixed = jax.nn.silu(causal_conv(
        h @ _weight(w, "qkv_proj"), _weight(w, "conv1d")))
    q, k, v = (x.reshape(lead + (heads, d))
               for x in jnp.split(mixed, 3, axis=-1))
    q, k = l2norm(q) * d ** -0.5, l2norm(k)                 # (c)
    f_a, g_a, b = jnp.split(h @ _weight(w, "fgb_proj"), [d, 2 * d], axis=-1)
    gate = w["self_attn.kda"]
    g = -jnp.exp(gate["A_log"])[:, None] * jax.nn.softplus(
        p.act(f_a) @ _weight(w, "f_b_proj") + gate["dt_bias"]
    ).reshape(lead + (heads, d))
    o = delta_rule(q, k, v, g, jax.nn.sigmoid(b))
    o = rms_norm(o, w["self_attn.o_norm"]["weight"], config["rms_norm_eps"])
    out_gate = jax.nn.sigmoid(p.act(g_a) @ _weight(w, "g_b_proj"))
    return p.act(o.reshape(lead + (heads * d,)) * out_gate) @ \
        _weight(w, "o_proj")


# ---- MLA ----

def attention(q: jax.Array, k: jax.Array, v: jax.Array,
              scale: float) -> jax.Array:
    """Causal attention of `q` `[b, t, heads, dk]` over `k` `[b, t,
    heads, dk]` and `v` `[b, t, heads, dv]`, a block of `QUERY_BLOCK`
    queries at a time. Returns `[b, t, heads, dv]`."""
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
    out = jnp.moveaxis(blocks, 0, 1).reshape((b, t + pad) + blocks.shape[3:])
    return out[:, :t]


def _heads(x: jax.Array, heads: int, first: int, second: int) -> jax.Array:
    """`x` `[.., heads * (first + second)]` as `[.., heads, first +
    second]`: the columns lie as the served tree has them, every head's
    `first` lanes and then every head's `second`
    (`perf/references/sarvam_mla.py::_heads`)."""
    lead = x.shape[:-1]
    return jnp.concatenate(
        [x[..., :heads * first].reshape(lead + (heads, first)),
         x[..., heads * first:].reshape(lead + (heads, second))], axis=-1)


def mla(config: dict, w: dict, hidden: jax.Array, p: Precision) -> jax.Array:
    """The MLA mixer's output before the residual add, NOT absorbed and
    (a) with no rotation."""
    heads = config["num_attention_heads"]
    latent, rope = config["kv_lora_rank"], config["qk_rope_head_dim"]
    nope, v_dim = config["qk_nope_head_dim"], config["v_head_dim"]
    eps = config["rms_norm_eps"]
    b, t, _ = hidden.shape
    h = p.act(rms_norm(hidden, w["input_layernorm"]["weight"], eps))
    q = _heads(h @ _weight(w, "q_proj"), heads, nope, rope)
    kva = h @ _weight(w, "kv_a_proj_with_mqa")
    # what a cache holds: the normed latent and the shared key part
    c = p.kv(rms_norm(kva[..., :latent],
                      w["self_attn.kv_a_layernorm"]["weight"], eps))
    k_r = p.kv(kva[..., None, latent:])
    kv = _heads(p.act(c) @ _weight(w, "kv_b_proj"), heads, nope, v_dim)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_r, (b, t, heads, rope))], -1)
    mixed = attention(q, k, kv[..., nope:], (nope + rope) ** -0.5)
    return p.act(mixed.reshape(b, t, heads * v_dim)) @ _weight(w, "o_proj")


# ---- the MLPs ----

def swiglu(z: jax.Array, gate_up: jax.Array, down: jax.Array,
           p: Precision) -> jax.Array:
    gate, up = jnp.split(z @ gate_up.astype(jnp.float32), 2, axis=-1)
    return p.act(jax.nn.silu(gate) * up) @ down.astype(jnp.float32)


def route(config: dict, w: dict, z: jax.Array
          ) -> Tuple[jax.Array, jax.Array]:
    """(b) `(weights [.., k], chosen [.., k])`: sigmoid scores of ALL
    routed experts; the `num_experts_per_token` largest of score + bias
    are chosen (one group); their weights are the scores WITHOUT the
    bias, renormalised over the chosen."""
    scores = jax.nn.sigmoid(z @ w["gate"].astype(jnp.float32))
    _, chosen = jax.lax.top_k(scores + w["e_bias"],
                              config["num_experts_per_token"])
    top = jnp.take_along_axis(scores, chosen, axis=-1)
    return top / jnp.sum(top, axis=-1, keepdims=True), chosen


def experts(config: dict, w: dict, z: jax.Array, p: Precision) -> jax.Array:
    """`sum_{e in S, e held} w_e E_e(z)`: every HELD expert for every
    token, kept where the router chose it."""
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


def feed_forward(config: dict, w: dict, hidden: jax.Array, p: Precision,
                 sparse: bool) -> jax.Array:
    z = rms_norm(hidden, w["post_attention_layernorm"]["weight"],
                 config["rms_norm_eps"])
    if not sparse:
        return swiglu(p.act(z), w["mlp.gate_up_proj"]["weight"],
                      w["mlp.down_proj"]["weight"], p)
    return config["routed_scaling_factor"] * experts(
        config, w["mlp.experts"], z, p) + swiglu(
            p.act(z), w["mlp.shared_experts.gate_up_proj"]["weight"],
            w["mlp.shared_experts.down_proj"]["weight"], p)


# ---- the stages ----

def embed(config: dict, w: dict, ids: jax.Array,
          p: Precision) -> jax.Array:
    return w["embed"]["weight"].astype(jnp.float32)[ids]


def _layer(mixer, sparse: bool):
    def layer(config: dict, w: dict, hidden: jax.Array,
              p: Precision) -> jax.Array:
        hidden = hidden + mixer(config, w, hidden, p)
        return hidden + feed_forward(config, w, hidden, p, sparse)
    return layer


layer_kda_dense = _layer(kda, False)
layer_kda_sparse = _layer(kda, True)
layer_mla_dense = _layer(mla, False)
layer_mla_sparse = _layer(mla, True)


def logits(config: dict, w: dict, hidden: jax.Array,
           p: Precision) -> jax.Array:
    x = rms_norm(hidden, w["norm"]["weight"], config["rms_norm_eps"])
    head = w["head"]["weight"].astype(jnp.float32)
    return (x @ head.T)[..., :config["vocab_size"]]
