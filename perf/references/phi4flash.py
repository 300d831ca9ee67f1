"""Phi-4-mini-flash-reasoning in plain `jax.numpy`, float32 (microsoft,
`phi4flash`; written from the model's config.json and the published
descriptions: SambaY, arXiv:2507.06607; Mamba, arXiv:2312.00752; YOCO,
arXiv:2405.05254; Differential Transformer, arXiv:2410.05258; with no
import of the program).

With `x` the residual stream and `LN` a LayerNorm with gain and bias,
every layer is

    x = x + mixer(LN1(x));  x = x + W_down (up * silu(gate)),
        [gate ; up] = W_gate_up LN2(x)

and the logits are `LN_f(x) E^T` over the tied embedding `E`. There is
no positional encoding anywhere. The mixer by layer index `l`, `n`
layers (`kinds`):

- l even, l <= n/2: **Mamba**. `[u ; z] = W_in h`;
  `u = silu(conv1d(u))`, causal, depthwise, `d_conv` taps, bias;
  `[dt ; B ; C] = W_x u`; `delta = softplus(W_dt dt + b_dt)`;
  `s_t = exp(delta_t A) s_{t-1} + delta_t B_t u_t`, `A = -exp(A_log)`,
  for each channel and state; `y_t = C_t . s_t + D u_t`; output
  `W_out (y * silu(z))`. Layer n/2 also hands `y` to the layers below:
  the memory.
- l odd, l < n/2: **window** attention, and l = n/2 + 1: **full**
  attention, both **differential**: heads in pairs, query heads
  (2j, 2j+1) as `q1_j, q2_j`, KV heads (2i, 2i+1) as `k1_i, k2_i` and
  `v_i = [v_2i ; v_2i+1]`; query pair j uses KV pair j // (pairs of
  queries / pairs of KV). `o_j = (softmax(q1 k1^T / sqrt(d)) - lambda
  softmax(q2 k2^T / sqrt(d))) v`, `lambda = exp(lq1 . lk1) - exp(lq2 .
  lk2) + lambda_init`, `lambda_init = 0.8 - 0.6 exp(-0.3 l)`;
  `o_j = RMSNorm(o_j; gain) (1 - lambda_init)`; output `W_o [o_j] + b`.
  Causal, and for a window layer also `t - s < sliding_window`. The full
  layer's K and V are kept for the layers below.
- l even, l > n/2 + 1: **gated memory unit**,
  `W_out (memory * silu(W_in h))`.
- l odd, l > n/2 + 1: **cross** attention: queries of its own
  (`W_q h + b`), the full layer's K and V, causal; the same
  differential form.

Assumed, because config.json does not say: the Mamba sizes (`d_state`
16, `d_conv` 4, `expand` 2, `dt_rank` hidden / 16), the biases (on the
convolution, `dt` and the attention projections; none on the other
Mamba projections), the differential form and its pairing of heads, the
sub-norm's eps, and the layer-kind rule above.

The harness carries ONE array from stage to stage, tells a stage
function nothing of its place in the stack, and compiles one program
for each distinct (function, shapes and ranges of its weights). Two
things ride in that array beside the stream, along its last axis:

- the layer's index, as one more channel after the stream's (`embed`
  sets it to 0, every layer adds 1): `lambda_init` is a function of
  it, and with it in the data the attention layers of a kind share one
  program (16 functions by name, or a leaf with a range of its own a
  layer, would each be a program: minutes of compiling);
- from layer n/2 on, the memory, and from layer n/2 + 1 on the full
  layer's K and V: `[x ; l ; memory]`, then `[x ; l ; memory ; K ; V]`.

The share the harness reads of such a stage, |y - x| / |x|, then has
the unchanged parts in its denominator, and reads lower than the
stream's own share by the square root of their share of the norm
(`PERF.md` section 2); the index channel adds a thousandth.

No kernel, no cache, no batching beyond a leading axis: the scan is a
sequential `lax.scan` over time, attention a block of queries at a
time. The contract with the harness is stated at the top of
`perf/references/llama.py`.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp

VOCAB_PAD = 64          # the server pads its vocabulary rows to this
GAIN = [0.75, 1.25]     # a norm's gains
BIAS = [-0.1, 0.1]      # a norm's and a projection's biases
SUBLN_EPS = 1e-5
QUERY_BLOCK = 256
#: the embedding's spread. The head is the embedding: the logit of the
#: token a position was given carries |E_tok|^2, sqrt(hidden) standard
#: deviations of the logits over the others were the stream still its
#: embedding. The layers' outputs have a spread near 1 each, so at a
#: quarter the embedding is a twentieth of the final stream and that
#: term is some two deviations, under the largest of 200,064 draws.
EMBED = 0.25
#: the spread of a projection's output for an input of spread 1 (1 by
#: default). Queries and keys at 1.6 give scores a spread of 2.5 under
#: the model's own scale, so that a query looks at a few keys
#: (`perf/references/llama.py` has the argument). `x_proj` at 2 makes
#: what the state carries the larger part of a Mamba layer's `y` beside
#: the skip `D u`; `dt_proj` at 0.5 moves `delta` by its input without
#: drowning its bias.
SPREAD = {"self_attn.qkv_proj": 1.6, "self_attn.q_proj": 1.6,
          "mixer.x_proj": 2.0, "mixer.dt_proj": 0.5}
#: `delta = softplus(b_dt + ...)` with `b_dt` in [-6, -3] is 0.0025 to
#: 0.05, and `A = -exp(A_log)` with `A_log` in [-1.5, 1.5] is -0.22 to
#: -4.5: `exp(delta A)` forgets in 4 steps at one end and in 1,800 at
#: the other, so a Mamba layer's output depends on inputs 64 and 512
#: positions back (`tests/models/test_phi4flash.py` holds that).
DT_BIAS = [-6.0, -3.0]
A_LOG = [-1.5, 1.5]
SKIP = [0.0, 0.5]       # D
CONV = 0.5              # a tap's spread: four of them give 1
LAMBDA = [-0.1, 0.1]    # the four lambda vectors


@dataclasses.dataclass(frozen=True)
class Precision:
    """Where a control's lower precision enters: `kv` rounds keys and
    values as a cache of fewer bits would hold them, `act` rounds what
    goes into every matmul of a layer."""
    kv: Callable = staticmethod(lambda x: x)
    act: Callable = staticmethod(lambda x: x)


def kinds(config: dict) -> List[str]:
    n = config["num_hidden_layers"]
    if config.get("mb_per_layer", 2) != 2 or n % 4:
        raise ValueError("the layer-kind rule is written for "
                         "mb_per_layer 2 and a multiple of 4 layers")
    half = n // 2
    return ["mamba" if l % 2 == 0 and l <= half else
            "gmu" if l % 2 == 0 else
            "window" if l < half else
            "full" if l == half + 1 else "cross" for l in range(n)]


def lambda_init(layer) -> jax.Array:
    return 0.8 - 0.6 * jnp.exp(-0.3 * layer)


def _sizes(config: dict) -> dict:
    hidden, heads = config["hidden_size"], config["num_attention_heads"]
    d_inner = config.get("mamba_expand", 2) * hidden
    return dict(
        hidden=hidden, heads=heads, kv_heads=config["num_key_value_heads"],
        head=hidden // heads, inter=config["intermediate_size"],
        d_inner=d_inner, d_state=config.get("mamba_d_state", 16),
        d_conv=config.get("mamba_d_conv", 4),
        dt_rank=config.get("mamba_dt_rank") or -(-hidden // 16))


def _uniform(spread: float, fan_in: int) -> List[float]:
    a = spread * (3 / fan_in) ** 0.5
    return [-a, a]


def tree(config: dict) -> Dict[str, Dict[str, tuple]]:
    z = _sizes(config)
    dtype = config["torch_dtype"]
    hidden, d_inner, head = z["hidden"], z["d_inner"], z["head"]
    rows = -(-config["vocab_size"] // VOCAB_PAD) * VOCAB_PAD

    def linear(name, n_in, n_out, bias=False):
        leaves = {"weight": ((n_in, n_out), dtype,
                             _uniform(SPREAD.get(name, 1.0), n_in))}
        if bias:
            leaves["bias"] = ((n_out,), dtype, BIAS)
        return leaves

    norm = {"weight": ((hidden,), dtype, GAIN),
            "bias": ((hidden,), dtype, BIAS)}
    out = {"model.embed_tokens": {"weight": (
               (rows, hidden), dtype,
               [-EMBED * 3 ** 0.5, EMBED * 3 ** 0.5])},
           "model.final_layernorm": norm}
    for l, kind in enumerate(kinds(config)):
        at = f"model.layers.{l}."
        out[at + "input_layernorm"] = norm
        out[at + "post_attention_layernorm"] = norm
        out[at + "mlp.gate_up_proj"] = linear(
            "mlp.gate_up_proj", hidden, 2 * z["inter"])
        out[at + "mlp.down_proj"] = linear(
            "mlp.down_proj", z["inter"], hidden)
        if kind == "mamba":
            out[at + "mixer.in_proj"] = linear(
                "mixer.in_proj", hidden, 2 * d_inner)
            out[at + "mixer.conv1d"] = {
                "weight": ((z["d_conv"], d_inner), dtype,
                           [-CONV * 3 ** 0.5, CONV * 3 ** 0.5]),
                "bias": ((d_inner,), dtype, BIAS)}
            out[at + "mixer.x_proj"] = linear(
                "mixer.x_proj", d_inner, z["dt_rank"] + 2 * z["d_state"])
            out[at + "mixer.dt_proj"] = {
                **linear("mixer.dt_proj", z["dt_rank"], d_inner),
                "bias": ((d_inner,), dtype, DT_BIAS)}
            out[at + "mixer.ssm"] = {
                "A_log": ((z["d_state"], d_inner), dtype, A_LOG),
                "D": ((d_inner,), dtype, SKIP)}
            out[at + "mixer.out_proj"] = linear(
                "mixer.out_proj", d_inner, hidden)
        elif kind == "gmu":
            out[at + "mixer.in_proj"] = linear(
                "mixer.in_proj", hidden, d_inner)
            out[at + "mixer.out_proj"] = linear(
                "mixer.out_proj", d_inner, hidden)
        else:
            if kind == "cross":
                out[at + "self_attn.q_proj"] = linear(
                    "self_attn.q_proj", hidden, z["heads"] * head,
                    bias=True)
            else:
                out[at + "self_attn.qkv_proj"] = linear(
                    "self_attn.qkv_proj", hidden,
                    (z["heads"] + 2 * z["kv_heads"]) * head, bias=True)
            out[at + "self_attn.o_proj"] = linear(
                "self_attn.o_proj", z["heads"] * head, hidden, bias=True)
            vec = ((head,), dtype, LAMBDA)
            out[at + "self_attn.diff"] = {
                "lambda_q1": vec, "lambda_k1": vec, "lambda_q2": vec,
                "lambda_k2": vec,
                "subln": ((2 * head,), dtype, GAIN)}
    return out


_COMMON = ("input_layernorm", "post_attention_layernorm",
           "mlp.gate_up_proj", "mlp.down_proj")
_MAMBA = ("mixer.in_proj", "mixer.conv1d", "mixer.x_proj", "mixer.dt_proj",
          "mixer.ssm", "mixer.out_proj")
_BUCKETS = {
    "mamba": _MAMBA, "gmu": ("mixer.in_proj", "mixer.out_proj"),
    "window": ("self_attn.qkv_proj", "self_attn.o_proj", "self_attn.diff"),
    "full": ("self_attn.qkv_proj", "self_attn.o_proj", "self_attn.diff"),
    "cross": ("self_attn.q_proj", "self_attn.o_proj", "self_attn.diff")}


def stages(config: dict) -> List[Tuple[str, Dict[str, str]]]:
    out = [("embed", {"embed": "model.embed_tokens"})]
    half = config["num_hidden_layers"] // 2
    for l, kind in enumerate(kinds(config)):
        fn = "layer_mamba_memory" if l == half else "layer_" + kind
        out.append((fn, {b: f"model.layers.{l}.{b}"
                         for b in _COMMON + _BUCKETS[kind]}))
    out.append(("logits", {"norm": "model.final_layernorm",
                           "head": "model.embed_tokens"}))
    return out


# ---- the arithmetic ----

def _f32(x) -> jax.Array:
    return x.astype(jnp.float32)


def layer_norm(x: jax.Array, w: dict, eps: float) -> jax.Array:
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * _f32(w["weight"]) + \
        _f32(w["bias"])


def linear(w: dict, x: jax.Array, p: Precision) -> jax.Array:
    y = p.act(x) @ _f32(w["weight"])
    return y + _f32(w["bias"]) if "bias" in w else y


def attention(q: jax.Array, k: jax.Array, v: jax.Array, scale: float,
              window: int = None) -> jax.Array:
    """Causal attention of `q` `[b, t, kv_heads, group, d]` over `k`
    `[b, t, kv_heads, d]` and `v` `[b, t, kv_heads, dv]`, a query
    attending over the `window` newest keys, its own among them (all of
    them for None). A block of `QUERY_BLOCK` queries at a time, against
    the keys that block can see."""
    b, t, kv_heads, group, _ = q.shape
    block = min(QUERY_BLOCK, t)
    pad = -t % block
    q = jnp.pad(q, ((0, 0), (0, pad)) + ((0, 0),) * 3)
    span = t if window is None else min(t, window + block - 1)

    def one(first):
        at = jnp.clip(first + block - span, 0, t - span)
        qb = jax.lax.dynamic_slice_in_dim(q, first, block, axis=1)
        kb = jax.lax.dynamic_slice_in_dim(k, at, span, axis=1)
        vb = jax.lax.dynamic_slice_in_dim(v, at, span, axis=1)
        scores = jnp.einsum("btkgd,bskd->bkgts", qb, kb) * scale
        q_pos = first + jnp.arange(block)[:, None]
        k_pos = at + jnp.arange(span)[None, :]
        seen = k_pos <= q_pos
        if window is not None:
            seen &= k_pos > q_pos - window
        weights = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bkgts,bskd->btkgd", weights, vb)

    blocks = jax.lax.map(one, jnp.arange(0, t + pad, block))
    out = jnp.moveaxis(blocks, 0, 1).reshape((b, t + pad) + blocks.shape[3:])
    return out[:, :t]


def differential(config: dict, w: dict, layer: jax.Array, q: jax.Array,
                 k: jax.Array, v: jax.Array, p: Precision,
                 window: int = None) -> jax.Array:
    """`q` `[b, t, heads * d]`, `k` and `v` `[b, t, kv_heads * d]`
    (already as a cache holds them), `layer` the layer's index: the
    differential form, the sub-norm and the output projection."""
    z = _sizes(config)
    b, t, _ = q.shape
    d, pairs, kv_pairs = z["head"], z["heads"] // 2, z["kv_heads"] // 2
    q = q.reshape(b, t, kv_pairs, pairs // kv_pairs, 2, d)
    k = k.reshape(b, t, kv_pairs, 2, d)
    v = v.reshape(b, t, kv_pairs, 2 * d)
    a1v = attention(q[..., 0, :], k[..., 0, :], v, d ** -0.5, window)
    a2v = attention(q[..., 1, :], k[..., 1, :], v, d ** -0.5, window)
    diff = w["self_attn.diff"]
    lam_init = lambda_init(layer)
    lam = jnp.exp(jnp.sum(_f32(diff["lambda_q1"]) *
                          _f32(diff["lambda_k1"]))) - \
        jnp.exp(jnp.sum(_f32(diff["lambda_q2"]) *
                        _f32(diff["lambda_k2"]))) + lam_init
    o = a1v - lam * a2v                         # [b, t, kv_pairs, g, 2d]
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) +
                          SUBLN_EPS) * _f32(diff["subln"]) * (1 - lam_init)
    return linear(w["self_attn.o_proj"], o.reshape(b, t, -1), p)


def mamba(config: dict, w: dict, h: jax.Array, p: Precision
          ) -> Tuple[jax.Array, jax.Array]:
    """The mixer's output and `y`, the scan's result before the gate."""
    z = _sizes(config)
    n, rank, taps = z["d_state"], z["dt_rank"], z["d_conv"]
    t = h.shape[1]
    x, gate = jnp.split(linear(w["mixer.in_proj"], h, p), 2, axis=-1)
    conv_w = _f32(w["mixer.conv1d"]["weight"])
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    u = jax.nn.silu(_f32(w["mixer.conv1d"]["bias"]) + sum(
        conv_w[k] * padded[:, k:k + t] for k in range(taps)))
    dt, b_in, c_in = jnp.split(linear(w["mixer.x_proj"], u, p),
                               [rank, rank + n], axis=-1)
    delta = jax.nn.softplus(linear(w["mixer.dt_proj"], dt, p))
    a = -jnp.exp(_f32(w["mixer.ssm"]["A_log"]))         # [n, d_inner]
    skip = _f32(w["mixer.ssm"]["D"])

    def step(s, xs):
        u_t, dl_t, b_t, c_t = xs
        s = jnp.exp(dl_t[:, None, :] * a) * s + \
            (dl_t * u_t)[:, None, :] * b_t[:, :, None]
        return s, jnp.sum(s * c_t[:, :, None], axis=1) + skip * u_t

    s0 = jnp.zeros((h.shape[0], n, z["d_inner"]), jnp.float32)
    _, y = jax.lax.scan(step, s0, tuple(
        jnp.moveaxis(a_, 1, 0) for a_ in (u, delta, b_in, c_in)))
    y = jnp.moveaxis(y, 0, 1)
    return linear(w["mixer.out_proj"], y * jax.nn.silu(gate), p), y


def _mlp(config: dict, w: dict, x: jax.Array, p: Precision) -> jax.Array:
    h = layer_norm(x, w["post_attention_layernorm"],
                   config["layer_norm_eps"])
    gate, up = jnp.split(linear(w["mlp.gate_up_proj"], h, p), 2, axis=-1)
    return x + linear(w["mlp.down_proj"], up * jax.nn.silu(gate), p)


def _normed(config: dict, w: dict, x: jax.Array) -> jax.Array:
    return layer_norm(x, w["input_layernorm"], config["layer_norm_eps"])


def _split(config: dict, wide: jax.Array) -> List[jax.Array]:
    """`[x ; l ; memory ; K ; V]`, as far as the array goes; `l` comes
    back as the layer's index, a scalar."""
    z = _sizes(config)
    kv = z["kv_heads"] * z["head"]
    cuts = [z["hidden"], z["hidden"] + 1, z["hidden"] + 1 + z["d_inner"],
            z["hidden"] + 1 + z["d_inner"] + kv]
    x, layer, *rest = jnp.split(
        wide, [c for c in cuts if c < wide.shape[-1]], axis=-1)
    return [x, layer[0, 0, 0]] + rest


def _joined(x: jax.Array, layer: jax.Array, *rest) -> jax.Array:
    """The array that goes on to layer `layer + 1`."""
    return jnp.concatenate(
        [x, jnp.broadcast_to(layer + 1.0, x.shape[:-1] + (1,))] +
        list(rest), axis=-1)


def embed(config: dict, w: dict, ids: jax.Array,
          p: Precision) -> jax.Array:
    return _joined(_f32(w["embed"]["weight"])[ids], jnp.float32(-1.0))


def layer_mamba(config: dict, w: dict, wide: jax.Array,
                p: Precision) -> jax.Array:
    x, layer = _split(config, wide)
    out, _ = mamba(config, w, _normed(config, w, x), p)
    return _joined(_mlp(config, w, x + out, p), layer)


def layer_mamba_memory(config: dict, w: dict, wide: jax.Array,
                       p: Precision) -> jax.Array:
    """The last Mamba layer: `[x ; l ; memory]` goes on."""
    x, layer = _split(config, wide)
    out, memory = mamba(config, w, _normed(config, w, x), p)
    return _joined(_mlp(config, w, x + out, p), layer, memory)


def _self_attention(config, w, layer, x, p, window):
    z = _sizes(config)
    q, k, v = jnp.split(
        linear(w["self_attn.qkv_proj"], _normed(config, w, x), p),
        [z["heads"] * z["head"], (z["heads"] + z["kv_heads"]) * z["head"]],
        axis=-1)
    k, v = p.kv(k), p.kv(v)
    return differential(config, w, layer, q, k, v, p, window), k, v


def layer_window(config: dict, w: dict, wide: jax.Array,
                 p: Precision) -> jax.Array:
    x, layer = _split(config, wide)
    out, _, _ = _self_attention(config, w, layer, x, p,
                                int(config["sliding_window"]))
    return _joined(_mlp(config, w, x + out, p), layer)


def layer_full(config: dict, w: dict, wide: jax.Array,
               p: Precision) -> jax.Array:
    """`[x ; l ; memory]` in, `[x ; l ; memory ; K ; V]` on: K and V as
    the cache holds them."""
    x, layer, memory = _split(config, wide)
    out, k, v = _self_attention(config, w, layer, x, p, None)
    return _joined(_mlp(config, w, x + out, p), layer, memory, k, v)


def layer_gmu(config: dict, w: dict, wide: jax.Array,
              p: Precision) -> jax.Array:
    x, layer, memory, k, v = _split(config, wide)
    gate = jax.nn.silu(linear(w["mixer.in_proj"], _normed(config, w, x), p))
    out = linear(w["mixer.out_proj"], memory * gate, p)
    return _joined(_mlp(config, w, x + out, p), layer, memory, k, v)


def layer_cross(config: dict, w: dict, wide: jax.Array,
                p: Precision) -> jax.Array:
    x, layer, memory, k, v = _split(config, wide)
    q = linear(w["self_attn.q_proj"], _normed(config, w, x), p)
    out = differential(config, w, layer, q, k, v, p)
    return _joined(_mlp(config, w, x + out, p), layer, memory, k, v)


def logits(config: dict, w: dict, wide: jax.Array,
           p: Precision) -> jax.Array:
    x = _split(config, wide)[0]
    x = layer_norm(x, w["norm"], config["layer_norm_eps"])
    return (x @ _f32(w["head"]["weight"]).T)[..., :config["vocab_size"]]
