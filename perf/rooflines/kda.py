"""What the two Kimi Delta Attention kernels have to move and to
compute (`ops/pallas/kda.py`; `kda-update*` and `kda-chunk*` in the
trace), one KDA layer's call each, and what a decode step of a
Kimi Linear configuration reads beside them.

The decode step's update, a row: its heads' matrices read and written
(heads x d x d float32, twice), the convolutions' tail read and
written (taps - 1 rows of 3 x heads x d channels in the model's type,
twice), the convolutions' new input in (the model's type), q, k, g, v
and the write strength in and the output out (float32). Operations:
seven a state element (decayed, read against k, written, read against
q), against the bf16 peak as the accepted state kernels' are
(`perf/rooflines/ssm_scan.py`): the table of peaks has none for the
vector unit, and the bytes bind by two orders of magnitude.

The chunk kernel, a token: q, k, v and g in and the output out (float32
rows of heads x d) and the write strength; a row's state is read and
written once a call. Operations, a chunk of C tokens and a head, as the
PRODUCTS the chunked form is made of (a multiply and an add each
element of a product): the two C x C score matrices over d (2 C^2 d),
the triangular solve as the eleven C x C x C products that invert I + A
(no row-by-row substitution is counted, and none is done), the solve's
result through the right-hand side and the scores through it (2 C^2 d),
the keys and the queries against the chunk's first state and the keys
into its last (3 C d^2). They are float32 products that the matmul unit
does in several bfloat16 passes; they are counted ONCE against the
bf16 peak, so the share cannot flatter the kernel. Padding is not
counted: `chunks` are the chunks that hold a live token.
"""
from __future__ import annotations

from typing import Tuple

OPS_AN_ELEMENT = 7.0
#: tokens a chunk (`ops/pallas/kda.py::CHUNK`) and the C x C x C
#: products of its inverse
CHUNK = 64
INVERSE_PRODUCTS = 11
LANE_TILE = 128


def _sizes(config: dict) -> Tuple[int, int, int]:
    stated = config["linear_attn_config"]
    return (stated["num_heads"], stated["head_dim"],
            stated["short_conv_kernel_size"])


def kda_layers(config: dict) -> int:
    """The KDA layers held: the entries of `kda_layers` (which count
    from one) up to `num_hidden_layers`."""
    return sum(1 for l in config["linear_attn_config"]["kda_layers"]
               if l <= config["num_hidden_layers"])


def state_bytes(config: dict) -> int:
    """A row's matrices in one KDA layer, float32."""
    heads, d, _ = _sizes(config)
    return heads * d * d * 4


def update_count(config: dict, rows: float,
                 bytes_per_value: int = 2) -> Tuple[float, float]:
    """`(bytes, operations)` of one layer's `kda-update` call over
    `rows` decode rows."""
    heads, d, taps = _sizes(config)
    width = heads * d
    row = 2 * state_bytes(config) \
        + 2 * (taps - 1) * 3 * width * bytes_per_value \
        + 3 * width * bytes_per_value + (5 * width + heads) * 4
    return rows * row, OPS_AN_ELEMENT * rows * heads * d * d


def chunk_count(config: dict, tokens: float, chunks: float,
                rows: float) -> Tuple[float, float]:
    """`(bytes, operations)` of one layer's `kda-chunk` call over
    `tokens` live prompt tokens in `chunks` chunks of `rows` prompt
    rows."""
    heads, d, _ = _sizes(config)
    moved = tokens * (5 * heads * d + heads) * 4 + \
        rows * 2 * state_bytes(config)
    products = 4 * CHUNK * CHUNK * d + INVERSE_PRODUCTS * CHUNK ** 3 + \
        3 * CHUNK * d * d
    return moved, 2.0 * products * heads * chunks


def latent_lanes(config: dict) -> int:
    """A row of an MLA layer's latent page: `[c | k_r]` up to whole
    lane tiles (NOT `head_dim`, which this family publishes as the
    hidden size over the heads)."""
    row = config["kv_lora_rank"] + config["qk_rope_head_dim"]
    return -(-row // LANE_TILE) * LANE_TILE


def latent_count(config: dict, live_pages: float, keys: float, rows: int,
                 page_size: int = 16, bytes_per_value: int = 2
                 ) -> Tuple[float, float]:
    """`(bytes, operations)` of one MLA layer's absorbed decode call
    over latent pages, as `perf/rooflines/paged_decode_latent.py::count`
    counts them, with the row from `kv_lora_rank + qk_rope_head_dim`."""
    heads, row = config["num_attention_heads"], latent_lanes(config)
    values = config["kv_lora_rank"]
    moved = (live_pages * page_size * row +
             rows * (heads * row + 2 * row + heads * values)) * \
        bytes_per_value
    return moved, 2.0 * heads * keys * (row + values)


def step_bytes(config: dict, rows: float, latent_tokens: float,
               experts_touched: float, bytes_per_value: int = 2
               ) -> Tuple[float, float]:
    """`(the KDA state's bytes, all the bytes)` of a decode step of
    `rows` rows by the configuration's own count: every KDA layer's
    state both ways; the KDA and MLA projections, the dense MLPs, each
    expert layer's router and shared experts, the `experts_touched`
    held experts with a pair (summed over the expert layers), the
    head's held rows; and the latent rows the MLA layers read
    (`latent_tokens`: the rows' context lengths summed)."""
    hidden, heads = config["hidden_size"], config["num_attention_heads"]
    latent, rope = config["kv_lora_rank"], config["qk_rope_head_dim"]
    nope, v_dim = config["qk_nope_head_dim"], config["v_head_dim"]
    k_heads, d, taps = _sizes(config)
    width = k_heads * d
    layers, n_kda = config["num_hidden_layers"], kda_layers(config)
    dense = config["first_k_dense_replace"]
    expert = 3 * hidden * config["moe_intermediate_size"]
    routed = config.get("num_routed_experts") or config["num_experts"]
    kda = hidden * 3 * width + taps * 3 * width + \
        hidden * (2 * d + k_heads) + 2 * d * width + width * hidden
    mla = hidden * heads * (nope + rope) + hidden * (latent + rope) \
        + latent * heads * (nope + v_dim) + heads * v_dim * hidden
    weights = bytes_per_value * (
        n_kda * kda + (layers - n_kda) * mla
        + dense * 3 * hidden * config["intermediate_size"]
        + (layers - dense) * (hidden * routed +
                              config["num_shared_experts"] * expert)
        + experts_touched * expert + config["vocab_size"] * hidden)
    state = n_kda * rows * 2 * state_bytes(config)
    pages = latent_tokens * (layers - n_kda) * latent_lanes(config) * \
        bytes_per_value
    return state, state + weights + pages
