"""Phi-4-mini-flash-reasoning (microsoft/Phi-4-mini-flash-reasoning,
`phi4flash`; SambaY, arXiv:2507.06607).

No reference implementation in the CUDA tree; written from the
checkpoint's config.json and the published descriptions (Mamba,
arXiv:2312.00752; YOCO, arXiv:2405.05254; Differential Transformer,
arXiv:2410.05258). A decoder of `n` layers, `LN` a LayerNorm with gain
and bias:

    x = x + mixer_l(LN1_l(x));  x = x + W_down (up * silu(gate)),
        [gate ; up] = W_gate_up LN2_l(x)

with no positional encoding anywhere, and the logits `LN_f(x) E^T` over
the tied embedding. The mixer by layer index
(`transformers_utils/configs/phi4flash.py::layer_kinds`):

- **mamba** (`layers/mamba.py::MambaMixer`, shared with
  `models/jamba.py`): a selective state-space layer. Its state
  `[d_state, d_inner]` (float32) and the last `d_conv - 1` inputs of its
  causal convolution live in the sequence's STATE SLOT, beside the KV
  pages (`common/config.py::StateSpec`): a prompt chunk starts from the
  slot (from zeros at position 0) and leaves its last token's state
  there, a decode step moves it on by one token in place
  (`ops/pallas/ssm_scan.py`). The last mamba layer also hands its
  scan's output, before the gate, to the layers below: the memory.
- **window**, **full** (`DiffAttention`): differential attention,
  `(softmax(q1 k1^T) - lambda softmax(q2 k2^T)) v` over pairs of heads,
  a sub-norm over each pair's output. The full layer's K and V are kept
  for the layers below.
- **gmu** (`GatedMemoryUnit`): `W_out (memory * silu(W_in h))`.
- **cross** (`DiffAttention` without K and V of its own): its queries
  over the full layer's pages; it writes none
  (`common/config.py::PageGroups`: "reads layer k's pages").

Differential attention needs no attention kernel of its own: a pair of
KV heads is held as ONE head of twice the size, `K = [k1 ; k2]`,
`V = [v1 ; v2]`, and a pair of query heads is sent as two heads of that
size, `[q1 ; 0]` and `[0 ; q2]`, with the scale of the model's own
head. The paged kernels then return `A1 v` and `A2 v`, and the
difference, the sub-norm and the factor follow here.
"""
from __future__ import annotations

import math
from typing import Dict, Iterable, List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from aphrodite_tpu.common.config import PageGroups
from aphrodite_tpu.modeling.input_metadata import InputMetadata
from aphrodite_tpu.modeling.layers.activation import silu_and_mul
from aphrodite_tpu.modeling.layers.attention import PagedAttention
from aphrodite_tpu.modeling.layers.layernorm import layer_norm, rms_norm
from aphrodite_tpu.modeling.layers.linear import (
    ColumnParallelLinear, LinearMethod, MergedColumnParallelLinear,
    QKVParallelLinear, RowParallelLinear, replicated_specs)
from aphrodite_tpu.modeling.layers.mamba import MambaMixer
from aphrodite_tpu.modeling.layers.vocab_embedding import (
    ParallelLMHead, VocabParallelEmbedding)

KVCache = Tuple[jax.Array, jax.Array]
Params = Dict[str, Dict[str, jax.Array]]

SUBLN_EPS = 1e-5
#: queries x keys a row from which a prompt chunk's attention takes the
#: keys in blocks: 40 heads of float32 scores over 2,048 x 2,048 are
#: 671 MB a layer, so a whole chunk is blocked already
PREFILL_BLOCKED_FROM = 1 << 21


def lambda_init(layer_idx: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * layer_idx)


class DiffAttention:
    """Differential attention over pairs of heads, through the paged
    kernels (module docstring). `own_kv` False: a cross layer, queries
    only, over the pages and this step's K and V of the full layer."""

    def __init__(self, config, layer_idx: int, prefix: str,
                 groups: PageGroups, dtype,
                 linear_method: Optional[LinearMethod], *,
                 window: Optional[int], own_kv: bool) -> None:
        self.prefix = prefix
        self.dtype = dtype
        self.own_kv = own_kv
        hidden = config.hidden_size
        self.num_heads = config.num_attention_heads
        self.num_kv_heads = config.num_key_value_heads
        self.head_dim = hidden // self.num_heads
        self.pairs = self.num_heads // 2
        self.lambda_init = lambda_init(layer_idx)
        kw = dict(bias=True, dtype=dtype, linear_method=linear_method)
        if own_kv:
            self.qkv_proj = QKVParallelLinear(
                hidden, self.head_dim, self.num_heads, self.num_kv_heads,
                **kw)
        else:
            self.q_proj = ColumnParallelLinear(
                hidden, self.num_heads * self.head_dim, **kw)
        self.o_proj = RowParallelLinear(
            self.num_heads * self.head_dim, hidden, **kw)
        # a pair of KV heads is one head of twice the size; a pair of
        # query heads two of them, each half zeros
        self.attn = PagedAttention(
            self.num_heads, 2 * self.head_dim, scale=self.head_dim ** -0.5,
            num_kv_heads=self.num_kv_heads // 2, sliding_window=window,
            page_group=groups.group_of_layer[layer_idx],
            writes_kv=own_kv, blocked_from=PREFILL_BLOCKED_FROM)
        self.cache_slot = groups.slot_of_layer[layer_idx]

    def init(self) -> Params:
        p = self.prefix
        vec = jnp.zeros((self.head_dim,), dtype=self.dtype)
        proj = {f"{p}.qkv_proj": self.qkv_proj.init()} if self.own_kv \
            else {f"{p}.q_proj": self.q_proj.init()}
        return {
            **proj,
            f"{p}.o_proj": self.o_proj.init(),
            f"{p}.diff": {
                "lambda_q1": vec, "lambda_k1": vec, "lambda_q2": vec,
                "lambda_k2": vec,
                "subln": jnp.ones((2 * self.head_dim,), dtype=self.dtype)},
        }

    def __call__(self, params: Params, h: jax.Array, cache: KVCache,
                 metadata: InputMetadata, shared_kv=None):
        """Returns the output, the page arrays as the layer leaves
        them, and this step's packed `(k, v)` (`shared_kv` passed on
        by a cross layer)."""
        p = self.prefix
        batch, seq = h.shape[:2]
        if self.own_kv:
            q, k, v = self.qkv_proj.split(
                self.qkv_proj(params[f"{p}.qkv_proj"], h))
            shared_kv = (k, v)      # 20 heads of 64 are 10 of 128
        else:
            q = self.q_proj(params[f"{p}.q_proj"], h)
            # a decode step reads the pages alone
            k, v = shared_kv if metadata.is_prompt else (None, None)
        # heads (2j, 2j+1) -> [q1_j ; 0] and [0 ; q2_j]
        q = q.reshape(batch, seq, self.pairs, 2, self.head_dim)
        zeros = jnp.zeros_like(q[..., 0, :])
        packed = jnp.stack(
            [jnp.concatenate([q[..., 0, :], zeros], axis=-1),
             jnp.concatenate([zeros, q[..., 1, :]], axis=-1)], axis=3)
        k_pages, v_pages = cache if cache is not None else (None, None)
        out, k_pages, v_pages = self.attn(
            packed.reshape(batch, seq, -1), k, v, k_pages, v_pages,
            metadata)
        out = out.reshape(batch, seq, self.pairs, 2, 2 * self.head_dim
                          ).astype(jnp.float32)
        diff = params[f"{p}.diff"]
        lam = jnp.exp(jnp.sum(diff["lambda_q1"].astype(jnp.float32) *
                              diff["lambda_k1"].astype(jnp.float32))) - \
            jnp.exp(jnp.sum(diff["lambda_q2"].astype(jnp.float32) *
                            diff["lambda_k2"].astype(jnp.float32))) + \
            self.lambda_init
        mixed = rms_norm(out[..., 0, :] - lam * out[..., 1, :],
                         diff["subln"], SUBLN_EPS) * (1.0 - self.lambda_init)
        out = self.o_proj(params[f"{p}.o_proj"],
                          mixed.reshape(batch, seq, -1).astype(self.dtype))
        return out, (None if cache is None else (k_pages, v_pages)), \
            shared_kv


class GatedMemoryUnit:
    """`W_out (memory * silu(W_in h))`, the memory the last mamba
    layer's scan output at the same position."""

    def __init__(self, config, prefix: str, dtype,
                 linear_method: Optional[LinearMethod]) -> None:
        self.prefix = prefix
        self.dtype = dtype
        kw = dict(bias=False, dtype=dtype, linear_method=linear_method)
        self.in_proj = ColumnParallelLinear(
            config.hidden_size, config.mamba_d_inner, **kw)
        self.out_proj = RowParallelLinear(
            config.mamba_d_inner, config.hidden_size, **kw)

    def init(self) -> Params:
        return {f"{self.prefix}.in_proj": self.in_proj.init(),
                f"{self.prefix}.out_proj": self.out_proj.init()}

    def __call__(self, params: Params, h: jax.Array, memory: jax.Array):
        gate = jax.nn.silu(self.in_proj(
            params[f"{self.prefix}.in_proj"], h).astype(jnp.float32))
        return self.out_proj(params[f"{self.prefix}.out_proj"],
                             memory * gate.astype(self.dtype))


class Phi4FlashDecoderLayer:

    def __init__(self, config, idx: int, kind: str, groups: PageGroups,
                 dtype, linear_method: Optional[LinearMethod]) -> None:
        self.prefix = prefix = f"model.layers.{idx}"
        self.kind = kind
        self.dtype = dtype
        self.hidden_size = config.hidden_size
        self.eps = config.layer_norm_eps
        if kind == "mamba":
            self.mixer = MambaMixer(config, f"{prefix}.mixer", dtype,
                                    linear_method)
        elif kind == "gmu":
            self.mixer = GatedMemoryUnit(config, f"{prefix}.mixer", dtype,
                                         linear_method)
        else:
            self.mixer = DiffAttention(
                config, idx, f"{prefix}.self_attn", groups, dtype,
                linear_method,
                window=config.sliding_window if kind == "window" else None,
                own_kv=kind != "cross")
        self.gate_up_proj = MergedColumnParallelLinear(
            config.hidden_size, [config.intermediate_size] * 2,
            bias=config.mlp_bias, dtype=dtype, linear_method=linear_method)
        self.down_proj = RowParallelLinear(
            config.intermediate_size, config.hidden_size,
            bias=config.mlp_bias, dtype=dtype, linear_method=linear_method)

    def _norm(self) -> Dict[str, jax.Array]:
        return {"weight": jnp.ones((self.hidden_size,), dtype=self.dtype),
                "bias": jnp.zeros((self.hidden_size,), dtype=self.dtype)}

    def init(self) -> Params:
        p = self.prefix
        return {
            **self.mixer.init(),
            f"{p}.input_layernorm": self._norm(),
            f"{p}.post_attention_layernorm": self._norm(),
            f"{p}.mlp.gate_up_proj": self.gate_up_proj.init(),
            f"{p}.mlp.down_proj": self.down_proj.init(),
        }

    def mlp(self, params: Params, x: jax.Array) -> jax.Array:
        p = self.prefix
        norm = params[f"{p}.post_attention_layernorm"]
        h = layer_norm(x, norm["weight"], norm["bias"], self.eps)
        return x + self.down_proj(
            params[f"{p}.mlp.down_proj"],
            silu_and_mul(self.gate_up_proj(
                params[f"{p}.mlp.gate_up_proj"], h)))

    def normed(self, params: Params, x: jax.Array) -> jax.Array:
        norm = params[f"{self.prefix}.input_layernorm"]
        return layer_norm(x, norm["weight"], norm["bias"], self.eps)


class Phi4FlashForCausalLM:

    def __init__(self, config, dtype: jnp.dtype = jnp.bfloat16,
                 linear_method: Optional[LinearMethod] = None) -> None:
        self.config = config
        self.dtype = dtype
        kinds = config.layer_kinds
        self.groups = PageGroups.of(config.page_layer_kinds,
                                    config.sliding_window, stateful=True)
        #: the attention layers' `blocked_from`, for the runner's
        #: count of the tiles a prompt step visits
        self.prefill_blocked_from = PREFILL_BLOCKED_FROM
        self.embed_tokens = VocabParallelEmbedding(
            config.vocab_size, config.hidden_size, dtype=dtype)
        self.layers = [
            Phi4FlashDecoderLayer(config, i, kind, self.groups, dtype,
                                  linear_method)
            for i, kind in enumerate(kinds)]
        self.lm_head = ParallelLMHead(config.vocab_size,
                                      config.hidden_size, dtype=dtype)
        #: the model's one `(tail, state)` pair follows the page pairs
        #: in `kv_caches`; a mamba layer's place on its leading axis
        self.state_pair = self.groups.layers_per_group
        self.state_at = {
            layer.prefix: i for i, layer in enumerate(
                l for l in self.layers if l.kind == "mamba")}

    def init_params(self) -> Params:
        params: Params = {"model.embed_tokens": self.embed_tokens.init()}
        for layer in self.layers:
            params.update(layer.init())
        params["model.final_layernorm"] = self.layers[0]._norm()
        return params

    def param_specs(self) -> Dict[str, Dict[str, P]]:
        """One chip holds the model whole (the state arrays and the
        Pallas scan are single-device programs): every leaf
        replicated."""
        return replicated_specs(jax.eval_shape(self.init_params))

    def __call__(self, params: Params, input_ids, positions,
                 kv_caches: Optional[List[KVCache]],
                 metadata: InputMetadata):
        """`kv_caches`: a pair of page arrays for each place in a page
        group, then the one `(tail, state)` pair of all the mamba
        layers."""
        x = self.embed_tokens(params["model.embed_tokens"], input_ids)
        caches = list(kv_caches) if kv_caches is not None else None
        memory = shared_kv = None
        for layer in self.layers:
            h = layer.normed(params, x)
            if layer.kind == "mamba":
                at = self.state_pair
                out, memory, new = layer.mixer(
                    params, h, positions,
                    caches[at] if caches is not None else None, metadata,
                    self.state_at[layer.prefix])
                if new is not None:
                    caches[at] = new
            elif layer.kind == "gmu":
                out = layer.mixer(params, h, memory)
            else:
                at = layer.mixer.cache_slot
                out, new, shared_kv = layer.mixer(
                    params, h, caches[at] if caches is not None else None,
                    metadata, shared_kv if layer.kind == "cross" else None)
                if new is not None:
                    caches[at] = new
            x = layer.mlp(params, x + out)
        norm = params["model.final_layernorm"]
        return layer_norm(x, norm["weight"], norm["bias"],
                          self.config.layer_norm_eps), caches

    def compute_logits(self, params: Params, hidden):
        return self.lm_head.compute_logits(params["model.embed_tokens"],
                                           hidden)

    def load_weights(self, weights: Iterable[Tuple[str, np.ndarray]]):
        raise NotImplementedError(
            "Phi4FlashForCausalLM is served with --load-format dummy: "
            "how the published checkpoint pairs its heads for the "
            "differential form is not stated in its config.json, and no "
            "loader is promised (perf/configs/phi-4-mini-flash-bf16.json"
            ", `assumed`)")
