"""EvaByte (EvaByte/EvaByte, 6.5B): a byte-level decoder with EVA's
chunked attention.

No reference implementation in the CUDA tree and no EvaByte source on
this machine; written from the checkpoint's config.json (`attention_class`
"eva", `window_size` 2,048, `chunk_size` 16, `num_pred_heads` 8,
`norm_add_unit_offset`, `fp32_skip_add`, `fp32_logits`, `mixedp_attn`)
and from EVA's paper (Zheng et al., "Efficient Attention via Control
Variates", ICLR 2023) in the simplified form the release describes.
What config.json leaves open is listed as `assumed` in
`perf/configs/evabyte-6.5b-bf16.json`.

    n(x)    = x / rms(x) * (1 + g)
    q, k, v = rope(n(x) W_q), rope(n(x) W_k), n(x) W_v     32 heads of 128
    query t, in window w = t // 2048, attends in ONE softmax over
        the exact keys j, 2048 w <= j <= t, and
        a pooled (kbar_c, vbar_c) for every 16-byte chunk c < 128 w
        (`modeling/layers/eva_attention.py`)
    y = x + a W_o ;  z = y + W_down(silu(W_gate n'(y)) * W_up n'(y))
    logits = n''(z) W_head[:320]^T

The residual stream and the logits are float32 (`fp32_skip_add`,
`fp32_logits`); weights, matmul operands, K, V and the pooled rows are
the model's type. The head matrix holds `num_pred_heads` heads of
`vocab_size` rows; head 0, the next-byte head, is served.

Every layer is of the page-group kind "pooled"
(`common/config.py::PageGroups`): the chunk is the KV page, so a
finished window's pooled rows are whole pages, and a layer's attention
is the existing decode and prefill kernels over the sequence's one
table `[summary pages ; window pages]`. Positions reach the keys
through the rotary embedding before they are written, never through
the table. The pooled rows are written by `summarise_windows`, a small
program of its own that the runner dispatches for the rows that closed
a window (`ModelRunner.summarise_windows`).
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from aphrodite_tpu.common.config import PageGroups
from aphrodite_tpu.modeling.input_metadata import InputMetadata
from aphrodite_tpu.modeling.layers.activation import silu_and_mul
from aphrodite_tpu.modeling.layers.attention import PagedAttention
from aphrodite_tpu.modeling.layers.eva_attention import summarise_pages
from aphrodite_tpu.modeling.layers.layernorm import rms_norm
from aphrodite_tpu.modeling.layers.linear import (LinearMethod,
                                                  MergedColumnParallelLinear,
                                                  QKVParallelLinear,
                                                  RowParallelLinear)
from aphrodite_tpu.modeling.layers.rotary_embedding import get_rope
from aphrodite_tpu.modeling.layers.vocab_embedding import (
    VocabParallelEmbedding)

KVCache = Tuple[jax.Array, jax.Array]
Params = Dict[str, Dict[str, jax.Array]]


def offset_rms_norm(x: jax.Array, gain: jax.Array, eps: float,
                    dtype) -> jax.Array:
    """`x / rms(x) * (1 + gain)` (`norm_add_unit_offset`) of the
    float32 stream, returned in `dtype`."""
    return rms_norm(x, 1.0 + gain.astype(jnp.float32), eps).astype(dtype)


class EvaByteDecoderLayer:

    def __init__(self, config, idx: int, groups: PageGroups, dtype,
                 linear_method: Optional[LinearMethod]) -> None:
        self.prefix = f"model.layers.{idx}"
        self.rms_eps = config.rms_norm_eps
        self.dtype = dtype
        self.hidden_size = hidden = config.hidden_size
        self.num_heads = config.num_attention_heads
        self.head_dim = hidden // self.num_heads
        self.scale = self.head_dim ** -0.5
        self.qkv_proj = QKVParallelLinear(
            hidden, self.head_dim, self.num_heads, self.num_heads,
            bias=False, dtype=dtype, linear_method=linear_method)
        self.o_proj = RowParallelLinear(
            hidden, hidden, bias=False, dtype=dtype,
            linear_method=linear_method)
        self.gate_up_proj = MergedColumnParallelLinear(
            hidden, [config.intermediate_size] * 2, dtype=dtype,
            linear_method=linear_method)
        self.down_proj = RowParallelLinear(
            config.intermediate_size, hidden, dtype=dtype,
            linear_method=linear_method)
        self.rotary = get_rope(
            self.head_dim, self.head_dim,
            max_position=config.max_position_embeddings,
            base=config.rope_theta, is_neox_style=True,
            rope_scaling=getattr(config, "rope_scaling", None))
        # (no window mask: what a query may see is what its table holds)
        self.attn = PagedAttention(
            self.num_heads, self.head_dim, scale=self.scale,
            num_kv_heads=self.num_heads,
            page_group=groups.group_of_layer[idx])
        self.cache_slot = groups.slot_of_layer[idx]

    def init(self) -> Params:
        p = self.prefix
        zeros = jnp.zeros((self.hidden_size,), dtype=self.dtype)
        vectors = jnp.zeros((self.num_heads, self.head_dim),
                            dtype=self.dtype)
        return {
            f"{p}.self_attn.qkv_proj": self.qkv_proj.init(),
            f"{p}.self_attn.o_proj": self.o_proj.init(),
            f"{p}.self_attn": {"adaptive_phi": vectors,
                               "adaptive_mu_k": vectors},
            f"{p}.mlp.gate_up_proj": self.gate_up_proj.init(),
            f"{p}.mlp.down_proj": self.down_proj.init(),
            # (a gain of 0 is a norm of 1 under the unit offset)
            f"{p}.input_layernorm": {"weight": zeros},
            f"{p}.post_attention_layernorm": {"weight": zeros},
        }

    def specs(self) -> Dict[str, Dict[str, P]]:
        p = self.prefix
        return {
            f"{p}.self_attn.qkv_proj": self.qkv_proj.specs(),
            f"{p}.self_attn.o_proj": self.o_proj.specs(),
            f"{p}.self_attn": {"adaptive_phi": P(None, None),
                               "adaptive_mu_k": P(None, None)},
            f"{p}.mlp.gate_up_proj": self.gate_up_proj.specs(),
            f"{p}.mlp.down_proj": self.down_proj.specs(),
            f"{p}.input_layernorm": {"weight": P(None)},
            f"{p}.post_attention_layernorm": {"weight": P(None)},
        }

    def __call__(self, params: Params, positions, stream, kv_cache,
                 metadata):
        """`stream`: the float32 residual stream; returns it with the
        layer added."""
        p = self.prefix
        normed = offset_rms_norm(
            stream, params[f"{p}.input_layernorm"]["weight"],
            self.rms_eps, self.dtype)
        qkv = self.qkv_proj(params[f"{p}.self_attn.qkv_proj"], normed)
        q, k, v = self.qkv_proj.split(qkv)
        b, s = q.shape[:2]
        q, k = self.rotary(
            positions, q.reshape(b, s, self.num_heads, self.head_dim),
            k.reshape(b, s, self.num_heads, self.head_dim))
        q, k = q.reshape(b, s, -1), k.reshape(b, s, -1)
        k_pages, v_pages = kv_cache if kv_cache is not None else (None, None)
        out, k_pages, v_pages = self.attn(q, k, v, k_pages, v_pages,
                                          metadata)
        stream = stream + self.o_proj(
            params[f"{p}.self_attn.o_proj"], out).astype(jnp.float32)
        normed = offset_rms_norm(
            stream, params[f"{p}.post_attention_layernorm"]["weight"],
            self.rms_eps, self.dtype)
        gate_up = self.gate_up_proj(params[f"{p}.mlp.gate_up_proj"], normed)
        stream = stream + self.down_proj(
            params[f"{p}.mlp.down_proj"],
            silu_and_mul(gate_up)).astype(jnp.float32)
        return stream, (None if k_pages is None else (k_pages, v_pages))

    def summarise(self, params: Params, kv_cache: KVCache, src, dst
                  ) -> KVCache:
        vectors = params[f"{self.prefix}.self_attn"]
        return summarise_pages(
            *kv_cache, src, dst, vectors["adaptive_phi"],
            vectors["adaptive_mu_k"], self.scale, self.num_heads)


class EvaByteForCausalLM:

    def __init__(self, config, dtype: jnp.dtype = jnp.bfloat16,
                 linear_method: Optional[LinearMethod] = None) -> None:
        self.config = config
        self.dtype = dtype
        self.groups = PageGroups.of(
            ["pooled"] * config.num_hidden_layers, None,
            pooled_window=config.window_size)
        self.embed_tokens = VocabParallelEmbedding(
            config.vocab_size, config.hidden_size, dtype=dtype)
        self.layers = [
            EvaByteDecoderLayer(config, i, self.groups, dtype,
                                linear_method)
            for i in range(config.num_hidden_layers)]
        self.rms_eps = config.rms_norm_eps
        self.vocab_size = config.vocab_size
        #: rows of the head matrix: `num_pred_heads` heads of
        #: `vocab_size` rows, the next-byte head first
        self.head_rows = config.num_pred_heads * config.vocab_size

    def init_params(self) -> Params:
        params: Params = {"model.embed_tokens": self.embed_tokens.init()}
        for layer in self.layers:
            params.update(layer.init())
        hidden = self.config.hidden_size
        params["model.norm"] = {
            "weight": jnp.zeros((hidden,), dtype=self.dtype)}
        params["lm_head"] = {
            "weight": jnp.zeros((self.head_rows, hidden),
                                dtype=self.dtype)}
        return params

    def param_specs(self) -> Dict[str, Dict[str, P]]:
        specs = {"model.embed_tokens": self.embed_tokens.specs()}
        for layer in self.layers:
            specs.update(layer.specs())
        specs["model.norm"] = {"weight": P(None)}
        specs["lm_head"] = {"weight": P(None, None)}
        return specs

    def __call__(self, params: Params, input_ids, positions,
                 kv_caches: Optional[List[KVCache]],
                 metadata: InputMetadata):
        """Returns the final norm's output in float32 (the head's
        input) and the page arrays, a pair a layer of the one page
        group."""
        stream = self.embed_tokens(params["model.embed_tokens"],
                                   input_ids).astype(jnp.float32)
        caches = list(kv_caches) if kv_caches is not None else None
        for layer in self.layers:
            cache = caches[layer.cache_slot] if caches is not None \
                else None
            stream, new_cache = layer(params, positions, stream, cache,
                                      metadata)
            if new_cache is not None:
                caches[layer.cache_slot] = new_cache
        hidden = offset_rms_norm(stream, params["model.norm"]["weight"],
                                 self.rms_eps, jnp.float32)
        return hidden, caches

    def compute_logits(self, params: Params, hidden) -> jax.Array:
        """Float32 logits of the next-byte head (`fp32_logits`)."""
        head = params["lm_head"]["weight"][:self.vocab_size]
        return jnp.matmul(hidden.astype(jnp.float32),
                          head.astype(jnp.float32).T,
                          precision=jax.lax.Precision.HIGHEST)

    def summarise_windows(self, params: Params,
                          kv_caches: List[KVCache], src, dst
                          ) -> List[KVCache]:
        """The pooled rows of the windows that rows have finished:
        `src[i]` the finished window's pages in order, `dst[i]` the
        summary pages taken for them, in every layer's page arrays."""
        caches = list(kv_caches)
        for layer in self.layers:
            caches[layer.cache_slot] = layer.summarise(
                params, caches[layer.cache_slot], src, dst)
        return caches

    # ---- weight loading ----
    _STACKED = [("q_proj", "qkv_proj", "q"), ("k_proj", "qkv_proj", "k"),
                ("v_proj", "qkv_proj", "v"),
                ("gate_proj", "gate_up_proj", 0),
                ("up_proj", "gate_up_proj", 1)]

    def load_weights(self, weights: Iterable[Tuple[str, np.ndarray]]):
        """The tensor names are ASSUMED (no EvaByte checkpoint is on
        this machine): the Llama family's, with
        `self_attn.adaptive_phi` and `self_attn.adaptive_mu_k`
        `[heads, head]` (any leading axes of size 1 dropped) beside
        the projections."""
        loaders = {}
        for layer in self.layers:
            p = layer.prefix
            loaders[f"{p}.self_attn.qkv_proj"] = layer.qkv_proj
            loaders[f"{p}.self_attn.o_proj"] = layer.o_proj
            loaders[f"{p}.mlp.gate_up_proj"] = layer.gate_up_proj
            loaders[f"{p}.mlp.down_proj"] = layer.down_proj
        params: Dict[str, Dict[str, np.ndarray]] = {}

        def bucket(key):
            return params.setdefault(key, {})

        heads = self.config.num_attention_heads
        for name, tensor in weights:
            if "rotary_emb.inv_freq" in name:
                continue
            if name == "lm_head.weight":
                bucket("lm_head")["weight"] = tensor
                continue
            if name == "model.embed_tokens.weight":
                self.embed_tokens.weight_loader(
                    bucket("model.embed_tokens"), "weight", tensor)
                continue
            if name == "model.norm.weight" or \
                    name.endswith("_layernorm.weight"):
                key, pname = name.rsplit(".", 1)
                bucket(key)[pname] = tensor
                continue
            key, pname = name.rsplit(".", 1)
            if pname in ("adaptive_phi", "adaptive_mu_k"):
                bucket(key)[pname] = np.asarray(tensor).reshape(heads, -1)
                continue
            for hf_frag, merged, shard_id in self._STACKED:
                if f".{hf_frag}." in name:
                    key, pname = name.replace(hf_frag, merged).rsplit(".", 1)
                    loaders[key].weight_loader(bucket(key), pname, tensor,
                                               shard_id)
                    break
            else:
                if key in loaders:
                    loaders[key].weight_loader(bucket(key), pname, tensor)
        return params
