"""SmallThinker MoE (PowerInfer/SmallThinker-21BA3B-Instruct, -4BA0.6B).

No reference implementation in the CUDA tree; written from the
checkpoints' config.json and the model card's description: a decoder
whose layers alternate between full attention with NO positional
encoding and attention over a causal window of `sliding_window_size`
keys with rotary embedding (`sliding_window_layout[l]`, `rope_layout[l]`),
and whose feed-forward is 64 small ReGLU experts, 6 a token, routed from
the layer's INPUT: the router reads the residual stream as the layer
receives it, before the input norm and before attention, so that the
experts' weights can be fetched while attention runs.

    r = x W_r                            (router logits, from the input)
    y = x + Attn_l(RMSNorm(x))
    z = y + sum_k w_k W_down,k (relu(W_gate,k m) * W_up,k m),
        m = RMSNorm(y), k over the 6 largest r, w = softmax over them

Each layer names its page group (`common/config.py::PageGroups`): the
full layers keep every page, the window layers let theirs go. The
attention block, the norms and the rotary embedding are Llama's; the
experts are `FusedMoE` with its ReLU gate and the caller's router
logits. On one chip the grouped (`ragged_dot`) path is the one taken.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from aphrodite_tpu.common.config import PageGroups
from aphrodite_tpu.modeling.input_metadata import InputMetadata
from aphrodite_tpu.modeling.layers.attention import PagedAttention
from aphrodite_tpu.modeling.layers.fused_moe import (FusedMoE,
                                                     sum_counts)
from aphrodite_tpu.modeling.layers.layernorm import (fused_add_rms_norm,
                                                     rms_norm)
from aphrodite_tpu.modeling.layers.linear import (LinearMethod,
                                                  QKVParallelLinear,
                                                  RowParallelLinear)
from aphrodite_tpu.modeling.layers.rotary_embedding import get_rope
from aphrodite_tpu.modeling.layers.vocab_embedding import (
    ParallelLMHead, VocabParallelEmbedding)

KVCache = Tuple[jax.Array, jax.Array]
Params = Dict[str, Dict[str, jax.Array]]

#: counted in the step program by every expert layer and summed over
#: the layers: `ModelRunner` pulls them with the step's result
STEP_COUNTERS = ("moe.tokens_routed", "moe.experts_touched")


class SmallThinkerDecoderLayer:

    def __init__(self, config, idx: int, groups: PageGroups, dtype,
                 linear_method: Optional[LinearMethod]) -> None:
        self.prefix = f"model.layers.{idx}"
        self.rms_eps = config.rms_norm_eps
        self.dtype = dtype
        self.hidden_size = hidden = config.hidden_size
        self.num_heads = config.num_attention_heads
        self.num_kv_heads = config.num_key_value_heads
        self.head_dim = getattr(config, "head_dim", None) or \
            hidden // self.num_heads
        self.qkv_proj = QKVParallelLinear(
            hidden, self.head_dim, self.num_heads, self.num_kv_heads,
            bias=False, dtype=dtype, linear_method=linear_method)
        self.o_proj = RowParallelLinear(
            self.num_heads * self.head_dim, hidden, bias=False,
            dtype=dtype, linear_method=linear_method)
        # a full layer has no positional encoding at all
        self.rotary = get_rope(
            self.head_dim, self.head_dim,
            max_position=config.max_position_embeddings,
            base=config.rope_theta, is_neox_style=True,
            rope_scaling=getattr(config, "rope_scaling", None)) \
            if config.rope_layout[idx] else None
        self.attn = PagedAttention(
            self.num_heads, self.head_dim, scale=self.head_dim ** -0.5,
            num_kv_heads=self.num_kv_heads,
            sliding_window=config.sliding_window_size
            if config.sliding_window_layout[idx] else None,
            page_group=groups.group_of_layer[idx])
        self.cache_slot = groups.slot_of_layer[idx]
        self.num_experts = config.moe_num_primary_experts
        self.moe = FusedMoE(
            num_experts=self.num_experts,
            top_k=config.moe_num_active_primary_experts,
            hidden_size=hidden,
            intermediate_size=config.moe_ffn_hidden_size,
            renormalize=getattr(config, "norm_topk_prob", True),
            activation="relu", own_router=False, dtype=dtype)

    def init(self) -> Params:
        p = self.prefix
        ones = jnp.ones((self.hidden_size,), dtype=self.dtype)
        return {
            f"{p}.self_attn.qkv_proj": self.qkv_proj.init(),
            f"{p}.self_attn.o_proj": self.o_proj.init(),
            f"{p}.block_sparse_moe.primary_router": {
                "weight": jnp.zeros((self.hidden_size, self.num_experts),
                                    dtype=self.dtype)},
            f"{p}.block_sparse_moe.experts": self.moe.init(),
            f"{p}.input_layernorm": {"weight": ones},
            f"{p}.post_attention_layernorm": {"weight": ones},
        }

    def specs(self) -> Dict[str, Dict[str, P]]:
        p = self.prefix
        return {
            f"{p}.self_attn.qkv_proj": self.qkv_proj.specs(),
            f"{p}.self_attn.o_proj": self.o_proj.specs(),
            f"{p}.block_sparse_moe.primary_router": {
                "weight": P(None, None)},
            f"{p}.block_sparse_moe.experts": self.moe.specs(),
            f"{p}.input_layernorm": {"weight": P(None)},
            f"{p}.post_attention_layernorm": {"weight": P(None)},
        }

    def router_logits(self, params: Params, layer_input) -> jax.Array:
        """The 64 router logits, float32, from the stream as the
        layer receives it: before the input norm, before attention."""
        return layer_input.astype(jnp.float32) @ params[
            f"{self.prefix}.block_sparse_moe.primary_router"][
                "weight"].astype(jnp.float32)

    def __call__(self, params: Params, positions, hidden, residual,
                 kv_cache, metadata, counts: list):
        p = self.prefix
        normed, residual = fused_add_rms_norm(
            hidden, residual, params[f"{p}.input_layernorm"]["weight"],
            self.rms_eps)
        # `residual` is now the stream as this layer receives it
        router_logits = self.router_logits(params, residual)

        qkv = self.qkv_proj(params[f"{p}.self_attn.qkv_proj"], normed)
        q, k, v = self.qkv_proj.split(qkv)
        if self.rotary is not None:
            b, s = q.shape[:2]
            q, k = self.rotary(
                positions, q.reshape(b, s, self.num_heads, self.head_dim),
                k.reshape(b, s, self.num_kv_heads, self.head_dim))
            q, k = q.reshape(b, s, -1), k.reshape(b, s, -1)
        k_pages, v_pages = kv_cache if kv_cache is not None else (None, None)
        out, k_pages, v_pages = self.attn(q, k, v, k_pages, v_pages,
                                          metadata)
        attn_out = self.o_proj(params[f"{p}.self_attn.o_proj"], out)

        normed, residual = fused_add_rms_norm(
            attn_out, residual,
            params[f"{p}.post_attention_layernorm"]["weight"], self.rms_eps)
        moe_out = self.moe(params[f"{p}.block_sparse_moe.experts"], normed,
                           router_logits=router_logits, counts=counts)
        return moe_out, residual, \
            (None if k_pages is None else (k_pages, v_pages))


class SmallThinkerForCausalLM:

    @property
    def step_counters(self) -> Tuple[str, ...]:
        """What a step program of this model counts: `STEP_COUNTERS`,
        and the rows its expert kernels walk where they run."""
        return STEP_COUNTERS + self.layers[0].moe.kernel_counters

    def __init__(self, config, dtype: jnp.dtype = jnp.bfloat16,
                 linear_method: Optional[LinearMethod] = None) -> None:
        self.config = config
        self.dtype = dtype
        self.groups = PageGroups.of(
            [bool(x) for x in config.sliding_window_layout],
            config.sliding_window_size)
        self.embed_tokens = VocabParallelEmbedding(
            config.vocab_size, config.hidden_size, dtype=dtype)
        self.layers = [
            SmallThinkerDecoderLayer(config, i, self.groups, dtype,
                                     linear_method)
            for i in range(config.num_hidden_layers)]
        self.lm_head = ParallelLMHead(config.vocab_size,
                                      config.hidden_size, dtype=dtype)
        self.rms_eps = config.rms_norm_eps
        self.tie_word_embeddings = getattr(config, "tie_word_embeddings",
                                           False)
        #: experts a step could touch: experts x expert layers
        self.expert_slots = config.moe_num_primary_experts * \
            config.num_hidden_layers
        #: what the expert layers of the program being traced counted
        self._counts: list = []

    def init_params(self) -> Params:
        params: Params = {"model.embed_tokens": self.embed_tokens.init()}
        for layer in self.layers:
            params.update(layer.init())
        params["model.norm"] = {
            "weight": jnp.ones((self.config.hidden_size,),
                               dtype=self.dtype)}
        if not self.tie_word_embeddings:
            params["lm_head"] = self.lm_head.init()
        return params

    def param_specs(self) -> Dict[str, Dict[str, P]]:
        specs = {"model.embed_tokens": self.embed_tokens.specs()}
        for layer in self.layers:
            specs.update(layer.specs())
        specs["model.norm"] = {"weight": P(None)}
        if not self.tie_word_embeddings:
            specs["lm_head"] = self.lm_head.specs()
        return specs

    def __call__(self, params: Params, input_ids, positions,
                 kv_caches: Optional[List[KVCache]],
                 metadata: InputMetadata):
        """`kv_caches` is a pair of page arrays for each place in a
        page group (`PageGroups.layers_per_group`), not for each
        layer: the layers of the groups take turns on them."""
        hidden = self.embed_tokens(params["model.embed_tokens"],
                                   input_ids)
        residual = None
        caches = list(kv_caches) if kv_caches is not None else None
        self._counts = counts = []
        for layer in self.layers:
            cache = caches[layer.cache_slot] if caches is not None \
                else None
            hidden, residual, new_cache = layer(
                params, positions, hidden, residual, cache, metadata,
                counts)
            if new_cache is not None:
                caches[layer.cache_slot] = new_cache
        hidden = rms_norm(hidden + residual,
                          params["model.norm"]["weight"], self.rms_eps)
        return hidden, caches

    def take_step_counts(self) -> jax.Array:
        """`step_counters` of the step just traced, summed over its
        expert layers: int32, inside the same program."""
        counts, self._counts = self._counts, []
        return sum_counts(counts, self.step_counters)

    def compute_logits(self, params: Params, hidden):
        head = params["model.embed_tokens"] if self.tie_word_embeddings \
            else params["lm_head"]
        return self.lm_head.compute_logits(head, hidden)

    # ---- weight loading ----
    _STACKED = [("q_proj", "qkv_proj", "q"), ("k_proj", "qkv_proj", "k"),
                ("v_proj", "qkv_proj", "v")]
    # HF expert tensor name -> stacked param name
    _EXPERT_MAP = {"gate": "w_gate", "up": "w_up", "down": "w_down"}

    def load_weights(self, weights: Iterable[Tuple[str, np.ndarray]]):
        """HF names: `...block_sparse_moe.primary_router.weight`
        `[E, hidden]` and `...block_sparse_moe.experts.<id>.
        {gate,up,down}.weight`, as the published checkpoints name
        them."""
        loaders = {}
        for layer in self.layers:
            loaders[f"{layer.prefix}.self_attn.qkv_proj"] = layer.qkv_proj
            loaders[f"{layer.prefix}.self_attn.o_proj"] = layer.o_proj
        moes = {layer.prefix: layer.moe for layer in self.layers}
        params: Dict[str, Dict[str, np.ndarray]] = {}

        def bucket(key):
            return params.setdefault(key, {})

        for name, tensor in weights:
            if "rotary_emb.inv_freq" in name:
                continue
            if name.startswith("lm_head"):
                if not self.tie_word_embeddings:
                    self.lm_head.weight_loader(bucket("lm_head"), "weight",
                                               tensor)
                continue
            if name == "model.embed_tokens.weight":
                self.embed_tokens.weight_loader(
                    bucket("model.embed_tokens"), "weight", tensor)
                continue
            if name == "model.norm.weight":
                bucket("model.norm")["weight"] = tensor
                continue
            if name.endswith("_layernorm.weight"):
                key, pname = name.rsplit(".", 1)
                bucket(key)[pname] = tensor
                continue
            if ".block_sparse_moe." in name:
                prefix, rest = name.split(".block_sparse_moe.")
                if rest == "primary_router.weight":
                    bucket(f"{prefix}.block_sparse_moe.primary_router")[
                        "weight"] = np.ascontiguousarray(tensor.T)
                    continue
                parts = rest.split(".")     # experts.<id>.<which>.weight
                moes[prefix].load_expert_weight(
                    bucket(f"{prefix}.block_sparse_moe.experts"),
                    self._EXPERT_MAP[parts[2]], int(parts[1]), tensor)
                continue
            for hf_frag, merged, shard_id in self._STACKED:
                if f".{hf_frag}." in name:
                    key, pname = name.replace(hf_frag, merged).rsplit(".", 1)
                    loaders[key].weight_loader(bucket(key), pname, tensor,
                                               shard_id)
                    break
            else:
                key, pname = name.rsplit(".", 1)
                if key in loaders:
                    loaders[key].weight_loader(bucket(key), pname, tensor)
        return params
