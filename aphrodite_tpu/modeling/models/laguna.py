"""Laguna (poolside/Laguna-S-2.1, -XS.2).

No reference implementation in the CUDA tree and no publisher's code
on this machine; written from the checkpoints' config.json. What that
file leaves open is ASSUMED, each in one place here (the same list is
in `perf/configs/laguna-s-2.1-bf16.json` and in the benchmark's
reference): (a) the gate is a sigmoid of a linear map of the attention
block's normed input, a scalar a head, applied to that head's output
before `o_proj` (`LagunaDecoderLayer._gate`); (b) the router scores by
softmax over all its logits before the top-k (`FusedMoE.route`);
(c) the shared expert is added ungated and unscaled; (d) no bias
anywhere and no norm on queries or keys; (e) the class name and the
checkpoint's tensor names (`load_weights`).

A layer `l` reads what it is from the config's per-layer lists: `H_l`
query heads over the model's KV heads, a full layer or one under a
causal window, its rotary embedding (`rope_parameters`: the full
layers' YaRN over half of each head, the window layers' plain one over
the whole), a gate a head, and a dense SwiGLU MLP or the experts:

    h = RMSNorm(x);  a = Attn_l(rope_l(h W_q, h W_k), h W_v)
    y = x + (sigmoid(h W_g)[:, None] * a) W_o;  m = RMSNorm(y)
    dense:   z = y + W_down (silu(W_gate m) * W_up m)
    sparse:  z = y + s * sum_{e in top-k, e held} w_e E_e(m) + E_shared(m)

with `w` the top-k of the softmax over ALL routed experts,
renormalised, and `s` `moe_routed_scaling_factor`. The model may hold a
share of each layer's experts (`LagunaConfig.num_routed_experts`,
`first_held_expert`): one chip's part of an expert-parallel layer,
`FusedMoE` with a share. Each layer names its page group
(`common/config.py::PageGroups`) from `layer_types`.
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
from aphrodite_tpu.modeling.layers.fused_moe import (FusedMoE,
                                                     sum_counts)
from aphrodite_tpu.modeling.layers.layernorm import (fused_add_rms_norm,
                                                     rms_norm)
from aphrodite_tpu.modeling.layers.linear import (ColumnParallelLinear,
                                                  LinearMethod,
                                                  MergedColumnParallelLinear,
                                                  QKVParallelLinear,
                                                  RowParallelLinear)
from aphrodite_tpu.modeling.layers.rotary_embedding import get_rope
from aphrodite_tpu.modeling.layers.vocab_embedding import (
    ParallelLMHead, VocabParallelEmbedding)

KVCache = Tuple[jax.Array, jax.Array]
Params = Dict[str, Dict[str, jax.Array]]

#: counted in the step program by every expert layer and summed over
#: the layers: `ModelRunner` pulls them with the step's result
STEP_COUNTERS = ("moe.tokens_routed", "moe.experts_touched",
                 "moe.pairs_held")

#: queries x keys a row from which a prompt step's attention goes in
#: tiles: 72 heads of float32 scores over a 2,048-token chunk against
#: 4,096 keys are 2.4 GB a layer, so a chunk of the scheduler's size
#: is always tiled (`PagedAttention.blocked_from`)
PREFILL_BLOCKED_FROM = 1 << 21

_ROPE_KEYS = ("rope_type", "factor", "original_max_position_embeddings",
              "beta_fast", "beta_slow", "attention_factor")


def _rope_of(config, kind: str, max_model_len: Optional[int]):
    """The rotary embedding of the layers of `kind`, its table no
    longer than the server's longest sequence."""
    stated = config.rope_parameters[kind]
    head = config.head_dim
    scaling = {k: stated[k] for k in _ROPE_KEYS if k in stated}
    if scaling.get("rope_type", "default") == "default":
        scaling = None
    return get_rope(
        head, int(head * stated.get("partial_rotary_factor", 1)),
        max_position=config.max_position_embeddings,
        base=stated["rope_theta"], is_neox_style=True,
        rope_scaling=scaling, max_len=max_model_len)


class LagunaMLP:
    """SwiGLU, `width` wide: the dense layers' MLP and the sparse
    layers' shared expert."""

    def __init__(self, prefix: str, hidden: int, width: int, dtype,
                 linear_method: Optional[LinearMethod]) -> None:
        self.prefix = prefix
        self.gate_up_proj = MergedColumnParallelLinear(
            hidden, [width] * 2, dtype=dtype, linear_method=linear_method)
        self.down_proj = RowParallelLinear(
            width, hidden, dtype=dtype, linear_method=linear_method)

    def layers(self) -> Dict[str, object]:
        return {f"{self.prefix}.gate_up_proj": self.gate_up_proj,
                f"{self.prefix}.down_proj": self.down_proj}

    def __call__(self, params: Params, hidden: jax.Array) -> jax.Array:
        gate_up = self.gate_up_proj(
            params[f"{self.prefix}.gate_up_proj"], hidden)
        return self.down_proj(params[f"{self.prefix}.down_proj"],
                              silu_and_mul(gate_up))


class LagunaDecoderLayer:

    def __init__(self, config, idx: int, groups: PageGroups, dtype,
                 linear_method: Optional[LinearMethod],
                 max_model_len: Optional[int]) -> None:
        self.prefix = p = f"model.layers.{idx}"
        self.rms_eps = config.rms_norm_eps
        self.dtype = dtype
        self.hidden_size = hidden = config.hidden_size
        self.num_heads = config.num_attention_heads_per_layer[idx]
        self.num_kv_heads = config.num_key_value_heads
        self.head_dim = config.head_dim
        kind = config.layer_types[idx]
        self.qkv_proj = QKVParallelLinear(
            hidden, self.head_dim, self.num_heads, self.num_kv_heads,
            bias=False, dtype=dtype, linear_method=linear_method)
        self.o_proj = RowParallelLinear(
            self.num_heads * self.head_dim, hidden, bias=False,
            dtype=dtype, linear_method=linear_method)
        #: (a) a gate a head, from the block's normed input
        self.g_proj = ColumnParallelLinear(
            hidden, self.num_heads, bias=False, dtype=dtype,
            linear_method=linear_method) \
            if config.gating_types[idx] == "per_head" else None
        self.rotary = _rope_of(config, kind, max_model_len)
        self.attn = PagedAttention(
            self.num_heads, self.head_dim, scale=self.head_dim ** -0.5,
            num_kv_heads=self.num_kv_heads,
            sliding_window=config.sliding_window
            if kind == "sliding_attention" else None,
            page_group=groups.group_of_layer[idx],
            blocked_from=PREFILL_BLOCKED_FROM)
        self.cache_slot = groups.slot_of_layer[idx]
        self.sparse = config.mlp_layer_types[idx] == "sparse"
        if self.sparse:
            self.moe = FusedMoE(
                num_experts=config.num_experts,
                top_k=config.num_experts_per_tok, hidden_size=hidden,
                intermediate_size=config.moe_intermediate_size,
                renormalize=config.norm_topk_prob,
                routed_experts=config.num_routed_experts,
                first_expert=config.first_held_expert, dtype=dtype)
            self.routed_scale = float(config.moe_routed_scaling_factor)
            self.mlp = LagunaMLP(
                f"{p}.mlp.shared_expert", hidden,
                config.shared_expert_intermediate_size, dtype,
                linear_method)
        else:
            self.moe = None
            self.mlp = LagunaMLP(f"{p}.mlp", hidden,
                                 config.intermediate_size, dtype,
                                 linear_method)

    def linears(self) -> Dict[str, object]:
        """Every linear layer of this layer by its bucket."""
        out = {f"{self.prefix}.self_attn.qkv_proj": self.qkv_proj,
               f"{self.prefix}.self_attn.o_proj": self.o_proj,
               **self.mlp.layers()}
        if self.g_proj is not None:
            out[f"{self.prefix}.self_attn.g_proj"] = self.g_proj
        return out

    def init(self) -> Params:
        p = self.prefix
        ones = jnp.ones((self.hidden_size,), dtype=self.dtype)
        params = {key: layer.init() for key, layer in self.linears().items()}
        params[f"{p}.input_layernorm"] = {"weight": ones}
        params[f"{p}.post_attention_layernorm"] = {"weight": ones}
        if self.sparse:
            params[f"{p}.mlp.experts"] = self.moe.init()
        return params

    def specs(self) -> Dict[str, Dict[str, P]]:
        p = self.prefix
        specs = {key: layer.specs() for key, layer in self.linears().items()}
        specs[f"{p}.input_layernorm"] = {"weight": P(None)}
        specs[f"{p}.post_attention_layernorm"] = {"weight": P(None)}
        if self.sparse:
            specs[f"{p}.mlp.experts"] = self.moe.specs()
        return specs

    def _gate(self, params: Params, normed, out):
        """`out` `[b, s, heads * head]`, each head's times the sigmoid
        of its gate."""
        if self.g_proj is None:
            return out
        gate = jax.nn.sigmoid(self.g_proj(
            params[f"{self.prefix}.self_attn.g_proj"],
            normed).astype(jnp.float32)).astype(out.dtype)
        b, s = out.shape[:2]
        return (out.reshape(b, s, self.num_heads, self.head_dim) *
                gate[..., None]).reshape(b, s, -1)

    def __call__(self, params: Params, positions, hidden, residual,
                 kv_cache, metadata, counts: list):
        p = self.prefix
        normed, residual = fused_add_rms_norm(
            hidden, residual, params[f"{p}.input_layernorm"]["weight"],
            self.rms_eps)
        qkv = self.qkv_proj(params[f"{p}.self_attn.qkv_proj"], normed)
        q, k, v = self.qkv_proj.split(qkv)
        b, s = q.shape[:2]
        q, k = self.rotary(
            positions, q.reshape(b, s, self.num_heads, self.head_dim),
            k.reshape(b, s, self.num_kv_heads, self.head_dim))
        q, k = q.reshape(b, s, -1), k.reshape(b, s, -1)
        k_pages, v_pages = kv_cache if kv_cache is not None else (None, None)
        out, k_pages, v_pages = self.attn(q, k, v, k_pages, v_pages,
                                          metadata)
        out = self._gate(params, normed, out.reshape(b, s, -1))
        attn_out = self.o_proj(params[f"{p}.self_attn.o_proj"], out)

        normed, residual = fused_add_rms_norm(
            attn_out, residual,
            params[f"{p}.post_attention_layernorm"]["weight"], self.rms_eps)
        mlp_out = self.mlp(params, normed)
        if self.sparse:
            # (c) the shared expert ungated and unscaled, beside the
            # routed sum times the model's factor
            routed = self.moe(params[f"{p}.mlp.experts"], normed,
                              counts=counts)
            mlp_out = mlp_out + routed * jnp.asarray(self.routed_scale,
                                                     routed.dtype)
        return mlp_out, residual, \
            (None if k_pages is None else (k_pages, v_pages))


class LagunaForCausalLM:

    @property
    def step_counters(self) -> Tuple[str, ...]:
        """What a step program of this model counts: `STEP_COUNTERS`,
        and the rows its expert kernels walk where they run."""
        return STEP_COUNTERS + next(
            layer.moe for layer in self.layers
            if layer.moe is not None).kernel_counters
    #: `modeling/loader.py` hands the server's longest sequence to the
    #: constructor: the rotary tables reach it and no further
    takes_max_model_len = True

    def __init__(self, config, dtype: jnp.dtype = jnp.bfloat16,
                 linear_method: Optional[LinearMethod] = None,
                 max_model_len: Optional[int] = None) -> None:
        self.config = config
        self.dtype = dtype
        self.groups = PageGroups.of(config.page_layer_kinds,
                                    config.sliding_window)
        #: the attention layers' `blocked_from`, for the runner's count
        #: of a prompt step's tiles
        self.prefill_blocked_from = PREFILL_BLOCKED_FROM
        self.embed_tokens = VocabParallelEmbedding(
            config.vocab_size, config.hidden_size, dtype=dtype)
        self.layers = [
            LagunaDecoderLayer(config, i, self.groups, dtype,
                               linear_method, max_model_len)
            for i in range(config.num_hidden_layers)]
        self.lm_head = ParallelLMHead(config.vocab_size,
                                      config.hidden_size, dtype=dtype)
        self.rms_eps = config.rms_norm_eps
        self.tie_word_embeddings = getattr(config, "tie_word_embeddings",
                                           False)
        #: held experts a step could touch: held experts x expert layers
        self.expert_slots = config.num_experts * len(config.sparse_layers)
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
                ("v_proj", "qkv_proj", "v"),
                ("gate_proj", "gate_up_proj", 0),
                ("up_proj", "gate_up_proj", 1)]
    # HF expert tensor name -> stacked param name
    _EXPERT_MAP = {"gate_proj": "w_gate", "up_proj": "w_up",
                   "down_proj": "w_down"}

    def load_weights(self, weights: Iterable[Tuple[str, np.ndarray]]):
        """(e) The names are ASSUMED, the Qwen-MoE family's, whose key
        names the config has: `...self_attn.{q,k,v,o,g}_proj.weight`,
        `...mlp.{gate,up,down}_proj.weight` (a dense layer),
        `...mlp.gate.weight` `[routed experts, hidden]` (the router),
        `...mlp.experts.<id>.{gate,up,down}_proj.weight` and
        `...mlp.shared_expert.{gate,up,down}_proj.weight`. A model that
        holds a share takes its own experts' tensors (`<id>` counted
        over all routed experts) and the first `vocab_size` rows of
        the embedding and the head, and passes the rest by."""
        loaders = {}
        for layer in self.layers:
            loaders.update(layer.linears())
        moes = {layer.prefix: layer.moe for layer in self.layers
                if layer.sparse}
        first = self.config.first_held_expert
        rows = self.config.vocab_size
        params: Dict[str, Dict[str, np.ndarray]] = {}

        def bucket(key):
            return params.setdefault(key, {})

        for name, tensor in weights:
            if "rotary_emb.inv_freq" in name:
                continue
            if name.startswith("lm_head"):
                if not self.tie_word_embeddings:
                    self.lm_head.weight_loader(bucket("lm_head"), "weight",
                                               tensor[:rows])
                continue
            if name == "model.embed_tokens.weight":
                self.embed_tokens.weight_loader(
                    bucket("model.embed_tokens"), "weight", tensor[:rows])
                continue
            if name == "model.norm.weight":
                bucket("model.norm")["weight"] = tensor
                continue
            if name.endswith("_layernorm.weight"):
                key, pname = name.rsplit(".", 1)
                bucket(key)[pname] = tensor
                continue
            if ".mlp.gate." in name or ".mlp.experts." in name:
                prefix, rest = name.split(".mlp.")
                moe, into = moes[prefix], bucket(f"{prefix}.mlp.experts")
                if rest == "gate.weight":
                    moe.load_gate_weight(into, tensor)
                    continue
                parts = rest.split(".")     # experts.<id>.<which>.weight
                held = int(parts[1]) - first
                if 0 <= held < moe.num_experts:
                    moe.load_expert_weight(
                        into, self._EXPERT_MAP[parts[2]], held, tensor)
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
