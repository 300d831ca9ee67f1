"""Sarvam MLA (sarvamai/sarvam-105b, `model_type` "sarvam_mla").

No reference implementation in the CUDA tree and no publisher's code
on this machine; written from the checkpoint's config.json, whose keys
are DeepSeek-V2's multi-head latent attention one for one. What that
file leaves open is ASSUMED, each in one place here (the same list is
in `perf/configs/sarvam-105b-bf16.json` and in the benchmark's
reference): (a) `use_qk_norm` is the RMSNorm on the latent
(`kv_a_layernorm`; with no `q_lora_rank` there is no query latent to
norm, and a per-head norm on up-projected keys could not be served
from a latent cache); (b) the router scores by sigmoid, its selection
bias is in the top-k alone, the chosen weights are renormalised, no
expert groups (`FusedMoE.route`); (c) the shared expert is added
ungated; (d) half-split rotary pairs, DeepSeek's `m^2` on the softmax
scale; (e) no bias in any projection; (f) the class name and the
checkpoint's tensor names (`load_weights`).

A layer, with `x' = RMSNorm(x)`:

    q = x' W_q                      64 heads of [q_nope 128 | q_rope 64]
    [c | k_r] = x' W_kva            512 + 64;  c <- RMSNorm_512(c)
    q_rope, k_r rotated (YaRN over the 64 rotary lanes), k_r ONE vector
    [k_nope_h | v_h] = c W_kvb      a head 128 + 128
    s_h = [q_nope_h | q_rope_h] . [k_nope_h | k_r] * 192^-0.5 * m^2
    y = x + concat_h(softmax(s_h) v_h) W_o

What a token leaves in the cache is `[c | k_r]` and nothing else: the
layer's pages are LATENT (`common/config.py::PageGroups.latent`), and
`modeling/layers/mla.py` attends not absorbed in a prompt step and
absorbed in a decode step. Layer 0 to `first_k_dense_replace` are a
dense SwiGLU MLP; the others `y + shared(y') + s * sum_k w_k E_k(y')`
with `w` the sigmoid scores of the top-k of score + bias,
renormalised, over a share of the experts (`FusedMoE` with a share, as
Laguna's).
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from aphrodite_tpu.modeling.input_metadata import InputMetadata
from aphrodite_tpu.modeling.layers.activation import silu_and_mul
from aphrodite_tpu.modeling.layers.fused_moe import FusedMoE, sum_counts
from aphrodite_tpu.modeling.layers.layernorm import (fused_add_rms_norm,
                                                     rms_norm)
from aphrodite_tpu.modeling.layers.linear import (
    ColumnParallelLinear, LinearMethod, MergedColumnParallelLinear,
    RowParallelLinear)
from aphrodite_tpu.modeling.layers.mla import LatentAttention
from aphrodite_tpu.modeling.layers.rotary_embedding import (
    deepseek_yarn_softmax_mscale, get_rope)
from aphrodite_tpu.modeling.layers.vocab_embedding import (
    ParallelLMHead, VocabParallelEmbedding)

Params = Dict[str, Dict[str, jax.Array]]

#: counted in the step program: `ModelRunner` pulls them with the
#: step's result. The prefix tokens a prompt step up-projected from
#: the latent pages (`LatentAttention`), then the expert layers' three
#: as Laguna's, summed over the layers
STEP_COUNTERS = ("mla.prefix_tokens_expanded", "moe.tokens_routed",
                 "moe.experts_touched", "moe.pairs_held")

#: queries x keys a row from which a prompt step's `jnp` attention
#: goes in tiles: 64 heads of float32 scores over a 2,048-token chunk
#: against 8,192 keys are 4.3 GB a layer
PREFILL_BLOCKED_FROM = 1 << 21


class SarvamMLP:
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


def _by_part(heads: int, first: int, second: int) -> np.ndarray:
    """Where each output column of a projection whose checkpoint holds a
    head's `[first | second]` side by side lies in the program's
    layout, every head's `first` lanes and then every head's `second`
    (`q_proj`: nope and rotary lanes; `kv_b_proj`: keys and values):
    a head's lanes are then whole lane tiles of a contiguous half, and
    no step program re-lays a weight out to split a 192-lane head (a
    transposing copy of the 100 MB `q_proj` a layer a step, as the
    compiler had it)."""
    at = np.arange(heads)[:, None] * (first + second)
    return np.concatenate([(at + np.arange(first)).reshape(-1),
                           (at + first + np.arange(second)).reshape(-1)])


class SarvamMLADecoderLayer:

    def __init__(self, config, idx: int, dtype,
                 linear_method: Optional[LinearMethod],
                 max_model_len: Optional[int]) -> None:
        self.prefix = p = f"model.layers.{idx}"
        self.rms_eps = config.rms_norm_eps
        self.dtype = dtype
        self.hidden_size = hidden = config.hidden_size
        self.num_heads = heads = config.num_attention_heads
        self.nope, self.rope = config.qk_nope_head_dim, \
            config.qk_rope_head_dim
        self.v_dim, self.latent = config.v_head_dim, config.kv_lora_rank
        self.q_proj = ColumnParallelLinear(
            hidden, heads * (self.nope + self.rope), bias=False,
            dtype=dtype, linear_method=linear_method)
        self.kv_a_proj = ColumnParallelLinear(
            hidden, self.latent + self.rope, bias=False, dtype=dtype,
            linear_method=linear_method)
        self.kv_b_proj = ColumnParallelLinear(
            self.latent, heads * (self.nope + self.v_dim), bias=False,
            dtype=dtype, linear_method=linear_method)
        self.o_proj = RowParallelLinear(
            heads * self.v_dim, hidden, bias=False, dtype=dtype,
            linear_method=linear_method)
        scaling = dict(config.rope_scaling)
        self.rotary = get_rope(
            self.rope, self.rope,
            max_position=config.max_position_embeddings,
            base=config.rope_theta, is_neox_style=True,   # (d)
            rope_scaling=scaling, max_len=max_model_len)
        # (d) DeepSeek's second mscale, squared, on the softmax scale
        self.attn = LatentAttention(
            heads, self.nope, self.rope, self.v_dim, self.latent,
            scale=(self.nope + self.rope) ** -0.5 *
            deepseek_yarn_softmax_mscale(scaling) ** 2,
            blocked_from=PREFILL_BLOCKED_FROM)
        self.sparse = idx >= config.first_k_dense_replace
        if self.sparse:
            # (b) sigmoid scores, the bias in the selection alone
            self.moe = FusedMoE(
                num_experts=config.num_experts,
                top_k=config.num_experts_per_tok, hidden_size=hidden,
                intermediate_size=config.moe_intermediate_size,
                renormalize=True, scoring="sigmoid",
                selection_bias=config.moe_router_enable_expert_bias,
                routed_experts=config.num_routed_experts,
                first_expert=config.first_held_expert, dtype=dtype)
            self.routed_scale = float(config.routed_scaling_factor)
            self.mlp = SarvamMLP(
                f"{p}.mlp.shared_experts", hidden,
                config.moe_intermediate_size * config.num_shared_experts,
                dtype, linear_method)
        else:
            self.moe = None
            self.mlp = SarvamMLP(f"{p}.mlp", hidden,
                                 config.intermediate_size, dtype,
                                 linear_method)

    def linears(self) -> Dict[str, object]:
        """Every linear layer of this layer by its bucket."""
        p = self.prefix
        return {f"{p}.self_attn.q_proj": self.q_proj,
                f"{p}.self_attn.kv_a_proj_with_mqa": self.kv_a_proj,
                f"{p}.self_attn.kv_b_proj": self.kv_b_proj,
                f"{p}.self_attn.o_proj": self.o_proj,
                **self.mlp.layers()}

    def _norms(self) -> Dict[str, int]:
        p = self.prefix
        return {f"{p}.input_layernorm": self.hidden_size,
                f"{p}.post_attention_layernorm": self.hidden_size,
                f"{p}.self_attn.kv_a_layernorm": self.latent}

    def init(self) -> Params:
        params = {key: layer.init() for key, layer in self.linears().items()}
        for key, width in self._norms().items():
            params[key] = {"weight": jnp.ones((width,), dtype=self.dtype)}
        if self.sparse:
            params[f"{self.prefix}.mlp.experts"] = self.moe.init()
        return params

    def specs(self) -> Dict[str, Dict[str, P]]:
        specs = {key: layer.specs() for key, layer in self.linears().items()}
        for key in self._norms():
            specs[key] = {"weight": P(None)}
        if self.sparse:
            specs[f"{self.prefix}.mlp.experts"] = self.moe.specs()
        return specs

    def __call__(self, params: Params, positions, hidden, residual,
                 cache, metadata, counts: list, expanded: list):
        p = self.prefix
        normed, residual = fused_add_rms_norm(
            hidden, residual, params[f"{p}.input_layernorm"]["weight"],
            self.rms_eps)
        b, s = normed.shape[:2]
        heads = self.num_heads
        # (`_by_part`: every head's nope lanes, then every head's
        # rotary lanes)
        q = self.q_proj(params[f"{p}.self_attn.q_proj"], normed)
        q_nope = q[..., :heads * self.nope].reshape(b, s, heads, self.nope)
        q_rope = q[..., heads * self.nope:].reshape(b, s, heads, self.rope)
        kva = self.kv_a_proj(params[f"{p}.self_attn.kv_a_proj_with_mqa"],
                             normed)
        # (a) the norm of `use_qk_norm`: on the latent
        c = rms_norm(kva[..., :self.latent],
                     params[f"{p}.self_attn.kv_a_layernorm"]["weight"],
                     self.rms_eps)
        q_rope, k_r = self.rotary(positions, q_rope,
                                  kva[..., None, self.latent:])
        w_kvb = params[f"{p}.self_attn.kv_b_proj"]["weight"]
        pages = None if cache is None else cache[0]
        out, pages, from_pages = self.attn(
            q_nope, q_rope, c, k_r[..., 0, :],
            w_kvb[:, :heads * self.nope].reshape(self.latent, heads,
                                                 self.nope),
            w_kvb[:, heads * self.nope:].reshape(self.latent, heads,
                                                 self.v_dim),
            pages, metadata)
        expanded.append(from_pages)
        attn_out = self.o_proj(params[f"{p}.self_attn.o_proj"], out)

        normed, residual = fused_add_rms_norm(
            attn_out, residual,
            params[f"{p}.post_attention_layernorm"]["weight"], self.rms_eps)
        mlp_out = self.mlp(params, normed)
        if self.sparse:
            # (c) the shared expert ungated, beside the routed sum
            # times the model's factor
            routed = self.moe(params[f"{p}.mlp.experts"], normed,
                              counts=counts)
            mlp_out = mlp_out + routed * jnp.asarray(self.routed_scale,
                                                     routed.dtype)
        return mlp_out, residual, (None if pages is None else (pages,))


class SarvamMLAForCausalLM:

    #: `modeling/loader.py` hands the server's longest sequence to the
    #: constructor: the rotary tables reach it and no further
    takes_max_model_len = True

    def __init__(self, config, dtype: jnp.dtype = jnp.bfloat16,
                 linear_method: Optional[LinearMethod] = None,
                 max_model_len: Optional[int] = None) -> None:
        self.config = config
        self.dtype = dtype
        #: the attention layers' `blocked_from`, for the runner's count
        #: of a prompt step's tiles
        self.prefill_blocked_from = PREFILL_BLOCKED_FROM
        self.embed_tokens = VocabParallelEmbedding(
            config.vocab_size, config.hidden_size, dtype=dtype)
        self.layers = [
            SarvamMLADecoderLayer(config, i, dtype, linear_method,
                                  max_model_len)
            for i in range(config.num_hidden_layers)]
        self.lm_head = ParallelLMHead(config.vocab_size,
                                      config.hidden_size, dtype=dtype)
        self.rms_eps = config.rms_norm_eps
        #: held experts a step could touch: held experts x expert layers
        self.expert_slots = config.num_experts * len(config.sparse_layers)
        #: what the layers of the program being traced counted
        self._counts: list = []
        self._expanded: list = []

    @property
    def step_counters(self) -> Tuple[str, ...]:
        """What a step program of this model counts: `STEP_COUNTERS`,
        and the rows its expert kernels walk where they run."""
        return STEP_COUNTERS + next(
            (layer.moe.kernel_counters for layer in self.layers
             if layer.moe is not None), ())

    def init_params(self) -> Params:
        params: Params = {"model.embed_tokens": self.embed_tokens.init()}
        for layer in self.layers:
            params.update(layer.init())
        params["model.norm"] = {
            "weight": jnp.ones((self.config.hidden_size,),
                               dtype=self.dtype)}
        params["lm_head"] = self.lm_head.init()
        return params

    def param_specs(self) -> Dict[str, Dict[str, P]]:
        specs = {"model.embed_tokens": self.embed_tokens.specs()}
        for layer in self.layers:
            specs.update(layer.specs())
        specs["model.norm"] = {"weight": P(None)}
        specs["lm_head"] = self.lm_head.specs()
        return specs

    def __call__(self, params: Params, input_ids, positions,
                 kv_caches: Optional[List[tuple]],
                 metadata: InputMetadata):
        """`kv_caches[l]` is `(pages,)`: layer `l`'s ONE array of
        latent pages."""
        hidden = self.embed_tokens(params["model.embed_tokens"],
                                   input_ids)
        residual = None
        caches = list(kv_caches) if kv_caches is not None else None
        self._counts, self._expanded = counts, expanded = [], []
        for i, layer in enumerate(self.layers):
            hidden, residual, new_cache = layer(
                params, positions, hidden, residual,
                None if caches is None else caches[i], metadata, counts,
                expanded)
            if new_cache is not None:
                caches[i] = new_cache
        hidden = rms_norm(hidden + residual,
                          params["model.norm"]["weight"], self.rms_eps)
        return hidden, caches

    def take_step_counts(self) -> jax.Array:
        """`step_counters` of the step just traced: the expert layers'
        summed over them, and the prefix tokens a prompt step read back
        from the pages (every layer reads the same ones: the first's);
        int32, inside the same program."""
        counts, self._counts = self._counts, []
        expanded, self._expanded = self._expanded, []
        return jnp.concatenate([
            expanded[0][None],
            sum_counts(counts, self.step_counters[1:]).astype(jnp.int32)])

    def compute_logits(self, params: Params, hidden):
        return self.lm_head.compute_logits(params["lm_head"], hidden)

    # ---- weight loading ----
    _STACKED = [("gate_proj", "gate_up_proj", 0),
                ("up_proj", "gate_up_proj", 1)]
    # HF expert tensor name -> stacked param name
    _EXPERT_MAP = {"gate_proj": "w_gate", "up_proj": "w_up",
                   "down_proj": "w_down"}
    #: the selection bias, under DeepSeek-V3's name or the
    #: Ling/Bailing-V2 family's
    _BIAS_NAMES = ("gate.e_score_correction_bias", "gate.expert_bias")

    def load_weights(self, weights: Iterable[Tuple[str, np.ndarray]]):
        """(f) The names are ASSUMED, DeepSeek-V2's convention, whose
        key names the config has: `...self_attn.{q_proj,
        kv_a_proj_with_mqa,kv_b_proj,o_proj}.weight`,
        `...self_attn.kv_a_layernorm.weight`,
        `...mlp.{gate,up,down}_proj.weight` (a dense layer),
        `...mlp.gate.weight` `[routed experts, hidden]` (the router),
        `...mlp.gate.e_score_correction_bias` or `...mlp.gate.
        expert_bias` `[routed experts]`,
        `...mlp.experts.<id>.{gate,up,down}_proj.weight` and
        `...mlp.shared_experts.{gate,up,down}_proj.weight`; `q_proj`'s
        and `kv_b_proj`'s output rows, a head's parts side by side
        there, go into the program's order (`_by_part`). A model that
        holds a share takes its own experts' tensors (`<id>`
        counted over all routed experts) and the first `vocab_size`
        rows of the embedding and the head, and passes the rest by."""
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
                self.lm_head.weight_loader(bucket("lm_head"), "weight",
                                           tensor[:rows])
                continue
            if name == "model.embed_tokens.weight":
                self.embed_tokens.weight_loader(
                    bucket("model.embed_tokens"), "weight", tensor[:rows])
                continue
            if name == "model.norm.weight" or \
                    name.endswith("layernorm.weight"):
                key, pname = name.rsplit(".", 1)
                bucket(key)[pname] = tensor
                continue
            if ".mlp.gate." in name or ".mlp.experts." in name:
                prefix, rest = name.split(".mlp.")
                moe, into = moes[prefix], bucket(f"{prefix}.mlp.experts")
                if rest == "gate.weight":
                    moe.load_gate_weight(into, tensor)
                elif rest in self._BIAS_NAMES:
                    into["e_bias"] = np.asarray(tensor, np.float32)
                else:
                    parts = rest.split(".")  # experts.<id>.<which>.weight
                    held = int(parts[1]) - first
                    if 0 <= held < moe.num_experts:
                        moe.load_expert_weight(
                            into, self._EXPERT_MAP[parts[2]], held, tensor)
                continue
            if name.endswith(("q_proj.weight", "kv_b_proj.weight")):
                # torch's [out, in]: the rows into the program's order
                layer = self.layers[0]
                tensor = tensor[_by_part(
                    layer.num_heads, layer.nope,
                    layer.rope if "q_proj" in name else layer.v_dim)]
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
