"""Kimi Linear (moonshotai/Kimi-Linear-48B-A3B-Instruct, `model_type`
"kimi_linear"; Kimi Linear, arXiv:2510.26692).

No reference implementation in the CUDA tree and no publisher's code
on this machine; written from the checkpoint's config.json and the
paper's equations. A block is `h = h + mixer(RMSNorm(h))`,
`h = h + mlp(RMSNorm(h))`, then a final RMSNorm and an untied head.
The mixer by `linear_attn_config`'s two lists (which count layers from
one):

- **KDA** (`layers/kda.py::KimiDeltaAttention`): a gated delta rule
  whose heads' matrices and convolution tail live in the sequence's
  STATE SLOT (`common/config.py::StateSpec`);
- **MLA** (`layers/mla.py::LatentAttention`, shared with
  `models/sarvam_mla.py`): DeepSeek-V2's multi-head latent attention,
  `q_lora_rank` null, with NO rotary embedding anywhere
  (`mla_use_nope`): `q_h = x' W_q` split `[nope | rest]`, `[c | k_r] =
  x' W_kva`, `c <- RMSNorm(c)`, `[k_nope_h | v_h] = c W_kvb`, scores
  `[q_nope_h | q_rest_h] . [k_nope_h | k_r] * (nope + rest)^-0.5`
  under a causal mask. What a token leaves in the layer's pages is
  `[c | k_r]`: they are LATENT (`PageGroups.latent`); a decode step
  attends absorbed, a prompt step up-projects inside the step.

So a sequence holds a state slot AND latent pages: the page groups
state `stateful` and `latent` together. The first
`first_k_dense_replace` layers have a dense SwiGLU MLP, the others
`shared(y') + s * sum_k w_k E_k(y')` with `w` the sigmoid scores of the
top-k of score + bias, renormalised, over a share of the experts
(`FusedMoE` with a share, as Sarvam's and Laguna's).

ASSUMED, each in one place here (the same list is in
`perf/configs/kimi-linear-48b-a3b-bf16.json` and in the benchmark's
reference): (a) `mla_use_nope` keeps the `qk_rope_head_dim` lanes of q
and the one shared key part, unrotated; (b) the router's selection bias
exists (`e_score_correction_bias`) and enters the top-k alone; the
shared expert is added ungated; (c) in a KDA layer: the two gates'
inner width is `head_dim`, no bias in any projection or convolution,
one `A_log` a head and one `dt_bias` a channel, the L2 norm's eps
1e-6, q scaled by `head_dim^-0.5` after the norm, the output norm's
gain one head-width vector, the state in float32 (`layers/kda.py`);
(d) the class name and the checkpoint's tensor names (`load_weights`).

One chip serves it (`param_specs`): latent pages and state arrays are
single-device, and a mesh is refused by name where both are
(`common/config.py::LATENT_PAGE_REFUSALS`).
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from aphrodite_tpu.common.config import PageGroups
from aphrodite_tpu.modeling.input_metadata import InputMetadata
from aphrodite_tpu.modeling.layers.fused_moe import FusedMoE, sum_counts
from aphrodite_tpu.modeling.layers.kda import KimiDeltaAttention
from aphrodite_tpu.modeling.layers.layernorm import (fused_add_rms_norm,
                                                     rms_norm)
from aphrodite_tpu.modeling.layers.linear import (
    ColumnParallelLinear, LinearMethod, RowParallelLinear,
    replicated_specs)
from aphrodite_tpu.modeling.layers.mla import LatentAttention
from aphrodite_tpu.modeling.layers.vocab_embedding import (
    ParallelLMHead, VocabParallelEmbedding)
from aphrodite_tpu.modeling.models.sarvam_mla import (
    PREFILL_BLOCKED_FROM, STEP_COUNTERS, SarvamMLP, _by_part)
from aphrodite_tpu.ops.pallas import kda

Params = Dict[str, Dict[str, jax.Array]]


class KimiLatentAttention:
    """The MLA mixer without a rotary embedding."""

    def __init__(self, config, prefix: str, page_group: int, dtype,
                 linear_method: Optional[LinearMethod]) -> None:
        self.prefix = prefix
        hidden = config.hidden_size
        self.num_heads = heads = config.num_attention_heads
        self.nope, self.rope = config.qk_nope_head_dim, \
            config.qk_rope_head_dim
        self.v_dim, self.latent = config.v_head_dim, config.kv_lora_rank
        self.eps = config.rms_norm_eps
        self.dtype = dtype
        kw = dict(bias=False, dtype=dtype, linear_method=linear_method)
        self.q_proj = ColumnParallelLinear(
            hidden, heads * (self.nope + self.rope), **kw)
        self.kv_a_proj = ColumnParallelLinear(
            hidden, self.latent + self.rope, **kw)
        self.kv_b_proj = ColumnParallelLinear(
            self.latent, heads * (self.nope + self.v_dim), **kw)
        self.o_proj = RowParallelLinear(heads * self.v_dim, hidden, **kw)
        self.attn = LatentAttention(
            heads, self.nope, self.rope, self.v_dim, self.latent,
            scale=(self.nope + self.rope) ** -0.5, page_group=page_group,
            blocked_from=PREFILL_BLOCKED_FROM)

    def linears(self) -> Dict[str, object]:
        p = self.prefix
        return {f"{p}.q_proj": self.q_proj,
                f"{p}.kv_a_proj_with_mqa": self.kv_a_proj,
                f"{p}.kv_b_proj": self.kv_b_proj,
                f"{p}.o_proj": self.o_proj}

    def init(self) -> Params:
        params = {key: layer.init() for key, layer in self.linears().items()}
        params[f"{self.prefix}.kv_a_layernorm"] = {
            "weight": jnp.ones((self.latent,), dtype=self.dtype)}
        return params

    def __call__(self, params: Params, normed, pages, metadata):
        """Returns the output, the layer's pages and the prefix tokens
        this step up-projected from them."""
        p, heads = self.prefix, self.num_heads
        b, s = normed.shape[:2]
        # (`sarvam_mla._by_part`: every head's nope lanes, then every
        # head's other lanes)
        q = self.q_proj(params[f"{p}.q_proj"], normed)
        q_nope = q[..., :heads * self.nope].reshape(b, s, heads, self.nope)
        q_rest = q[..., heads * self.nope:].reshape(b, s, heads, self.rope)
        kva = self.kv_a_proj(params[f"{p}.kv_a_proj_with_mqa"], normed)
        c = rms_norm(kva[..., :self.latent],
                     params[f"{p}.kv_a_layernorm"]["weight"], self.eps)
        # (a) no rotation: the shared key part as the projection gives it
        w_kvb = params[f"{p}.kv_b_proj"]["weight"]
        out, pages, from_pages = self.attn(
            q_nope, q_rest, c, kva[..., self.latent:],
            w_kvb[:, :heads * self.nope].reshape(self.latent, heads,
                                                 self.nope),
            w_kvb[:, heads * self.nope:].reshape(self.latent, heads,
                                                 self.v_dim),
            pages, metadata)
        return self.o_proj(params[f"{p}.o_proj"], out), pages, from_pages


class KimiLinearDecoderLayer:

    def __init__(self, config, idx: int, kind: str, groups: PageGroups,
                 dtype, linear_method: Optional[LinearMethod]) -> None:
        self.prefix = p = f"model.layers.{idx}"
        self.kind = kind
        self.dtype = dtype
        self.hidden_size = hidden = config.hidden_size
        self.rms_eps = config.rms_norm_eps
        if kind == "kda":
            self.mixer = KimiDeltaAttention(
                hidden, config.kda_heads, config.kda_head_dim,
                config.kda_conv, config.rms_norm_eps, f"{p}.self_attn",
                dtype, linear_method)
        else:
            self.mixer = KimiLatentAttention(
                config, f"{p}.self_attn", groups.group_of_layer[idx],
                dtype, linear_method)
            #: which of `kv_caches`' places holds this layer's pages
            self.cache_slot = groups.slot_of_layer[idx]
        self.sparse = idx >= config.first_k_dense_replace
        if self.sparse:
            # (b) sigmoid scores, the bias in the selection alone
            self.moe = FusedMoE(
                num_experts=config.num_experts,
                top_k=config.num_experts_per_token, hidden_size=hidden,
                intermediate_size=config.moe_intermediate_size,
                renormalize=True, scoring="sigmoid", selection_bias=True,
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
        return {**self.mixer.linears(), **self.mlp.layers()}

    def init(self) -> Params:
        p = self.prefix
        params = {**self.mixer.init(),
                  **{key: layer.init()
                     for key, layer in self.mlp.layers().items()}}
        for name in ("input_layernorm", "post_attention_layernorm"):
            params[f"{p}.{name}"] = {
                "weight": jnp.ones((self.hidden_size,), dtype=self.dtype)}
        if self.sparse:
            params[f"{p}.mlp.experts"] = self.moe.init()
        return params

    def feed_forward(self, params: Params, mixed, residual, counts: list):
        p = self.prefix
        normed, residual = fused_add_rms_norm(
            mixed, residual,
            params[f"{p}.post_attention_layernorm"]["weight"], self.rms_eps)
        out = self.mlp(params, normed)
        if self.sparse:
            # (b) the shared expert ungated, beside the routed sum
            # times the model's factor
            routed = self.moe(params[f"{p}.mlp.experts"], normed,
                              counts=counts)
            out = out + routed * jnp.asarray(self.routed_scale,
                                             routed.dtype)
        return out, residual


class KimiLinearForCausalLM:

    #: tokens a grid cell of the KDA layers' chunk kernel takes: the
    #: runner counts a prompt step's chunks by it
    #: (`aphrodite:kda_prompt_chunks_total`), and a model that has it
    #: counts its state layers' rows and tokens as `kda.*`
    kda_chunk_tokens = kda.CHUNK

    def __init__(self, config, dtype: jnp.dtype = jnp.bfloat16,
                 linear_method: Optional[LinearMethod] = None) -> None:
        self.config = config
        self.dtype = dtype
        #: the attention layers' `blocked_from`, for the runner's count
        #: of a prompt step's tiles
        self.prefill_blocked_from = PREFILL_BLOCKED_FROM
        self.groups = PageGroups.of(
            config.page_layer_kinds, None, stateful=True,
            latent=config.latent_value_lanes)
        self.embed_tokens = VocabParallelEmbedding(
            config.vocab_size, config.hidden_size, dtype=dtype)
        self.layers = [
            KimiLinearDecoderLayer(config, i, kind, self.groups, dtype,
                                   linear_method)
            for i, kind in enumerate(config.layer_kinds)]
        self.lm_head = ParallelLMHead(config.vocab_size,
                                      config.hidden_size, dtype=dtype)
        self.rms_eps = config.rms_norm_eps
        #: the model's one `(tail, state)` pair follows the pages in
        #: `kv_caches`; a KDA layer's place on its leading axis
        self.state_pair = self.groups.layers_per_group
        self.state_at = {
            layer.prefix: i for i, layer in enumerate(
                l for l in self.layers if l.kind == "kda")}
        #: held experts a step could touch: held experts x expert layers
        self.expert_slots = config.num_experts * len(config.sparse_layers)
        #: what the layers of the program being traced counted
        self._counts: list = []
        self._expanded: list = []

    @property
    def step_counters(self) -> Tuple[str, ...]:
        """What a step program of this model counts: Sarvam's
        (`STEP_COUNTERS`), and the rows its expert kernels walk where
        they run."""
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
        """One chip holds the model whole (latent pages, the state
        arrays and the KDA kernels are single-device): every leaf
        replicated."""
        return replicated_specs(jax.eval_shape(self.init_params))

    def __call__(self, params: Params, input_ids, positions,
                 kv_caches: Optional[List[tuple]],
                 metadata: InputMetadata):
        """`kv_caches`: `(pages,)`, ONE array of latent pages, for each
        MLA layer of the page group, then the one `(tail, state)` pair
        of all the KDA layers."""
        hidden = self.embed_tokens(params["model.embed_tokens"],
                                   input_ids)
        residual = None
        caches = list(kv_caches) if kv_caches is not None else None
        self._counts, self._expanded = counts, expanded = [], []
        for layer in self.layers:
            normed, residual = fused_add_rms_norm(
                hidden, residual,
                params[f"{layer.prefix}.input_layernorm"]["weight"],
                self.rms_eps)
            if layer.kind == "kda":
                at = self.state_pair
                mixed, new = layer.mixer(
                    params, normed, positions,
                    None if caches is None else caches[at], metadata,
                    self.state_at[layer.prefix])
            else:
                at = layer.cache_slot
                mixed, pages, from_pages = layer.mixer(
                    params, normed,
                    None if caches is None else caches[at][0], metadata)
                expanded.append(from_pages)
                new = None if pages is None else (pages,)
            if new is not None:
                caches[at] = new
            hidden, residual = layer.feed_forward(params, mixed, residual,
                                                  counts)
        hidden = rms_norm(hidden + residual,
                          params["model.norm"]["weight"], self.rms_eps)
        return hidden, caches

    def take_step_counts(self) -> jax.Array:
        """`step_counters` of the step just traced
        (`SarvamMLAForCausalLM.take_step_counts`); a stack without an
        MLA layer up-projects nothing."""
        counts, self._counts = self._counts, []
        expanded, self._expanded = self._expanded, []
        return jnp.concatenate([
            (expanded[0] if expanded else jnp.int32(0))[None],
            sum_counts(counts, self.step_counters[1:]).astype(jnp.int32)])

    def compute_logits(self, params: Params, hidden):
        return self.lm_head.compute_logits(params["lm_head"], hidden)

    # ---- weight loading ----
    #: (checkpoint name fragment, merged parameter, shard id): every
    #: layer's MLPs, and a KDA layer's mixer (an MLA layer's `q_proj`
    #: is its own)
    _STACKED = [("gate_proj", "gate_up_proj", 0),
                ("up_proj", "gate_up_proj", 1)]
    _KDA_STACKED = [("self_attn.q_proj", "self_attn.qkv_proj", 0),
                    ("self_attn.k_proj", "self_attn.qkv_proj", 1),
                    ("self_attn.v_proj", "self_attn.qkv_proj", 2),
                    ("self_attn.f_a_proj", "self_attn.fgb_proj", 0),
                    ("self_attn.g_a_proj", "self_attn.fgb_proj", 1),
                    ("self_attn.b_proj", "self_attn.fgb_proj", 2)]
    _EXPERT_MAP = {"gate_proj": "w_gate", "up_proj": "w_up",
                   "down_proj": "w_down", "w1": "w_gate", "w3": "w_up",
                   "w2": "w_down"}
    _BIAS_NAMES = ("gate.e_score_correction_bias", "gate.expert_bias")
    _CONVS = ("q_conv1d", "k_conv1d", "v_conv1d")

    def load_weights(self, weights: Iterable[Tuple[str, np.ndarray]]):
        """(d) The names are ASSUMED, by the family's convention. Every
        layer: `model.layers.N.{input,post_attention}_layernorm.weight`.
        A KDA layer: `...self_attn.{q,k,v}_proj.weight`,
        `...self_attn.{q,k,v}_conv1d.weight` `[channels, 1, taps]`,
        `...self_attn.{f_a,f_b,g_a,g_b,b,o}_proj.weight`,
        `...self_attn.A_log` (one a head, any shape), `...self_attn.
        dt_bias`, `...self_attn.o_norm.weight`. An MLA layer: DeepSeek-V2's
        (`SarvamMLAForCausalLM.load_weights`). The MLPs: `...mlp.*` or
        `...block_sparse_moe.*` with `gate.weight`, `gate.
        e_score_correction_bias`, `experts.<id>.{gate,up,down}_proj` or
        `experts.<id>.{w1,w3,w2}`, `shared_experts.*`. A model that
        holds a share takes its own experts' tensors and the first
        `vocab_size` rows of the embedding and the head."""
        loaders, kinds = {}, {}
        for layer in self.layers:
            loaders.update(layer.linears())
            kinds[layer.prefix] = layer.kind
        moes = {layer.prefix: layer.moe for layer in self.layers
                if layer.sparse}
        first = self.config.first_held_expert
        rows = self.config.vocab_size
        width = self.config.kda_heads * self.config.kda_head_dim
        params: Dict[str, Dict[str, np.ndarray]] = {}

        def bucket(key):
            return params.setdefault(key, {})

        for name, tensor in weights:
            name = name.replace(".block_sparse_moe.", ".mlp.")
            if name.startswith("lm_head"):
                self.lm_head.weight_loader(bucket("lm_head"), "weight",
                                           tensor[:rows])
                continue
            if name == "model.embed_tokens.weight":
                self.embed_tokens.weight_loader(
                    bucket("model.embed_tokens"), "weight", tensor[:rows])
                continue
            if name == "model.norm.weight" or name.endswith(
                    ("layernorm.weight", "o_norm.weight")):
                key, pname = name.rsplit(".", 1)
                bucket(key)[pname] = tensor
                continue
            if name.endswith((".A_log", ".dt_bias")):
                key, pname = name.rsplit(".", 1)
                bucket(f"{key}.kda")[pname] = np.asarray(
                    tensor, np.float32).reshape(-1)
                continue
            conv = next((i for i, c in enumerate(self._CONVS)
                         if f".{c}." in name), None)
            if conv is not None:
                # `[channels, 1, taps]` -> this projection's columns of
                # the one `[taps, 3 x channels]`
                key = name.split(f".{self._CONVS[conv]}.")[0] + ".conv1d"
                into = bucket(key)
                if "weight" not in into:
                    into["weight"] = np.zeros(
                        (tensor.shape[-1], 3 * width), tensor.dtype)
                into["weight"][:, conv * width:(conv + 1) * width] = \
                    tensor[:, 0, :].T
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
            prefix = name.split(".self_attn.")[0]
            if kinds.get(prefix) == "mla" and name.endswith(
                    ("q_proj.weight", "kv_b_proj.weight")):
                # torch's [out, in]: the rows into the program's order
                mixer = next(l.mixer for l in self.layers
                             if l.prefix == prefix)
                tensor = tensor[_by_part(
                    mixer.num_heads, mixer.nope,
                    mixer.rope if "q_proj" in name else mixer.v_dim)]
            for frag, merged, shard_id in self._STACKED + (
                    self._KDA_STACKED if kinds.get(prefix) == "kda" else []):
                if f".{frag}." in name:
                    key, pname = name.replace(frag, merged).rsplit(".", 1)
                    loaders[key].weight_loader(bucket(key), pname, tensor,
                                               shard_id)
                    break
            else:
                key, pname = name.rsplit(".", 1)
                if key in loaders:
                    loaders[key].weight_loader(bucket(key), pname, tensor)
        return params
