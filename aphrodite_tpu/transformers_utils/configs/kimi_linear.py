"""Kimi Linear config (moonshotai/Kimi-Linear-48B-A3B-Instruct,
`model_type` "kimi_linear"; Kimi Linear, arXiv:2510.26692).
transformers 4.57 has no such model type; the field schema is the
checkpoint's own config.json, declared here as a defaults table (the
48B-A3B values) so that no remote code runs.

A layer's mixer follows from `linear_attn_config`'s two lists, which
count layers from ONE as published: Kimi Delta Attention (KDA: a gated
delta rule, `num_heads` heads of `head_dim` x `head_dim`, a causal
convolution of `short_conv_kernel_size` taps before q, k and v) where
`kda_layers` names it, multi-head latent attention (DeepSeek-V2's keys,
`q_lora_rank` null) where `full_attn_layers` does, with NO rotary
embedding (`mla_use_nope`). A file may hold fewer layers than the
lists name (a pipeline stage's): the entries past `num_hidden_layers`
are read by nothing. The first `first_k_dense_replace` layers have a
dense SwiGLU MLP, every other one `num_experts` routed experts
(`num_experts_per_token` a token by sigmoid scores under a selection
bias, renormalised, times `routed_scaling_factor`) beside
`num_shared_experts` shared ones. The longest context is
`model_max_length`; there is no `max_position_embeddings`.

Two keys are NOT the publisher's (`num_routed_experts`,
`first_held_expert`): they cut an expert layer to one chip's share of
an expert-parallel stage, as `configs/sarvam_mla.py` has them. With
them `num_experts` counts the experts HELD; the router still scores
`num_routed_experts`."""
from typing import List

from transformers.configuration_utils import PretrainedConfig

_DEFAULTS = {
    "vocab_size": 163840,
    "hidden_size": 2304,
    "intermediate_size": 9216,
    "moe_intermediate_size": 1024,
    "num_hidden_layers": 27,
    "num_attention_heads": 32,
    "num_key_value_heads": 32,
    "head_dim": 72,
    "kv_lora_rank": 512,
    "q_lora_rank": None,
    "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64,
    "v_head_dim": 128,
    "mla_use_nope": True,
    "hidden_act": "silu",
    "model_max_length": 1048576,
    "rms_norm_eps": 1e-5,
    "rope_theta": 10000,
    "rope_scaling": None,
    "linear_attn_config": None,
    "first_k_dense_replace": 1,
    "moe_layer_freq": 1,
    "num_experts": 256,
    "num_experts_per_token": 8,
    "num_shared_experts": 1,
    "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid",
    "routed_scaling_factor": 2.446,
    "use_grouped_topk": True,
    "num_expert_group": 1,
    "topk_group": 1,
    "num_nextn_predict_layers": 0,
    # the share of an expert-parallel stage; not the publisher's
    "num_routed_experts": None,     # the router's width (num_experts)
    "first_held_expert": 0,
}

_LINEAR_ATTN = {
    "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19,
                   21, 22, 23, 25, 26],
    "full_attn_layers": [4, 8, 12, 16, 20, 24, 27],
    "num_heads": 32, "head_dim": 128, "short_conv_kernel_size": 4}


class KimiLinearConfig(PretrainedConfig):
    model_type = "kimi_linear"
    keys_to_ignore_at_inference = ["past_key_values"]

    def __init__(self, **kwargs) -> None:
        for name, default in _DEFAULTS.items():
            setattr(self, name, kwargs.pop(name, default))
        if self.linear_attn_config is None:
            self.linear_attn_config = {k: (list(v) if isinstance(v, list)
                                           else v)
                                       for k, v in _LINEAR_ATTN.items()}
        if self.num_routed_experts is None:
            self.num_routed_experts = self.num_experts
        refused = [
            (self.q_lora_rank is not None,
             f"q_lora_rank is {self.q_lora_rank}; queries are projected "
             "directly (q_lora_rank null)"),
            (not self.mla_use_nope or self.rope_scaling is not None,
             "the latent attention layers are written without a rotary "
             "embedding (mla_use_nope true, rope_scaling null)"),
            (self.hidden_act != "silu", "the MLPs are SwiGLU"),
            (self.moe_router_activation_func != "sigmoid" or
             not self.moe_renormalize,
             "the router scores by sigmoid and renormalises the chosen "
             "weights"),
            (self.num_expert_group != 1 or self.topk_group != 1,
             f"num_expert_group {self.num_expert_group} and topk_group "
             f"{self.topk_group}; the top-k is over ONE group of experts"),
            (self.moe_layer_freq != 1,
             "every layer past first_k_dense_replace is an expert layer "
             "(moe_layer_freq 1)"),
            (self.num_nextn_predict_layers != 0,
             "no next-token-prediction layers are built"),
            (self.num_key_value_heads != self.num_attention_heads,
             "the latent attention layers have a key a query head"),
            (not 0 <= self.first_held_expert <=
             self.num_routed_experts - self.num_experts,
             f"experts {self.first_held_expert} to "
             f"{self.first_held_expert + self.num_experts - 1} of "
             f"{self.num_routed_experts}"),
        ]
        for bad, why in refused:
            if bad:
                raise ValueError(f"kimi_linear: {why}")
        self.layer_kinds            # every layer in exactly ONE list
        #: what `ModelConfig` reads the longest context from (a file
        #: may state it beside `model_max_length`; the two agree)
        stated = kwargs.pop("max_position_embeddings", None)
        if stated not in (None, self.model_max_length):
            raise ValueError(
                f"kimi_linear: max_position_embeddings {stated} is not "
                f"model_max_length {self.model_max_length}")
        self.max_position_embeddings = self.model_max_length
        kwargs.setdefault("tie_word_embeddings", False)
        super().__init__(**kwargs)

    @property
    def layer_kinds(self) -> List[str]:
        """"kda" or "mla" for each of the `num_hidden_layers` layers
        held, by the two lists (which count from one)."""
        kda = set(self.linear_attn_config["kda_layers"])
        mla = set(self.linear_attn_config["full_attn_layers"])
        kinds = []
        for l in range(1, self.num_hidden_layers + 1):
            if (l in kda) == (l in mla):
                raise ValueError(
                    f"kimi_linear: layer {l} is in "
                    f"{'both' if l in kda else 'neither'} of "
                    "linear_attn_config's kda_layers and full_attn_layers")
            kinds.append("kda" if l in kda else "mla")
        return kinds

    @property
    def kda_heads(self) -> int:
        return self.linear_attn_config["num_heads"]

    @property
    def kda_head_dim(self) -> int:
        return self.linear_attn_config["head_dim"]

    @property
    def kda_conv(self) -> int:
        return self.linear_attn_config["short_conv_kernel_size"]

    @property
    def sparse_layers(self) -> List[int]:
        return list(range(self.first_k_dense_replace,
                          self.num_hidden_layers))

    @property
    def num_experts_per_tok(self) -> int:
        """(the name the engine's memory headroom reads)"""
        return self.num_experts_per_token

    # What the cache layer is told (`common/config.py`).
    #: one "head" a token in a latent attention layer's pages:
    #: `[latent | the unrotated shared key part]`
    paged_kv_heads = 1

    @property
    def paged_head_dim(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_value_lanes(self) -> int:
        """A page of a layer is ONE array: a token's values are the
        first `kv_lora_rank` lanes of its key (`PageGroups.latent`)."""
        return self.kv_lora_rank

    @property
    def page_layer_kinds(self) -> list:
        """`PageGroups.of`'s entry for each layer: a latent attention
        layer holds pages of every key, a KDA layer holds none."""
        return ["full" if kind == "mla" else None
                for kind in self.layer_kinds]

    def state_spec(self, dtype: str):
        """`StateSpec`'s (layers, arrays), what a slot HOLDS: a KDA
        layer keeps the last `short_conv_kernel_size - 1` inputs of its
        three convolutions (q, k and v side by side) in the model's
        type and its heads' matrices `[heads, head_dim, head_dim]` in
        float32."""
        heads, dim = self.kda_heads, self.kda_head_dim
        return self.layer_kinds.count("kda"), (
            ((self.kda_conv - 1, 3 * heads * dim), dtype),
            ((heads, dim, dim), "float32"))
