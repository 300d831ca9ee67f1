"""Sarvam MLA config (sarvamai/sarvam-105b, `model_type` "sarvam_mla").
transformers 4.57 has no such model type; the field schema is the
checkpoint's own config.json, declared here as a defaults table (the
105B values) so that no remote code runs.

The keys are DeepSeek-V2's multi-head latent attention one for one
(`kv_lora_rank`, `qk_nope_head_dim`, `qk_rope_head_dim`, `v_head_dim`;
NO `q_lora_rank`: queries are projected directly), a `deepseek_yarn`
rotary embedding over the `qk_rope_head_dim` rotary lanes, the first
`first_k_dense_replace` layers a dense SwiGLU MLP and every other one
`num_experts` routed experts (`num_experts_per_tok` a token, a
selection bias, times `routed_scaling_factor`) beside
`num_shared_experts` shared ones. `head_dim` (576) is what a token
leaves in the cache of a layer: the latent and the one rotary key.

Two keys are NOT the publisher's (`num_routed_experts`,
`first_held_expert`): they cut an expert layer to one chip's share of
an expert-parallel stage, as `configs/laguna.py` has them. With them
`num_experts` counts the experts HELD; the router still scores
`num_routed_experts`."""
from typing import List

from transformers.configuration_utils import PretrainedConfig

_DEFAULTS = {
    "vocab_size": 262144,
    "hidden_size": 4096,
    "intermediate_size": 16384,
    "moe_intermediate_size": 2048,
    "num_hidden_layers": 32,
    "num_attention_heads": 64,
    "kv_lora_rank": 512,
    "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64,
    "q_head_dim": 192,
    "v_head_dim": 128,
    "head_dim": 576,
    "hidden_act": "silu",
    "max_position_embeddings": 131072,
    "rms_norm_eps": 1e-6,
    "rope_theta": 10000,
    "default_theta": 10000,
    "rope_scaling": None,
    "use_qk_norm": True,
    "first_k_dense_replace": 1,
    "num_experts": 128,
    "num_experts_per_tok": 8,
    "num_shared_experts": 1,
    "moe_router_enable_expert_bias": True,
    "routed_scaling_factor": 2.5,
    "attn_implementation": None,
    # the share of an expert-parallel stage; not the publisher's
    "num_routed_experts": None,     # the router's width (num_experts)
    "first_held_expert": 0,
}

_ROPE_SCALING = {
    "type": "deepseek_yarn", "factor": 40, "beta_fast": 32,
    "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
    "original_max_position_embeddings": 4096}


class SarvamMLAConfig(PretrainedConfig):
    model_type = "sarvam_mla"
    keys_to_ignore_at_inference = ["past_key_values"]

    def __init__(self, **kwargs) -> None:
        for name, default in _DEFAULTS.items():
            setattr(self, name, kwargs.pop(name, default))
        if self.rope_scaling is None:
            self.rope_scaling = dict(_ROPE_SCALING)
        if self.num_routed_experts is None:
            self.num_routed_experts = self.num_experts
        if self.q_head_dim != self.qk_nope_head_dim + self.qk_rope_head_dim:
            raise ValueError(
                f"sarvam_mla: q_head_dim {self.q_head_dim} is not "
                f"{self.qk_nope_head_dim} + {self.qk_rope_head_dim}")
        if self.head_dim != self.kv_lora_rank + self.qk_rope_head_dim:
            raise ValueError(
                f"sarvam_mla: head_dim {self.head_dim} is not the latent "
                f"{self.kv_lora_rank} + the rotary key "
                f"{self.qk_rope_head_dim}")
        if self.hidden_act != "silu":
            raise ValueError("sarvam_mla: the MLPs are SwiGLU")
        if not 0 <= self.first_held_expert <= \
                self.num_routed_experts - self.num_experts:
            raise ValueError(
                f"sarvam_mla: experts {self.first_held_expert} to "
                f"{self.first_held_expert + self.num_experts - 1} of "
                f"{self.num_routed_experts}")
        kwargs.setdefault("tie_word_embeddings", False)
        super().__init__(**kwargs)

    @property
    def sparse_layers(self) -> List[int]:
        return list(range(self.first_k_dense_replace,
                          self.num_hidden_layers))

    # What the cache layer is told (`common/config.py`).
    #: one "head" a token: `[latent | rotary key]`, `head_dim` lanes
    paged_kv_heads = 1

    @property
    def latent_value_lanes(self) -> int:
        """A page of a layer is ONE array: a token's values are the
        first `kv_lora_rank` lanes of its key (`PageGroups.latent`)."""
        return self.kv_lora_rank
