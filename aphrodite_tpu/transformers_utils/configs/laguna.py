"""Laguna config (poolside/Laguna-S-2.1 and -XS.2, `model_type`
"laguna"). transformers 4.57 has no such model type; the field schema
is the checkpoints' own config.json, declared here as a defaults table
(the S-2.1 values) so that no remote code runs.

Five lists say what a layer is, entry `l` for layer `l`:
`layer_types` ("full_attention" or "sliding_attention", a causal window
of `sliding_window` keys), `num_attention_heads_per_layer` (its query
heads, over `num_key_value_heads` KV heads in every layer),
`gating_types` ("per_head": a gate a head on the attention output),
`mlp_layer_types` ("dense", `intermediate_size` wide, or "sparse":
`num_experts` routed experts, `num_experts_per_tok` a token, beside a
shared one) and `mlp_only_layers` (the dense layers' indices, the same
fact once more). `rope_parameters` holds a rotary embedding for each
attention kind.

Two keys are NOT the publisher's (`num_routed_experts`,
`first_held_expert`): they cut an expert layer to one chip's share of
an expert-parallel stage. With them `num_experts` counts the experts
HELD; the router still scores `num_routed_experts` and a pair whose
expert is held elsewhere is left out
(`modeling/layers/fused_moe.py`)."""
from typing import List

from transformers.configuration_utils import PretrainedConfig

_ROPE = {
    "full_attention": {
        "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
        "original_max_position_embeddings": 8192, "beta_slow": 1,
        "beta_fast": 32, "attention_factor": 1.4852030263919618,
        "partial_rotary_factor": 0.5},
    "sliding_attention": {
        "rope_type": "default", "rope_theta": 10000,
        "partial_rotary_factor": 1},
}

_DEFAULTS = {
    "vocab_size": 100352,
    "hidden_size": 3072,
    "intermediate_size": 12288,
    "num_hidden_layers": 48,
    "num_attention_heads": 48,
    "num_key_value_heads": 8,
    "head_dim": 128,
    "max_position_embeddings": 1048576,
    "attention_bias": False,
    "rms_norm_eps": 1e-6,
    "num_experts": 256,
    "num_experts_per_tok": 10,
    "moe_intermediate_size": 1024,
    "shared_expert_intermediate_size": 1024,
    "norm_topk_prob": True,
    "decoder_sparse_step": 1,
    "mlp_only_layers": None,
    "gating": "per-head",
    "sliding_window": 512,
    "rope_parameters": None,
    "layer_types": None,
    "mlp_layer_types": None,
    "gating_types": None,
    "num_attention_heads_per_layer": None,
    "moe_apply_router_weight_on_input": False,
    "moe_routed_scaling_factor": 2.5,
    "moe_router_logit_softcapping": 0,
    # the share of an expert-parallel stage; not the publisher's
    "num_routed_experts": None,     # the router's width (num_experts)
    "first_held_expert": 0,
}


class LagunaConfig(PretrainedConfig):
    model_type = "laguna"
    keys_to_ignore_at_inference = ["past_key_values"]

    def __init__(self, **kwargs) -> None:
        for name, default in _DEFAULTS.items():
            setattr(self, name, kwargs.pop(name, default))
        layers = self.num_hidden_layers
        # the published pattern: one full layer, then three windowed;
        # the first layer dense, every other sparse
        if self.layer_types is None:
            self.layer_types = [
                "sliding_attention" if i % 4 else "full_attention"
                for i in range(layers)]
        if self.mlp_only_layers is None:
            self.mlp_only_layers = [0]
        if self.mlp_layer_types is None:
            self.mlp_layer_types = [
                "dense" if i in self.mlp_only_layers else "sparse"
                for i in range(layers)]
        if self.gating_types is None:
            per_head = self.gating in ("per-head", "per_head", True)
            self.gating_types = ["per_head" if per_head else "none"] * layers
        if self.num_attention_heads_per_layer is None:
            self.num_attention_heads_per_layer = \
                [self.num_attention_heads] * layers
        if self.rope_parameters is None:
            self.rope_parameters = {k: dict(v) for k, v in _ROPE.items()}
        if self.num_routed_experts is None:
            self.num_routed_experts = self.num_experts
        for key in ("layer_types", "mlp_layer_types", "gating_types",
                    "num_attention_heads_per_layer"):
            if len(getattr(self, key)) != layers:
                raise ValueError(
                    f"laguna: {key} has {len(getattr(self, key))} entries "
                    f"for {layers} layers")
        for kind in set(self.layer_types):
            if kind not in self.rope_parameters:
                raise ValueError(f"laguna: no rope_parameters for {kind!r}")
        for heads in self.num_attention_heads_per_layer:
            if heads % self.num_key_value_heads:
                raise ValueError(
                    f"laguna: {heads} query heads over "
                    f"{self.num_key_value_heads} KV heads")
        if self.moe_router_logit_softcapping:
            raise ValueError("laguna: a capped router is not written")
        if self.moe_apply_router_weight_on_input:
            raise ValueError("laguna: router weights go on the experts' "
                             "outputs here, not on their input")
        if not 0 <= self.first_held_expert <= \
                self.num_routed_experts - self.num_experts:
            raise ValueError(
                f"laguna: experts {self.first_held_expert} to "
                f"{self.first_held_expert + self.num_experts - 1} of "
                f"{self.num_routed_experts}")
        kwargs.setdefault("tie_word_embeddings", False)
        super().__init__(**kwargs)

    @property
    def sparse_layers(self) -> List[int]:
        return [i for i, kind in enumerate(self.mlp_layer_types)
                if kind == "sparse"]

    # What the cache layer is told (`common/config.py`).
    @property
    def page_layer_kinds(self) -> list:
        """`PageGroups.of`'s entry for each layer."""
        return ["window" if kind == "sliding_attention" else "full"
                for kind in self.layer_types]
