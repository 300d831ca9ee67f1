"""Jamba config (ai21labs, `model_type` "jamba"; Jamba,
arXiv:2403.19887; the sizes of AI21-Jamba2-3B are the defaults). The
field schema is the checkpoint's own config.json, declared here as a
defaults table so that what the cache layer is told (`layer_kinds`,
`page_layer_kinds`, `state_spec`) lives beside it and no remote code
runs.

A layer's mixer follows from its index `l`: attention where
`l % attn_layer_period == attn_layer_offset`, a Mamba layer elsewhere.
Its feed-forward is an expert layer where
`l % expert_layer_period == expert_layer_offset` and `num_experts > 1`,
the dense MLP elsewhere; a config with more than one expert is refused
(`modeling/models/jamba.py` builds the dense MLP alone).
"""
from typing import List

from transformers.configuration_utils import PretrainedConfig

_DEFAULTS = {
    "vocab_size": 65536,
    "hidden_size": 2560,
    "intermediate_size": 8192,
    "num_hidden_layers": 28,
    "num_attention_heads": 20,
    "num_key_value_heads": 1,
    "hidden_act": "silu",
    "max_position_embeddings": 262144,
    "rms_norm_eps": 1e-6,
    "sliding_window": None,
    "attn_layer_period": 14,
    "attn_layer_offset": 7,
    "expert_layer_period": 2,
    "expert_layer_offset": 1,
    "num_experts": 1,
    "num_experts_per_tok": 1,
    "num_logits_to_keep": 1,
    "use_mamba_kernels": True,
    "mamba_d_state": 16,
    "mamba_d_conv": 4,
    "mamba_expand": 2,
    "mamba_dt_rank": None,          # "auto": hidden_size / 16
    "mamba_conv_bias": True,
    "mamba_proj_bias": False,
}


class JambaConfig(PretrainedConfig):
    model_type = "jamba"
    keys_to_ignore_at_inference = ["past_key_values"]

    def __init__(self, **kwargs) -> None:
        for name, default in _DEFAULTS.items():
            setattr(self, name, kwargs.pop(name, default))
        if self.mamba_dt_rank in (None, "auto"):
            self.mamba_dt_rank = -(-self.hidden_size // 16)
        if self.num_experts > 1:
            raise ValueError(
                f"jamba: num_experts is {self.num_experts}; a stack "
                "whose feed-forward alternates dense and expert layers "
                "is not built here, only num_experts 1")
        if self.sliding_window is not None:
            raise ValueError(
                f"jamba: sliding_window is {self.sliding_window}; the "
                "attention layers are written for full attention "
                "(sliding_window null)")
        if not self.mamba_conv_bias or self.mamba_proj_bias:
            raise ValueError(
                f"jamba: mamba_conv_bias is {self.mamba_conv_bias} and "
                f"mamba_proj_bias {self.mamba_proj_bias}; the mixer is "
                "written with a bias on its convolution and none on "
                "its projections")
        if not 0 <= self.attn_layer_offset < self.attn_layer_period:
            raise ValueError(
                f"jamba: attn_layer_offset {self.attn_layer_offset} lies "
                f"outside attn_layer_period {self.attn_layer_period}")
        if not kwargs.setdefault("tie_word_embeddings", True):
            raise ValueError(
                "jamba: tie_word_embeddings is false; the head is "
                "written as the embedding, and a checkpoint's own "
                "lm_head would be served unread")
        super().__init__(**kwargs)

    @property
    def layer_kinds(self) -> List[str]:
        return ["attention"
                if l % self.attn_layer_period == self.attn_layer_offset
                else "mamba" for l in range(self.num_hidden_layers)]

    @property
    def mamba_d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    # What the cache layer is told (`common/config.py`).
    @property
    def page_layer_kinds(self) -> list:
        """`PageGroups.of`'s entry for each layer: an attention layer
        holds pages of every key, a Mamba layer holds none."""
        return ["full" if kind == "attention" else None
                for kind in self.layer_kinds]

    def state_spec(self, dtype: str):
        """`StateSpec`'s (layers, arrays), what a slot HOLDS: a Mamba
        layer keeps the last `d_conv - 1` inputs of its convolution in
        the model's type and its scan's state `[d_state, d_inner]` in
        float32. (How the device lays them out, the tail `d_conv` rows
        a slot in one array for the model, is `StateSpec.allocated`.)"""
        return self.layer_kinds.count("mamba"), (
            ((self.mamba_d_conv - 1, self.mamba_d_inner), dtype),
            ((self.mamba_d_state, self.mamba_d_inner), "float32"))
