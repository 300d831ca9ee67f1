"""Phi-4-mini-flash-reasoning config (microsoft/Phi-4-mini-flash-reasoning,
`model_type` "phi4flash"; SambaY, arXiv:2507.06607). transformers 4.57
has no such model type; the field schema is the checkpoint's own
config.json, declared here as a defaults table so that no remote code
runs. The Mamba sizes config.json leaves out are the family's defaults.

A layer's mixer follows from its index `l` (`layer_kinds`), with
`n = num_hidden_layers` and `mb_per_layer` = 2:

    l even, l <= n/2       "mamba"   a selective state-space layer; the
                                     last of them (l = n/2) also hands
                                     its scan's output to the layers
                                     below as their memory
    l odd,  l <  n/2       "window"  differential attention over a
                                     causal window of `sliding_window`
    l = n/2 + 1            "full"    differential attention over every
                                     key; its K and V are kept for the
                                     layers below
    l even, l >  n/2 + 1   "gmu"     a gated memory unit over the
                                     memory; no state of its own
    l odd,  l >  n/2 + 1   "cross"   differential attention of its own
                                     queries over the full layer's K
                                     and V; it writes none
"""
from typing import List

from transformers.configuration_utils import PretrainedConfig

_DEFAULTS = {
    "vocab_size": 200064,
    "hidden_size": 2560,
    "intermediate_size": 10240,
    "num_hidden_layers": 32,
    "num_attention_heads": 40,
    "num_key_value_heads": 20,
    "hidden_act": "silu",
    "max_position_embeddings": 262144,
    "layer_norm_eps": 1e-5,
    "sliding_window": 512,
    "mb_per_layer": 2,
    "mlp_bias": False,
    "lm_head_bias": False,
    "embd_pdrop": 0,
    "resid_pdrop": 0,
    # not in config.json: the family's defaults
    "mamba_d_state": 16,
    "mamba_d_conv": 4,
    "mamba_expand": 2,
    "mamba_dt_rank": None,          # hidden_size / 16
}


class Phi4FlashConfig(PretrainedConfig):
    model_type = "phi4flash"
    keys_to_ignore_at_inference = ["past_key_values"]

    def __init__(self, **kwargs) -> None:
        for name, default in _DEFAULTS.items():
            setattr(self, name, kwargs.pop(name, default))
        if self.mamba_dt_rank is None:
            self.mamba_dt_rank = -(-self.hidden_size // 16)
        if self.mb_per_layer != 2 or self.num_hidden_layers % 4:
            raise ValueError(
                "phi4flash: the layer-kind rule is written for "
                "mb_per_layer 2 and a layer count that is a multiple "
                f"of 4, not {self.mb_per_layer} and "
                f"{self.num_hidden_layers}")
        if self.num_attention_heads % 2 or self.num_key_value_heads % 2:
            raise ValueError("phi4flash: differential attention takes "
                             "its heads in pairs")
        kwargs.setdefault("tie_word_embeddings", True)
        super().__init__(**kwargs)

    @property
    def layer_kinds(self) -> List[str]:
        half = self.num_hidden_layers // 2
        kinds = []
        for l in range(self.num_hidden_layers):
            if l % 2 == 0:
                kinds.append("mamba" if l <= half else "gmu")
            elif l < half:
                kinds.append("window")
            else:
                kinds.append("full" if l == half + 1 else "cross")
        return kinds

    @property
    def mamba_d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    # What the cache layer is told (`common/config.py`).
    @property
    def page_layer_kinds(self) -> list:
        """`PageGroups.of`'s entry for each layer: the two attention
        kinds hold pages, a cross layer reads the full layer's, a
        Mamba layer and a gated unit hold none."""
        kinds = self.layer_kinds
        kept = kinds.index("full")
        return [kind if kind in ("full", "window") else
                kept if kind == "cross" else None for kind in kinds]

    def state_spec(self, dtype: str):
        """`StateSpec`'s (layers, arrays), what a slot HOLDS: a Mamba
        layer keeps the last `d_conv - 1` inputs of its convolution in
        the model's type and its scan's state `[d_state, d_inner]` in
        float32. (How the device lays them out, the tail `d_conv` rows
        a slot in one array for the model, is `StateSpec.allocated`.)"""
        return self.layer_kinds.count("mamba"), (
            ((self.mamba_d_conv - 1, self.mamba_d_inner), dtype),
            ((self.mamba_d_state, self.mamba_d_inner), "float32"))

    # What the KV pages hold: a differential pair of KV heads as one
    # head of twice the size, `K = [k1 ; k2]`, `V = [v1 ; v2]`
    # (`modeling/models/phi4flash.py`).
    @property
    def paged_kv_heads(self) -> int:
        return self.num_key_value_heads // 2

    @property
    def paged_head_dim(self) -> int:
        return 2 * (self.hidden_size // self.num_attention_heads)
