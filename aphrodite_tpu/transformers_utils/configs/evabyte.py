"""EvaByte config (EvaByte/EvaByte, 6.5B, byte-level). transformers
4.57 has no `evabyte` model type; the field schema is the checkpoint's
own config.json, declared here as a defaults table (the published
values) so that no remote code runs.

`attention_class` "eva" with `window_size` and `chunk_size`: a query
attends over the exact keys of its own aligned window of `window_size`
positions and over one pooled key and value for every chunk of
`chunk_size` positions of the windows behind it
(`modeling/models/evabyte.py`). `num_pred_heads` heads of
`vocab_size` rows each share the head matrix; head 0 predicts the
next byte and is the one served."""
from transformers.configuration_utils import PretrainedConfig

_DEFAULTS = {
    "vocab_size": 320,
    "hidden_size": 4096,
    "intermediate_size": 11008,
    "num_hidden_layers": 32,
    "num_attention_heads": 32,
    "num_key_value_heads": 32,
    "hidden_act": "silu",
    "max_position_embeddings": 32768,
    "max_seq_length": 32768,
    "rms_norm_eps": 1e-5,
    "rope_theta": 100000,
    "rope_scaling": None,
    "attention_bias": False,
    "attention_class": "eva",
    "window_size": 2048,
    "chunk_size": 16,
    "num_chunks": None,
    "num_pred_heads": 8,
    "norm_add_unit_offset": True,
    "fp32_ln": False,
    "fp32_logits": True,
    "fp32_skip_add": True,
    "mixedp_attn": True,
    "init_fn": "v2",
    "init_std": 0.01275,
    "init_cutoff_factor": None,
    "lazy_init": True,
}


class EvaByteConfig(PretrainedConfig):
    model_type = "evabyte"
    keys_to_ignore_at_inference = ["past_key_values"]

    def __init__(self, **kwargs) -> None:
        for name, default in _DEFAULTS.items():
            setattr(self, name, kwargs.pop(name, default))
        if self.attention_class != "eva":
            raise ValueError(
                f"attention_class {self.attention_class!r}: only 'eva' "
                "is implemented")
        if self.num_key_value_heads != self.num_attention_heads:
            raise ValueError("EVA's pooling vectors are one a head: "
                             "num_key_value_heads has to equal "
                             "num_attention_heads")
        if self.window_size % self.chunk_size ** 2:
            raise ValueError(
                f"window_size {self.window_size} has to be a multiple "
                f"of chunk_size squared ({self.chunk_size}^2): a "
                "finished window's pooled keys fill whole pages")
        kwargs.setdefault("tie_word_embeddings", False)
        super().__init__(**kwargs)

    # What the engine reads (`common/config.py::ModelConfig`): every
    # layer holds a window list and a summary list (`PageGroups`,
    # kind "pooled"), and the chunk is the KV page.
    @property
    def page_layer_kinds(self):
        return ["pooled"] * self.num_hidden_layers

    @property
    def pooled_window(self) -> int:
        return self.window_size
