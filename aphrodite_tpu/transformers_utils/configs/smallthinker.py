"""SmallThinker config (PowerInfer/SmallThinker-21BA3B-Instruct and
-4BA0.6B-Instruct). transformers 4.57 has no `smallthinker` model type;
the field schema is the checkpoints' own config.json, declared here as
a defaults table (the 21B-A3B values) so that no remote code runs.

`sliding_window_layout[l]` is 1 where layer `l` attends over a causal
window of `sliding_window_size` keys, 0 where it attends over all of
them; `rope_layout[l]` is 1 where the layer rotates queries and keys,
0 where it uses no positional encoding at all."""
from transformers.configuration_utils import PretrainedConfig

_DEFAULTS = {
    "vocab_size": 151936,
    "hidden_size": 2560,
    "num_hidden_layers": 52,
    "num_attention_heads": 28,
    "num_key_value_heads": 4,
    "head_dim": 128,
    "max_position_embeddings": 16384,
    "rms_norm_eps": 1e-6,
    "rope_theta": 1500000,
    "rope_scaling": None,
    "moe_ffn_hidden_size": 768,
    "moe_num_primary_experts": 64,
    "moe_num_active_primary_experts": 6,
    "moe_primary_router_apply_softmax": True,
    "norm_topk_prob": True,
    "sliding_window_size": 4096,
    "sliding_window_layout": None,
    "rope_layout": None,
    "model_name": "smallthinker_21b_instruct",
}


class SmallThinkerConfig(PretrainedConfig):
    model_type = "smallthinker"
    keys_to_ignore_at_inference = ["past_key_values"]

    def __init__(self, **kwargs) -> None:
        for name, default in _DEFAULTS.items():
            setattr(self, name, kwargs.pop(name, default))
        layers = self.num_hidden_layers
        # the published pattern: one full layer, then three windowed
        if self.sliding_window_layout is None:
            self.sliding_window_layout = [int(i % 4 != 0)
                                          for i in range(layers)]
        if self.rope_layout is None:
            self.rope_layout = list(self.sliding_window_layout)
        for key in ("sliding_window_layout", "rope_layout"):
            if len(getattr(self, key)) != layers:
                raise ValueError(
                    f"{key} has {len(getattr(self, key))} entries for "
                    f"{layers} layers")
        kwargs.setdefault("tie_word_embeddings", False)
        super().__init__(**kwargs)
