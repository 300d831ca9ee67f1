"""Jamba (ai21labs, `model_type` "jamba"; Jamba, arXiv:2403.19887;
AI21-Jamba2-3B's config.json): Mamba layers with an attention layer
every `attn_layer_period`.

No reference implementation in the CUDA tree; written from the
checkpoint's config.json and the published descriptions. `x_0 =
E[ids]`; for layer `l`, with `RMS_g(v) = v / sqrt(mean(v^2) + eps) * g`
and `eps = rms_norm_eps`:

    x = x + mixer_l(RMS_in(x))
    x = x + W_down (silu(W_gate h) * (W_up h)),   h = RMS_ff(x)

(`input_layernorm`, `pre_ff_layernorm`; no bias anywhere but where
stated) and the logits are `RMS_final(x_n) E^T` over the tied
embedding. The mixer by layer index
(`transformers_utils/configs/jamba.py::layer_kinds`):

- **attention** where `l % attn_layer_period == attn_layer_offset`:
  `q = W_q h` (`num_attention_heads` heads), `k = W_k h`, `v = W_v h`
  (`num_key_value_heads` heads), NO rotary or other positional
  encoding, causal `softmax(q k^T / sqrt(d)) v`, `W_o`. The attention
  layers are one page group (`common/config.py::PageGroups`).
- **mamba** elsewhere (`layers/mamba.py::MambaMixer`, shared with
  `models/phi4flash.py`), with an RMSNorm on each of dt, B and C:
  `[u ; z] = W_in h`; `u = silu(conv1d_causal(u) + b_conv)`;
  `[dt ; B ; C] = W_x u`; `dt = RMS_dt(dt)`, `B = RMS_B(B)`,
  `C = RMS_C(C)`; `delta = softplus(W_dt dt + b_dt)`; `A = -exp(A_log)`;
  `s_t = exp(delta_t A) s_{t-1} + (delta_t u_t) B_t^T`;
  `y_t = s_t C_t + D u_t`; out `= W_out (y * silu(z))`. The state and
  the convolution's tail live in the sequence's state slot.

The feed-forward of every layer is the dense MLP: a config with
`num_experts > 1` is refused where it is read (`JambaConfig`).
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
from aphrodite_tpu.modeling.layers.layernorm import rms_norm
from aphrodite_tpu.modeling.layers.linear import (
    LinearMethod, MergedColumnParallelLinear, QKVParallelLinear,
    RowParallelLinear, replicated_specs)
from aphrodite_tpu.modeling.layers.mamba import MambaMixer
from aphrodite_tpu.modeling.layers.vocab_embedding import (
    ParallelLMHead, VocabParallelEmbedding)

KVCache = Tuple[jax.Array, jax.Array]
Params = Dict[str, Dict[str, jax.Array]]


class JambaAttention:
    """Grouped-query attention with no positional encoding."""

    def __init__(self, config, layer_idx: int, prefix: str,
                 groups: PageGroups, dtype,
                 linear_method: Optional[LinearMethod]) -> None:
        self.prefix = prefix
        hidden = config.hidden_size
        self.num_heads = config.num_attention_heads
        self.num_kv_heads = config.num_key_value_heads
        self.head_dim = hidden // self.num_heads
        kw = dict(bias=False, dtype=dtype, linear_method=linear_method)
        self.qkv_proj = QKVParallelLinear(
            hidden, self.head_dim, self.num_heads, self.num_kv_heads, **kw)
        self.o_proj = RowParallelLinear(
            self.num_heads * self.head_dim, hidden, **kw)
        self.attn = PagedAttention(
            self.num_heads, self.head_dim, scale=self.head_dim ** -0.5,
            num_kv_heads=self.num_kv_heads,
            page_group=groups.group_of_layer[layer_idx])
        self.cache_slot = groups.slot_of_layer[layer_idx]

    def init(self) -> Params:
        return {f"{self.prefix}.qkv_proj": self.qkv_proj.init(),
                f"{self.prefix}.o_proj": self.o_proj.init()}

    def __call__(self, params: Params, h: jax.Array,
                 cache: Optional[KVCache], metadata: InputMetadata):
        q, k, v = self.qkv_proj.split(
            self.qkv_proj(params[f"{self.prefix}.qkv_proj"], h))
        k_pages, v_pages = cache if cache is not None else (None, None)
        out, k_pages, v_pages = self.attn(q, k, v, k_pages, v_pages,
                                          metadata)
        return self.o_proj(params[f"{self.prefix}.o_proj"], out), \
            (None if cache is None else (k_pages, v_pages))


class JambaDecoderLayer:

    def __init__(self, config, idx: int, kind: str, groups: PageGroups,
                 dtype, linear_method: Optional[LinearMethod]) -> None:
        self.prefix = prefix = f"model.layers.{idx}"
        self.kind = kind
        self.dtype = dtype
        self.hidden_size = config.hidden_size
        self.eps = config.rms_norm_eps
        if kind == "mamba":
            self.mixer = MambaMixer(
                config, f"{prefix}.mamba", dtype, linear_method,
                inner_norms=True, eps=config.rms_norm_eps)
        else:
            self.mixer = JambaAttention(config, idx, f"{prefix}.self_attn",
                                        groups, dtype, linear_method)
        self.gate_up_proj = MergedColumnParallelLinear(
            config.hidden_size, [config.intermediate_size] * 2,
            bias=False, dtype=dtype, linear_method=linear_method)
        self.down_proj = RowParallelLinear(
            config.intermediate_size, config.hidden_size, bias=False,
            dtype=dtype, linear_method=linear_method)

    def _gain(self) -> Dict[str, jax.Array]:
        return {"weight": jnp.ones((self.hidden_size,), dtype=self.dtype)}

    def init(self) -> Params:
        p = self.prefix
        return {
            **self.mixer.init(),
            f"{p}.input_layernorm": self._gain(),
            f"{p}.pre_ff_layernorm": self._gain(),
            f"{p}.feed_forward.gate_up_proj": self.gate_up_proj.init(),
            f"{p}.feed_forward.down_proj": self.down_proj.init(),
        }

    def normed(self, params: Params, x: jax.Array) -> jax.Array:
        return rms_norm(x, params[f"{self.prefix}.input_layernorm"]["weight"],
                        self.eps)

    def feed_forward(self, params: Params, x: jax.Array) -> jax.Array:
        p = self.prefix
        h = rms_norm(x, params[f"{p}.pre_ff_layernorm"]["weight"], self.eps)
        return x + self.down_proj(
            params[f"{p}.feed_forward.down_proj"],
            silu_and_mul(self.gate_up_proj(
                params[f"{p}.feed_forward.gate_up_proj"], h)))


class JambaForCausalLM:

    def __init__(self, config, dtype: jnp.dtype = jnp.bfloat16,
                 linear_method: Optional[LinearMethod] = None) -> None:
        self.config = config
        self.dtype = dtype
        self.groups = PageGroups.of(config.page_layer_kinds, None,
                                    stateful=True)
        self.embed_tokens = VocabParallelEmbedding(
            config.vocab_size, config.hidden_size, dtype=dtype)
        self.layers = [
            JambaDecoderLayer(config, i, kind, self.groups, dtype,
                              linear_method)
            for i, kind in enumerate(config.layer_kinds)]
        self.lm_head = ParallelLMHead(config.vocab_size,
                                      config.hidden_size, dtype=dtype)
        #: the model's one `(tail, state)` pair follows the page pairs
        #: in `kv_caches`; a mamba layer's place on its leading axis
        self.state_pair = self.groups.layers_per_group
        self.state_at = {
            layer.prefix: i for i, layer in enumerate(
                l for l in self.layers if l.kind == "mamba")}

    def init_params(self) -> Params:
        params: Params = {"model.embed_tokens": self.embed_tokens.init()}
        for layer in self.layers:
            params.update(layer.init())
        params["model.final_layernorm"] = self.layers[0]._gain()
        return params

    def param_specs(self) -> Dict[str, Dict[str, P]]:
        """One chip holds the model whole (the state arrays and the
        Pallas scan are single-device programs): every leaf
        replicated."""
        return replicated_specs(jax.eval_shape(self.init_params))

    def __call__(self, params: Params, input_ids, positions,
                 kv_caches: Optional[List[KVCache]],
                 metadata: InputMetadata):
        """`kv_caches`: a pair of page arrays for each attention layer
        of the page group, then the one `(tail, state)` pair of all
        the mamba layers."""
        x = self.embed_tokens(params["model.embed_tokens"], input_ids)
        caches = list(kv_caches) if kv_caches is not None else None
        for layer in self.layers:
            h = layer.normed(params, x)
            if layer.kind == "mamba":
                at = self.state_pair
                out, _, new = layer.mixer(
                    params, h, positions,
                    caches[at] if caches is not None else None, metadata,
                    self.state_at[layer.prefix])
            else:
                at = layer.mixer.cache_slot
                out, new = layer.mixer(
                    params, h, caches[at] if caches is not None else None,
                    metadata)
            if new is not None:
                caches[at] = new
            x = layer.feed_forward(params, x + out)
        return rms_norm(x, params["model.final_layernorm"]["weight"],
                        self.config.rms_norm_eps), caches

    def compute_logits(self, params: Params, hidden):
        return self.lm_head.compute_logits(params["model.embed_tokens"],
                                           hidden)

    # ---- weight loading ----
    # (HF name fragment, our merged param, shard id)
    _STACKED = [
        ("q_proj", "qkv_proj", "q"),
        ("k_proj", "qkv_proj", "k"),
        ("v_proj", "qkv_proj", "v"),
        ("gate_proj", "gate_up_proj", 0),
        ("up_proj", "gate_up_proj", 1),
    ]

    def load_weights(self, weights: Iterable[Tuple[str, np.ndarray]]
                     ) -> Dict[str, Dict[str, np.ndarray]]:
        """The Hugging Face names: `model.layers.N.mamba.{in_proj,
        conv1d, x_proj, dt_proj, dt_layernorm, b_layernorm,
        c_layernorm, A_log, D, out_proj}`, `.self_attn.{q,k,v,o}_proj`,
        `.feed_forward.{gate,up,down}_proj`, `input_layernorm`,
        `pre_ff_layernorm`, `final_layernorm`. The convolution
        `[d_inner, 1, d_conv]` and `A_log` `[d_inner, d_state]` are
        held channel-last here."""
        loaders = {}
        for layer in self.layers:
            p, mixer = layer.prefix, layer.mixer
            loaders[f"{p}.feed_forward.gate_up_proj"] = layer.gate_up_proj
            loaders[f"{p}.feed_forward.down_proj"] = layer.down_proj
            if layer.kind == "mamba":
                for name in ("in_proj", "x_proj", "dt_proj", "out_proj"):
                    loaders[f"{mixer.prefix}.{name}"] = getattr(mixer, name)
            else:
                loaders[f"{mixer.prefix}.qkv_proj"] = mixer.qkv_proj
                loaders[f"{mixer.prefix}.o_proj"] = mixer.o_proj

        params: Dict[str, Dict[str, np.ndarray]] = {}

        def bucket(key: str) -> Dict[str, np.ndarray]:
            return params.setdefault(key, {})

        for name, tensor in weights:
            if name.startswith("lm_head"):
                continue                    # the embedding is the head
            key, pname = name.rsplit(".", 1)
            if key == "model.embed_tokens":
                self.embed_tokens.weight_loader(bucket(key), pname, tensor)
            elif pname in ("A_log", "D"):
                # `model.layers.N.mamba.A_log`: a leaf of the mixer
                bucket(f"{key}.ssm")[pname] = \
                    tensor.T if pname == "A_log" else tensor
            elif key.endswith(".conv1d"):
                bucket(key)[pname] = tensor[:, 0, :].T \
                    if pname == "weight" else tensor
            elif key.endswith("layernorm"):
                # the layers' norms, the final one, the mixer's inner
                # ones: gains as they come
                bucket(key)[pname] = tensor
            else:
                for hf_frag, merged, shard_id in self._STACKED:
                    if f".{hf_frag}." in name:
                        key = key.replace(hf_frag, merged)
                        loaders[key].weight_loader(bucket(key), pname,
                                                   tensor, shard_id)
                        break
                else:
                    if key not in loaders:
                        raise ValueError(
                            f"jamba: no parameter of the model takes "
                            f"the checkpoint's {name!r}")
                    loaders[key].weight_loader(bucket(key), pname, tensor)
        return params
