"""SqueezeLLM 4-bit LUT (non-uniform) quantization.

Reference: `aphrodite/modeling/layers/quantization/squeezellm.py` +
`kernels/quantization/squeezellm/quant_cuda_kernel.cu`.

Checkpoint layout:
  qweight       [in/8, out] int32 — 8 nibbles along IN
  lookup_table  [out, 16] float16 — per-output-channel codebook

Dequant: w[i, j] = lookup_table[j, q[i, j]] (a gather, the TPU-native
form of the CUDA LUT kernel).
"""
from __future__ import annotations

from typing import Any, Dict

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from aphrodite_tpu.common.compat import context_tp
from aphrodite_tpu.common.utils import note_kernel_path
from aphrodite_tpu.modeling.layers.linear import LinearMethod
from aphrodite_tpu.modeling.layers.quantization.base_config import (
    QuantizationConfig)
from aphrodite_tpu.modeling.layers.quantization.gptq import _unpack_rows


class SqueezeLLMConfig(QuantizationConfig):

    def __init__(self, weight_bits: int = 4) -> None:
        if weight_bits != 4:
            raise ValueError("SqueezeLLM supports 4-bit only, got "
                             f"{weight_bits}")
        self.weight_bits = weight_bits
        self.pack_factor = 32 // weight_bits

    @classmethod
    def get_name(cls) -> str:
        return "squeezellm"

    @classmethod
    def from_config(cls, config: Dict[str, Any]) -> "SqueezeLLMConfig":
        return cls(weight_bits=cls.get_from_keys(config, ["wbits"], 4))

    def get_linear_method(self) -> "SqueezeLLMLinearMethod":
        return SqueezeLLMLinearMethod(self)


class SqueezeLLMLinearMethod(LinearMethod):

    def __init__(self, config: SqueezeLLMConfig) -> None:
        self.config = config

    def create_weights(self, in_features, out_features, dtype, bias,
                       out_axis, in_axis):
        params = {
            "qweight": jnp.zeros(
                (in_features // self.config.pack_factor, out_features),
                dtype=jnp.int32),
            "lookup_table": jnp.zeros((out_features, 16), dtype=dtype),
        }
        if bias:
            params["bias"] = jnp.zeros((out_features,), dtype=dtype)
        return params

    def create_specs(self, bias, out_axis, in_axis):
        specs = {
            "qweight": P(in_axis, out_axis),
            "lookup_table": P(out_axis, None),
        }
        if bias:
            specs["bias"] = P(out_axis)
        return specs

    def dequantize(self, params: Dict[str, jax.Array],
                   dtype=jnp.bfloat16) -> jax.Array:
        q = _unpack_rows(params["qweight"], 4)     # [in, out]
        lut = params["lookup_table"].astype(jnp.float32)  # [out, 16]
        # lut.T [16, out]; gather per (i, j): lut.T[q[i,j], j]
        w = jnp.take_along_axis(lut.T, q, axis=0)
        return w.astype(dtype)

    def apply(self, params: Dict[str, jax.Array],
              x: jax.Array) -> jax.Array:
        in_features = params["qweight"].shape[0] * \
            self.config.pack_factor
        out_features = params["lookup_table"].shape[0]
        if self._use_pallas(in_features, out_features):
            from aphrodite_tpu.ops.pallas.quant_matmul import (
                squeezellm_matmul)
            lead = x.shape[:-1]
            note_kernel_path("quant_matmul", "pallas",
                             "squeezellm squeezellm_matmul")
            y = squeezellm_matmul(
                x.reshape(-1, in_features), params["qweight"],
                params["lookup_table"])
            y = y.reshape(*lead, out_features)
        else:
            note_kernel_path("quant_matmul", "reference",
                             "squeezellm LUT gather + dot: "
                             f"backend={jax.default_backend()}, "
                             f"tp={context_tp()}")
            w = self.dequantize(params, x.dtype)
            y = x @ w
        if "bias" in params:
            y = y + params["bias"]
        return y

    def _use_pallas(self, in_features: int, out_features: int) -> bool:
        """Fused LUT kernel on TPU (codes stay packed in HBM); the XLA
        gather fallback everywhere else re-materializes the dense
        weight every step."""
        from aphrodite_tpu.common import flags
        if flags.get_bool("APHRODITE_DISABLE_PALLAS_QUANT"):
            return False
        from aphrodite_tpu.ops.pallas.quant_matmul import (
            squeezellm_supported)
        # Pallas kernels are single-device programs: tp>1 traces take
        # the GSPMD-partitionable gather path (MESH003).
        return (jax.default_backend() == "tpu" and
                context_tp() == 1 and
                squeezellm_supported(in_features, out_features))

    def load_weight(self, params, name: str,
                    hf_tensor: np.ndarray) -> np.ndarray:
        return hf_tensor

    def out_scale(self, name: str) -> int:
        return 1
