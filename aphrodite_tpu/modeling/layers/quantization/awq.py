"""AWQ 4-bit weight-only (llm-awq checkpoint format).

Reference: `aphrodite/modeling/layers/quantization/awq.py` + CUDA
`kernels/quantization/awq/gemm_kernels.cu` / `dequantize.cuh`.

Checkpoint layout:
  qweight [in, out/8] int32 — 8 nibbles along OUT, interleaved order
  qzeros  [in/group, out/8] int32 — same nibble order
  scales  [in/group, out] float16

Nibble interleave (from `dequantize.cuh:40-53`): output element e lives
at nibble position [0,4,1,5,2,6,3,7][e]. Dequant: w = (q - z) * s.
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

# Element e -> nibble shift position.
AWQ_ORDER = (0, 4, 1, 5, 2, 6, 3, 7)


class AWQConfig(QuantizationConfig):

    def __init__(self, weight_bits: int = 4, group_size: int = 128,
                 zero_point: bool = True) -> None:
        if weight_bits != 4:
            raise ValueError("AWQ supports 4-bit only, got "
                             f"{weight_bits}")
        self.weight_bits = weight_bits
        self.group_size = group_size
        self.zero_point = zero_point
        self.pack_factor = 32 // weight_bits

    @classmethod
    def get_name(cls) -> str:
        return "awq"

    @classmethod
    def from_config(cls, config: Dict[str, Any]) -> "AWQConfig":
        return cls(
            weight_bits=cls.get_from_keys(config, ["w_bit", "bits"], 4),
            group_size=cls.get_from_keys(config,
                                         ["q_group_size", "group_size"],
                                         128),
            zero_point=cls.get_from_keys(config, ["zero_point"], True))

    def get_linear_method(self) -> "AWQLinearMethod":
        return AWQLinearMethod(self)


def _unpack_awq(packed: jax.Array) -> jax.Array:
    """int32 [r, c] -> [r, c*8] int32, AWQ interleaved nibble order."""
    shifts = jnp.asarray([4 * p for p in AWQ_ORDER], dtype=jnp.uint32)
    u = packed.astype(jnp.uint32)
    vals = (u[:, :, None] >> shifts[None, None, :]) & 0xF
    return vals.reshape(packed.shape[0], -1).astype(jnp.int32)


class AWQLinearMethod(LinearMethod):

    def __init__(self, config: AWQConfig) -> None:
        self.config = config

    def create_weights(self, in_features, out_features, dtype, bias,
                       out_axis, in_axis):
        cfg = self.config
        groups = max(1, in_features // cfg.group_size)
        params = {
            "qweight": jnp.zeros(
                (in_features, out_features // cfg.pack_factor),
                dtype=jnp.int32),
            "qzeros": jnp.zeros(
                (groups, out_features // cfg.pack_factor),
                dtype=jnp.int32),
            "scales": jnp.zeros((groups, out_features), dtype=dtype),
        }
        if bias:
            params["bias"] = jnp.zeros((out_features,), dtype=dtype)
        return params

    def create_specs(self, bias, out_axis, in_axis):
        specs = {
            "qweight": P(in_axis, out_axis),
            "qzeros": P(in_axis, out_axis),
            "scales": P(in_axis, out_axis),
        }
        if bias:
            specs["bias"] = P(out_axis)
        return specs

    def dequantize(self, params: Dict[str, jax.Array],
                   dtype=jnp.bfloat16) -> jax.Array:
        cfg = self.config
        q = _unpack_awq(params["qweight"])           # [in, out]
        z = _unpack_awq(params["qzeros"])            # [groups, out]
        scales = params["scales"].astype(jnp.float32)
        in_features = q.shape[0]
        g = jnp.arange(in_features) // cfg.group_size
        w = (q - z[g]).astype(jnp.float32) * scales[g]
        return w.astype(dtype)

    def apply(self, params: Dict[str, jax.Array],
              x: jax.Array) -> jax.Array:
        cfg = self.config
        qw = params["qweight"]
        in_features, n_packed = qw.shape
        lead = x.shape[:-1]
        # Pallas kernels are single-device programs: tp>1 traces take
        # the GSPMD-partitionable dequant-then-dot path (MESH003).
        if jax.default_backend() == "tpu" and context_tp() == 1:
            from aphrodite_tpu.common import flags
            from aphrodite_tpu.ops.pallas.quant_matmul import (
                awq_matmul, awq_matmul_a8, awq_supported)
            if awq_supported(in_features, n_packed * 8, cfg.group_size):
                # APHRODITE_W4A8: int8 activations into the MXU int8
                # mode — same opt-in/accuracy story as the GPTQ path
                # (AWQ is always 4-bit, so no bits gate needed). The a8
                # kernel selects classic vs deferred-rescale per
                # shape. Decode-shaped calls (m <= 64) take the
                # streamed work-list grid with its explicit weight DMA
                # ring.
                mm = awq_matmul_a8 if flags.get_bool(
                    "APHRODITE_W4A8") else awq_matmul
                note_kernel_path("quant_matmul", "pallas",
                                 f"awq {mm.__name__}")
                y = mm(x.reshape(-1, in_features), qw,
                       params["qzeros"], params["scales"],
                       group_size=cfg.group_size)
                y = y.reshape(*lead, n_packed * 8)
                if "bias" in params:
                    y = y + params["bias"]
                return y
        # XLA fallback: dequantize the whole matrix then matmul (the
        # ~9x-HBM-traffic path — only for shapes the kernel rejects).
        note_kernel_path("quant_matmul", "reference",
                         "awq dequantize-then-dot: "
                         f"backend={jax.default_backend()}, "
                         f"tp={context_tp()}")
        w = self.dequantize(params, x.dtype)
        y = x @ w
        if "bias" in params:
            y = y + params["bias"]
        return y

    def load_weight(self, params, name: str,
                    hf_tensor: np.ndarray) -> np.ndarray:
        return hf_tensor

    def out_scale(self, name: str) -> int:
        return self.config.pack_factor if name in ("qweight",
                                                   "qzeros") else 1
