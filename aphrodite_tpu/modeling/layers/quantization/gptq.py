"""GPTQ weight-only int4/int8 (AutoGPTQ checkpoint format).

Reference: `aphrodite/modeling/layers/quantization/gptq.py:79-211` and
the exllama CUDA kernels (`kernels/quantization/gptq/q_gemm.cu`).

Checkpoint layout (AutoGPTQ v1):
  qweight [in/pack, out]  int32, pack = 32//bits nibbles along IN dim
  qzeros  [in/group, out/pack] int32, nibbles along OUT dim, stores z-1
  scales  [in/group, out] float16
  g_idx   [in] int32 group index per input row (act-order support)

Dequant: w[i, j] = scales[g_idx[i], j] * (q[i, j] - (z[g_idx[i], j] + 1))
(the AutoGPTQ off-by-one: zeros are stored minus 1; the kernels add it
back — `q_gemm.cu` and the reference gptq.py follow this convention).

TPU mapping: unpack + dequant in jnp feeding the bf16 MXU matmul. The
unpack is bitwise-and/shift chains XLA fuses into the GEMM prologue.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from aphrodite_tpu.common.compat import context_tp
from aphrodite_tpu.common.utils import note_kernel_path
from aphrodite_tpu.modeling.layers.linear import LinearMethod
from aphrodite_tpu.modeling.layers.quantization.base_config import (
    QuantizationConfig)


class GPTQConfig(QuantizationConfig):

    def __init__(self, weight_bits: int = 4, group_size: int = 128,
                 desc_act: bool = False) -> None:
        self.weight_bits = weight_bits
        self.group_size = group_size
        self.desc_act = desc_act
        if weight_bits not in (2, 4, 8):
            raise ValueError(
                f"GPTQ weight_bits must be 2/4/8, got {weight_bits}")
        self.pack_factor = 32 // weight_bits

    @classmethod
    def get_name(cls) -> str:
        return "gptq"

    @classmethod
    def from_config(cls, config: Dict[str, Any]) -> "GPTQConfig":
        return cls(
            weight_bits=cls.get_from_keys(config, ["bits"], 4),
            group_size=cls.get_from_keys(config, ["group_size"], 128),
            desc_act=cls.get_from_keys(config, ["desc_act"], False))

    def get_linear_method(self) -> "GPTQLinearMethod":
        return GPTQLinearMethod(self)


def _unpack_rows(packed: jax.Array, bits: int) -> jax.Array:
    """int32 [r, c] with 32//bits values packed along ROWS ->
    [r * pack, c] int32."""
    pack = 32 // bits
    shifts = jnp.arange(pack, dtype=jnp.uint32) * bits
    u = packed.astype(jnp.uint32)
    # [r, pack, c] -> [r*pack, c]
    vals = (u[:, None, :] >> shifts[None, :, None]) & ((1 << bits) - 1)
    return vals.reshape(-1, packed.shape[1]).astype(jnp.int32)


def _unpack_cols(packed: jax.Array, bits: int) -> jax.Array:
    """int32 [r, c] with 32//bits values packed along COLUMNS ->
    [r, c * pack] int32."""
    pack = 32 // bits
    shifts = jnp.arange(pack, dtype=jnp.uint32) * bits
    u = packed.astype(jnp.uint32)
    vals = (u[:, :, None] >> shifts[None, None, :]) & ((1 << bits) - 1)
    return vals.reshape(packed.shape[0], -1).astype(jnp.int32)


class GPTQLinearMethod(LinearMethod):

    def __init__(self, config: GPTQConfig) -> None:
        self.config = config

    def create_weights(self, in_features, out_features, dtype, bias,
                       out_axis, in_axis):
        cfg = self.config
        groups = max(1, in_features // cfg.group_size) \
            if cfg.group_size != -1 else 1
        params = {
            "qweight": jnp.zeros(
                (in_features // cfg.pack_factor, out_features),
                dtype=jnp.int32),
            "qzeros": jnp.zeros(
                (groups, out_features // cfg.pack_factor),
                dtype=jnp.int32),
            "scales": jnp.zeros((groups, out_features), dtype=dtype),
            "g_idx": jnp.zeros((in_features,), dtype=jnp.int32),
        }
        if bias:
            params["bias"] = jnp.zeros((out_features,), dtype=dtype)
        return params

    def create_specs(self, bias, out_axis, in_axis):
        specs = {
            "qweight": P(in_axis, out_axis),
            "qzeros": P(in_axis, out_axis),
            "scales": P(in_axis, out_axis),
            "g_idx": P(in_axis),
        }
        if bias:
            specs["bias"] = P(out_axis)
        return specs

    def dequantize(self, params: Dict[str, jax.Array],
                   dtype=jnp.bfloat16) -> jax.Array:
        bits = self.config.weight_bits
        q = _unpack_rows(params["qweight"], bits)          # [in, out]
        z = _unpack_cols(params["qzeros"], bits) + 1       # [groups, out]
        g = params["g_idx"]                                # [in]
        scales = params["scales"].astype(jnp.float32)
        w = (q - z[g]).astype(jnp.float32) * scales[g]
        return w.astype(dtype)

    def apply(self, params: Dict[str, jax.Array],
              x: jax.Array) -> jax.Array:
        cfg = self.config
        in_features = params["g_idx"].shape[0]
        out_features = params["scales"].shape[1]
        if self._use_pallas(in_features, out_features):
            from aphrodite_tpu.common import flags
            from aphrodite_tpu.ops.pallas.quant_matmul import (
                gptq_matmul, gptq_matmul_a8)
            lead = x.shape[:-1]
            # APHRODITE_W4A8=1: int8 activations into the MXU's 2x-rate
            # int8 mode (weights stay int4 at rest; activation rounding
            # is the only approximation). Off by default — numerics are
            # no longer bit-identical to the W4A16 path. 4-bit only:
            # 8-bit codes minus their zero point span [-256, 254] and
            # would wrap on the kernel's int8 cast. The a8 kernel
            # selects between the classic and the deferred-rescale
            # (int32 group accumulator) variants per shape (see the
            # quant_matmul module docstring). At m <= 64 (decode and
            # bs=1 bursts) both kernels take the STREAMED
            # work-list grid — the activation block stays resident in
            # VMEM and weight tiles flow through an explicit
            # cross-cell DMA ring.
            mm = gptq_matmul_a8 if (
                flags.get_bool("APHRODITE_W4A8") and
                cfg.weight_bits == 4) else gptq_matmul
            note_kernel_path("quant_matmul", "pallas",
                             f"gptq {mm.__name__}")
            y = mm(
                x.reshape(-1, in_features), params["qweight"],
                params["qzeros"], params["scales"],
                bits=cfg.weight_bits, group_size=cfg.group_size)
            y = y.reshape(*lead, out_features)
        else:
            note_kernel_path("quant_matmul", "reference",
                             "gptq dequantize-then-dot: "
                             f"backend={jax.default_backend()}, "
                             f"tp={context_tp()}")
            w = self.dequantize(params, x.dtype)
            y = x @ w
        if "bias" in params:
            y = y + params["bias"]
        return y

    def _use_pallas(self, in_features: int, out_features: int) -> bool:
        """Fused dequant-matmul kernel on TPU; the XLA dequantize-then-dot
        fallback everywhere else (it materializes the full bf16 weight in
        HBM every call — ~9x the traffic at int4 7B scale)."""
        from aphrodite_tpu.common import flags
        if flags.get_bool("APHRODITE_DISABLE_PALLAS_QUANT"):
            return False
        from aphrodite_tpu.ops.pallas.quant_matmul import gptq_supported
        # Pallas kernels are single-device programs: tp>1 traces take
        # the GSPMD-partitionable dequant-then-dot path (MESH003).
        return (jax.default_backend() == "tpu" and
                context_tp() == 1 and
                gptq_supported(in_features, out_features,
                               self.config.weight_bits,
                               self.config.group_size,
                               self.config.desc_act))

    def load_weight(self, params, name: str,
                    hf_tensor: np.ndarray) -> np.ndarray:
        # Packed tensors keep checkpoint layout (out on the last dim
        # already); bias/scales likewise need no transpose.
        return hf_tensor

    def out_scale(self, name: str) -> int:
        """Divisor on output-dim offsets for merged-layer placement."""
        return self.config.pack_factor if name == "qzeros" else 1
