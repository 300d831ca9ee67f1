"""Model construction + weight loading entrypoint.

Reference: `aphrodite/modeling/loader.py:35` (`get_model`).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp
from jax.sharding import Mesh

from aphrodite_tpu.common.config import ModelConfig
from aphrodite_tpu.common.logger import init_logger
from aphrodite_tpu.modeling.hf_loader import (hf_model_weights_iterator,
                                              initialize_dummy_params,
                                              shard_params)
from aphrodite_tpu.modeling.models import ModelRegistry

logger = init_logger(__name__)

_DTYPES = {
    "float16": jnp.float16,
    "bfloat16": jnp.bfloat16,
    "float32": jnp.float32,
}


def _get_model_architecture(config) -> type:
    architectures = getattr(config, "architectures", [])
    for arch in architectures:
        model_cls = ModelRegistry.load_model_cls(arch)
        if model_cls is not None:
            return model_cls
    raise ValueError(
        f"Model architectures {architectures} are not supported for now. "
        f"Supported architectures: {ModelRegistry.get_supported_archs()}")


def get_model(model_config: ModelConfig,
              mesh: Optional[Mesh] = None,
              lora_config=None) -> Tuple[object, dict]:
    """Build the model and its (sharded) parameters.

    Returns (model, params). With a mesh, every parameter is device_put
    with its NamedSharding; single-chip gets plain device arrays. With a
    lora_config, every linear layer is built through LoRALinearMethod so
    its bucket carries slot-stacked adapter tensors.
    """
    model_cls = _get_model_architecture(model_config.hf_config)
    dtype = _DTYPES[model_config.dtype]

    linear_method = None
    if model_config.quantization is not None:
        try:
            from aphrodite_tpu.modeling.layers.quantization import (
                get_quantization_config)
        except ImportError as e:
            raise NotImplementedError(
                f"Quantization method {model_config.quantization!r} is not "
                "implemented yet in the TPU backend.") from e
        quant_config = get_quantization_config(model_config)
        linear_method = quant_config.get_linear_method()

    if lora_config is not None:
        from aphrodite_tpu.lora.layers import LoRALinearMethod
        from aphrodite_tpu.modeling.layers.linear import LinearMethod
        linear_method = LoRALinearMethod(
            linear_method or LinearMethod(),
            max_loras=lora_config.max_loras,
            max_rank=lora_config.max_lora_rank)

    # a model whose rotary tables would else span its whole trained
    # range is told the longest sequence this server admits
    bounds = {"max_model_len": model_config.max_model_len} \
        if getattr(model_cls, "takes_max_model_len", False) else {}
    model = model_cls(model_config.hf_config, dtype=dtype,
                      linear_method=linear_method, **bounds)
    if mesh is not None and mesh.shape.get("tp", 1) > 1:
        _mark_moe_sharded(model)

    if model_config.load_format == "dummy":
        return model, initialize_dummy_params(
            model, seed=model_config.seed, mesh=mesh)

    weights_iter = hf_model_weights_iterator(
        model_config.model, model_config.load_format,
        gguf_at_rest=model_config.quantization == "gguf")
    params_np = model.load_weights(weights_iter)
    if lora_config is not None:
        _add_empty_lora_params(model, params_np)
    params = shard_params(params_np, model.param_specs(), mesh, dtype)
    return model, params


def _mark_moe_sharded(model) -> None:
    """Flag every FusedMoE layer that its expert axis is mesh-partitioned
    (selects the dense GSPMD combine over the single-chip ragged-dot
    dispatch — see layers/fused_moe.py)."""
    from aphrodite_tpu.modeling.layers.fused_moe import FusedMoE
    seen = set()

    def walk(obj, depth=0):
        if id(obj) in seen or depth > 12:
            return
        seen.add(id(obj))
        if isinstance(obj, FusedMoE):
            obj.sharded = True
            return
        if isinstance(obj, dict):
            for it in obj.values():
                walk(it, depth + 1)
            return
        if isinstance(obj, (list, tuple)):
            for it in obj:
                walk(it, depth + 1)
            return
        d = getattr(obj, "__dict__", None)
        if d:
            for it in d.values():
                walk(it, depth + 1)

    walk(model)


def _add_empty_lora_params(model, params_np) -> None:
    """Checkpoints carry no adapter slots; add zeroed stacked LoRA params
    so the param-tree structure is stable for jit."""
    import numpy as np
    from aphrodite_tpu.lora.layers import LORA_A, LORA_B
    init = model.init_params()
    for key, bucket in init.items():
        for pname in (LORA_A, LORA_B):
            if pname in bucket:
                params_np.setdefault(key, {})[pname] = np.zeros(
                    bucket[pname].shape, dtype=np.float32)
