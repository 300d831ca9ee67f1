"""Model zoo: architectures keyed by HF `architectures[0]`
(reference: `aphrodite/modeling/models/__init__.py:12-39`).

Registry entries are import paths resolved lazily so importing the package
doesn't pull every model."""
from __future__ import annotations

import importlib
from typing import List, Optional, Type

# HF architecture name -> (module under aphrodite_tpu.modeling.models,
# class name). Llama covers the Llama-family checkpoints the reference
# maps to its LlamaForCausalLM; Mistral/Yi are Llama-architecture
# variants parameterized by their HF configs. Entries are added here
# only once the module exists.
_MODELS = {
    "LlamaForCausalLM": ("llama", "LlamaForCausalLM"),
    "LLaMAForCausalLM": ("llama", "LlamaForCausalLM"),
    "MistralForCausalLM": ("llama", "LlamaForCausalLM"),
    "YiForCausalLM": ("llama", "LlamaForCausalLM"),
    "DeciLMForCausalLM": ("decilm", "DeciLMForCausalLM"),
    "MixtralForCausalLM": ("mixtral", "MixtralForCausalLM"),
    "DeepseekForCausalLM": ("deepseek", "DeepseekForCausalLM"),
    "OPTForCausalLM": ("opt", "OPTForCausalLM"),
    "EvaByteForCausalLM": ("evabyte", "EvaByteForCausalLM"),
    "GPTJForCausalLM": ("gpt_j", "GPTJForCausalLM"),
    "GPTNeoXForCausalLM": ("gpt_neox", "GPTNeoXForCausalLM"),
    "JambaForCausalLM": ("jamba", "JambaForCausalLM"),
    "KimiLinearForCausalLM": ("kimi_linear", "KimiLinearForCausalLM"),
    "LagunaForCausalLM": ("laguna", "LagunaForCausalLM"),
    "PhiForCausalLM": ("phi", "PhiForCausalLM"),
    "Phi4FlashForCausalLM": ("phi4flash", "Phi4FlashForCausalLM"),
    "Qwen2ForCausalLM": ("qwen2", "Qwen2ForCausalLM"),
    "SarvamMLAForCausalLM": ("sarvam_mla", "SarvamMLAForCausalLM"),
    "SmallThinkerForCausalLM": ("smallthinker", "SmallThinkerForCausalLM"),
}


class ModelRegistry:

    @staticmethod
    def load_model_cls(model_arch: str) -> Optional[Type]:
        if model_arch not in _MODELS:
            return None
        module_name, cls_name = _MODELS[model_arch]
        module = importlib.import_module(
            f"aphrodite_tpu.modeling.models.{module_name}")
        return getattr(module, cls_name)

    @staticmethod
    def get_supported_archs() -> List[str]:
        return list(_MODELS.keys())
