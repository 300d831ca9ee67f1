"""HF config loading (reference: aphrodite/transformers_utils/config.py)."""
from __future__ import annotations

from typing import Optional

from transformers import AutoConfig, PretrainedConfig


def get_config(model: str,
               trust_remote_code: bool = False,
               revision: Optional[str] = None) -> PretrainedConfig:
    if model.endswith(".gguf"):
        # Single-file GGUF checkpoint: config comes from its metadata
        # (reference `transformers_utils/config.py:77-78`).
        from aphrodite_tpu.modeling.gguf import extract_gguf_config
        return extract_gguf_config(model)
    # Checkpoints whose model_type transformers doesn't know load via
    # our config classes without trust_remote_code (reference
    # `transformers_utils/config.py:66-67,93-94`). Hub ids fetch just
    # config.json to inspect the declared type.
    import json as _json
    import os as _os
    cfg_json = _os.path.join(model, "config.json")
    if not _os.path.isfile(cfg_json) and not _os.path.isdir(model):
        try:
            from huggingface_hub import hf_hub_download
            cfg_json = hf_hub_download(model, "config.json",
                                       revision=revision)
        except Exception:
            cfg_json = ""          # offline / not a hub id
    if cfg_json and _os.path.isfile(cfg_json):
        with open(cfg_json) as f:
            declared = _json.load(f).get("model_type", "").lower()
        from aphrodite_tpu.transformers_utils import configs
        cls = {"yi": configs.YiConfig, "qwen": configs.QWenConfig,
               "smallthinker": configs.SmallThinkerConfig,
               "phi4flash": configs.Phi4FlashConfig,
               "jamba": configs.JambaConfig,
               "kimi_linear": configs.KimiLinearConfig,
               "laguna": configs.LagunaConfig,
               "evabyte": configs.EvaByteConfig,
               "sarvam_mla": configs.SarvamMLAConfig}.get(declared)
        if cls is not None:
            return cls.from_pretrained(model, revision=revision)
    try:
        config = AutoConfig.from_pretrained(
            model, trust_remote_code=trust_remote_code, revision=revision)
    except ValueError as e:
        if (not trust_remote_code
                and "requires you to execute" in str(e)):
            raise RuntimeError(
                "Failed to load the model config. If the model is a custom "
                "model not yet available in the HuggingFace transformers "
                "library, consider setting `trust_remote_code=True` or "
                "using the `--trust-remote-code` flag.") from e
        raise
    return config
