"""Custom HF config classes for checkpoints whose config.json declares a
model_type transformers doesn't ship (reference
`aphrodite/transformers_utils/configs/`): loading them through these
classes avoids trust_remote_code."""
from aphrodite_tpu.transformers_utils.configs.evabyte import EvaByteConfig
from aphrodite_tpu.transformers_utils.configs.jamba import JambaConfig
from aphrodite_tpu.transformers_utils.configs.kimi_linear import (
    KimiLinearConfig)
from aphrodite_tpu.transformers_utils.configs.laguna import LagunaConfig
from aphrodite_tpu.transformers_utils.configs.phi4flash import (
    Phi4FlashConfig)
from aphrodite_tpu.transformers_utils.configs.qwen import QWenConfig
from aphrodite_tpu.transformers_utils.configs.sarvam_mla import (
    SarvamMLAConfig)
from aphrodite_tpu.transformers_utils.configs.smallthinker import (
    SmallThinkerConfig)
from aphrodite_tpu.transformers_utils.configs.yi import YiConfig

__all__ = ["EvaByteConfig", "JambaConfig", "KimiLinearConfig",
           "LagunaConfig", "Phi4FlashConfig", "QWenConfig", "SarvamMLAConfig",
           "SmallThinkerConfig", "YiConfig"]
