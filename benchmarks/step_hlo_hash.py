"""Do the other models' step programs lower as they did? The sha256 of
the StableHLO of the REAL `ModelRunner._step` at a toy size, a prompt
step and a decode step, float32 and bfloat16, for the five models the
benchmark had before PR 48 (Mistral, SmallThinker, Phi-4-mini-flash,
Jamba and, in a tree that has it, Laguna) and, in a tree that has it,
Sarvam MLA (PR 52: latent pages), in the tree given:

    python benchmarks/step_hlo_hash.py <root of a tree> > a.txt
    python benchmarks/step_hlo_hash.py <root of its parent> > b.txt
    diff <(grep -v INFO a.txt) <(grep -v INFO b.txt)

On the CPU, half a minute a tree, no chip. A PR that touches code the
models share (a layer, the loader, the runner) and means to leave
their programs alone shows it so: 16 equal hashes say that no
operation, shape or constant of those programs changed (PR 43: the
share of experts in `FusedMoE`, the rotary tables' `max_len`).
A parent is a `git archive` of it in a scratch directory."""
import hashlib, os, sys
root = os.path.abspath(sys.argv[1])
os.chdir(root); sys.path.insert(0, root)
os.environ["JAX_PLATFORMS"] = "cpu"
import jax, jax.numpy as jnp, numpy as np
from aphrodite_tpu.common.config import ModelConfig, SchedulerConfig
from aphrodite_tpu.common.sampling_params import SamplingParams
from aphrodite_tpu.common.sequence import SequenceData, SequenceGroupMetadata
from aphrodite_tpu.executor.model_runner import ModelRunner
from aphrodite_tpu.modeling.models import ModelRegistry
from aphrodite_tpu.transformers_utils import configs
from transformers import LlamaConfig

def hf(arch):
    if arch == "mistral":
        c = LlamaConfig(vocab_size=256, hidden_size=64, intermediate_size=128, num_hidden_layers=2,
                        num_attention_heads=4, num_key_value_heads=2, max_position_embeddings=512, rope_theta=1e6)
        c.architectures = ["MistralForCausalLM"]; return c
    if arch == "smallthinker":
        c = configs.SmallThinkerConfig(vocab_size=256, hidden_size=64, num_hidden_layers=4, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, max_position_embeddings=512, moe_ffn_hidden_size=32,
            moe_num_primary_experts=16, moe_num_active_primary_experts=4, sliding_window_size=32)
        c.architectures = ["SmallThinkerForCausalLM"]; return c
    if arch == "laguna":
        kinds = ["full_attention", "sliding_attention", "sliding_attention", "full_attention"]
        c = configs.LagunaConfig(vocab_size=256, hidden_size=64, intermediate_size=128, num_hidden_layers=4,
            num_attention_heads=12, num_key_value_heads=2, head_dim=16, max_position_embeddings=256, num_experts=8,
            num_routed_experts=16, first_held_expert=0, num_experts_per_tok=4, moe_intermediate_size=32,
            shared_expert_intermediate_size=32, mlp_only_layers=[0], gating="per-head", sliding_window=24,
            rope_parameters={"full_attention": {"rope_theta": 500000, "rope_type": "yarn", "factor": 8,
                "original_max_position_embeddings": 32, "beta_slow": 1, "beta_fast": 32, "attention_factor": 1.2,
                "partial_rotary_factor": 0.5},
                "sliding_attention": {"rope_type": "default", "rope_theta": 10000, "partial_rotary_factor": 1}},
            layer_types=kinds, mlp_layer_types=["dense"] + ["sparse"] * 3, gating_types=["per_head"] * 4,
            num_attention_heads_per_layer=[12, 18, 18, 12], moe_routed_scaling_factor=2.5)
        c.architectures = ["LagunaForCausalLM"]; return c
    if arch == "sarvam":
        c = configs.SarvamMLAConfig(vocab_size=256, hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
            num_hidden_layers=3, num_attention_heads=4, kv_lora_rank=128, qk_nope_head_dim=32, qk_rope_head_dim=16,
            q_head_dim=48, v_head_dim=32, head_dim=144, max_position_embeddings=256, num_experts=4, num_routed_experts=16,
            first_held_expert=4, num_experts_per_tok=4, rope_scaling={"type": "deepseek_yarn", "factor": 8,
                "beta_fast": 32, "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1, "original_max_position_embeddings": 32})
        c.architectures = ["SarvamMLAForCausalLM"]; return c
    if arch == "kimi":
        c = configs.KimiLinearConfig(vocab_size=256, hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
            num_hidden_layers=8, num_attention_heads=4, num_key_value_heads=4, kv_lora_rank=128, qk_nope_head_dim=32,
            qk_rope_head_dim=16, v_head_dim=32, model_max_length=256, num_experts=4, num_routed_experts=16,
            first_held_expert=4, num_experts_per_token=4, linear_attn_config={"kda_layers": [1, 2, 3, 5, 6, 7],
                "full_attn_layers": [4, 8], "num_heads": 2, "head_dim": 32, "short_conv_kernel_size": 4})
        c.architectures = ["KimiLinearForCausalLM"]; return c
    if arch == "phi4flash":
        c = configs.Phi4FlashConfig(vocab_size=256, hidden_size=64, intermediate_size=128, num_hidden_layers=8,
            num_attention_heads=4, num_key_value_heads=2, max_position_embeddings=512, sliding_window=32, mamba_d_state=8)
        c.architectures = ["Phi4FlashForCausalLM"]; return c
    c = configs.JambaConfig(vocab_size=256, hidden_size=64, intermediate_size=128, num_hidden_layers=6,
        num_attention_heads=4, num_key_value_heads=1, max_position_embeddings=512, attn_layer_period=3,
        attn_layer_offset=1, expert_layer_period=2, expert_layer_offset=1, num_experts=1, num_experts_per_tok=1,
        mamba_d_state=16, mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=4, sliding_window=None)
    c.architectures = ["JambaForCausalLM"]; return c

def programs(arch, dtype):
    cfg = hf(arch)
    mc = ModelConfig("x", dtype=dtype, max_model_len=256, hf_config=cfg)
    cls = ModelRegistry.load_model_cls(cfg.architectures[0])
    model = cls(cfg, jnp.dtype(dtype), **({"max_model_len": 256} if getattr(cls, "takes_max_model_len", False) else {}))
    params = jax.eval_shape(model.init_params)
    spec = mc.get_state_spec(); groups = mc.get_page_groups()
    SLOTS, pages = 8, 64
    runner = ModelRunner(model, params, mc, SchedulerConfig(None, SLOTS, 256, 256), 16, pages * 16,
                         num_state_slots=SLOTS if spec else None)
    kv = [tuple(jax.ShapeDtypeStruct((pages, 16, h * __import__("aphrodite_tpu.ops.kv_cache", fromlist=["x"]).padded_head_size(mc.get_head_size())), jnp.dtype(dtype)) for _ in range(getattr(groups, "arrays_per_page", 2)))
          for h in mc.get_kv_heads_per_slot()]
    if spec is not None:
        kv.append(tuple(jax.ShapeDtypeStruct((spec.layers, SLOTS + 1) + s, jnp.dtype(d)) for s, d in spec.allocated))
    step = jax.jit(runner._step, static_argnames=("is_prompt", "use_prefix"), donate_argnums=(3,))
    n = len(groups.kinds)
    mds = [SequenceGroupMetadata(str(i), True, {i: SequenceData([5 + j % 50 for j in range(48)])},
            SamplingParams(temperature=0.0, max_tokens=4), {i: [1, 2, 3]}, {},
            group_tables=None if groups.plain else {i: [(0, [1, 2, 3])] * n}, state_slots={i: i} if spec else None) for i in range(2)]
    inputs, _ = runner._prepare_prompt(mds)
    out = {}
    out["prompt"] = step.lower(params, inputs["input_ids"], inputs["positions"], kv, inputs["metadata"], inputs["sel"],
                               is_prompt=True, use_prefix=False).as_text()
    rows, ctx = 4, 40
    group_rows = [[(0, [1, 2, 3])] * n for _ in range(rows)]
    batch = runner._send_decode_batch([5] * rows, [ctx - 1] * rows, [0] * rows, [ctx] * rows, [[1, 2, 3]] * rows if groups.plain else None,
                                      group_rows=None if groups.plain else group_rows, state_slots=list(range(rows)) if spec else None)
    out["decode"] = step.lower(params, None, None, kv, batch["metadata"], None, is_prompt=False, use_prefix=False).as_text()
    return out

for arch in ("mistral", "smallthinker", "phi4flash", "jamba") + (
        ("laguna",) if hasattr(configs, "LagunaConfig") else ()) + (
        ("sarvam",) if hasattr(configs, "SarvamMLAConfig") else ()) + (
        ("kimi",) if hasattr(configs, "KimiLinearConfig") else ()):
    for dtype in ("float32", "bfloat16"):
        for name, text in programs(arch, dtype).items():
            print(arch, dtype, name, hashlib.sha256(text.encode()).hexdigest()[:16], len(text))
