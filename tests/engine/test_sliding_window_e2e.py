"""Engine e2e sliding-window coverage where the window actually
BINDS: generation runs far past the window, so the one page group of a
Mistral-style model, a window group, lets go of the pages the window
has passed while decode takes new ones (`processing/block_manager.py`,
the same code that serves SmallThinker's window layers beside its full
ones). Ground truth is HF transformers' eager Mistral (which masks by
the same sliding window) generating greedily from identical
weights."""
import numpy as np
import pytest

import torch

WINDOW = 24
BLOCK = 8          # window == 3 pages exactly: the table holds 3 or 4


@pytest.fixture(scope="module")
def mistral_dir(tmp_path_factory):
    from transformers import MistralConfig, MistralForCausalLM
    torch.manual_seed(7)
    cfg = MistralConfig(vocab_size=128, hidden_size=64,
                        intermediate_size=128, num_hidden_layers=2,
                        num_attention_heads=4, num_key_value_heads=2,
                        max_position_embeddings=256,
                        sliding_window=WINDOW,
                        tie_word_embeddings=False,
                        attn_implementation="eager")
    model = MistralForCausalLM(cfg).eval().to(torch.float32)
    path = tmp_path_factory.mktemp("mistral-sw")
    model.save_pretrained(path, safe_serialization=True)
    return str(path), model


def test_sliding_window_wrap_matches_hf(mistral_dir):
    path, hf_model = mistral_dir
    prompt = [5, 9, 11, 3, 7, 2, 8, 4, 6, 10]
    steps = 40                       # 10 + 40 = 50 >> window 24

    with torch.no_grad():
        hf_ids = torch.tensor([prompt], dtype=torch.long)
        hf_out = hf_model.generate(
            hf_ids, max_new_tokens=steps, do_sample=False,
            num_beams=1, pad_token_id=0)
    hf_tokens = hf_out[0, len(prompt):].tolist()

    from aphrodite_tpu.common.sampling_params import SamplingParams
    from aphrodite_tpu.endpoints.llm import LLM
    llm = LLM(model=path, load_format="safetensors", dtype="float32",
              max_model_len=128, max_num_seqs=2, block_size=BLOCK,
              swap_space=0.01, skip_tokenizer_init=True,
              disable_log_stats=True)
    # The window must actually be in force and smaller than the output.
    assert llm.engine.model_config.get_sliding_window() == WINDOW
    out = llm.generate(
        prompt_token_ids=[prompt],
        sampling_params=SamplingParams(temperature=0.0,
                                       max_tokens=steps,
                                       ignore_eos=True))
    got = list(out[0].outputs[0].token_ids)
    # The table slid: the sequence never held more than the window and
    # a page, and every page is back on the free list.
    manager = llm.engine.scheduler.block_manager
    assert manager.window_pages_freed == (len(prompt) + steps - 2
                                          - WINDOW + 1) // BLOCK
    assert manager.get_num_free_gpu_blocks() == \
        manager.num_total_gpu_blocks
    assert got == hf_tokens


def test_sliding_window_batch_with_unwrapped_peer(mistral_dir):
    """A wrapped long sequence co-batched with a short one: per-row
    context clamps must not leak across rows."""
    path, hf_model = mistral_dir
    prompts = [[5, 9, 11, 3, 7, 2, 8, 4, 6, 10], [12, 14, 3]]
    steps = 36

    hf_tokens = []
    for p in prompts:
        with torch.no_grad():
            out = hf_model.generate(
                torch.tensor([p], dtype=torch.long),
                max_new_tokens=steps, do_sample=False, num_beams=1,
                pad_token_id=0)
        hf_tokens.append(out[0, len(p):].tolist())

    from aphrodite_tpu.common.sampling_params import SamplingParams
    from aphrodite_tpu.endpoints.llm import LLM
    llm = LLM(model=path, load_format="safetensors", dtype="float32",
              max_model_len=128, max_num_seqs=4, block_size=BLOCK,
              swap_space=0.01, skip_tokenizer_init=True,
              disable_log_stats=True)
    outs = llm.generate(
        prompt_token_ids=prompts,
        sampling_params=SamplingParams(temperature=0.0,
                                       max_tokens=steps,
                                       ignore_eos=True))
    for o, want in zip(outs, hf_tokens):
        assert list(o.outputs[0].token_ids) == want
