"""SmallThinker against its plain reference
(`perf/references/smallthinker.py`, float32, no import of the program)
on seeded weights at a toy size with every mechanism present: window
and full layers with a page group each, rotary on the window layers
alone, 64 ReGLU experts top-6 routed from the layer's input.

Logits are compared, not tokens. Float32 on both sides, so the only
difference is the order of sums: the limit, 1e-4 of the logits' spread
at a position, is some ten times what was read (6e-6 to 1e-5 over
sums of 64 to 128 terms through 4 to 8 layers) and a thousandth of
what the least of the mechanisms moves when it is left out (0.1 of the
spread and more, asserted below)."""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from perf import cells, serve_child, server as srv, weights

ROOT = cells.ROOT
ref = cells.load_module(os.path.join(ROOT, "perf", "references",
                                     "smallthinker.py"))
LIMIT = 1e-4
WINDOW, PAGE, CHUNK = 24, 8, 16


def _config(layers=4, experts=64, top_k=6, window=WINDOW):
    pattern = [int(i % 4 != 0) for i in range(layers)]
    return dict(
        architectures=["SmallThinkerForCausalLM"],
        model_type="smallthinker", vocab_size=256, hidden_size=64,
        num_hidden_layers=layers, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, max_position_embeddings=256,
        rms_norm_eps=1e-6, rope_theta=10000.0, rope_scaling=None,
        moe_ffn_hidden_size=32, moe_num_primary_experts=experts,
        moe_num_active_primary_experts=top_k,
        moe_primary_router_apply_softmax=True, norm_topk_prob=True,
        sliding_window_size=window, sliding_window_layout=pattern,
        rope_layout=list(pattern), tie_word_embeddings=False,
        torch_dtype="float32", perf=dict(reference="smallthinker"))


def _reference_logits(config, params, ids):
    x = jnp.asarray([ids], jnp.int32)
    with jax.default_matmul_precision("highest"):
        for fn, buckets in ref.stages(config):
            w = {local: params[b] for local, b in buckets.items()}
            x = getattr(ref, fn)(config, w, x, ref.Precision())
    return np.asarray(x[0])


def _off(served, want):
    """The largest difference of a position's logits, in spreads."""
    return max(float(np.abs(s - w).max() / w.std())
               for s, w in zip(served, want))


# ---- the engine: chunked prefill across the window, decode past two ----

@pytest.mark.parametrize("layers,experts,top_k", [(4, 64, 6), (8, 8, 2)],
                         ids=["64-experts-top-6", "two-periods-8-top-2"])
def test_engine_logits_against_the_reference(layers, experts, top_k,
                                             tmp_path, monkeypatch):
    """Through `LLM.generate`: the scheduler writes the 50-token
    prompt in chunks of 16 across the window of 24 (three pages), the
    window groups let pages go in every chunk and every eighth step,
    the rows run a step ahead, and 60 decode steps go through the
    cache past two windows. Every logit row the program computed for
    a sampled position is held to the reference's full forward pass
    over prompt and reply."""
    from aphrodite_tpu.modeling import loader
    monkeypatch.setenv("APHRODITE_SPEC", "0")
    monkeypatch.setattr(loader, "initialize_dummy_params",
                        loader.initialize_dummy_params)
    config = _config(layers, experts, top_k)
    model_dir = str(tmp_path / "model")
    srv.write_model_dir(model_dir, {k: v for k, v in config.items()
                                    if k != "perf"})
    serve_child.serve_weights_of(config)
    from aphrodite_tpu import LLM, SamplingParams
    llm = LLM(model=model_dir, load_format="dummy", dtype="float32",
              max_model_len=128, block_size=PAGE, max_num_seqs=4,
              max_chunk_tokens=CHUNK, swap_space=0.01,
              skip_tokenizer_init=True, disable_log_stats=True, seed=3)
    engine = llm.engine
    model = engine.executor.model_runner.model
    groups = engine.cache_config.page_groups
    assert groups.kinds == ("full", "window", "window", "window")
    assert groups.layers_per_group == layers // 4
    assert len(engine.executor.cache_engine.kv_caches) == layers // 4

    rows, compute = [], model.compute_logits

    def spy(params, hidden):
        out = compute(params, hidden)
        jax.debug.callback(lambda x: rows.append(np.asarray(x)), out,
                           ordered=True)
        return out
    model.compute_logits = spy
    prompt = np.random.default_rng(0).integers(3, 256, 50).tolist()
    steps = 60
    (out,) = llm.generate(
        prompt_token_ids=[prompt], sampling_params=SamplingParams(
            temperature=0.0, max_tokens=steps, ignore_eos=True))
    reply = list(out.outputs[0].token_ids)
    assert len(reply) == steps
    # the last prompt chunk's row and a row a decode step
    served = [r[0][:256] for r in rows[-steps:]]
    counts = engine.tracer.counts
    manager = engine.scheduler.block_manager
    assert counts["runner.ahead"] >= steps - 4
    assert counts["cache.window_pages_freed"] == \
        manager.window_pages_freed == 3 * (
            (len(prompt) + steps - 1 - WINDOW) // PAGE)
    assert counts["moe.tokens_routed"] >= \
        (len(prompt) + steps - 1) * top_k * layers
    assert 0 < counts["moe.decode_experts_touched"] <= \
        counts["moe.decode_expert_slots"]
    assert counts["attn.pages_live.window"] < \
        counts["attn.window_pages_unwindowed"]
    assert manager.get_num_free_gpu_blocks() == \
        manager.num_total_gpu_blocks

    params = weights.whole(ref.tree(config), ref.stages(config), 3)
    ids = prompt + reply
    mine = _reference_logits(config, params, ids)
    want = [mine[len(prompt) - 1 + j] for j in range(steps)]
    assert _off(served, want) <= LIMIT
    assert all(int(s.argmax()) == int(w.argmax())
               for s, w in zip(served, want))
    # the window binds at these lengths: a reference that sees every
    # key is far from what was served
    wide = _reference_logits(dict(config, sliding_window_size=10 ** 6),
                             params, ids)
    assert _off(served, [wide[len(prompt) - 1 + j]
                         for j in range(steps)]) > 1e3 * LIMIT


# ---- each mechanism shows in the logits ----

def _program_model(config):
    from aphrodite_tpu.modeling.models.smallthinker import \
        SmallThinkerForCausalLM
    from aphrodite_tpu.transformers_utils.configs import SmallThinkerConfig
    hf = SmallThinkerConfig(**{
        k: v for k, v in config.items()
        if k not in ("perf", "architectures", "model_type",
                     "torch_dtype")})
    return SmallThinkerForCausalLM(hf, jnp.float32)


def _window_ignored(model):
    for layer in model.layers:
        layer.attn.sliding_window = None


def _rotary_on_a_full_layer(model):
    model.layers[0].rotary = model.layers[1].rotary


def _silu_for_relu(model):
    for layer in model.layers:
        layer.moe.act = jax.nn.silu


def _router_fed_the_normed_input(model):
    from aphrodite_tpu.modeling.layers.layernorm import rms_norm
    for layer in model.layers:
        def normed(params, x, layer=layer, own=layer.router_logits):
            return own(params, rms_norm(
                x, params[f"{layer.prefix}.input_layernorm"]["weight"],
                layer.rms_eps))
        layer.router_logits = normed


@pytest.mark.parametrize("break_it", [
    None, _window_ignored, _rotary_on_a_full_layer, _silu_for_relu,
    _router_fed_the_normed_input],
    ids=lambda f: f.__name__.strip("_") if f else "as-written")
def test_each_mechanism_shows_in_the_logits(break_it):
    """The model's forward pass over 70 tokens (cache-less prefill),
    as written and with one mechanism broken at a time: as written it
    is the reference's to 1e-4 of a position's spread, and each break
    is a thousand times the limit away, so the comparison above would
    fail on any of them."""
    from aphrodite_tpu.modeling.input_metadata import InputMetadata
    config = _config()
    model = _program_model(config)
    have = jax.eval_shape(model.init_params)
    tree = ref.tree(config)
    assert {b: {n: (tuple(a.shape), a.dtype.name) for n, a in v.items()}
            for b, v in have.items()} == \
        {b: {n: (tuple(s[0]), s[1]) for n, s in v.items()}
         for b, v in tree.items()}
    if break_it is not None:
        break_it(model)
    params = weights.whole(tree, ref.stages(config), 5)
    ids = np.random.default_rng(2).integers(3, 256, 70).tolist()
    n = len(ids)
    hidden, _ = model(
        params, jnp.asarray([ids], jnp.int32),
        jnp.arange(n, dtype=jnp.int32)[None], None,
        InputMetadata(slot_mapping=jnp.arange(n, dtype=jnp.int32),
                      block_tables=jnp.zeros((1, 1), jnp.int32),
                      context_lens=jnp.zeros((1,), jnp.int32),
                      prompt_lens=jnp.asarray([n], jnp.int32),
                      is_prompt=True))
    served = np.asarray(model.compute_logits(params, hidden))[0][:, :256]
    want = _reference_logits(config, params, ids)
    off = _off(served, want)
    if break_it is None:
        assert off <= LIMIT
    else:
        assert off > 1e3 * LIMIT, off
