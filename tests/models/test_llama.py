"""Llama model tests: tiny-config forward on CPU, prefill/decode KV
consistency, and TP-sharded parity on the virtual 8-device mesh
(the reference's model tests need real GPUs + HF checkpoints; here a
dense-attention jnp reference computed from the same params is the gold
standard)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from aphrodite_tpu.modeling.hf_loader import initialize_dummy_params
from aphrodite_tpu.modeling.input_metadata import InputMetadata
from aphrodite_tpu.modeling.models.llama import LlamaForCausalLM


class TinyConfig:
    architectures = ["LlamaForCausalLM"]
    vocab_size = 128
    hidden_size = 64
    intermediate_size = 128
    num_hidden_layers = 2
    num_attention_heads = 4
    num_key_value_heads = 2
    rms_norm_eps = 1e-6
    max_position_embeddings = 256
    rope_theta = 10000.0
    tie_word_embeddings = False


PAGE_SIZE = 16
NUM_PAGES = 32


def make_caches(model, dtype=jnp.float32):
    cfg = model.config
    from aphrodite_tpu.ops.kv_cache import padded_head_size
    head_dim = padded_head_size(
        cfg.hidden_size // cfg.num_attention_heads)
    return [
        (jnp.zeros((NUM_PAGES, PAGE_SIZE,
                    cfg.num_key_value_heads * head_dim), dtype=dtype),
         jnp.zeros((NUM_PAGES, PAGE_SIZE,
                    cfg.num_key_value_heads * head_dim), dtype=dtype))
        for _ in range(cfg.num_hidden_layers)
    ]


@pytest.fixture(scope="module")
def model_and_params():
    model = LlamaForCausalLM(TinyConfig(), dtype=jnp.float32)
    params = initialize_dummy_params(model, seed=0, scale=2e-2)
    return model, params


def dense_reference_logits(model, params, token_ids):
    """Forward with NO kv cache (pure dense attention) as gold standard."""
    b = 1
    s = len(token_ids)
    ids = jnp.asarray([token_ids], dtype=jnp.int32)
    pos = jnp.arange(s, dtype=jnp.int32)[None]
    meta = InputMetadata(
        slot_mapping=jnp.full((s,), NUM_PAGES * PAGE_SIZE, jnp.int32),
        block_tables=jnp.full((b, 1), NUM_PAGES, jnp.int32),
        context_lens=jnp.zeros((b,), jnp.int32),
        prompt_lens=jnp.full((b,), s, jnp.int32),
        is_prompt=True)
    hidden, _ = model(params, ids, pos, None, meta)
    return model.compute_logits(params, hidden)[0]


def test_prefill_then_decode_matches_dense(model_and_params):
    """Prefill 6 tokens through the paged cache, then decode 3 more;
    every step's logits must match the dense no-cache forward."""
    model, params = model_and_params
    token_ids = [1, 5, 9, 2, 7, 3]
    caches = make_caches(model)

    s = len(token_ids)
    ids = jnp.asarray([token_ids], dtype=jnp.int32)
    pos = jnp.arange(s, dtype=jnp.int32)[None]
    # Sequence uses pages 0..  (slot = position)
    meta = InputMetadata(
        slot_mapping=jnp.arange(s, dtype=jnp.int32),
        block_tables=jnp.asarray([[0, 1, NUM_PAGES, NUM_PAGES]],
                                 jnp.int32),
        context_lens=jnp.zeros((1,), jnp.int32),
        prompt_lens=jnp.asarray([s], jnp.int32),
        is_prompt=True)
    hidden, caches = model(params, ids, pos, caches, meta)
    logits = model.compute_logits(params, hidden)[0]

    ref = dense_reference_logits(model, params, token_ids)
    np.testing.assert_allclose(np.asarray(logits[s - 1]),
                               np.asarray(ref[s - 1]), rtol=2e-4,
                               atol=2e-4)

    # Decode steps.
    for step in range(3):
        next_tok = int(jnp.argmax(logits[-1] if logits.ndim == 2
                                  else logits))
        token_ids.append(next_tok)
        cur = len(token_ids) - 1
        ids = jnp.asarray([[next_tok]], dtype=jnp.int32)
        pos = jnp.asarray([[cur]], dtype=jnp.int32)
        meta = InputMetadata(
            slot_mapping=jnp.asarray([cur], jnp.int32),
            block_tables=jnp.asarray([[0, 1, NUM_PAGES, NUM_PAGES]],
                                     jnp.int32),
            context_lens=jnp.asarray([cur + 1], jnp.int32),
            is_prompt=False)
        hidden, caches = model(params, ids, pos, caches, meta)
        logits_step = model.compute_logits(params, hidden)[0, 0]

        ref = dense_reference_logits(model, params, token_ids)
        np.testing.assert_allclose(np.asarray(logits_step),
                                   np.asarray(ref[cur]), rtol=2e-4,
                                   atol=2e-4)
        logits = logits_step


def test_tp_sharded_forward_matches_single_device(model_and_params,
                                                  cpu_devices):
    """Same logits when params are sharded over a tp=4 mesh and the
    forward runs under jit with GSPMD-inserted collectives."""
    model, params = model_and_params
    token_ids = [3, 1, 4, 1, 5, 9, 2, 6]
    ref = dense_reference_logits(model, params, token_ids)

    mesh = Mesh(np.asarray(cpu_devices[:4]).reshape(4), ("tp",))
    specs = model.param_specs()
    sharded = {
        k: {n: jax.device_put(a, NamedSharding(mesh, specs[k][n]))
            for n, a in bucket.items()}
        for k, bucket in params.items()
    }

    s = len(token_ids)
    ids = jnp.asarray([token_ids], dtype=jnp.int32)
    pos = jnp.arange(s, dtype=jnp.int32)[None]
    meta = InputMetadata(
        slot_mapping=jnp.full((s,), NUM_PAGES * PAGE_SIZE, jnp.int32),
        block_tables=jnp.full((1, 1), NUM_PAGES, jnp.int32),
        context_lens=jnp.zeros((1,), jnp.int32),
        prompt_lens=jnp.full((1,), s, jnp.int32),
        is_prompt=True)

    @jax.jit
    def fwd(p, ids, pos, meta):
        hidden, _ = model(p, ids, pos, None, meta)
        return model.compute_logits(p, hidden)

    with jax.set_mesh(mesh):
        logits = fwd(sharded, ids, pos, meta)
    np.testing.assert_allclose(np.asarray(logits[0]), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)
