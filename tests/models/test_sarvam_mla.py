"""Sarvam MLA against its plain reference (`perf/references/
sarvam_mla.py`: the NOT absorbed equations, float32, no import of the
program) on seeded weights at a toy size with every mechanism present:
a leading dense layer, four heads of multi-head latent attention
(latent 128, 32 + 16 query lanes, values of 32: a page's row is 144
lanes padded to 256), `deepseek_yarn` over the 16 rotary lanes past
its original range, 4 held experts of 16 routed under a sigmoid router
with a selection bias, top-4, beside a shared expert, latent pages.

Logits are compared, not tokens. Float32 on both sides, so the only
difference is the order of sums (the program's decode step is
ABSORBED: other sums than the reference's): the limit, 1e-4 of the
logits' spread at a position, is some ten times what was read and a
thousandth of what the least of the mechanisms moves when it is left
out (asserted below)."""
import itertools
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from perf import cells, serve_child, server as srv, weights

ROOT = cells.ROOT
ref = cells.load_module(os.path.join(ROOT, "perf", "references",
                                     "sarvam_mla.py"))
LIMIT = 1e-4
VOCAB, PAGE, CHUNK, SEED = 256, 8, 16, 5
ROPE = {"type": "deepseek_yarn", "factor": 8, "beta_fast": 32,
        "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 32}


def _config(**changed):
    return {**dict(
        architectures=["SarvamMLAForCausalLM"], model_type="sarvam_mla",
        vocab_size=VOCAB, hidden_size=64, intermediate_size=128,
        moe_intermediate_size=32, num_hidden_layers=3,
        num_attention_heads=4, kv_lora_rank=128, qk_nope_head_dim=32,
        qk_rope_head_dim=16, q_head_dim=48, v_head_dim=32, head_dim=144,
        max_position_embeddings=256, rms_norm_eps=1e-6, rope_theta=10000,
        rope_scaling=ROPE, use_qk_norm=True, first_k_dense_replace=1,
        num_experts=4, num_routed_experts=16, first_held_expert=4,
        num_experts_per_tok=4, num_shared_experts=1,
        moe_router_enable_expert_bias=True, routed_scaling_factor=2.5,
        tie_word_embeddings=False, torch_dtype="float32",
        perf=dict(reference="sarvam_mla")), **changed}


def _hf(config):
    from aphrodite_tpu.transformers_utils.configs import SarvamMLAConfig
    return SarvamMLAConfig(**{
        k: v for k, v in config.items()
        if k not in ("perf", "architectures", "model_type", "torch_dtype")})


def _program_model(config, **kwargs):
    from aphrodite_tpu.modeling.models.sarvam_mla import (
        SarvamMLAForCausalLM)
    return SarvamMLAForCausalLM(_hf(config), jnp.float32, **kwargs)


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


def _prompt(seed, n=50):
    return np.random.default_rng(seed).integers(3, VOCAB, n).tolist()


class Served:
    """An engine over the toy model with the benchmark's weights, and
    every logit row its programs compute."""

    def __init__(self, tmp_path, monkeypatch, config=None, **overrides):
        from aphrodite_tpu.engine.aphrodite_engine import AphroditeEngine
        from aphrodite_tpu.engine.args_tools import EngineArgs
        from aphrodite_tpu.modeling import loader
        monkeypatch.setenv("APHRODITE_SPEC", "0")
        monkeypatch.setattr(loader, "initialize_dummy_params",
                            loader.initialize_dummy_params)
        self.config = config or _config()
        model_dir = str(tmp_path / "model")
        srv.write_model_dir(model_dir, {k: v for k, v in self.config.items()
                                        if k != "perf"})
        serve_child.serve_weights_of(self.config)
        pages = overrides.pop("num_gpu_blocks", None)
        args = EngineArgs(**{**dict(
            model=model_dir, load_format="dummy", dtype="float32",
            max_model_len=128, block_size=PAGE, max_num_seqs=4,
            max_chunk_tokens=CHUNK, swap_space=0.01,
            skip_tokenizer_init=True, disable_log_stats=True, seed=SEED),
            **overrides})
        configs = args.create_engine_configs()
        if pages is not None:
            configs[1].num_gpu_blocks = pages
        self.engine = AphroditeEngine(*configs)
        self.model = self.engine.executor.model_runner.model
        self.rows, compute = [], self.model.compute_logits

        def spy(params, hidden):
            out = compute(params, hidden)
            jax.debug.callback(lambda x: self.rows.append(np.asarray(x)),
                               out, ordered=True)
            return out
        self.model.compute_logits = spy
        self.params = weights.whole(ref.tree(self.config),
                                    ref.stages(self.config), SEED)
        self._ids = itertools.count()

    def run(self, prompts, steps=40, sampling=None):
        """[each request's outputs' token ids]; `sampling`: what each
        request's `SamplingParams` changes."""
        from aphrodite_tpu.common.sampling_params import SamplingParams
        names = [str(next(self._ids)) for _ in prompts]
        for name, prompt, own in zip(names, prompts,
                                     sampling or [{}] * len(prompts)):
            sp = SamplingParams(**{**dict(temperature=0.0, max_tokens=steps,
                                          ignore_eos=True), **own})
            self.engine.add_request(name, None, sp,
                                    prompt_token_ids=list(prompt))
        done = {}
        while self.engine.has_unfinished_requests():
            for out in self.engine.step():
                if out.finished:
                    done[out.request_id] = [list(c.token_ids)
                                            for c in out.outputs]
        return [done[name] for name in names]

    def want(self, prompt, reply, config=None):
        logits = _reference_logits(config or self.config, self.params,
                                   prompt + reply)
        return [logits[len(prompt) - 1 + j, :VOCAB]
                for j in range(len(reply))]


@pytest.fixture
def served(tmp_path, monkeypatch):
    return Served(tmp_path, monkeypatch)


# ---- the engine: prefill, then decode through the latent cache ----

def test_engine_logits_against_the_reference(tmp_path, monkeypatch):
    """Through the engine: the scheduler writes the 50-token prompt
    whole (nothing else runs), and 40 absorbed decode steps go through
    the cache, the rows a step ahead. Every logit row the program
    computed for a sampled position is held to the reference's NOT
    absorbed full forward pass over prompt and reply."""
    s = Served(tmp_path, monkeypatch)
    engine = s.engine
    groups = engine.cache_config.page_groups
    assert groups.kinds == ("full",) and groups.latent == 128
    assert not groups.plain and groups.arrays_per_page == 1
    caches = engine.executor.cache_engine.kv_caches
    # ONE array a layer: [pages, page, 144 lanes padded to 256]
    assert len(caches) == 3 and all(len(entry) == 1 for entry in caches)
    assert caches[0][0].shape[1:] == (PAGE, 256)
    prompt, steps = _prompt(0), 40
    ((reply,),) = s.run([prompt], steps)
    assert len(reply) == steps
    served = [r[0][:VOCAB] for r in s.rows[-steps:]]
    counts = engine.tracer.counts
    manager = engine.scheduler.block_manager
    assert counts["runner.ahead"] >= steps - 4
    # a decode step reads every row's context once a layer
    first = len(prompt) + 1
    assert counts["mla.latent_tokens_read"] == sum(
        range(first, first + steps - 1))
    assert counts["attn.prefill_steps"] == 1
    assert counts["mla.prefix_tokens_expanded"] == 0
    # every token has top-4 pairs in each of the two expert layers;
    # a quarter of the experts is held
    assert counts["moe.tokens_routed"] >= (len(prompt) + steps - 1) * 4 * 2
    assert 0.1 < counts["moe.pairs_held"] / counts["moe.tokens_routed"] < 0.5
    assert counts["moe.decode_expert_slots"] % (4 * 2) == 0
    assert manager.get_num_free_gpu_blocks() == \
        manager.num_total_gpu_blocks

    want = s.want(prompt, reply)
    assert _off(served, want) <= LIMIT
    assert all(int(a.argmax()) == int(b.argmax())
               for a, b in zip(served, want))


def test_a_prompt_in_chunks_reads_its_prefix_back(tmp_path, monkeypatch):
    """Two 70-token prompts that arrive together while three rows
    decode (more than a round's tokens, fewer prompts than rows: the
    scheduler writes them in chunks of 16 beside the rows' steps):
    each chunk after a prompt's first gathers its prefix from the
    latent pages and up-projects it (16 + 32 + 48 + 64 prefix tokens
    for a prompt in five chunks, counted in the programs). Each reply is the one the engine
    gives the prompt alone, and every position's logits are the
    reference's."""
    from aphrodite_tpu.common.sampling_params import SamplingParams
    prompts, steps = [_prompt(seed, 70) for seed in (0, 1)], 12
    early = [_prompt(seed, 20) for seed in (3, 4, 5)]
    alone = [reply for (reply,) in Served(
        tmp_path / "alone", monkeypatch).run(prompts, steps)]
    s = Served(tmp_path / "beside", monkeypatch,
               max_num_batched_tokens=128, max_num_seqs=8)
    engine = s.engine

    def add(name, ids, n):
        engine.add_request(name, None, SamplingParams(
            temperature=0.0, max_tokens=n, ignore_eos=True),
            prompt_token_ids=list(ids))
    for i, prompt in enumerate(early):
        add(f"early-{i}", prompt, 60)
    for _ in range(3):
        engine.step()
    for i, prompt in enumerate(prompts):
        add(str(i), prompt, steps)
    done = {}
    while engine.has_unfinished_requests():
        for out in engine.step():
            if out.finished:
                done[out.request_id] = list(out.outputs[0].token_ids)
    counts = engine.tracer.counts
    # (the second prompt's tail goes whole once it waits alone)
    assert counts["attn.prefill_steps"] >= 1 + 5
    assert counts["mla.prefix_tokens_expanded"] >= 16 + 32 + 48 + 64
    assert [done[str(i)] for i in range(2)] == alone
    rows = [row[:VOCAB] for batch in s.rows for row in batch]
    for prompt, reply in zip(prompts + early, alone + [
            done[f"early-{i}"] for i in range(3)]):
        for want in s.want(prompt, reply):
            assert min(_off([row], [want]) for row in rows) <= LIMIT
    manager = engine.scheduler.block_manager
    assert manager.get_num_free_gpu_blocks() == \
        manager.num_total_gpu_blocks


@pytest.mark.parametrize("name,changed", [
    ("plain-rotary", dict(rope_scaling=dict(ROPE, factor=1.0001))),
    ("no-softmax-mscale", dict(rope_scaling=dict(ROPE, mscale_all_dim=0,
                                                 mscale=0))),
    ("other-share", dict(first_held_expert=8)),
    ("routed-unscaled", dict(routed_scaling_factor=1.0)),
])
def test_every_mechanism_moves_the_logits(name, changed, served):
    """What was served is far from a reference with one mechanism
    left out or altered: the comparison above can see each."""
    prompt, steps = _prompt(1), 12
    served.rows.clear()
    ((reply,),) = served.run([prompt], steps)
    rows = [r[0][:VOCAB] for r in served.rows[-steps:]]
    assert _off(rows, served.want(prompt, reply)) <= LIMIT
    other = dict(served.config, **changed)
    assert _off(rows, served.want(prompt, reply, other)) > 1e2 * LIMIT


def test_a_fork_over_latent_pages(served):
    """Two samples of one prompt: the child shares the parent's latent
    pages and copies on its first write (one array a layer). Each
    row's logits, step by step, are the reference's over that row's
    own tokens."""
    prompt, steps = _prompt(7, 37), 12
    served.rows.clear()
    (pair,) = served.run([prompt], steps, [dict(
        temperature=1.0, n=2, best_of=2, seed=11)])
    assert len(pair) == 2 and pair[0] != pair[1]
    want = [served.want(prompt, reply) for reply in pair]
    decode = [r[:, :VOCAB] for r in served.rows[-(steps - 1):]]
    assert all(r.shape[0] == 2 for r in decode)
    for j, rows in enumerate(decode, start=1):
        straight = max(_off([rows[0]], [want[0][j]]),
                       _off([rows[1]], [want[1][j]]))
        crossed = max(_off([rows[0]], [want[1][j]]),
                      _off([rows[1]], [want[0][j]]))
        assert min(straight, crossed) <= LIMIT
    manager = served.engine.scheduler.block_manager
    assert manager.get_num_free_gpu_blocks() == \
        manager.num_total_gpu_blocks


def test_preemption_by_recompute_over_latent_pages(tmp_path, monkeypatch):
    """A pool too small for two rows to grow in: the younger row is
    preempted by recompute, gives its pages back and starts again from
    position 0. Both replies are the roomy engine's."""
    prompts = [_prompt(8, 40), _prompt(9, 40)]
    roomy = Served(tmp_path / "roomy", monkeypatch).run(prompts, steps=60)
    tight = Served(tmp_path / "tight", monkeypatch, num_gpu_blocks=20)
    assert tight.run(prompts, steps=60) == roomy
    assert tight.engine.tracer.counts["preemptions"] >= 1
    manager = tight.engine.scheduler.block_manager
    assert manager.get_num_free_gpu_blocks() == 20


# ---- the layer: absorbed against not absorbed ----

def test_absorbed_decode_is_the_not_absorbed_layer():
    """`LatentAttention` alone: a prompt step over 21 tokens (NOT
    absorbed: K and V up-projected) writes the pages; a decode step
    for token 22 (absorbed, over the pages) gives what the prompt
    path gives for the same token at the end of a 22-token prompt."""
    from aphrodite_tpu.modeling.input_metadata import InputMetadata
    from aphrodite_tpu.modeling.layers.mla import LatentAttention
    heads, nope, rope, v_dim, latent, n = 4, 32, 16, 32, 128, 22
    attn = LatentAttention(heads, nope, rope, v_dim, latent, scale=0.2)
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    q_nope = jax.random.normal(keys[0], (1, n, heads, nope))
    q_rope = jax.random.normal(keys[1], (1, n, heads, rope))
    c = jax.random.normal(keys[2], (1, n, latent))
    k_r = jax.random.normal(keys[3], (1, n, rope))
    w_uk = jax.random.normal(keys[4], (latent, heads, nope)) * 0.1
    w_uv = jax.random.normal(keys[5], (latent, heads, v_dim)) * 0.1
    pages = jnp.zeros((6, PAGE, attn.lanes))
    table = jnp.asarray([[4, 1, 3]], jnp.int32)

    def slots(positions):
        return jnp.asarray([int(table[0, p // PAGE]) * PAGE + p % PAGE
                            for p in positions], jnp.int32)

    def prompt(upto, pages):
        meta = InputMetadata(
            slot_mapping=slots(range(upto)), block_tables=table,
            context_lens=jnp.zeros((1,), jnp.int32),
            prompt_lens=jnp.asarray([upto], jnp.int32), is_prompt=True)
        return attn(q_nope[:, :upto], q_rope[:, :upto], c[:, :upto],
                    k_r[:, :upto], w_uk, w_uv, pages, meta)
    whole, _, _ = prompt(n, pages)
    _, pages, expanded = prompt(n - 1, pages)
    assert int(expanded) == 0
    # a token's row: [c | k_r | zeros]
    row = np.asarray(pages[4, 0])
    assert np.array_equal(row[:latent], np.asarray(c[0, 0]))
    assert np.array_equal(row[latent:latent + rope], np.asarray(k_r[0, 0]))
    assert not row[latent + rope:].any()
    meta = InputMetadata(
        slot_mapping=slots([n - 1]), block_tables=table,
        context_lens=jnp.asarray([n], jnp.int32), is_prompt=False)
    out, pages, _ = attn(q_nope[:, -1:], q_rope[:, -1:], c[:, -1:],
                         k_r[:, -1:], w_uk, w_uv, pages, meta)
    assert np.allclose(out[0, 0], whole[0, -1], atol=2e-5)
    assert np.array_equal(np.asarray(pages[3, (n - 1) % PAGE, :latent]),
                          np.asarray(c[0, -1]))


# ---- a prompt step: K at the keys' padded width, V at its own ----

def _cell_layer(heads):
    """`LatentAttention` at the cell's widths a head (128 + 64 query
    lanes, values of 128, latent 512: a row of 640 lanes), seeded."""
    from aphrodite_tpu.modeling.layers.mla import LatentAttention
    nope, rope, v_dim, latent = 128, 64, 128, 512
    attn = LatentAttention(heads, nope, rope, v_dim, latent,
                           scale=(nope + rope) ** -0.5)
    keys = jax.random.split(jax.random.PRNGKey(53), 2)
    w_uk = jax.random.normal(keys[0], (latent, heads, nope)) * 0.05
    w_uv = jax.random.normal(keys[1], (latent, heads, v_dim)) * 0.05
    return attn, w_uk, w_uv


def test_the_up_weights_hold_values_at_their_own_width():
    """`_up_weights` at the cell's widths, for the flash kernel: `W_K`
    `[640, heads, 256]` (a head's 128 nope columns, `k_r` through an
    identity into the next 64, 64 zero columns: 192 is a lane tile and
    a half) and `W_V` `[640, heads, 128]`, `W_UV` over zero rows for
    `k_r` and the pad, with NO zero column: the kernel multiplies no
    lane that the layer filled with zeros (PR 53; it was 256 columns a
    head, half of them zero)."""
    attn, w_uk, w_uv = _cell_layer(3)
    w_k, w_v = attn._up_weights(w_uk, w_uv, 256, 128)
    assert w_k.shape == (640, 3, 256) and w_v.shape == (640, 3, 128)
    w_k, w_v = np.asarray(w_k), np.asarray(w_v)
    assert np.array_equal(w_v[:512], np.asarray(w_uv))
    assert not w_v[512:].any() and np.abs(w_v).max(axis=0).min() > 0
    assert np.array_equal(w_k[:512, :, :128], np.asarray(w_uk))
    assert not w_k[:512, :, 128:].any() and not w_k[576:].any()
    for head in range(3):
        assert np.array_equal(w_k[512:576, head, 128:192], np.eye(64))
        assert not w_k[512:576, head, :128].any() and \
            not w_k[512:576, head, 192:].any()
    # the `jnp` functions have one head width: the values padded to it
    w_k, w_v = attn._up_weights(w_uk, w_uv, 192, 192)
    assert w_k.shape == w_v.shape == (640, 3, 192)
    assert not np.asarray(w_v)[..., 128:].any()


@pytest.mark.parametrize("ctx", [0, 24], ids=["own-keys", "gathered-prefix"])
def test_a_prompt_step_hands_the_kernel_values_128_lanes_a_head(
        ctx, monkeypatch):
    """A prompt step of `LatentAttention` at the cell's widths with
    the dispatch's rule answering as on one TPU and the flash kernel
    interpreted: q and K reach the kernel at 256 lanes a head, V at
    128, the kernel's result is `[b, s, heads, 128]` and is the
    layer's as it comes (nothing to slice); and it is what the `jnp`
    side gives (one head width, 192: the path a tree before PR 53 took
    off a TPU too), on a prompt's own keys and behind a prefix
    gathered from the pages."""
    from aphrodite_tpu.modeling.input_metadata import InputMetadata
    from aphrodite_tpu.ops.pallas import prefill_attention as flash
    heads, n = 2, 21
    attn, w_uk, w_uv = _cell_layer(heads)
    keys = jax.random.split(jax.random.PRNGKey(ctx), 4)
    q_nope = jax.random.normal(keys[0], (1, n, heads, 128))
    q_rope = jax.random.normal(keys[1], (1, n, heads, 64))
    c = jax.random.normal(keys[2], (1, ctx + n, 512))
    k_r = jax.random.normal(keys[3], (1, ctx + n, 64))
    table = jnp.asarray([[5, 2, 7, 1, 3, 6]], jnp.int32)
    slots = jnp.asarray([int(table[0, p // PAGE]) * PAGE + p % PAGE
                         for p in range(ctx + n)], jnp.int32)
    pages = jnp.zeros((8, PAGE, attn.lanes))
    if ctx:     # the prefix's rows, as an earlier chunk left them
        pages = pages.reshape(-1, attn.lanes).at[slots[:ctx]].set(
            attn._rows(c[0, :ctx], k_r[0, :ctx])).reshape(pages.shape)
    meta = InputMetadata(
        slot_mapping=slots[ctx:], block_tables=table,
        context_lens=jnp.asarray([ctx], jnp.int32),
        prompt_lens=jnp.asarray([n], jnp.int32), is_prompt=True,
        use_prefix=bool(ctx))

    def step():
        out, _, expanded = attn(q_nope, q_rope, c[:, ctx:], k_r[:, ctx:],
                                w_uk, w_uv, pages, meta)
        assert int(expanded) == ctx
        return np.asarray(out)
    want = step()
    assert want.shape == (1, n, heads * 128)
    kernel, calls = flash.prefill_flash_attention, []

    def interpreted(q, k, v, *args, **kwargs):
        out = kernel(q, k, v, *args, interpret=True, **kwargs)
        calls.append((q.shape, k.shape, v.shape, out.shape))
        return out
    monkeypatch.setattr(flash, "prefill_flash_attention", interpreted)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    got = step()
    keys_seen = (ctx + n if not ctx else table.shape[1] * PAGE)
    assert calls == [((1, n, heads, 256), (1, keys_seen, heads, 256),
                      (1, keys_seen, heads, 128), (1, n, heads, 128))]
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


# ---- the shares add up to the uncut layer ----

def test_the_shares_add_up_to_the_uncut_layer():
    """The four shares' routed parts (experts 0-3, 4-7, 8-11, 12-15 of
    16) plus the shared expert and attention counted once are the
    layer that holds all 16, in the program and in the reference."""
    whole = _config(num_experts=16, num_routed_experts=16,
                    first_held_expert=0)
    params = weights.whole(ref.tree(whole), ref.stages(whole), SEED)
    at = "model.layers.1."
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 12, 64))

    def cut(bucket, first):
        return {name: leaf if name in ("gate", "e_bias")
                else leaf[first:first + 4] for name, leaf in bucket.items()}

    def reference_routed(config, bucket):
        with jax.default_matmul_precision("highest"):
            return ref.experts(config, bucket, x, ref.Precision())

    def program_routed(config, bucket):
        layer = _program_model(config).layers[1]
        return layer.moe(bucket, x)
    for routed in (reference_routed, program_routed):
        uncut = routed(whole, params[at + "mlp.experts"])
        parts = sum(routed(_config(first_held_expert=first),
                           cut(params[at + "mlp.experts"], first))
                    for first in (0, 4, 8, 12))
        assert float(jnp.abs(uncut).max()) > 0.01
        assert np.allclose(parts, uncut, atol=1e-5)


# ---- the router's second form ----

def test_the_router_against_the_equations():
    """`FusedMoE.route` with `scoring="sigmoid"` and a selection bias:
    the chosen are the largest of sigmoid(logit) + bias, their weights
    sigmoid(logit) WITHOUT it over the chosen, renormalised; a bias
    that reorders changes the choice and never a weight's numerator."""
    from aphrodite_tpu.modeling.layers.fused_moe import FusedMoE
    moe = FusedMoE(8, 2, 16, 8, scoring="sigmoid", selection_bias=True,
                   dtype=jnp.float32)
    assert moe.init()["e_bias"].shape == (8,)
    logits = jnp.asarray([[0.0, 2.0, 1.9, -1.0, 0.5, 1.0, -3.0, 0.2]])
    bias = jnp.zeros((8,)).at[5].set(0.3)
    scores = 1 / (1 + np.exp(-np.asarray(logits[0])))
    probs, vals, idx = moe.route(logits, bias)
    assert np.allclose(probs[0], scores, atol=1e-6)
    # expert 5 (score 0.73 + 0.3) passes expert 2 (0.87)
    assert sorted(np.asarray(idx[0]).tolist()) == [1, 5]
    want = scores[np.asarray(idx[0])]
    assert np.allclose(vals[0], want / want.sum(), atol=1e-6)
    _, vals, idx = moe.route(logits, None)
    assert sorted(np.asarray(idx[0]).tolist()) == [1, 2]
    # the reference's own routing over random logits, a bias that
    # reorders (a tenth of the sigmoid's range)
    z = jax.random.normal(jax.random.PRNGKey(4), (64, 16))
    w = {"gate": jax.random.normal(jax.random.PRNGKey(5), (16, 8)),
         "e_bias": jax.random.uniform(jax.random.PRNGKey(6), (8,),
                                      minval=-0.1, maxval=0.1)}
    config = _config(num_experts_per_tok=2)
    with jax.default_matmul_precision("highest"):
        want_vals, want_idx = ref.route(config, w, z)
        _, vals, idx = moe.route(z @ w["gate"], w["e_bias"])
        _, _, unbiased = moe.route(z @ w["gate"], None)
    assert np.array_equal(idx, want_idx)
    assert np.allclose(vals, want_vals, atol=1e-6)
    assert not np.array_equal(np.sort(unbiased), np.sort(idx))
    # the softmax form is what it was
    soft = FusedMoE(8, 2, 16, 8, dtype=jnp.float32)
    assert "e_bias" not in soft.init()
    probs, vals, idx = soft.route(logits)
    assert np.allclose(np.asarray(probs).sum(), 1.0, atol=1e-6)
    with pytest.raises(ValueError, match="softmax.*sigmoid"):
        FusedMoE(8, 2, 16, 8, scoring="tanh")


def test_deepseek_yarn_is_the_references():
    """The program's `deepseek_yarn` table over the rotary lanes and
    its softmax mscale against the reference's own arithmetic."""
    from aphrodite_tpu.modeling.layers.rotary_embedding import (
        deepseek_yarn_softmax_mscale, get_rope)
    config = _config()
    rope = get_rope(16, 16, 256, 10000, True, dict(ROPE), max_len=64)
    inv, scale = ref.inverse_frequencies(config)
    angle = np.arange(64, dtype=np.float32)[:, None] * np.asarray(inv)
    assert scale == 1.0
    assert np.allclose(rope.cos_sin_cache[:, :8], np.cos(angle), atol=1e-5)
    assert np.allclose(rope.cos_sin_cache[:, 8:], np.sin(angle), atol=1e-5)
    m = deepseek_yarn_softmax_mscale(ROPE)
    assert np.isclose(m, 0.1 * np.log(8) + 1)
    assert np.isclose(ref.softmax_scale(config), 48 ** -0.5 * m * m)
    # the published numbers: m = 1.3689 at factor 40
    assert np.isclose(deepseek_yarn_softmax_mscale(
        dict(ROPE, factor=40)), 1.3689, atol=1e-4)


# ---- the loader ----

def test_load_weights_round_trip_by_the_assumed_names():
    """The program's own tree written out under the ASSUMED checkpoint
    names (DeepSeek-V2's convention, every routed expert's tensors
    and the whole vocabulary), loaded back by a model that holds a
    share: its own experts and rows, the rest passed by."""
    whole = _config(num_experts=16, num_routed_experts=16,
                    first_held_expert=0, vocab_size=VOCAB)
    share = _config()       # experts 4-7 of 16
    params = weights.whole(ref.tree(whole), ref.stages(whole), SEED)

    def checkpoint():
        for bucket, leaves in params.items():
            for name, leaf in leaves.items():
                leaf = np.asarray(leaf)
                if bucket.endswith("mlp.experts"):
                    at = bucket[:-len("experts")]
                    if name == "gate":
                        yield at + "gate.weight", leaf.T
                    elif name == "e_bias":
                        yield at + "gate.e_score_correction_bias", leaf
                    else:
                        which = {"w_gate": "gate_proj", "w_up": "up_proj",
                                 "w_down": "down_proj"}[name]
                        for e in range(leaf.shape[0]):
                            yield f"{at}experts.{e}.{which}.weight", \
                                leaf[e].T
                elif bucket.endswith("gate_up_proj"):
                    at = bucket[:-len("gate_up_proj")]
                    gate, up = np.split(leaf, 2, axis=1)
                    yield at + "gate_proj.weight", gate.T
                    yield at + "up_proj.weight", up.T
                elif bucket.endswith(("q_proj", "kv_b_proj")):
                    # a checkpoint has a head's two parts side by side
                    from aphrodite_tpu.modeling.models.sarvam_mla import (
                        _by_part)
                    order = _by_part(4, 32, 16 if "q_proj" in bucket
                                     else 32)
                    yield f"{bucket}.{name}", leaf.T[np.argsort(order)]
                elif leaf.ndim == 2 and not bucket.endswith(
                        ("embed_tokens", "lm_head")):
                    yield f"{bucket}.{name}", leaf.T
                else:
                    yield f"{bucket}.{name}", leaf
        yield "model.layers.0.self_attn.rotary_emb.inv_freq", np.zeros(8)

    model = _program_model(share)
    loaded = model.load_weights(checkpoint())
    want = jax.eval_shape(model.init_params)
    assert {b: set(v) for b, v in loaded.items()} == \
        {b: set(v) for b, v in want.items()}
    for bucket, leaves in loaded.items():
        for name, leaf in leaves.items():
            full = np.asarray(params[bucket][name])
            if bucket.endswith("mlp.experts") and name.startswith("w_"):
                full = full[4:8]
            assert leaf.shape == want[bucket][name].shape
            assert np.array_equal(leaf, full), (bucket, name)
    # the family's other name of the bias
    other = [(n.replace("e_score_correction_bias", "expert_bias"), t)
             for n, t in checkpoint()]
    again = model.load_weights(other)
    assert np.array_equal(again["model.layers.1.mlp.experts"]["e_bias"],
                          loaded["model.layers.1.mlp.experts"]["e_bias"])


def test_the_registry_and_the_config_class(tmp_path):
    """`model_type` "sarvam_mla" loads through the repo's own config
    class with no remote code, and the architecture is registered."""
    from aphrodite_tpu.modeling.models import ModelRegistry
    from aphrodite_tpu.transformers_utils.config import get_config
    from aphrodite_tpu.transformers_utils.configs import SarvamMLAConfig
    config = _config()
    srv.write_model_dir(str(tmp_path), {k: v for k, v in config.items()
                                        if k != "perf"})
    loaded = get_config(str(tmp_path))
    assert isinstance(loaded, SarvamMLAConfig)
    assert loaded.sparse_layers == [1, 2]
    assert loaded.paged_kv_heads == 1 and loaded.latent_value_lanes == 128
    assert ModelRegistry.load_model_cls("SarvamMLAForCausalLM").__name__ \
        == "SarvamMLAForCausalLM"
    published = SarvamMLAConfig()
    assert published.rope_scaling["type"] == "deepseek_yarn"
    assert published.head_dim == 576 and published.num_routed_experts == 128
    with pytest.raises(ValueError, match="head_dim"):
        SarvamMLAConfig(head_dim=512)
    with pytest.raises(ValueError, match="experts 120 to 135"):
        SarvamMLAConfig(num_experts=16, num_routed_experts=128,
                        first_held_expert=120)
