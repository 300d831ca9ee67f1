"""Laguna against its plain reference (`perf/references/laguna.py`,
float32, no import of the program) on seeded weights at a toy size
with every mechanism present: a leading dense layer, window and full
layers of 18 and 12 query heads over 2 KV heads (9 and 6 query rows a
KV head, the published 72 and 48 over 8), a gate a head, YaRN over
half of a full layer's head and the plain embedding over a window
layer's whole head, 8 held experts of 16 routed, top-4, beside a
shared expert, five page groups.

Logits are compared, not tokens. Float32 on both sides, so the only
difference is the order of sums: the limit, 1e-4 of the logits' spread
at a position, is some ten times what was read (4e-6 to 1e-5 over
sums of 64 to 288 terms through 5 layers) and a thousandth of what the
least of the mechanisms moves when it is left out (0.1 of the spread
and more, asserted below)."""
import itertools
import json
import math
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from perf import cells, serve_child, server as srv, weights

ROOT = cells.ROOT
ref = cells.load_module(os.path.join(ROOT, "perf", "references",
                                     "laguna.py"))
LIMIT = 1e-4
VOCAB, WINDOW, PAGE, CHUNK, SEED = 256, 24, 8, 16, 3
KINDS = ["full_attention", "sliding_attention", "sliding_attention",
         "sliding_attention", "full_attention"]
ROPE = {
    "full_attention": {
        "rope_theta": 500000, "rope_type": "yarn", "factor": 8,
        "original_max_position_embeddings": 32, "beta_slow": 1,
        "beta_fast": 32, "attention_factor": 0.1 * math.log(8) + 1,
        "partial_rotary_factor": 0.5},
    "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                          "partial_rotary_factor": 1}}


def _config(hidden=64, **changed):
    return {**dict(
        architectures=["LagunaForCausalLM"], model_type="laguna",
        vocab_size=VOCAB, hidden_size=hidden, intermediate_size=2 * hidden,
        num_hidden_layers=5, num_attention_heads=12,
        num_key_value_heads=2, head_dim=16, max_position_embeddings=256,
        attention_bias=False, rms_norm_eps=1e-6, num_experts=8,
        num_routed_experts=16, first_held_expert=0, num_experts_per_tok=4,
        moe_intermediate_size=32, shared_expert_intermediate_size=32,
        norm_topk_prob=True, mlp_only_layers=[0], gating="per-head",
        sliding_window=WINDOW, rope_parameters=ROPE, layer_types=KINDS,
        mlp_layer_types=["dense"] + ["sparse"] * 4,
        gating_types=["per_head"] * 5,
        num_attention_heads_per_layer=[12, 18, 18, 18, 12],
        moe_routed_scaling_factor=2.5, tie_word_embeddings=False,
        torch_dtype="float32", perf=dict(reference="laguna")), **changed}


def _hf(config):
    from aphrodite_tpu.transformers_utils.configs import LagunaConfig
    return LagunaConfig(**{
        k: v for k, v in config.items()
        if k not in ("perf", "architectures", "model_type", "torch_dtype")})


def _program_model(config, **kwargs):
    from aphrodite_tpu.modeling.models.laguna import LagunaForCausalLM
    return LagunaForCausalLM(_hf(config), jnp.float32, **kwargs)


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

    def __init__(self, tmp_path, monkeypatch, **overrides):
        from aphrodite_tpu.engine.aphrodite_engine import AphroditeEngine
        from aphrodite_tpu.engine.args_tools import EngineArgs
        from aphrodite_tpu.modeling import loader
        monkeypatch.setenv("APHRODITE_SPEC", "0")
        monkeypatch.setattr(loader, "initialize_dummy_params",
                            loader.initialize_dummy_params)
        self.config = _config()
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

    def want(self, prompt, reply):
        logits = _reference_logits(self.config, self.params,
                                   prompt + reply)
        return [logits[len(prompt) - 1 + j, :VOCAB]
                for j in range(len(reply))]


@pytest.fixture
def served(tmp_path, monkeypatch):
    return Served(tmp_path, monkeypatch)


# ---- the engine: chunked prefill across the window, decode past two ----

@pytest.mark.parametrize("chunk", [CHUNK, 64],
                         ids=["chunks-of-16", "one-chunk"])
def test_engine_logits_against_the_reference(chunk, tmp_path, monkeypatch):
    """Through the engine: the scheduler writes the 50-token prompt in
    chunks of 16 across the window of 24 (the first window SHORTER
    than two chunks: three pages), the three window groups let pages
    go in every chunk and every eighth step, the rows run a step
    ahead, and 60 decode steps go through the cache past two windows.
    Every logit row the program computed for a sampled position is
    held to the reference's full forward pass over prompt and reply."""
    s = Served(tmp_path, monkeypatch, max_chunk_tokens=chunk)
    engine, model = s.engine, s.model
    groups = engine.cache_config.page_groups
    # five groups of one layer, ONE pair of page arrays, one free list
    assert groups.kinds == ("full", "window", "window", "window", "full")
    assert groups.layers_per_group == 1
    assert len(engine.executor.cache_engine.kv_caches) == 1
    # 6 and 9 query rows a KV head
    assert [layer.num_heads // layer.num_kv_heads
            for layer in model.layers] == [6, 9, 9, 9, 6]
    prompt, steps = _prompt(0), 60
    ((reply,),) = s.run([prompt], steps)
    assert len(reply) == steps
    # the last prompt chunk's row and a row a decode step
    served = [r[0][:VOCAB] for r in s.rows[-steps:]]
    counts = engine.tracer.counts
    manager = engine.scheduler.block_manager
    assert counts["runner.ahead"] >= steps - 4
    assert counts["cache.window_pages_freed"] == \
        manager.window_pages_freed == 3 * (
            (len(prompt) + steps - 1 - WINDOW) // PAGE)
    # every token has top-4 pairs in each of the four expert layers;
    # some of them, not all, meet one of the 8 held experts of 16
    assert counts["moe.tokens_routed"] >= (len(prompt) + steps - 1) * 4 * 4
    assert 0.3 < counts["moe.pairs_held"] / counts["moe.tokens_routed"] < 0.7
    assert 0 < counts["moe.decode_experts_touched"] <= \
        counts["moe.decode_expert_slots"]
    assert counts["moe.decode_expert_slots"] % (8 * 4) == 0
    assert counts["attn.pages_live.window"] < \
        counts["attn.window_pages_unwindowed"]
    assert manager.get_num_free_gpu_blocks() == \
        manager.num_total_gpu_blocks

    want = s.want(prompt, reply)
    assert _off(served, want) <= LIMIT
    assert all(int(a.argmax()) == int(b.argmax())
               for a, b in zip(served, want))
    # the window binds at these lengths: a reference that sees every
    # key is far from what was served
    wide = _reference_logits(dict(s.config, sliding_window=10 ** 6),
                             s.params, prompt + reply)
    assert _off(served, [wide[len(prompt) - 1 + j, :VOCAB]
                         for j in range(steps)]) > 1e3 * LIMIT


@pytest.mark.parametrize("chunk,past", [(CHUNK, False), (64, True)],
                         ids=["under-blocked-from", "past-blocked-from"])
def test_the_flash_kernel_serves_what_the_jnp_functions_serve(
        chunk, past, tmp_path, monkeypatch):
    """A prompt in chunks under `blocked_from` (`prefill_attention` on
    the `jnp` side) and one in a chunk past it (the walk), window
    layers of 9 and full layers of 6 query heads a KV head: with the
    dispatch's rule answering as on one TPU and the kernel interpreted,
    every prompt step's attention is the kernel's, the tokens are the
    `jnp` path's and the logits its own to the order of the sums. The
    counters say which path a step took; the tile counters count the
    512-rule's tiles of a step past the threshold on either path."""
    import functools
    from aphrodite_tpu.engine.metrics import _STAGE_COUNTERS
    from aphrodite_tpu.executor import model_runner
    from aphrodite_tpu.modeling.layers import attention as layer_mod
    from aphrodite_tpu.modeling.models import laguna
    from aphrodite_tpu.ops.pallas import prefill_attention as flash
    monkeypatch.setattr(laguna, "PREFILL_BLOCKED_FROM", 2048)
    prompt, steps = _prompt(4), 6

    def serve(name):
        s = Served(tmp_path / name, monkeypatch, max_chunk_tokens=chunk)
        ((reply,),) = s.run([prompt], steps)
        counts = s.engine.tracer.counts
        exported = {metric: total(s.engine.tracer.seconds, counts)
                    for metric, _, total in _STAGE_COUNTERS}
        with open(os.path.join(ROOT, "README.md")) as f:
            readme = f.read()
        for metric, counter in (
                ("aphrodite:prefill_attn_steps_total",
                 "attn.prefill_steps"),
                ("aphrodite:prefill_attn_kernel_steps_total",
                 "attn.prefill_kernel_steps")):
            assert exported[metric] == counts[counter]
            assert metric in readme
        return reply, [r[0][:VOCAB] for r in s.rows[-steps:]], counts

    reply, rows, counts = serve("jnp")
    chunks = -(-len(prompt) // chunk)
    assert counts["attn.prefill_steps"] == chunks
    assert counts["attn.prefill_kernel_steps"] == 0
    assert (counts["attn.prefill_tiles_padded"] > 0) == past

    rule, kernel, calls = layer_mod.takes_prefill_kernel, \
        flash.prefill_flash_attention, []

    def on_one_tpu(*args):
        with monkeypatch.context() as m:
            m.setattr(jax, "default_backend", lambda: "tpu")
            return rule(*args)

    def interpreted(q, k, *args, **kwargs):
        calls.append((q.shape, k.shape))
        return kernel(q, k, *args, interpret=True, **kwargs)
    monkeypatch.setattr(layer_mod, "takes_prefill_kernel", on_one_tpu)
    monkeypatch.setattr(model_runner, "takes_prefill_kernel", on_one_tpu)
    monkeypatch.setattr(flash, "prefill_flash_attention", interpreted)
    flash_reply, flash_rows, flash_counts = serve("flash")
    # traced once a layer and a step program, heads padded to the lanes
    assert len(calls) % 5 == 0 and {q[3] for q, _ in calls} == {128}
    assert {q[2] for q, _ in calls} == {12, 18}
    assert flash_counts["attn.prefill_kernel_steps"] == \
        flash_counts["attn.prefill_steps"] == chunks
    assert [flash_counts[f"attn.prefill_tiles_{kind}"]
            for kind in ("visited", "padded")] == \
        [counts[f"attn.prefill_tiles_{kind}"]
         for kind in ("visited", "padded")]
    assert flash_reply == reply
    assert _off(flash_rows, rows) <= LIMIT


def test_a_fork_under_five_groups(served):
    """Two samples of one prompt: the child shares the parent's pages
    in all five tables and copies on its first write. Each row's
    logits, step by step, are the reference's over that row's own
    tokens."""
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


def test_preemption_by_recompute_under_five_groups(tmp_path, monkeypatch):
    """A pool too small for two rows to grow in: the younger row is
    preempted by recompute, gives the pages of all five tables back
    and starts again from position 0. Both replies are the roomy
    engine's."""
    prompts = [_prompt(8, 40), _prompt(9, 40)]
    roomy = Served(tmp_path / "roomy", monkeypatch).run(prompts, steps=60)
    tight = Served(tmp_path / "tight", monkeypatch, num_gpu_blocks=60)
    assert tight.run(prompts, steps=60) == roomy
    assert tight.engine.tracer.counts["preemptions"] >= 1
    manager = tight.engine.scheduler.block_manager
    assert manager.get_num_free_gpu_blocks() == 60


def test_what_follows_pages_alone_is_refused_or_skipped(served):
    """What page groups refuse for the other models they refuse here:
    the prefix cache at the door (an agent fleet's traffic would want
    it first), bursts and speculative rounds never chosen."""
    from aphrodite_tpu.common.sampling_params import SamplingParams
    engine = served.engine
    with pytest.raises(ValueError, match="the prefix cache"):
        engine.add_request("p", None, SamplingParams(max_tokens=4),
                           prompt_token_ids=_prompt(1, 24), prefix_pos=8)
    engine.scheduler_config.multi_step = 4
    assert engine._burst_steps([], None) == (1, None)


# ---- a layer of each of the three kinds, and each mechanism ----

def _forward(model, params, ids):
    from aphrodite_tpu.modeling.input_metadata import InputMetadata
    n = len(ids)
    hidden, _ = model(
        params, jnp.asarray([ids], jnp.int32),
        jnp.arange(n, dtype=jnp.int32)[None], None,
        InputMetadata(slot_mapping=jnp.arange(n, dtype=jnp.int32),
                      block_tables=jnp.zeros((1, 1), jnp.int32),
                      context_lens=jnp.zeros((1,), jnp.int32),
                      prompt_lens=jnp.asarray([n], jnp.int32),
                      is_prompt=True))
    return np.asarray(model.compute_logits(params, hidden))[0][:, :VOCAB]


@pytest.mark.parametrize("at,stage", [
    (0, "layer_full_dense"), (2, "layer_window_sparse"),
    (4, "layer_full_sparse")])
def test_a_layer_of_each_kind_against_its_stage(at, stage):
    """One layer of the program alone (cache-less prefill over 70
    tokens, past two windows) against the reference's stage function
    of that kind: full and dense, window and sparse, full and sparse."""
    from aphrodite_tpu.modeling.input_metadata import InputMetadata
    config = _config()
    assert [fn for fn, _ in ref.stages(config)] == [
        "embed", "layer_full_dense", "layer_window_sparse",
        "layer_window_sparse", "layer_window_sparse", "layer_full_sparse",
        "logits"]
    fn, buckets = ref.stages(config)[at + 1]
    assert fn == stage
    model = _program_model(config)
    params = weights.whole(ref.tree(config), ref.stages(config), 4)
    n = 70
    x = jnp.asarray(np.random.default_rng(at).standard_normal(
        (1, n, 64)), jnp.float32)
    meta = InputMetadata(slot_mapping=jnp.arange(n, dtype=jnp.int32),
                         block_tables=jnp.zeros((1, 1), jnp.int32),
                         context_lens=jnp.zeros((1,), jnp.int32),
                         prompt_lens=jnp.asarray([n], jnp.int32),
                         is_prompt=True)
    out, residual, _ = model.layers[at](
        params, jnp.arange(n, dtype=jnp.int32)[None], x, None, None, meta,
        [])
    with jax.default_matmul_precision("highest"):
        want = getattr(ref, fn)(
            config, {local: params[b] for local, b in buckets.items()}, x,
            ref.Precision())
    got = np.asarray(out + residual)
    assert np.abs(got - np.asarray(want)).max() <= \
        LIMIT * float(np.asarray(want).std())


def _window_ignored(model):
    for layer in model.layers:
        layer.attn.sliding_window = None


def _the_window_layers_rotary_on_a_full_layer(model):
    model.layers[4].rotary = model.layers[1].rotary


def _yarn_without_its_factor(model):
    import copy
    rope = copy.copy(model.layers[0].rotary)
    rope.cos_sin_cache = rope.cos_sin_cache / rope.mscale
    model.layers[0].rotary = model.layers[4].rotary = rope


def _the_gate_left_open(model):
    for layer in model.layers:
        layer.g_proj = None


def _the_shared_expert_scaled_with_the_routed(model):
    for layer in model.layers[1:]:
        own = layer.mlp

        def scaled(params, hidden, own=own, by=layer.routed_scale):
            return own(params, hidden) * by
        layer.mlp = scaled


def _the_routed_sum_unscaled(model):
    for layer in model.layers[1:]:
        layer.routed_scale = 1.0


def _the_top_k_over_the_held_experts_alone(model):
    """A share that routes over its own experts: every token then has
    four held pairs, where the router scores all sixteen."""
    from aphrodite_tpu.modeling.layers.fused_moe import FusedMoE
    for layer in model.layers[1:]:
        whole = FusedMoE(8, 4, 64, 32, dtype=jnp.float32)

        def held_alone(params, hidden, counts=None, whole=whole):
            return whole(dict(params, gate=params["gate"][:, :8]), hidden,
                         counts=counts)
        layer.moe = held_alone


@pytest.mark.parametrize("break_it", [
    None, _window_ignored, _the_window_layers_rotary_on_a_full_layer,
    _yarn_without_its_factor, _the_gate_left_open,
    _the_shared_expert_scaled_with_the_routed, _the_routed_sum_unscaled,
    _the_top_k_over_the_held_experts_alone],
    ids=lambda f: f.__name__.strip("_") if f else "as-written")
def test_each_mechanism_shows_in_the_logits(break_it):
    """The model's forward pass over 70 tokens (cache-less prefill),
    as written and with one mechanism broken at a time: as written it
    is the reference's to 1e-4 of a position's spread, and each break
    is a thousand times the limit away, so the comparison above would
    fail on any of them."""
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
    ids = _prompt(2, 70)
    off = _off(_forward(model, params, ids),
               _reference_logits(config, params, ids))
    if break_it is None:
        assert off <= LIMIT
    else:
        assert off > 1e3 * LIMIT, off


# ---- the two rotary embeddings, the gate ----

def _direct_rotation(x, positions, inv_freq, scale):
    """`x` `[tokens, dim]` rotated pair by pair, the pair of dimension
    j being j + dim / 2: a 2 x 2 rotation by position x frequency,
    times `scale`, written out with no table."""
    half = x.shape[-1] // 2
    out = np.empty_like(x)
    for t, pos in enumerate(positions):
        for j in range(half):
            c, s = math.cos(pos * inv_freq[j]), math.sin(pos * inv_freq[j])
            a, b = x[t, j], x[t, j + half]
            out[t, j] = (a * c - b * s) * scale
            out[t, j + half] = (b * c + a * s) * scale
    return out


@pytest.mark.parametrize("kind", ["full_attention", "sliding_attention"])
def test_both_rotary_embeddings_against_a_direct_formula(kind):
    """The program's rotary embedding of each attention kind, and the
    reference's, against the formula written out. Full: YaRN over the
    first half of the head (Peng et al. 2023, by parts: a dimension
    that turns more than `beta_fast` times over the original range
    keeps theta's frequency, one that turns less than `beta_slow`
    times has it divided by `factor`, a ramp between), cos and sin
    times `attention_factor`, the other half of the head untouched.
    Window: theta's own frequencies over the whole head."""
    from aphrodite_tpu.modeling.models.laguna import _rope_of
    config = _config()
    stated, head = ROPE[kind], 16
    dim = int(head * stated["partial_rotary_factor"])
    theta = stated["rope_theta"]
    inv = [theta ** (-2.0 * j / dim) for j in range(dim // 2)]
    scale = 1.0
    if kind == "full_attention":
        span, factor = 32, 8
        # wavelength 2 pi / inv; turns over the original range
        turns = [span * f / (2 * math.pi) for f in inv]
        low = max(math.floor(dim * math.log(span / (32 * 2 * math.pi)) /
                             (2 * math.log(theta))), 0)
        high = min(math.ceil(dim * math.log(span / (1 * 2 * math.pi)) /
                             (2 * math.log(theta))), dim - 1)
        assert (low, high) == (0, 1) and turns[0] > 1 > turns[1]
        ramp = [min(max((j - low) / (high - low), 0.0), 1.0)
                for j in range(dim // 2)]
        inv = [f * (1 - r) + f / factor * r for f, r in zip(inv, ramp)]
        scale = stated["attention_factor"]
        assert abs(scale - 1.2079) < 1e-4
    positions = [0, 1, 7, 31, 32, 100, 127]
    rs = np.random.default_rng(5)
    q = rs.standard_normal((len(positions), 3, head)).astype(np.float32)
    k = rs.standard_normal((len(positions), 2, head)).astype(np.float32)
    rope = _rope_of(_hf(config), kind, 128)
    got_q, got_k = rope(jnp.asarray(positions), jnp.asarray(q),
                        jnp.asarray(k))
    for got, x in ((got_q, q), (got_k, k)):
        for h in range(x.shape[1]):
            want = np.concatenate([
                _direct_rotation(x[:, h, :dim], positions, inv, scale),
                x[:, h, dim:]], axis=-1)
            np.testing.assert_allclose(np.asarray(got)[:, h], want,
                                       atol=2e-5)
    # the reference's, at positions 0..127
    x = rs.standard_normal((1, 128, 2, head)).astype(np.float32)
    mine = np.asarray(ref.rotary(jnp.asarray(x), stated))
    for h in range(2):
        want = np.concatenate([
            _direct_rotation(x[0, :, h, :dim], range(128), inv, scale),
            x[0, :, h, dim:]], axis=-1)
        np.testing.assert_allclose(mine[0, :, h], want, atol=2e-5)


def test_the_gate_is_moved_by_its_weights():
    """A head's output times the sigmoid of its own gate, a linear map
    of the block's normed input: weights of zero halve every head,
    the drawn weights give each head of each token its own factor, and
    one head's column moves that head alone."""
    config = _config()
    layer = _program_model(config).layers[1]
    rs = np.random.default_rng(1)
    normed = jnp.asarray(rs.standard_normal((1, 5, 64)), jnp.float32)
    out = jnp.asarray(rs.standard_normal((1, 5, 18 * 16)), jnp.float32)
    key = f"{layer.prefix}.self_attn.g_proj"
    w = rs.standard_normal((64, 18)).astype(np.float32) * 0.3
    gated = np.asarray(layer._gate({key: {"weight": jnp.asarray(w)}},
                                   normed, out))
    want = np.asarray(out).reshape(1, 5, 18, 16) / \
        (1 + np.exp(-(np.asarray(normed) @ w)))[..., None]
    np.testing.assert_allclose(gated, want.reshape(1, 5, -1), rtol=1e-5,
                               atol=1e-6)
    halved = layer._gate({key: {"weight": jnp.zeros((64, 18))}}, normed,
                         out)
    np.testing.assert_allclose(np.asarray(halved), np.asarray(out) / 2,
                               rtol=1e-6)
    w2 = w.copy()
    w2[:, 7] += 1.0
    moved = np.asarray(layer._gate({key: {"weight": jnp.asarray(w2)}},
                                   normed, out)).reshape(1, 5, 18, 16)
    differs = np.abs(moved - gated.reshape(1, 5, 18, 16)).max(axis=(0, 1, 3))
    assert differs[7] > 1e-3 and np.delete(differs, 7).max() == 0


# ---- the rotary tables reach the server's longest sequence ----

def test_the_rotary_tables_stop_at_max_model_len(tmp_path, monkeypatch):
    """A YaRN model built for 1,048,576 positions, served at
    `--max-model-len 8192`: the step programs capture tables of 8,192
    rows (512 KB and 4 MB of float32 at the published head, where the
    whole range is 268 MB and 537 MB a table), and the rows that are
    there are the whole table's."""
    from aphrodite_tpu.engine.args_tools import EngineArgs
    from aphrodite_tpu.modeling import loader
    from aphrodite_tpu.modeling.layers.rotary_embedding import get_rope
    published = {k: dict(v) for k, v in ROPE.items()}
    published["full_attention"].update(
        factor=128, original_max_position_embeddings=8192,
        attention_factor=1.4852030263919618)
    config = _config(max_position_embeddings=1048576,
                     rope_parameters=published)
    model_dir = str(tmp_path / "model")
    srv.write_model_dir(model_dir, {k: v for k, v in config.items()
                                    if k != "perf"})
    model_config = EngineArgs(
        model=model_dir, load_format="dummy", dtype="float32",
        max_model_len=8192, skip_tokenizer_init=True
    ).create_engine_configs()[0]
    assert model_config.max_model_len == 8192
    model, _ = loader.get_model(model_config)
    assert {layer.rotary.cos_sin_cache.shape for layer in model.layers} == \
        {(8192, 8), (8192, 16)}
    # the capped table is the head of the uncapped one (built here at
    # a range a test can afford: the first 8,192 x 4 of 32,768 rows)
    whole = get_rope(16, 8, 1048576, 500000, True, dict(
        rope_type="yarn", factor=4, original_max_position_embeddings=8192,
        beta_fast=32, beta_slow=1, attention_factor=1.2))
    capped = get_rope(16, 8, 1048576, 500000, True, dict(
        rope_type="yarn", factor=4, original_max_position_embeddings=8192,
        beta_fast=32, beta_slow=1, attention_factor=1.2), max_len=8192)
    assert whole.cos_sin_cache.shape == (32768, 8)
    np.testing.assert_array_equal(capped.cos_sin_cache,
                                  whole.cos_sin_cache[:8192])
    assert whole.mscale == capped.mscale == 1.2


@pytest.mark.parametrize("arch", ["llama", "smallthinker"])
def test_other_models_tables_are_what_they_were(arch):
    """Mistral's (the Llama class) and SmallThinker's rotary tables
    span `max_position_embeddings` whatever the server admits: their
    constructors are told no `max_model_len`, and their step programs
    capture the table they always did."""
    from aphrodite_tpu.modeling.models import ModelRegistry
    from transformers import LlamaConfig
    if arch == "llama":
        cls = ModelRegistry.load_model_cls("MistralForCausalLM")
        hf = LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=64,
                         num_hidden_layers=1, num_attention_heads=2,
                         num_key_value_heads=1, max_position_embeddings=4096,
                         rope_theta=1e6)
        rotary = cls(hf, jnp.float32).layers[0].self_attn.rotary
    else:
        from aphrodite_tpu.transformers_utils.configs import \
            SmallThinkerConfig
        cls = ModelRegistry.load_model_cls("SmallThinkerForCausalLM")
        hf = SmallThinkerConfig(
            vocab_size=64, hidden_size=32, num_hidden_layers=4,
            num_attention_heads=2, num_key_value_heads=1, head_dim=16,
            max_position_embeddings=4096, moe_ffn_hidden_size=16,
            moe_num_primary_experts=8, moe_num_active_primary_experts=2)
        rotary = cls(hf, jnp.float32).layers[1].rotary
    assert not getattr(cls, "takes_max_model_len", False)
    assert rotary.cos_sin_cache.shape[0] == 4096
    assert rotary.max_len is None


# ---- the config class, the loader's names ----

def test_the_config_loads_by_its_model_type(tmp_path):
    from aphrodite_tpu.transformers_utils.config import get_config
    from aphrodite_tpu.transformers_utils.configs import LagunaConfig
    config = _config()
    (tmp_path / "config.json").write_text(json.dumps(
        {k: v for k, v in config.items() if k != "perf"}))
    hf = get_config(str(tmp_path))
    assert isinstance(hf, LagunaConfig)
    assert hf.page_layer_kinds == ["full", "window", "window", "window",
                                   "full"]
    assert hf.sparse_layers == [1, 2, 3, 4]
    assert (hf.num_experts, hf.num_routed_experts) == (8, 16)


@pytest.mark.parametrize("key,value,said", [
    ("layer_types", KINDS[:4], "4 entries for 5 layers"),
    ("num_attention_heads_per_layer", [12, 18, 18, 18, 13],
     "13 query heads over 2"),
    ("moe_router_logit_softcapping", 30.0, "capped router"),
    ("first_held_expert", 9, "experts 9 to 16 of 16"),
])
def test_a_config_the_model_is_not_written_for_is_refused(key, value, said):
    with pytest.raises(ValueError, match=said):
        _hf(_config(**{key: value}))


def test_load_weights_takes_the_familys_names_and_its_own_share():
    """A checkpoint of the WHOLE layer under the assumed names (every
    matrix `[out, in]`, `q/k/v/g/o_proj` apart, `gate_proj` and
    `up_proj` apart, `mlp.gate` the router, sixteen experts by their
    index over all routed experts, 320 rows of vocabulary) loads into
    the tree of the chip that holds experts 8-15 and the first 256
    rows: its own experts, the whole router, its rows."""
    held = _config(first_held_expert=8)
    whole = _config(num_experts=16, vocab_size=320)
    model = _program_model(held)
    full = jax.tree_util.tree_map(np.asarray, weights.whole(
        ref.tree(whole), ref.stages(whole), 9))
    heads = dict(zip(range(5), whole["num_attention_heads_per_layer"]))

    def checkpoint():
        for bucket, leaves in full.items():
            value = leaves.get("weight")
            if bucket.endswith("mlp.experts"):
                at = bucket[:-len("experts")]
                yield at + "gate.weight", leaves["gate"].T
                for e in range(16):
                    for hf, mine in (("gate_proj", "w_gate"),
                                     ("up_proj", "w_up"),
                                     ("down_proj", "w_down")):
                        yield (f"{at}experts.{e}.{hf}.weight",
                               leaves[mine][e].T)
            elif bucket.endswith("qkv_proj"):
                q = heads[int(bucket.split(".")[2])] * 16
                for part, cols in (("q", slice(0, q)),
                                   ("k", slice(q, q + 32)),
                                   ("v", slice(q + 32, None))):
                    yield (bucket.replace("qkv_proj", part + "_proj") +
                           ".weight", value[:, cols].T)
            elif bucket.endswith("gate_up_proj"):
                half = value.shape[1] // 2
                yield (bucket.replace("gate_up_proj", "gate_proj") +
                       ".weight", value[:, :half].T)
                yield (bucket.replace("gate_up_proj", "up_proj") +
                       ".weight", value[:, half:].T)
            elif value.ndim == 2 and bucket not in ("model.embed_tokens",
                                                    "lm_head"):
                yield f"{bucket}.weight", value.T
            else:
                yield f"{bucket}.weight", value
        yield ("model.layers.0.self_attn.rotary_emb.inv_freq",
               np.zeros(4, np.float32))

    got = model.load_weights(checkpoint())
    want = {b: dict(v) for b, v in full.items()}
    for bucket, leaves in want.items():
        if bucket.endswith("mlp.experts"):
            for name in ("w_gate", "w_up", "w_down"):
                leaves[name] = leaves[name][8:]
        if bucket in ("model.embed_tokens", "lm_head"):
            leaves["weight"] = leaves["weight"][:256]
    assert {b: sorted(v) for b, v in got.items()} == \
        {b: sorted(v) for b, v in want.items()}
    have = jax.eval_shape(model.init_params)
    for bucket, leaves in want.items():
        for name, value in leaves.items():
            assert np.asarray(got[bucket][name]).shape == \
                have[bucket][name].shape, bucket
            np.testing.assert_array_equal(
                np.asarray(got[bucket][name]), value, err_msg=bucket)


# ---- the reference's ranges ----

def test_every_layer_counts_under_the_references_ranges():
    """`layer_share`, as the harness reads it (|y - x| / |x| of a
    stage), over the five layers at a width of 256 and the published
    ratio of experts (128 held of 256, top-10): every layer adds a good
    share of the stream, and within a sparse layer attention, the
    routed sum and the shared expert each carry a part of it. The
    gate is neither shut nor open: two gates in three lie between 0.2
    and 0.8."""
    config = _config(hidden=256, num_experts=128, num_routed_experts=256,
                     num_experts_per_tok=10, moe_intermediate_size=64,
                     shared_expert_intermediate_size=64)
    params = weights.whole(ref.tree(config), ref.stages(config), 1)
    x = jnp.asarray([_prompt(4, 96)], jnp.int32)
    shares = []
    with jax.default_matmul_precision("highest"):
        for fn, buckets in ref.stages(config)[:-1]:
            w = {local: params[b] for local, b in buckets.items()}
            y = getattr(ref, fn)(config, w, x, ref.Precision())
            if y.shape == x.shape:
                shares.append(float(jnp.linalg.norm(y - x) /
                                    jnp.linalg.norm(x)))
                if fn == "layer_window_sparse":
                    last = (w, x)
            x = y
        assert len(shares) == 5 and all(0.25 < s < 3 for s in shares), shares
        # inside the last window layer: its parts, each over the input
        w, x = last
        h = ref.rms_norm(x, w["input_layernorm"]["weight"], 1e-6)
        gates = np.asarray(jax.nn.sigmoid(
            h @ w["self_attn.g_proj"]["weight"]))
        assert 0.55 < np.mean((gates > 0.2) & (gates < 0.8)) < 0.85
        m = ref.rms_norm(x, w["post_attention_layernorm"]["weight"], 1e-6)
        routed = 2.5 * ref.experts(config, w["mlp.experts"], m,
                                   ref.Precision())
        shared = ref.swiglu(
            m, w["mlp.shared_expert.gate_up_proj"]["weight"],
            w["mlp.shared_expert.down_proj"]["weight"], ref.Precision())
        for part in (routed, shared):
            assert 0.1 < float(jnp.linalg.norm(part) /
                               jnp.linalg.norm(x)) < 2
