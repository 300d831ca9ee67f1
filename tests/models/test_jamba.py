"""Jamba against its plain reference (`perf/references/jamba.py`,
float32, no import of the program) on seeded weights at a toy size that
keeps the pattern: 6 layers, `attn_layer_period` 3 and
`attn_layer_offset` 1 (Mamba, attention, Mamba, Mamba, attention,
Mamba), 4 query heads on 1 KV head, `mamba_dt_rank` 4, the inner norms
on dt, B and C. The served path is the engine's: prefill in chunks
through the pages and the state slots, then decode through both, a
step ahead of the host.

Logits are compared, not tokens. Float32 on both sides, so the only
difference is the order of sums (the served scan runs a chunk at a
time from the slot, the attention over pages): the limit, 1e-4 of the
logits' spread at a position, is seven times what was read (1.4e-5
through 6 layers and 110 positions of recurrence, chunked or whole) and
a ten-thousandth of what the least of the mechanisms moves when it is
broken (3.7 spreads and more; a thousand times the limit is asserted
below)."""
import itertools
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from perf import cells, serve_child, server as srv, weights

ROOT = cells.ROOT
ref = cells.load_module(os.path.join(ROOT, "perf", "references",
                                     "jamba.py"))
LIMIT = 1e-4
PAGE, CHUNK, VOCAB = 8, 16, 256
SEED = 3


def _config(layers=6, hidden=64, **changed):
    return dict(dict(
        architectures=["JambaForCausalLM"], model_type="jamba",
        vocab_size=VOCAB, hidden_size=hidden, intermediate_size=2 * hidden,
        num_hidden_layers=layers, num_attention_heads=4,
        num_key_value_heads=1, max_position_embeddings=512,
        rms_norm_eps=1e-6, sliding_window=None, attn_layer_period=3,
        attn_layer_offset=1, expert_layer_period=2, expert_layer_offset=1,
        num_experts=1, num_experts_per_tok=1, mamba_d_state=16,
        mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=4,
        mamba_conv_bias=True, mamba_proj_bias=False,
        tie_word_embeddings=True, hidden_act="silu", torch_dtype="float32",
        perf=dict(reference="jamba")), **changed)


def _hf(config):
    from aphrodite_tpu.transformers_utils.configs import JambaConfig
    return JambaConfig(**{
        k: v for k, v in config.items()
        if k not in ("perf", "architectures", "model_type", "torch_dtype")})


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
            max_model_len=256, block_size=PAGE, max_num_seqs=4,
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


# ---- the engine: pages and state slots against the full forward pass ----

@pytest.mark.parametrize("chunk", [CHUNK, 64],
                         ids=["four-chunks", "whole-prompt"])
def test_engine_logits_against_the_reference(chunk, tmp_path, monkeypatch):
    """The 50-token prompt written in chunks of 16 (the state handed
    from chunk to chunk through the slot, the later chunks' attention
    over the pages) and whole; then 60 decode steps through pages and
    slots, a step ahead of the host, to 110 tokens. Every logit row the
    program computed for a sampled position is held to the reference's
    full forward pass over prompt and reply; so the two chunkings agree
    with each other as well."""
    s = Served(tmp_path, monkeypatch, max_chunk_tokens=chunk)
    engine = s.engine
    groups = engine.cache_config.page_groups
    assert groups.kinds == ("full",) and groups.stateful
    assert groups.group_of_layer == (-1, 0, -1, -1, 0, -1)
    assert groups.slot_of_layer == (-1, 0, -1, -1, 1, -1)
    assert groups.layers_per_group == 2 and groups.readers == (2,)
    # a pair of page arrays for each attention layer, then the one
    # (tail, state) pair of the four Mamba layers: the recurrent state
    # is float32 whatever the model's type, the tail kept four rows a
    # slot where the convolution reads three
    caches = engine.executor.cache_engine.kv_caches
    slots = engine.cache_config.num_state_slots
    assert slots == 4 and len(caches) == 2 + 1
    for k_pages, _ in caches[:2]:
        assert k_pages.shape[1:] == (PAGE, 128)     # one head, padded
    (tail, state), = caches[2:]
    assert state.dtype == jnp.float32 and \
        state.shape == (4, slots + 1, 16, 128)
    assert tail.shape == (4, slots + 1, 4, 128)
    prompt, steps = _prompt(0), 60
    ((reply,),) = s.run([prompt], steps)
    assert len(reply) == steps
    served = [r[0][:VOCAB] for r in s.rows[-steps:]]
    assert _off(served, s.want(prompt, reply)) <= LIMIT
    # the counters are the runner's and the block manager's, not the
    # model's: they count for Jamba as for Phi
    counts = engine.tracer.counts
    manager = engine.scheduler.block_manager
    assert counts["runner.ahead"] >= steps - 4
    assert counts["ssm.state_resets"] == 1
    assert counts["ssm.prefill_tokens"] == len(prompt)
    assert counts["ssm.decode_rows"] == counts["attn.decode_steps"] \
        == steps - 1
    assert counts["cache.state_assign"] == 1
    assert counts["ssm.slot_waits"] == 0
    # one group, read by its own two layers
    assert counts["attn.page_reads_shared"] == \
        2 * counts["attn.pages_live.full"]
    assert manager.get_num_free_gpu_blocks() == \
        manager.num_total_gpu_blocks
    assert manager.get_num_free_state_slots() == slots
    stats = engine._get_stats(None)
    assert (stats.ssm_slots_total, stats.ssm_slots_live) == (slots, 0)


def test_the_round_a_step_ahead_is_the_synced_round_token_for_token(
        served):
    """The state of step n is written on the device before step n+1
    reads it, in dispatch order, as its token is: a run a step ahead
    and the same run pulled every round give the same tokens."""
    prompts = [_prompt(5, 30), _prompt(6, 47)]
    counts = served.engine.tracer.counts
    ahead = served.run(prompts, steps=50)
    assert counts["runner.ahead"] > 40
    before = counts["runner.ahead"]
    served.engine._runs_ahead = lambda *a, **k: False
    assert served.run(prompts, steps=50) == ahead
    assert counts["runner.ahead"] == before


def test_a_fork_copies_the_parents_state(served):
    """Two samples of one prompt: the child takes a slot of its own
    and the parent's rows of every state array before its first step.
    Each row's logits, step by step, are the reference's over that
    row's own tokens."""
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
    assert manager.get_num_free_state_slots() == 4
    assert manager.take_state_copies() == []


def test_a_sampled_row_beside_greedy_ones(tmp_path, monkeypatch):
    """Three prompts of one length admitted together, the second
    sampled at temperature 1: the rows decode side by side, and each
    row's logits at each step are the reference's over some request's
    own tokens at that step (the sampled row's tokens are not the
    greedy ones, its logits are as exact)."""
    s = Served(tmp_path, monkeypatch, max_chunk_tokens=64)
    prompts, steps = [_prompt(20 + i, 20) for i in range(3)], 16
    greedy = s.run([prompts[1]], steps)[0][0]
    s.rows.clear()
    replies = [r[0] for r in s.run(prompts, steps, [
        {}, dict(temperature=1.0, seed=5), {}])]
    assert replies[1] != greedy
    want = [s.want(p, r) for p, r in zip(prompts, replies)]
    decode = [r[:, :VOCAB] for r in s.rows[-(steps - 1):]]
    assert all(r.shape[0] >= 3 for r in decode)
    for j, rows in enumerate(decode, start=1):
        matched = {min(range(3), key=lambda i: _off([rows[r]],
                                                    [want[i][j]]))
                   for r in range(3)}
        assert matched == {0, 1, 2}
        assert max(min(_off([rows[r]], [want[i][j]]) for i in range(3))
                   for r in range(3)) <= LIMIT


def test_preemption_by_recompute_starts_from_a_zeroed_slot(
        tmp_path, monkeypatch):
    """A pool too small for two rows to grow in: the younger row is
    preempted by recompute, gives pages and slot back, and starts again
    from position 0. Both replies are the roomy engine's."""
    prompts = [_prompt(8, 40), _prompt(9, 40)]
    roomy = Served(tmp_path / "roomy", monkeypatch).run(prompts, steps=60)
    tight = Served(tmp_path / "tight", monkeypatch, num_gpu_blocks=20)
    assert tight.run(prompts, steps=60) == roomy
    counts = tight.engine.tracer.counts
    assert counts["preemptions"] >= 1
    assert counts["ssm.state_resets"] == 2 + counts["preemptions"]


def test_what_follows_pages_alone_is_refused_or_skipped(served):
    """What state refuses for Phi it refuses for Jamba, with the same
    stated errors: the prefix cache at the door; bursts and speculative
    rounds never chosen; swap in the block manager
    (`tests/processing/test_state_slots.py`)."""
    from aphrodite_tpu.common.sampling_params import SamplingParams
    engine = served.engine
    with pytest.raises(ValueError, match="the prefix cache"):
        engine.add_request("p", None, SamplingParams(max_tokens=4),
                           prompt_token_ids=_prompt(1, 24), prefix_pos=8)
    engine.scheduler_config.multi_step = 4
    assert engine._burst_steps([], None) == (1, None)
    engine.scheduler_config.multi_step = 1
    engine._speculates = lambda: True
    assert engine._spec_drafts([], None) is None


# ---- the config: the rules, and what it refuses ----

def test_the_layer_rule_and_what_the_cache_layer_is_told():
    hf = _hf(_config(layers=28, attn_layer_period=14, attn_layer_offset=7))
    kinds = hf.layer_kinds
    assert [l for l, k in enumerate(kinds) if k == "attention"] == [7, 21]
    assert kinds.count("mamba") == 26 and kinds == ref.kinds(dict(
        num_hidden_layers=28, attn_layer_period=14, attn_layer_offset=7))
    assert hf.page_layer_kinds[7] == "full" and hf.page_layer_kinds[0] is None
    layers, arrays = hf.state_spec("bfloat16")
    assert layers == 26 and arrays == (((3, 128), "bfloat16"),
                                       ((16, 128), "float32"))
    # the published config needs no key but its own: every default is
    # AI21-Jamba2-3B's
    from aphrodite_tpu.transformers_utils.configs import JambaConfig
    assert JambaConfig().mamba_dt_rank == 160 and \
        JambaConfig().mamba_d_inner == 5120
    assert JambaConfig(mamba_dt_rank="auto", hidden_size=64).mamba_dt_rank \
        == 4


@pytest.mark.parametrize("key,value", [
    ("num_experts", 16), ("sliding_window", 4096),
    ("mamba_conv_bias", False), ("mamba_proj_bias", True),
    ("attn_layer_offset", 3), ("tie_word_embeddings", False)])
def test_a_config_the_model_is_not_written_for_is_refused(key, value):
    """More than one expert (a stack whose feed-forward alternates
    dense and expert layers), a sliding window, a convolution without
    its bias, projections with one, an offset outside the period, a
    head of its own (`load_weights` passes `lm_head` over: the
    embedding is the head): each refused where the
    config is read, by an error that names the key."""
    with pytest.raises(ValueError, match=key):
        _hf(_config(**{key: value}))
    if key in ("num_experts", "sliding_window"):
        with pytest.raises(ValueError, match=key):
            ref.kinds(_config(**{key: value}))


def test_the_config_loads_by_its_model_type(tmp_path):
    from aphrodite_tpu.modeling.models import ModelRegistry
    from aphrodite_tpu.transformers_utils.config import get_config
    from aphrodite_tpu.transformers_utils.configs import JambaConfig
    config = _config()
    srv.write_model_dir(str(tmp_path), {k: v for k, v in config.items()
                                        if k != "perf"})
    hf = get_config(str(tmp_path))
    assert type(hf) is JambaConfig and hf.attn_layer_period == 3
    assert ModelRegistry.load_model_cls(hf.architectures[0]).__name__ == \
        "JambaForCausalLM"


def test_load_weights_takes_the_hugging_face_names():
    """A checkpoint under the publisher's names (`mamba.A_log`
    `[d_inner, d_state]`, `mamba.conv1d.weight` `[d_inner, 1, d_conv]`,
    the three inner norms, `self_attn.{q,k,v}_proj` apart,
    `feed_forward.{gate,up}_proj` apart, every matrix `[out, in]`)
    loads into the tree the program serves, leaf for leaf."""
    config = _config()
    model = _program_model(config)
    want = jax.tree_util.tree_map(np.asarray, weights.whole(
        ref.tree(config), ref.stages(config), 9))
    heads, d, inter = 4, 16, 128

    def checkpoint():
        for bucket, leaves in want.items():
            for name, value in leaves.items():
                if bucket.endswith("mamba.ssm"):
                    yield (f"{bucket[:-4]}.{name}",
                           value.T if name == "A_log" else value)
                elif bucket.endswith("conv1d") and name == "weight":
                    yield f"{bucket}.weight", value.T[:, None, :]
                elif bucket.endswith("qkv_proj"):
                    for part, cols in (("q", slice(0, heads * d)),
                                       ("k", slice(heads * d, (heads + 1) * d)),
                                       ("v", slice((heads + 1) * d, None))):
                        yield (bucket.replace("qkv_proj", part + "_proj") +
                               ".weight", value[:, cols].T)
                elif bucket.endswith("gate_up_proj"):
                    yield (bucket.replace("gate_up_proj", "gate_proj") +
                           ".weight", value[:, :inter].T)
                    yield (bucket.replace("gate_up_proj", "up_proj") +
                           ".weight", value[:, inter:].T)
                elif name == "weight" and value.ndim == 2 and \
                        bucket != "model.embed_tokens":
                    yield f"{bucket}.weight", value.T
                else:
                    yield f"{bucket}.{name}", value
        yield "lm_head.weight", want["model.embed_tokens"]["weight"]

    with pytest.raises(ValueError, match="rotary_emb.inv_freq"):
        # a name no parameter takes is a fault, not a leaf to drop
        model.load_weights(itertools.chain(checkpoint(), [(
            "model.layers.1.self_attn.rotary_emb.inv_freq",
            np.zeros(8, np.float32))]))
    got = model.load_weights(checkpoint())
    assert {b: sorted(v) for b, v in got.items()} == \
        {b: sorted(v) for b, v in want.items()}
    for bucket, leaves in want.items():
        for name, value in leaves.items():
            np.testing.assert_array_equal(
                np.asarray(got[bucket][name]), value, err_msg=bucket)


# ---- each mechanism shows in the logits ----

def _program_model(config):
    from aphrodite_tpu.modeling.models.jamba import JambaForCausalLM
    return JambaForCausalLM(_hf(config), jnp.float32)


def _an_inner_norm_skipped(which):
    """The mixer norms dt, B and C in that order, a layer at a time:
    the `which`-th of every three is left out."""
    def break_it(model, monkeypatch):
        from aphrodite_tpu.modeling.layers import mamba
        calls, norm = itertools.count(), mamba.rms_norm
        monkeypatch.setattr(
            mamba, "rms_norm",
            lambda x, w, eps: x if next(calls) % 3 == which
            else norm(x, w, eps))
    break_it.__name__ = ("dt", "b", "c")[which] + "_layernorm_skipped"
    return break_it


def _attention_a_layer_early(model, monkeypatch):
    """The layer rule: the first attention layer runs at index 0 and
    the first Mamba layer at index 1, each with its own weights."""
    model.layers[0], model.layers[1] = model.layers[1], model.layers[0]


def _queries_and_keys_rotated(model, monkeypatch):
    """A rotary embedding where the model has no positional encoding."""
    from aphrodite_tpu.modeling.layers.rotary_embedding import get_rope
    for layer in model.layers:
        if layer.kind != "attention":
            continue
        mixer, rope = layer.mixer, get_rope(16, 16, 512, 10000.0, True)
        split = mixer.qkv_proj.split

        def rotated(qkv, split=split, rope=rope, mixer=mixer):
            q, k, v = split(qkv)
            b, s = q.shape[:2]
            q, k = rope(jnp.broadcast_to(jnp.arange(s), (b, s)),
                        q.reshape(b, s, mixer.num_heads, 16),
                        k.reshape(b, s, mixer.num_kv_heads, 16))
            return q.reshape(b, s, -1), k.reshape(b, s, -1), v
        mixer.qkv_proj.split = rotated


def _the_one_kv_heads_k_and_v_swapped(model, monkeypatch):
    """The single KV head: its K taken for V and its V for K (one head
    of each follows the 4 query heads in `qkv_proj`'s output)."""
    for layer in model.layers:
        if layer.kind == "attention":
            split = layer.mixer.qkv_proj.split
            layer.mixer.qkv_proj.split = \
                lambda qkv, split=split: split(qkv)[::2] + split(qkv)[1:2]


def _state_forgotten(model, monkeypatch):
    """Every token starts from a zero state: `y = D u`, no memory."""
    def scan(u, delta, b, c, a, d, state, slots, fresh, layer):
        return d[None, None] * u + jnp.einsum(
            "btc,btn,btn->btc", delta * u, b, c), state
    from aphrodite_tpu.ops.pallas import ssm_scan
    monkeypatch.setattr(ssm_scan, "selective_scan", scan)


@pytest.mark.parametrize("break_it", [
    None, _an_inner_norm_skipped(0), _an_inner_norm_skipped(1),
    _an_inner_norm_skipped(2), _attention_a_layer_early,
    _queries_and_keys_rotated, _the_one_kv_heads_k_and_v_swapped,
    _state_forgotten],
    ids=lambda f: f.__name__.strip("_") if f else "as-written")
def test_each_mechanism_shows_in_the_logits(break_it, monkeypatch):
    """The model's forward pass over 70 tokens (cache-less prefill), as
    written and with one mechanism broken at a time: as written it is
    the reference's to 1e-4 of a position's spread, and each break is
    a thousand times the limit away and more, so the comparison above
    would fail on any of them."""
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
        break_it(model, monkeypatch)
    params = weights.whole(tree, ref.stages(config), 5)
    ids = _prompt(2, 70)
    n = len(ids)
    hidden, _ = model(
        params, jnp.asarray([ids], jnp.int32),
        jnp.arange(n, dtype=jnp.int32)[None], None,
        InputMetadata(slot_mapping=jnp.arange(n, dtype=jnp.int32),
                      block_tables=jnp.zeros((1, 1), jnp.int32),
                      context_lens=jnp.zeros((1,), jnp.int32),
                      prompt_lens=jnp.asarray([n], jnp.int32),
                      is_prompt=True))
    served = np.asarray(model.compute_logits(params, hidden))[0][:, :VOCAB]
    off = _off(served, _reference_logits(config, params, ids)[:, :VOCAB])
    if break_it is None:
        assert off <= LIMIT
    else:
        assert off > 1e3 * LIMIT, off


# ---- the reference's ranges: every layer counts, and the state remembers --

def test_the_state_remembers_under_the_references_ranges():
    """At the published `d_state` and the tree's ranges for `delta`'s
    bias, `A_log` and the inner norms' gains, a Mamba layer's output at
    a position depends on the input 64 positions back by some percent
    of its norm and on the input 512 back by some tenths of a percent:
    `delta A` neither erases the state nor freezes it. And what the
    state carries is the larger part of the scan's output beside the
    skip `D u`."""
    config = _config(layers=3, hidden=256, mamba_dt_rank=16)
    params = weights.whole(ref.tree(config), ref.stages(config), 7)
    w = {b: params[f"model.layers.0.{b}"] for b in ref._MAMBA}
    rng = np.random.default_rng(0)
    tokens = 600
    h = jnp.asarray(rng.normal(size=(1, tokens, 256)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        out, y = ref.mamba(config, w, h, ref.Precision())
        moved = {}
        for back in (64, 512):
            other = h.at[0, tokens - 1 - back].set(jnp.asarray(
                rng.normal(size=(256,)), jnp.float32))
            out2, _ = ref.mamba(config, w, other, ref.Precision())
            moved[back] = float(jnp.linalg.norm(out2[0, -1] - out[0, -1]) /
                                jnp.linalg.norm(out[0, -1]))
        skipless = dict(w, **{"mamba.ssm": dict(
            w["mamba.ssm"], D=jnp.zeros_like(w["mamba.ssm"]["D"]))})
        _, carried = ref.mamba(config, skipless, h, ref.Precision())
    assert moved[64] > 0.015 and moved[512] > 0.003, moved
    assert moved[64] > moved[512]
    assert float(carried[0, 100:].std()) > \
        2 * float((y - carried)[0, 100:].std())


def test_every_layer_adds_a_few_tenths_under_the_references_ranges():
    """`layer_share`, as the harness reads it (|y - x| / |x| of a
    stage), over the 6 layers at a width of 256: the first layer meets
    the bare embedding (a quarter of a layer's spread) and reads over
    1; from the third on a layer of either kind adds a few tenths of
    the stream."""
    config = _config(hidden=256, mamba_dt_rank=16)
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
            x = y
    assert len(shares) == 6 and shares[0] > 1.0
    assert all(0.1 < s < 0.9 for s in shares[2:]), shares
