"""Phi-4-mini-flash against its plain reference
(`perf/references/phi4flash.py`, float32, no import of the program) on
seeded weights at a toy size with all four mixers present: 8 layers are
Mamba, window, Mamba, window, Mamba (the memory), full (the kept K and
V), a gated memory unit and a cross layer. The served path is the
engine's: prefill in chunks through the pages and the state slots, then
decode through both, a step ahead of the host.

Logits are compared, not tokens. Float32 on both sides, so the only
difference is the order of sums (the served scan runs a chunk at a
time from the slot, the attention over pages): the limit, 1e-4 of the
logits' spread at a position, is some six times what was read (9e-6 to
1.6e-5 through 8 layers and 110 positions of recurrence) and a
thousandth of what the least of the mechanisms moves when it is broken
(0.1 of the spread and more, asserted below)."""
import itertools
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from perf import cells, serve_child, server as srv, weights

ROOT = cells.ROOT
ref = cells.load_module(os.path.join(ROOT, "perf", "references",
                                     "phi4flash.py"))
LIMIT = 1e-4
WINDOW, PAGE, CHUNK, VOCAB = 24, 8, 16, 256
SEED = 3


def _config(layers=8, hidden=64):
    return dict(
        architectures=["Phi4FlashForCausalLM"], model_type="phi4flash",
        vocab_size=VOCAB, hidden_size=hidden, intermediate_size=2 * hidden,
        num_hidden_layers=layers, num_attention_heads=8,
        num_key_value_heads=4, max_position_embeddings=512,
        layer_norm_eps=1e-5, sliding_window=WINDOW, mb_per_layer=2,
        tie_word_embeddings=True, mlp_bias=False, lm_head_bias=False,
        hidden_act="silu", torch_dtype="float32",
        perf=dict(reference="phi4flash"))


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

    def run(self, prompts, steps=40, **sampling):
        """{request index: [each output's token ids]}"""
        from aphrodite_tpu.common.sampling_params import SamplingParams
        sp = SamplingParams(**{**dict(temperature=0.0, max_tokens=steps,
                                      ignore_eos=True), **sampling})
        names = [str(next(self._ids)) for _ in prompts]
        for name, prompt in zip(names, prompts):
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
                         ids=["four-chunks", "one-chunk"])
def test_engine_logits_against_the_reference(chunk, tmp_path, monkeypatch):
    """The 50-token prompt written in chunks of 16 across the window of
    24 (the state handed from chunk to chunk through the slot, the
    window groups letting pages go) and in one chunk; then 60 decode
    steps through pages and slots, a step ahead of the host, to 110
    tokens: past four windows. Every logit row the program computed for
    a sampled position is held to the reference's full forward pass
    over prompt and reply; so the two chunkings agree with each other
    as well."""
    s = Served(tmp_path, monkeypatch, max_chunk_tokens=chunk)
    engine = s.engine
    groups = engine.cache_config.page_groups
    assert groups.kinds == ("window", "window", "full")
    assert groups.group_of_layer == (-1, 0, -1, 1, -1, 2, -1, 2)
    assert groups.layers_per_group == 1 and groups.readers == (1, 1, 2)
    # a pair of page arrays, then the one (tail, state) pair of the
    # three Mamba layers: the recurrent state is float32 whatever the
    # model's type, the tail kept four rows a slot where the
    # convolution reads three
    caches = engine.executor.cache_engine.kv_caches
    slots = engine.cache_config.num_state_slots
    assert slots == 4 and len(caches) == 1 + 1
    (tail, state), = caches[1:]
    assert state.dtype == jnp.float32 and \
        state.shape == (3, slots + 1, 16, 128)
    assert tail.shape == (3, slots + 1, 4, 128)
    prompt, steps = _prompt(0), 60
    ((reply,),) = s.run([prompt], steps)
    assert len(reply) == steps
    served = [r[0][:VOCAB] for r in s.rows[-steps:]]
    assert _off(served, s.want(prompt, reply)) <= LIMIT
    counts = engine.tracer.counts
    manager = engine.scheduler.block_manager
    assert counts["runner.ahead"] >= steps - 4
    assert counts["ssm.state_resets"] == 1
    assert counts["ssm.prefill_tokens"] == len(prompt)
    assert counts["ssm.decode_rows"] == counts["attn.decode_steps"] \
        == steps - 1
    assert counts["cache.state_assign"] == 1
    # the full group's pages are read by two layers, the window
    # groups' by one
    assert counts["attn.page_reads_shared"] == \
        2 * counts["attn.pages_live.full"] + \
        counts["attn.pages_live.window"]
    assert counts["attn.pages_live.window"] < \
        counts["attn.window_pages_unwindowed"]
    assert manager.get_num_free_gpu_blocks() == \
        manager.num_total_gpu_blocks
    assert manager.get_num_free_state_slots() == slots


def test_a_blocked_prompt_steps_tiles_are_counted(tmp_path, monkeypatch):
    """Where the runner builds a prompt step that takes the blocked
    attention (`takes_blocked_prefill`) it counts the tiles the walk
    visits and those of the padded rectangle, each page group's view
    times the layers that read it; a step under the threshold counts
    neither. Here the threshold is 16 queries x 64 keys and a tile 8 x
    8, so the chunks behind a cached prefix count and a first chunk
    does not; the count is held to the mask itself (a tile is live when
    some query of it sees some key of it: one row's keys are one run,
    so the rule's range is exact)."""
    import functools

    from aphrodite_tpu.engine.metrics import _STAGE_COUNTERS
    from aphrodite_tpu.executor import model_runner
    from aphrodite_tpu.modeling.layers import attention as layer
    from aphrodite_tpu.modeling.models import phi4flash
    from aphrodite_tpu.ops.attention import make_causal_mask
    block = 8
    monkeypatch.setattr(phi4flash, "PREFILL_BLOCKED_FROM", CHUNK * 64)
    monkeypatch.setattr(layer, "prefill_attention_blocked",
                        functools.partial(layer.prefill_attention_blocked,
                                          key_block=block))
    monkeypatch.setattr(model_runner, "count_prefill_tiles",
                        functools.partial(model_runner.count_prefill_tiles,
                                          key_block=block))
    s = Served(tmp_path, monkeypatch)
    runner = s.engine.executor.model_runner
    assert runner.prefill_blocked_from == CHUNK * 64
    counts, groups = s.engine.tracer.counts, runner.page_groups
    names = ("attn.prefill_tiles_visited", "attn.prefill_tiles_padded")
    want, steps, count = [0, 0], [], runner._count_prefill_tiles

    def spy(group_rows, views, ctx_lens, plens, padded_len, use_prefix):
        steps.append((padded_len, use_prefix))
        for g, (rows, view) in enumerate(zip(group_rows, views)):
            kv_len = view.block_tables.shape[1] * PAGE \
                if use_prefix else padded_len
            if padded_len * kv_len < CHUNK * 64:
                continue
            (let_go, _), = rows
            ctx = int(ctx_lens[0]) - let_go if use_prefix else 0
            mask = np.array(make_causal_mask(
                padded_len, jnp.array([ctx]), kv_len,
                WINDOW if groups.kinds[g] == "window" else None))[0]
            mask &= np.arange(kv_len) < ctx + int(plens[0])
            live = mask.reshape(padded_len // block, block,
                                kv_len // block, block).any(axis=(1, 3))
            want[0] += int(live.sum()) * groups.readers[g]
            want[1] += live.size * groups.readers[g]
        return count(group_rows, views, ctx_lens, plens, padded_len,
                     use_prefix)
    monkeypatch.setattr(runner, "_count_prefill_tiles", spy)
    # ten tokens: one step of 16 queries on their own 16 keys
    s.run([_prompt(4, 10)], 2)
    assert steps == [(16, False)] and want == [0, 0]
    assert [counts[n] for n in names] == [0, 0]
    # fifty: a first chunk, then three behind a prefix on a table of
    # eight pages, each of its groups' views past the threshold
    s.run([_prompt(5)], 2)
    assert steps[1:] == [(16, False), (16, True), (16, True), (16, True)]
    assert [counts[n] for n in names] == want
    # two query blocks x eight key blocks, four layers (a window
    # layer's group each, the full group's two), three steps
    assert want == [108, 2 * 8 * 4 * 3]
    exported = {metric: total(s.engine.tracer.seconds, counts)
                for metric, _, total in _STAGE_COUNTERS}
    assert exported["aphrodite:prefill_attn_tiles_visited_total"] == \
        want[0]
    assert exported["aphrodite:prefill_attn_tiles_padded_total"] == want[1]
    for name in ("aphrodite:prefill_attn_tiles_visited_total",
                 "aphrodite:prefill_attn_tiles_padded_total"):
        with open(os.path.join(ROOT, "README.md")) as f:
            assert name in f.read()


def test_rows_that_swap_slots_and_a_slot_left_dirty(served):
    """Two prompts together, then again in the other order: each takes
    the slot the other had (and finds it as the other left it), and
    each reply is what it was. A third prompt alone then lands on a
    used slot too: the program starts it from zeros."""
    a, b = _prompt(1, 40), _prompt(2, 44)
    manager = served.engine.scheduler.block_manager
    seen = []
    assign = manager._assign_state_slot

    def spy(seq_id):
        seen.append(assign(seq_id))
        return seen[-1]
    manager._assign_state_slot = spy
    (ra,), (rb,) = served.run([a, b])
    (rb2,), (ra2,) = served.run([b, a])
    assert (ra, rb) == (ra2, rb2)
    assert seen[:2] == seen[2:][::-1] or seen[0] != seen[2]
    served.rows.clear()
    ((alone,),) = served.run([a])
    assert alone == ra and seen[-1] in seen[:4]
    assert _off([r[0][:VOCAB] for r in served.rows[-40:]],
                served.want(a, alone)) <= LIMIT


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
    (pair,) = served.run([prompt], steps, temperature=1.0, n=2, best_of=2,
                         seed=11)
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


def test_preemption_by_recompute_starts_from_a_zeroed_slot(
        tmp_path, monkeypatch):
    """A pool too small for two rows to grow in: the younger row is
    preempted by recompute, gives pages and slot back, and starts again
    from position 0. Both replies are the roomy engine's."""
    prompts = [_prompt(8, 40), _prompt(9, 40)]
    roomy = Served(tmp_path / "roomy", monkeypatch).run(prompts, steps=60)
    tight = Served(tmp_path / "tight", monkeypatch, num_gpu_blocks=44)
    assert tight.run(prompts, steps=60) == roomy
    counts = tight.engine.tracer.counts
    assert counts["preemptions"] >= 1
    assert counts["ssm.state_resets"] == 2 + counts["preemptions"]


def test_what_follows_pages_alone_is_refused_or_skipped(served):
    """The prefix cache at the door; bursts and speculative rounds
    never chosen; swap in the block manager
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


# ---- each mechanism shows in the logits ----

def _program_model(config):
    from aphrodite_tpu.modeling.models.phi4flash import \
        Phi4FlashForCausalLM
    from aphrodite_tpu.transformers_utils.configs import Phi4FlashConfig
    hf = Phi4FlashConfig(**{
        k: v for k, v in config.items()
        if k not in ("perf", "architectures", "model_type",
                     "torch_dtype")})
    return Phi4FlashForCausalLM(hf, jnp.float32)


def _window_ignored(model):
    for layer in model.layers:
        if layer.kind == "window":
            layer.mixer.attn.sliding_window = None


def _lambda_init_of_the_first_layer(model):
    for layer in model.layers:
        if layer.kind in ("window", "full", "cross"):
            layer.mixer.lambda_init = 0.2


class _Rewired:
    """A mixer whose result goes through `rewire` on its way out."""

    def __init__(self, inner, rewire):
        self.inner, self.rewire = inner, rewire
        self.cache_slot = getattr(inner, "cache_slot", None)

    def __call__(self, *args, **kwargs):
        return self.rewire(*self.inner(*args, **kwargs))


def _memory_of_the_first_mamba_layer(model):
    """The gated unit reads the first Mamba layer's scan output, not
    the last one's."""
    kept = []

    def first_memory(out, memory, new):
        kept.append(memory)
        return out, kept[0], new
    for layer in model.layers:
        if layer.kind == "mamba":
            layer.mixer = _Rewired(layer.mixer, first_memory)


def _cross_layer_with_k_and_v_swapped(model):
    """The cross layer is handed the full layer's V as K and K as V."""
    for layer in model.layers:
        if layer.kind == "full":
            layer.mixer = _Rewired(
                layer.mixer, lambda out, new, kv: (out, new, kv[::-1]))


def _state_forgotten(model):
    """Every token starts from a zero state: `y = D u`, no memory."""
    from aphrodite_tpu.ops.pallas import ssm_scan

    def scan(u, delta, b, c, a, d, state, slots, fresh, layer):
        return d[None, None] * u + jnp.einsum(
            "btc,btn,btn->btc", delta * u, b, c), state
    ssm_scan.selective_scan = scan


@pytest.mark.parametrize("break_it", [
    None, _window_ignored, _lambda_init_of_the_first_layer,
    _memory_of_the_first_mamba_layer, _cross_layer_with_k_and_v_swapped,
    _state_forgotten],
    ids=lambda f: f.__name__.strip("_") if f else "as-written")
def test_each_mechanism_shows_in_the_logits(break_it, monkeypatch):
    """The model's forward pass over 70 tokens (cache-less prefill), as
    written and with one mechanism broken at a time: as written it is
    the reference's to 1e-4 of a position's spread, and each break is a
    thousand times the limit away, so the comparison above would fail
    on any of them."""
    from aphrodite_tpu.modeling.input_metadata import InputMetadata
    from aphrodite_tpu.modeling.models import phi4flash
    from aphrodite_tpu.ops.pallas import ssm_scan
    monkeypatch.setattr(ssm_scan, "selective_scan",
                        ssm_scan.selective_scan)
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
    bias and `A_log`, a Mamba layer's output at a position depends on
    the input 64 positions back by some 5 to 9% of its norm and on the
    input 512 back by some 1% (read at hidden sizes 256 and 1,024):
    held to a third of those. And what the state carries is the larger
    part of the scan's output beside the skip `D u`."""
    config = _config(layers=4, hidden=256)
    params = weights.whole(ref.tree(config), ref.stages(config), 7)
    w = {b: params[f"model.layers.0.{b}"] for b in ref._MAMBA}
    assert ref._sizes(config)["d_state"] == 16
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
        skipless = dict(w, **{"mixer.ssm": dict(
            w["mixer.ssm"], D=jnp.zeros_like(w["mixer.ssm"]["D"]))})
        _, carried = ref.mamba(config, skipless, h, ref.Precision())
    assert moved[64] > 0.015 and moved[512] > 0.003, moved
    assert moved[64] > moved[512]
    assert float(carried[0, 100:].std()) > \
        2 * float((y - carried)[0, 100:].std())


def test_every_layer_adds_a_few_tenths_under_the_references_ranges():
    """`layer_share`, as the harness reads it (|y - x| / |x| of a
    stage that keeps its shape), over the 8 layers at a width of 256:
    the first layer meets the bare embedding (a quarter of a layer's
    spread) and reads over 1; from the third on a layer adds 0.2 to
    0.7 of the stream, the widened stages (the gated unit, the cross
    layer) diluted by what they carry unchanged."""
    config = _config(layers=8, hidden=256)
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
    # embed, the memory layer and the full layer change the shape
    assert len(shares) == 8 - 2
    assert shares[0] > 1.0
    assert all(0.1 < s < 0.9 for s in shares[2:]), shares
