"""Kimi Linear against its plain reference (`perf/references/
kimi_linear.py`: the delta rule a token at a time, latent attention
NOT absorbed, float32, no import of the program) on seeded weights at a
toy size with every mechanism present: the cell's own eight layers
(KDA, KDA, KDA, MLA twice over; a leading dense MLP), two KDA heads of
32 x 32 behind convolutions of four taps, four heads of latent
attention with no rotary embedding (latent 128, 32 + 16 query lanes:
a page's row is 144 lanes padded to 256), 4 held experts of 16 routed
under a sigmoid router with a selection bias, top-4, beside a shared
expert; state slots AND latent pages.

Logits are compared, not tokens. Float32 on both sides, so the only
difference is the order of sums (the program's decode step attends
ABSORBED and moves the state a step at a time from a slot): the limit,
1e-4 of the logits' spread at a position, is some ten times what was
read and a thousandth of what the least of the mechanisms moves when
it is left out (asserted below)."""
import itertools
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from perf import cells, serve_child, server as srv, weights

ROOT = cells.ROOT
ref = cells.load_module(os.path.join(ROOT, "perf", "references",
                                     "kimi_linear.py"))
LIMIT = 1e-4
VOCAB, PAGE, CHUNK, SEED = 256, 8, 16, 5
LINEAR = {"kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11],
          "full_attn_layers": [4, 8, 12], "num_heads": 2, "head_dim": 32,
          "short_conv_kernel_size": 4}


def _config(**changed):
    return {**dict(
        architectures=["KimiLinearForCausalLM"], model_type="kimi_linear",
        vocab_size=VOCAB, hidden_size=64, intermediate_size=128,
        moe_intermediate_size=32, num_hidden_layers=8,
        num_attention_heads=4, num_key_value_heads=4, head_dim=16,
        kv_lora_rank=128, qk_nope_head_dim=32, qk_rope_head_dim=16,
        v_head_dim=32, mla_use_nope=True, model_max_length=256,
        max_position_embeddings=256,
        rms_norm_eps=1e-5, linear_attn_config=LINEAR,
        first_k_dense_replace=1, num_experts=4, num_routed_experts=16,
        first_held_expert=4, num_experts_per_token=4, num_shared_experts=1,
        routed_scaling_factor=2.446, tie_word_embeddings=False,
        torch_dtype="float32", perf=dict(reference="kimi_linear")),
        **changed}


def _hf(config):
    from aphrodite_tpu.transformers_utils.configs import KimiLinearConfig
    return KimiLinearConfig(**{
        k: v for k, v in config.items()
        if k not in ("perf", "architectures", "model_type", "torch_dtype")})


def _program_model(config):
    from aphrodite_tpu.modeling.models.kimi_linear import (
        KimiLinearForCausalLM)
    return KimiLinearForCausalLM(_hf(config), jnp.float32)


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


# ---- the engine: prefill, then decode through slots and pages ----

def test_engine_logits_against_the_reference(tmp_path, monkeypatch):
    """Through the engine: the 50-token prompt goes whole, and 40
    decode steps go through the state slot (six KDA layers) and the
    latent pages (two MLA layers, absorbed). Every logit row the
    program computed for a sampled position is held to the reference's
    full forward pass over prompt and reply."""
    s = Served(tmp_path, monkeypatch)
    engine = s.engine
    groups = engine.cache_config.page_groups
    assert groups.kinds == ("full",) and groups.latent == 128
    assert groups.stateful and not groups.plain
    assert groups.slot_of_layer == (-1, -1, -1, 0, -1, -1, -1, 1)
    caches = engine.executor.cache_engine.kv_caches
    # ONE array an MLA layer, then the model's (tail, state) pair
    assert [len(entry) for entry in caches] == [1, 1, 2]
    assert caches[0][0].shape[1:] == (PAGE, 256)
    slots = engine.cache_config.num_state_slots
    tail, state = caches[2]
    assert tail.shape == (6, slots + 1, 4, 3 * 64)      # three rows kept
    assert state.shape == (6, slots + 1, 2, 32, 32)
    assert state.dtype == jnp.float32
    prompt, steps = _prompt(0), 40
    ((reply,),) = s.run([prompt], steps)
    assert len(reply) == steps
    served = [r[0][:VOCAB] for r in s.rows[-steps:]]
    counts = engine.tracer.counts
    manager = engine.scheduler.block_manager
    assert counts["runner.ahead"] >= steps - 4
    first = len(prompt) + 1
    assert counts["mla.latent_tokens_read"] == sum(
        range(first, first + steps - 1))
    assert counts["kda.decode_rows"] == steps - 1
    assert counts["kda.prompt_tokens"] == len(prompt)
    assert counts["kda.prompt_chunks"] == 1             # 50 of 64 tokens
    assert counts["ssm.state_resets"] == 1
    assert counts["ssm.decode_rows"] == 0
    assert counts["moe.tokens_routed"] >= (len(prompt) + steps - 1) * 4 * 7
    assert manager.get_num_free_gpu_blocks() == \
        manager.num_total_gpu_blocks

    want = s.want(prompt, reply)
    assert _off(served, want) <= LIMIT
    assert all(int(a.argmax()) == int(b.argmax())
               for a, b in zip(served, want))


def test_a_prompt_in_chunks_resumes_its_slot(tmp_path, monkeypatch):
    """Two 70-token prompts that arrive together while three rows
    decode: the scheduler writes them in chunks of 16 beside the rows'
    steps, each chunk after a prompt's first starts from the state its
    slot holds and the convolutions' tail there, and an MLA layer's
    gathers its prefix from the latent pages. Each reply is the one the
    engine gives the prompt alone, and every position's logits are the
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
    assert counts["attn.prefill_steps"] >= 1 + 5
    assert counts["mla.prefix_tokens_expanded"] >= 16 + 32 + 48 + 64
    assert counts["ssm.state_resets"] == 5
    assert counts["kda.prompt_tokens"] == 3 * 20 + 2 * 70
    assert counts["kda.prompt_chunks"] >= 3 + 2 * 5
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


@pytest.mark.parametrize("name,fault", [
    ("no-decay", lambda g, b: (jnp.zeros_like(g), b)),
    ("full-strength", lambda g, b: (g, jnp.ones_like(b))),
])
def test_the_delta_rules_gates_move_the_logits(name, fault, served,
                                               monkeypatch):
    """A reference whose recurrence has no decay, or writes at full
    strength, is far from what was served."""
    prompt, steps = _prompt(2), 12
    served.rows.clear()
    ((reply,),) = served.run([prompt], steps)
    rows = [r[0][:VOCAB] for r in served.rows[-steps:]]
    rule = ref.delta_rule
    monkeypatch.setattr(ref, "delta_rule",
                        lambda q, k, v, g, b: rule(q, k, v, *fault(g, b)))
    assert _off(rows, served.want(prompt, reply)) > 1e2 * LIMIT


def test_a_fork_copies_the_state_slot(served):
    """Two samples of one prompt: the child shares the parent's latent
    pages, copies on its first write, and takes a copy of the parent's
    state slot. Each row's logits, step by step, are the reference's
    over that row's own tokens."""
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


def test_preemption_by_recompute_gives_the_slot_back(tmp_path, monkeypatch):
    """A pool too small for two rows to grow in: the younger row is
    preempted by recompute, gives its pages and its slot back and
    starts again from position 0. Both replies are the roomy
    engine's."""
    prompts = [_prompt(8, 40), _prompt(9, 40)]
    roomy = Served(tmp_path / "roomy", monkeypatch).run(prompts, steps=60)
    tight = Served(tmp_path / "tight", monkeypatch, num_gpu_blocks=20)
    assert tight.run(prompts, steps=60) == roomy
    assert tight.engine.tracer.counts["preemptions"] >= 1
    manager = tight.engine.scheduler.block_manager
    assert manager.get_num_free_gpu_blocks() == 20


# ---- the state's precision ----

def test_a_bfloat16_state_leaves_the_tolerance():
    """The KDA state is float32 by the configuration's word. Carried in
    bfloat16 over 1,024 decode steps of the layer's own recurrence
    (`kda_update_ref`, the state rounded after every step as a slot of
    that type would hold it), the layer's output leaves what a float32
    state gives by far more than the limit the model is held to; so a
    slot of fewer bits cannot pass as an optimisation."""
    from aphrodite_tpu.ops.pallas import kda
    heads, d, steps = 2, 32, 1024
    keys = jax.random.split(jax.random.PRNGKey(3), 5)
    q = ref.l2norm(jax.random.normal(keys[0], (steps, 1, heads, d))) \
        * d ** -0.5
    k = ref.l2norm(jax.random.normal(keys[1], (steps, 1, heads, d)))
    v = jax.random.normal(keys[2], (steps, 1, heads, d))
    g = -jax.random.uniform(keys[3], (steps, 1, heads, d), minval=0.001,
                            maxval=0.17)
    b = jax.random.uniform(keys[4], (steps, 1, heads))

    def run(rounded):
        def step(s, xs):
            s, o = kda._step(s, *xs)
            return rounded(s), o
        _, o = jax.lax.scan(step, jnp.zeros((1, heads, d, d)),
                            (q, k, v, g, b))
        return np.asarray(o[-64:])
    exact = run(lambda s: s)
    low = run(lambda s: s.astype(jnp.bfloat16).astype(jnp.float32))
    off = np.abs(low - exact).max() / exact.std()
    assert off > 30 * LIMIT, off


# ---- the shares add up to the uncut layer ----

def test_the_shares_add_up_to_the_uncut_layer():
    """The four shares' routed parts (experts 0-3, 4-7, 8-11, 12-15 of
    16) plus the shared expert and the mixer counted once are the
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
        return _program_model(config).layers[1].moe(bucket, x)
    for routed in (reference_routed, program_routed):
        uncut = routed(whole, params[at + "mlp.experts"])
        parts = sum(routed(_config(first_held_expert=first),
                           cut(params[at + "mlp.experts"], first))
                    for first in (0, 4, 8, 12))
        assert float(jnp.abs(uncut).max()) > 0.01
        assert np.allclose(parts, uncut, atol=1e-5)
    # and the whole layer: mixer and shared expert once, the routed
    # parts of the four shares summed
    w = {b: params[at + b] for b in
         ref.MIXER_BUCKETS["kda"] + ref.MLP_BUCKETS[True]}
    with jax.default_matmul_precision("highest"):
        uncut = ref.layer_kda_sparse(whole, w, x, ref.Precision())
        mixed = x + ref.kda(whole, w, x, ref.Precision())
        z = ref.rms_norm(mixed, w["post_attention_layernorm"]["weight"],
                         whole["rms_norm_eps"])
        routed = sum(ref.experts(
            _config(first_held_expert=first),
            cut(w["mlp.experts"], first), z, ref.Precision())
            for first in (0, 4, 8, 12))
        shared = ref.swiglu(
            z, w["mlp.shared_experts.gate_up_proj"]["weight"],
            w["mlp.shared_experts.down_proj"]["weight"], ref.Precision())
    assert np.allclose(
        mixed + shared + whole["routed_scaling_factor"] * routed, uncut,
        atol=1e-4)


# ---- the configuration and the loader ----

def test_the_config_reads_the_published_lists():
    from aphrodite_tpu.transformers_utils.configs import KimiLinearConfig
    published = KimiLinearConfig()
    kinds = published.layer_kinds
    assert len(kinds) == 27 and kinds.count("kda") == 20
    assert [i + 1 for i, k in enumerate(kinds) if k == "mla"] == \
        [4, 8, 12, 16, 20, 24, 27]
    assert published.max_position_embeddings == 1048576
    assert published.paged_head_dim == 576
    layers, arrays = published.state_spec("bfloat16")
    assert layers == 20
    assert arrays == (((3, 12288), "bfloat16"),
                      ((32, 128, 128), "float32"))
    stage = KimiLinearConfig(num_hidden_layers=8)
    assert stage.page_layer_kinds == [None, None, None, "full"] * 2
    assert stage.state_spec("bfloat16")[0] == 6
    with pytest.raises(ValueError, match="layer 3 is in neither"):
        KimiLinearConfig(linear_attn_config=dict(
            LINEAR, kda_layers=[1, 2, 5, 6, 7]))
    with pytest.raises(ValueError, match="without a rotary"):
        KimiLinearConfig(mla_use_nope=False)
    with pytest.raises(ValueError, match="ONE group"):
        KimiLinearConfig(num_expert_group=8, topk_group=4)


def test_load_weights_round_trip():
    """The program's own tree written out under the checkpoint's
    (assumed) names, a tensor a projection as torch holds it, and read
    back by `load_weights`: every leaf comes back as it was."""
    config = _config(num_hidden_layers=4)
    model = _program_model(config)
    params = jax.tree_util.tree_map(
        np.asarray, weights.whole(ref.tree(config), ref.stages(config), 1))
    from aphrodite_tpu.modeling.models.sarvam_mla import _by_part
    width, d, heads = 64, 32, 2
    out = []
    for bucket, leaves in params.items():
        if bucket.endswith(".self_attn.qkv_proj"):
            at = bucket[:-len("qkv_proj")]
            for i, name in enumerate(("q_proj", "k_proj", "v_proj")):
                out.append((f"{at}{name}.weight", leaves["weight"][
                    :, i * width:(i + 1) * width].T))
        elif bucket.endswith(".self_attn.fgb_proj"):
            at = bucket[:-len("fgb_proj")]
            for name, lo, hi in (("f_a_proj", 0, d), ("g_a_proj", d, 2 * d),
                                 ("b_proj", 2 * d, 2 * d + heads)):
                out.append((f"{at}{name}.weight",
                            leaves["weight"][:, lo:hi].T))
        elif bucket.endswith(".conv1d"):
            at = bucket[:-len("conv1d")]
            for i, name in enumerate(("q_conv1d", "k_conv1d", "v_conv1d")):
                out.append((f"{at}{name}.weight", leaves["weight"][
                    :, i * width:(i + 1) * width].T[:, None, :]))
        elif bucket.endswith(".self_attn.kda"):
            at = bucket[:-len("kda")]
            out.append((at + "A_log", leaves["A_log"].reshape(1, 1, -1, 1)))
            out.append((at + "dt_bias", leaves["dt_bias"]))
        elif bucket.endswith(".mlp.experts"):
            at = bucket.replace(".mlp.experts", ".block_sparse_moe.")
            out.append((at + "gate.weight", leaves["gate"].T))
            out.append((at + "gate.e_score_correction_bias",
                        leaves["e_bias"]))
            for e in range(16):     # every routed expert; 4 are held
                held = e - 4
                for name, leaf in (("w1", "w_gate"), ("w3", "w_up"),
                                   ("w2", "w_down")):
                    tensor = leaves[leaf][held].T if 0 <= held < 4 else \
                        np.full_like(leaves[leaf][0].T, 7.0)
                    out.append((f"{at}experts.{e}.{name}.weight", tensor))
        elif bucket.endswith("gate_up_proj"):
            at = bucket[:-len("gate_up_proj")]
            half = leaves["weight"].shape[1] // 2
            out.append((at + "gate_proj.weight",
                        leaves["weight"][:, :half].T))
            out.append((at + "up_proj.weight", leaves["weight"][:, half:].T))
        elif bucket.endswith(("self_attn.q_proj", "self_attn.kv_b_proj")):
            second = 16 if bucket.endswith("q_proj") else 32
            inverse = np.argsort(_by_part(4, 32, second))
            out.append((bucket + ".weight", leaves["weight"].T[inverse]))
        elif bucket in ("model.embed_tokens", "lm_head"):
            # the checkpoint holds the whole vocabulary
            out.append((bucket + ".weight", np.concatenate(
                [leaves["weight"], np.full_like(leaves["weight"], 9.0)])))
        elif "weight" in leaves and leaves["weight"].ndim == 2:
            out.append((bucket + ".weight", leaves["weight"].T))
        else:
            out.append((bucket + ".weight", leaves["weight"]))
    loaded = model.load_weights(out)
    assert sorted(loaded) == sorted(params)
    for bucket, leaves in params.items():
        assert sorted(loaded[bucket]) == sorted(leaves), bucket
        for name, leaf in leaves.items():
            assert np.array_equal(loaded[bucket][name], leaf), (bucket, name)
