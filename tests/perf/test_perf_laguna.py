"""Tests of what PR 43 adds to the benchmark as new files and entries:
the configuration `laguna-s-2.1-bf16` (the catalog row cut to one
chip's share of a two-way expert-parallel stage), its reference's tree
against the program's at the published widths, the reference's stages
and both controls through the harness's own child at a toy size, the
traffic `agent-4k`, the two new per-layer readers and the two older
ones the cell joins on hand-made runs of the new cell, both roofline
counts by hand, and the manifest's new entries. No chip."""
import io
import json
import math
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from perf import cells, loops, reference_child
from perf import run as perf_run

ROOT = cells.ROOT
CELL = "laguna-s-2.1-bf16.agent-4k"
OLD_CELLS = ["mistral-7b-w4a8.batch", "smallthinker-21ba3b-bf16.batch-8k",
             "phi-4-mini-flash-bf16.reason-2k", "jamba2-3b-bf16.reason-512"]
NEW = ("moe_held_roofline_pct.batch", "decode_attn_heads_roofline_pct.batch")
JOINED = ("moe_experts_touched_pct.batch", "window_kv_held_pct.batch")
CUT = {"num_hidden_layers": 5, "num_experts": 128, "vocab_size": 50176}
LISTS = ("layer_types", "mlp_layer_types", "gating_types",
         "num_attention_heads_per_layer")
SHARE_KEYS = {"num_routed_experts": 256, "first_held_expert": 0}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
ref = cells.load_module(os.path.join(ROOT, "perf", "references",
                                     "laguna.py"))


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _hf(config):
    from aphrodite_tpu.transformers_utils.configs import LagunaConfig
    return LagunaConfig(**{
        k: v for k, v in config.items()
        if k not in ("perf", "architectures", "model_type", "torch_dtype")})


# ---- the configuration and the cell ----

@pytest.mark.skipif(not os.path.isfile(CATALOG), reason="no catalog here")
def test_the_configuration_is_the_catalog_row_cut_to_a_share():
    """Every published key under its own name; three numbers cut and
    listed, the five per-layer lists cut to their first five entries
    (`mlp_only_layers` as it is), two keys added and marked as not the
    publisher's, every width as published."""
    config = cells.load_cell(CELL, ROOT).config
    perf = config["perf"]
    with open(CATALOG) as f:
        (row,) = [r for r in map(json.loads, f)
                  if r["name"] == "Laguna-S-2.1"]
    published = row["config"]
    assert row["source_url"] == perf["source"]
    assert set(config) == set(published) | set(SHARE_KEYS) | {
        "architectures", "torch_dtype", "perf"}
    for key, value in published.items():
        if key in CUT:
            assert config[key] == CUT[key] != value
        elif key in LISTS:
            assert config[key] == value[:5] and len(value) == 48
        else:
            assert config[key] == value, key
    assert perf["reduced"] == list(CUT)
    assert {k: config[k] for k in SHARE_KEYS} == SHARE_KEYS
    assert sorted(perf["share_keys"]) == sorted(SHARE_KEYS)
    assert all("NOT the publisher's" in why
               for why in perf["share_keys"].values())
    # what `reduced` may never name: no width is cut
    for width in ("hidden_size", "intermediate_size", "head_dim",
                  "moe_intermediate_size", "shared_expert_intermediate_size",
                  "num_experts_per_tok", "num_key_value_heads",
                  "sliding_window", "max_position_embeddings"):
        assert config[width] == published[width]
    assert config["rope_parameters"] == published["rope_parameters"]
    assert config["mlp_only_layers"] == [0]
    # the guide's floors: a whole period and four layers after the
    # dense one, 8 experts or more, an eighth of the vocabulary
    assert config["num_hidden_layers"] >= 1 + 4
    assert config["layer_types"][1:5].count("full_attention") == 1
    assert config["num_experts"] >= 8
    assert config["vocab_size"] * 8 >= published["vocab_size"]
    assert config["architectures"] == ["LagunaForCausalLM"]
    assert config["torch_dtype"] == "bfloat16"
    assert perf["engine_args"][:2] == ["--max-model-len", "8192"]
    assert perf["env"] == {"APHRODITE_SPEC": "0"}
    assert perf["kernel_families"] == ["decode_attention", "kv_write"]
    assert perf["matmul_peak"] == "bf16_flops_per_s"
    assert sorted(perf["controls"]) == ["act8", "kv8"]
    assert perf["reference"] == "laguna"
    assert perf["reference_replies"] == 2
    for said in ("24 chips", "two chips", "experts 0-127", "256 experts",
                 "100,352", "48 layers", "5,572,076,544", "11.14 GB",
                 "no code stands in"):
        assert said in perf["deployment"], said
    assumed = perf["assumed"]
    assert [a[:3] for a in assumed] == ["(a)", "(b)", "(c)", "(d)", "(e)"]
    for said in ("sigmoid", "softmax over all 256", "ungated and unscaled",
                 "no bias", "tensor names"):
        assert said in " ".join(assumed), said
    entry = {c["name"]: c for c in _bench()["configs"]}["laguna-s-2.1-bf16"]
    assert entry["source"] == perf["source"]
    assert entry["reduced"] == perf["reduced"]
    assert entry["file"] == "perf/configs/laguna-s-2.1-bf16.json"


def test_the_parameters_to_the_parameter():
    """ISSUE 43's arithmetic: what the chip holds, and what one token
    multiplies (the routed term an expectation: 5 of a token's 10
    pairs meet a held expert under even routing)."""
    config = cells.load_cell(CELL, ROOT).config
    tree = ref.tree(config)
    sizes = {b: sum(int(np.prod(s[0])) for s in v.values())
             for b, v in tree.items()}
    assert sum(sizes.values()) == 5_572_076_544

    def layer(i, part=""):
        return sum(n for b, n in sizes.items()
                   if b.startswith(f"model.layers.{i}.{part}"))
    assert layer(0, "self_attn") == 44_187_648          # full, 48 heads
    assert layer(1, "self_attn") == 63_135_744          # window, 72
    assert layer(0, "mlp") == 113_246_208
    assert layer(0) == 157_440_000
    assert layer(1) == layer(2) == layer(3) == 1_281_325_056
    assert layer(4) == 1_262_376_960
    assert sizes["model.embed_tokens"] == sizes["lm_head"] == 154_140_672
    expert = 3 * 3072 * 1024
    attention = sum(layer(i, "self_attn") for i in range(5))
    assert attention == 277_782_528
    assert config["perf"]["parameters"] == 774_807_552 == \
        attention + 113_246_208 + 4 * (expert + 3072 * 256 + 5 * expert) + \
        154_140_672
    assert "EXPECTATION" in config["perf"]["parameters_why"]


def test_what_the_configuration_makes_of_the_cache_layer():
    """Two full and three window layers: gcd 1, five groups of one
    layer, ONE pair of page arrays, a page of 64 KiB; a row of the
    cell at its longest holds 694 pages, 45 MB."""
    from aphrodite_tpu.common.config import (CacheConfig, ModelConfig,
                                             ParallelConfig)
    from aphrodite_tpu.executor.cache_engine import CacheEngine
    cell = cells.load_cell(CELL, ROOT)
    model_config = ModelConfig("x", hf_config=_hf(cell.config),
                               dtype="bfloat16", max_model_len=8192)
    groups = model_config.get_page_groups()
    assert groups.kinds == ("full", "window", "window", "window", "full")
    assert groups.layers_per_group == 1 and groups.window == 512
    assert groups.readers == (1, 1, 1, 1, 1) and not groups.stateful
    assert model_config.get_kv_heads_per_slot() == [8]
    assert model_config.get_head_size() == 128
    assert model_config.get_state_spec() is None
    cache_config = CacheConfig(16, 0.9, 0.01, "auto", page_groups=groups)
    page = CacheEngine.get_cache_block_size(
        cache_config, model_config, ParallelConfig(1, 1))
    assert page == 16 * 8 * 128 * 2 * 2 == 65_536
    longest = 4096 + cell.traffic["params"]["output_len"]["max"]
    # the window, a page and the page being written
    row = 2 * -(-longest // 16) + 3 * (512 // 16 + 2)
    assert row == 694 and 45e6 < row * page < 46e6
    # the roofline file's view of the groups agrees with the program's
    heads = cells.load_module(os.path.join(ROOT, "perf", "rooflines",
                                           "paged_decode_heads.py"))
    assert heads.layers_per_group(cell.config) == groups.layers_per_group
    held = cells.load_module(os.path.join(ROOT, "perf", "rooflines",
                                          "moe_held.py"))
    assert held.expert_layers(cell.config) == 4


def test_the_references_tree_is_the_programs_at_the_published_widths():
    """What `perf/serve_child.py` checks when the server starts, here
    without a byte of weights: every bucket, leaf, shape and type."""
    from aphrodite_tpu.modeling.models.laguna import LagunaForCausalLM
    config = cells.load_cell(CELL, ROOT).config
    model = LagunaForCausalLM(_hf(config), jnp.dtype(config["torch_dtype"]),
                              max_model_len=8192)
    shapes = jax.eval_shape(model.init_params)
    have = {b: {n: (tuple(a.shape), a.dtype.name)
                for n, a in leaves.items()}
            for b, leaves in shapes.items()}
    tree = ref.tree(config)
    assert have == {b: {n: (tuple(s[0]), s[1]) for n, s in v.items()}
                    for b, v in tree.items()}
    assert sum(int(np.prod(a.shape)) for leaves in shapes.values()
               for a in leaves.values()) == 5_572_076_544
    assert shapes["model.layers.1.mlp.experts"]["gate"].shape == (3072, 256)
    assert shapes["model.layers.1.mlp.experts"]["w_gate"].shape == \
        (128, 3072, 1024)
    assert model.expert_slots == 512
    assert model.step_counters == ("moe.tokens_routed",
                                   "moe.experts_touched", "moe.pairs_held")
    # the tables of both rotary embeddings stop at the server's
    # longest sequence: 8,192 rows, not 1,048,576
    assert {layer.rotary.cos_sin_cache.shape for layer in model.layers} == \
        {(8192, 64), (8192, 128)}
    assert [fn for fn, _ in ref.stages(config)] == [
        "embed", "layer_full_dense", "layer_window_sparse",
        "layer_window_sparse", "layer_window_sparse", "layer_full_sparse",
        "logits"]
    # every stage's weights are named once, the untied head its own
    named = [b for _, buckets in ref.stages(config)
             for b in buckets.values()]
    assert sorted(named) == sorted(tree)


def test_the_traffic_is_4k_prompts_from_64_callers():
    cell = cells.load_cell(CELL, ROOT)
    loop, params = cell.traffic["loop"], cell.traffic["params"]
    clients = loop["clients"]
    assert (loop["kind"], loop["journal_callers"]) == ("closed", 1)
    assert clients in (64, 48) and loop["ramp_groups"] == [4]
    args = cell.config["perf"]["engine_args"]
    assert int(args[args.index("--max-num-seqs") + 1]) == clients
    vocab = cell.config["vocab_size"]
    shapes = cell.generator(params, 3000000877, 0, clients, None, vocab)
    assert len(shapes) == clients
    # eight times the window, two chunks of 2,048, ids of the held rows
    assert {len(s["prompt"]) for s in shapes} == {4096} == \
        {8 * cell.config["sliding_window"]}
    assert all(3 <= t < 50176 for s in shapes for t in s["prompt"])
    outs = sorted(s["max_tokens"] for s in shapes)
    assert 128 <= outs[0] < 140 and 628 < outs[-1] <= 640
    assert 380 < sum(outs) / clients < 388
    other = cell.generator(params, 12345, 0, clients, None, vocab)
    assert sorted(s["max_tokens"] for s in other) == outs
    assert not any(s["stream"] for s in shapes)
    assert all(s["sampling"] == {"temperature": 0.0} for s in shapes)
    # a group of callers queued stays well under the admission limit
    # of 8 x max_num_batched_tokens (8,192 at --max-model-len 8192)
    assert max(loop["ramp_groups"]) * 4096 * 4 <= 8 * 8192
    assert clients % sum(loop["ramp_groups"]) == 0
    canary = cell.traffic["canary"]
    assert len(canary["prompt_lens"]) == 3 and canary["max_tokens"] == 16
    # the canary's prompts take the cell's two chunks (2,048 and one
    # that pads to 2,048) and its rows stay within one table width
    for n in canary["prompt_lens"]:
        assert 2048 + 1024 < n < 4096
        assert -(-(n + 16) // 16) <= 256
    # the longest sequence fits the reference's rows: three rows of
    # float32 logits over the held vocabulary
    assert reference_child.padded(4096 + 640) == 5120
    rows = 1 + cell.config["perf"]["reference_replies"]
    assert rows == 3 and 3.0e9 < rows * 5120 * 50176 * 4 < 3.2e9


# ---- the reference through the harness's child ----

def _tiny():
    rope = {"full_attention": {
        "rope_theta": 500000, "rope_type": "yarn", "factor": 8,
        "original_max_position_embeddings": 32, "beta_slow": 1,
        "beta_fast": 32, "attention_factor": 0.1 * math.log(8) + 1,
        "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}}
    return dict(
        architectures=["LagunaForCausalLM"], model_type="laguna",
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=5, num_attention_heads=12, num_key_value_heads=2,
        head_dim=16, max_position_embeddings=256, rms_norm_eps=1e-6,
        num_experts=8, num_routed_experts=16, first_held_expert=0,
        num_experts_per_tok=4, moe_intermediate_size=32,
        shared_expert_intermediate_size=32, norm_topk_prob=True,
        mlp_only_layers=[0], sliding_window=16, rope_parameters=rope,
        layer_types=["full_attention"] + ["sliding_attention"] * 3 +
        ["full_attention"], mlp_layer_types=["dense"] + ["sparse"] * 4,
        gating_types=["per_head"] * 5,
        num_attention_heads_per_layer=[12, 18, 18, 18, 12],
        moe_routed_scaling_factor=2.5, tie_word_embeddings=False,
        torch_dtype="float32",
        perf=dict(reference="laguna", controls=dict(
            kv8=dict(kv="float8_e5m2"), act8=dict(act_bits=8))))


def test_the_stages_and_both_controls_through_the_harness_child(
        tmp_path, monkeypatch):
    """`perf/reference_child.py` as the harness starts it, on the CPU at
    a toy size: every stage maps the stream to itself and reports its
    share; a greedy continuation of the reference itself (the sound
    path) reads no gap at all, and both controls read one: `kv8` and
    `act8` fail limits set as the cell's are, between the two."""
    from perf import weights
    config = _tiny()
    params = weights.whole(ref.tree(config), ref.stages(config), 5)
    ids = np.random.default_rng(0).integers(3, 256, 40).tolist()
    steps = 24

    @jax.jit
    def forward(x):
        for fn, buckets in ref.stages(config):
            w = {local: params[b] for local, b in buckets.items()}
            x = getattr(ref, fn)(config, w, x, ref.Precision())
        return x

    # (causal: what lies behind a position does not reach it)
    with jax.default_matmul_precision("highest"):
        for _ in range(steps):
            x = np.zeros((1, 64), np.int32)
            x[0, :len(ids)] = ids
            ids.append(int(np.asarray(forward(x)[0, len(ids) - 1]).argmax()))
    job = dict(root=ROOT, config=config, name="laguna", seed=5,
               sequences=[dict(prompt=ids[:40], reply=ids[40:])], rows=2,
               cpu=True, controls=["kv8", "act8"],
               cache=str(tmp_path / "cache"))
    (tmp_path / "in.json").write_text(json.dumps(job))
    monkeypatch.setattr(sys, "stdin", io.StringIO("go\n"))
    cache_was = jax.config.jax_compilation_cache_dir
    try:
        assert reference_child.main(str(tmp_path / "in.json"),
                                    str(tmp_path / "out.json")) == 0
    finally:
        jax.config.update("jax_compilation_cache_dir", cache_was)
    out = json.loads((tmp_path / "out.json").read_text())

    def gaps(side):
        got = out[side]
        return (np.asarray(got["best"]) - np.asarray(got["chosen"])) / \
            np.asarray(got["std"])
    assert len(out["served"]["chosen"]) == steps
    # all 5 layer stages keep their shape and report a share
    assert 0.3 < out["layer_share"] < 3 and len(out["stage_s"]) == 1 + 5
    # the sound path: its own greedy tokens are its largest logits
    sound = gaps("served")
    assert sound.max() <= 1e-5
    for control in ("kv8", "act8"):
        lowered = gaps(control)
        assert (lowered >= -1e-5).all()
        # limits between the two readings hold the control off by the
        # mean and by the widest gap
        assert lowered.mean() > 1e-3 > 100 * max(sound.mean(), 1e-7)
        assert lowered.max() > 1e-2


def test_a_control_moves_the_reference_where_it_enters():
    """`kv8` rounds K and V of every layer's attention and nothing
    else (the embedding stage is untouched by both); `act8` every
    matmul's input, the router's among them: it moves a layer's output
    more than `kv8` does at this size."""
    from perf import weights
    config = _tiny()
    params = weights.whole(ref.tree(config), ref.stages(config), 5)
    lowered = reference_child.lowered
    sides = {"served": ref.Precision(),
             "kv8": ref.Precision(**lowered(dict(kv="float8_e5m2"))),
             "act8": ref.Precision(**lowered(dict(act_bits=8)))}
    x0 = jnp.asarray([np.random.default_rng(1).integers(3, 256, 48)],
                     jnp.int32)
    moved = {}
    for side, p in sides.items():
        x, per_stage = x0, []
        with jax.default_matmul_precision("highest"):
            for fn, buckets in ref.stages(config)[:-1]:
                w = {local: params[b] for local, b in buckets.items()}
                x = getattr(ref, fn)(config, w, x, p)
                per_stage.append(np.asarray(x))
        moved[side] = per_stage

    def diff(side, i):
        return float(np.abs(moved[side][i] - moved["served"][i]).max())
    assert diff("kv8", 0) == diff("act8", 0) == 0.0
    for stage in (1, 2, 5):
        assert diff("kv8", stage) > 1e-4 and diff("act8", stage) > 1e-4


# ---- the readers on hand-made runs of the new cell ----

def _run(samples, trace=None, seconds=10.0, log_setup="", cell=CELL):
    window = loops.Window(t0=100.0, replies=[], t_end=0.0,
                          seconds=seconds * max(1, len(samples) - 1))
    run = perf_run.Run(
        cell=cells.load_cell(cell, ROOT), window=window, t_start=0.0,
        samples=[(100.0 + i * seconds, s) for i, s in enumerate(samples)],
        steady_until=100.0 + window.seconds, log_setup=log_setup,
        log_window="", faults=[], trace=trace)
    run.peaks = cells.load_peaks("TPU v5 lite")
    return run


def _totals(**counters):
    return {f"aphrodite:{k}_total": float(v) for k, v in counters.items()}


#: two readings 10 s apart: 300 step programs, 250 of them decode steps
#: of 64 rows and 50 prompt steps of one 2,048-token chunk. A decode
#: step routes 640 pairs a layer, 320 of them held, over 118 of 128
#: held experts; a chunk 20,480, 10,240 held, over all 128. A row holds
#: 280 pages in each full group and 34 in each window group.
_DECODE, _CHUNKS, _ROWS = 250, 50, 64
_PAIRS = 4 * (_DECODE * 640 + _CHUNKS * 20480)
_HELD = _PAIRS // 2
_TOUCHED = 4 * (_DECODE * 118 + _CHUNKS * 128)
_FULL, _WINDOW = 2 * 280 * _ROWS, 3 * 34 * _ROWS
STEPS = [
    _totals(sampler_plans=1000, decode_attn_steps=900,
            moe_tokens_routed=5e6, moe_pairs_held=2.5e6,
            moe_experts_touched=1e5, moe_decode_experts_touched=8e4,
            moe_decode_expert_slots=9e4, kv_pages_live_full=1e7,
            kv_pages_live_window=1e6, window_pages_unwindowed=9e6),
    _totals(sampler_plans=1000 + _DECODE + _CHUNKS,
            decode_attn_steps=900 + _DECODE,
            moe_tokens_routed=5e6 + _PAIRS, moe_pairs_held=2.5e6 + _HELD,
            moe_experts_touched=1e5 + _TOUCHED,
            moe_decode_experts_touched=8e4 + 4 * _DECODE * 118,
            moe_decode_expert_slots=9e4 + _DECODE * 512,
            kv_pages_live_full=1e7 + _DECODE * _FULL,
            kv_pages_live_window=1e6 + _DECODE * _WINDOW,
            window_pages_unwindowed=9e6 + _DECODE * 3 * 280 * _ROWS)]
#: the traced 2 s: 40 decode steps and 8 chunks; a decode step's three
#: grouped matmuls take 5 ms a layer and a chunk's 9 ms; a full layer's
#: decode-attention call 1.6 ms, a window layer's 0.4 ms
OPS = {
    "ragged-dot-none bf16[640,1024] tpu_custom_call": [0.56, 320],
    "ragged-dot-none bf16[640,3072] tpu_custom_call": [0.24, 160],
    "ragged-dot-none bf16[20480,1024] tpu_custom_call": [0.2, 64],
    "ragged-dot-none bf16[20480,3072] tpu_custom_call": [0.088, 32],
    "ragged-dot-metadata s32[128] tpu_custom_call": [0.0048, 192],
    "_paged_decode_impl bf16[65,1,48,128] tpu_custom_call": [0.128, 80],
    "_paged_decode_impl bf16[65,1,72,128] tpu_custom_call": [0.048, 120],
    "fusion bf16[64,3072]": [0.3, 5000]}
TRACE = dict(busy_s=1.95, window_s=2.0, device_ops=[], idle_gaps=[],
             ops=OPS)
_EXPERT = 3 * 3072 * 1024
#: a layer's call in the window, a step in five: 300 steps x 4 layers
_LAYER_CALLS = (_DECODE + _CHUNKS) * 4
_PAGE_LAYER = 16 * 8 * 128 * 2 * 2      # a page of one layer, K and V
WANT = {
    # the window's sums: the experts' bytes bind, in the chunks too
    "moe_held_roofline_pct.batch":
        max((_TOUCHED * _EXPERT + 2 * _HELD * 3072) * 2 / 819e9,
            2.0 * _EXPERT * _HELD / 197e12) / _LAYER_CALLS /
        ((0.56 + 0.24 + 0.2 + 0.088 + 0.0048) / (576 / 3)) * 100,
    # a step's five calls: the pages of five groups once, the rows of
    # 65 x (2 x 48 + 3 x 72) heads in and out; 0.176 s over 200 calls
    "decode_attn_heads_roofline_pct.batch":
        (((_FULL + _WINDOW) * _PAGE_LAYER +
          2 * 65 * (2 * 48 + 3 * 72) * 128 * 2) / 819e9) /
        (0.176 / 200 * 5) * 100,
    "moe_experts_touched_pct.batch": 118 / 128 * 100,
    "window_kv_held_pct.batch": 34 / 280 * 100}


def _read(metric, run):
    return cells.load_function(
        cells.reader_path(ROOT, "layers", metric), "read")(run)


@pytest.mark.parametrize("metric", NEW + JOINED)
def test_each_reader_on_a_hand_made_run_of_the_new_cell(metric):
    got = _read(metric, _run(STEPS, TRACE))
    assert got == pytest.approx(WANT[metric], rel=1e-6)
    assert 0 < got < 100
    entry = {m["name"]: m for m in _bench()["per_layer"]}[metric]
    assert entry["moves"] == "out_tok_s" and CELL in entry["workloads"]
    assert entry["unit"] == "%"
    assert entry["source"] == ("device_trace" if "roofline" in metric
                               else "program_counter")


@pytest.mark.parametrize("metric", NEW)
def test_a_new_reader_that_finds_nothing_reads_nothing(metric):
    """The parent's program exports no `moe_pairs_held_total`; a
    `--trace 0` run has no trace, a CPU trace none of the kernels'
    names, an unknown device no peaks, another configuration none of
    the per-layer lists. None, never 0 and never an exception."""
    assert _read(metric, _run([], TRACE)) is None
    assert _read(metric, _run(STEPS)) is None
    assert _read(metric, _run(STEPS, dict(
        TRACE, ops={"fusion f32[8]": [1.0, 10]}))) is None
    gone = "moe_pairs_held" if metric.startswith("moe") \
        else "kv_pages_live_window"
    parent = [{k: v for k, v in s.items() if gone not in k} for s in STEPS]
    assert _read(metric, _run(parent, TRACE)) is None
    run = _run(STEPS, TRACE)
    run.peaks = None
    assert _read(metric, run) is None
    # configurations without the per-layer lists (Mistral's, and
    # SmallThinker's, whose experts have other key names)
    for cell in OLD_CELLS[:2]:
        assert _read(metric, _run(STEPS, TRACE, cell=cell)) is None


def test_both_roofline_counts_by_hand():
    config = cells.load_cell(CELL, ROOT).config
    held = cells.load_module(os.path.join(ROOT, "perf", "rooflines",
                                          "moe_held.py"))
    # a decode step's layer: 320 held pairs over 118 held experts
    moved, computed = held.count(config, 320, 118)
    assert moved == (118 * 3 * 3072 * 1024 + 2 * 320 * 3072) * 2
    assert computed == 2.0 * 3 * 3072 * 1024 * 320
    # bound by bytes there, by a factor of ninety
    assert 80 < (moved / 819e9) / (computed / 197e12) < 100
    # a chunk's layer, 10,240 held pairs over all 128: 80 rows an
    # expert, a third of the 240 operations a byte at which compute
    # would bind, so the experts' bytes bind there too
    moved, computed = held.count(config, 10240, 128)
    assert 3 < (moved / 819e9) / (computed / 197e12) < 3.5
    # a decode step's layers read 8.9 GB of experts at 92% touched
    assert 8.8e9 < 4 * held.count(config, 320, 118)[0] < 9.0e9
    heads = cells.load_module(os.path.join(ROOT, "perf", "rooflines",
                                           "paged_decode_heads.py"))
    full, window = 2 * 280 * 64, 3 * 34 * 64
    moved, computed = heads.count(config, full, window, 65)
    assert moved == (full + window) * 65536 + \
        2 * 65 * (2 * 48 + 3 * 72) * 128 * 2
    assert computed == 4.0 * 128 * 16 * (48 * full + 72 * window)
    # K and V of a step: 2.8 GB here, bound by bytes
    assert 2.7e9 < moved < 2.9e9
    assert moved / 819e9 > 5 * computed / 197e12
    # the accepted count of page groups reads `sliding_window_layout`,
    # which this configuration has not: it would take all five layers
    # for one group and count every group's pages five times
    groups = cells.load_module(os.path.join(
        ROOT, "perf", "rooflines", "paged_decode_groups.py"))
    theirs, _ = groups.count(dict(config, num_attention_heads=48),
                             full + window, 65)
    assert theirs > 4.9 * (full + window) * 65536


# ---- the manifest's new entries ----

def test_the_manifest_gains_a_configuration_a_cell_and_two_metrics():
    bench = _bench()
    assert [c["name"] for c in bench["configs"]][-1] == "laguna-s-2.1-bf16"
    assert len(bench["configs"][-1]["why"]) <= 200
    names = [w["name"] for w in bench["workloads"]]
    assert names == OLD_CELLS + [CELL]
    new = bench["workloads"][-1]
    assert (new["config"], new["traffic"], new["chips"]) == (
        "laguna-s-2.1-bf16", "agent-4k", 1)
    assert len(new["why"]) <= 200
    for said in ("callers", "4,096", "held expert", "deployment"):
        assert said in new["why"], said
    by_name = {m["name"]: m for m in
               bench["end_to_end"] + bench["per_layer"]}
    listed = [m["name"] for m in bench["per_layer"]]
    # appended: an entry put in the middle of a list reads as a change
    # to what was there
    assert listed[-2:] == list(NEW)
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
        assert os.path.isfile(cells.reader_path(ROOT, "layers", name))
    assert by_name[NEW[0]]["layer"] == \
        by_name["moe_experts_roofline_pct.batch"]["layer"]
    assert by_name[NEW[1]]["layer"] == \
        by_name["decode_attn_groups_roofline_pct.batch"]["layer"]
    assert by_name["out_tok_s"]["workloads"] == OLD_CELLS + [CELL]
    assert "workloads" not in by_name["setup_s"]
    # the 27 metrics every cell reports, and the two it joins
    every = [m["name"] for m in bench["per_layer"]
             if m.get("workloads") == OLD_CELLS + [CELL]]
    assert len(every) == 27
    assert by_name[JOINED[0]]["workloads"] == [OLD_CELLS[1], CELL]
    assert by_name[JOINED[1]]["workloads"] == OLD_CELLS[1:3] + [CELL]
    # the shares whose counts are wrong or absent here stay the older
    # cells'
    for name in ("decode_attn_roofline_pct.batch",
                 "decode_attn_groups_roofline_pct.batch",
                 "decode_attn_shared_roofline_pct.batch",
                 "decode_attn_mqa_roofline_pct.batch",
                 "moe_experts_roofline_pct.batch",
                 "ssm_update_roofline_pct.batch", "ssm_slot_waits.batch"):
        assert CELL not in by_name[name]["workloads"]
    reported = {m["name"] for m in cells.load_cell(CELL, ROOT).per_layer}
    assert reported == set(every) | set(JOINED) | set(NEW) | {
        "programs_warmed"}
    assert len(reported) == 32
    # nothing the older cells report has changed under them: without
    # the new cell the manifest is the parent's, entry for entry
    from conftest import without_cells
    before = without_cells(bench, cells=(CELL,))
    assert [w["name"] for w in before["workloads"]] == OLD_CELLS
    assert len(before["per_layer"]) == len(bench["per_layer"]) - 2
    assert len(before["configs"]) == len(bench["configs"]) - 1
    for cell in OLD_CELLS:
        assert not {m["name"] for m in cells.load_cell(cell, ROOT).per_layer
                    } & set(NEW)
