"""Tests of what PR 33 adds to the benchmark as new files and entries:
the configuration `smallthinker-21ba3b-bf16`, its reference's tree
against the program's at the published widths, the traffic `batch-8k`,
the four per-layer readers on hand-made runs, their roofline counts,
and the manifest's new entries. No chip."""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from perf import cells, loops
from perf import run as perf_run

ROOT = cells.ROOT
CELL = "smallthinker-21ba3b-bf16.batch-8k"
OLD_CELL = "mistral-7b-w4a8.batch"
NEW = ("moe_experts_roofline_pct.batch", "moe_experts_touched_pct.batch",
       "decode_attn_groups_roofline_pct.batch", "window_kv_held_pct.batch")
ref = cells.load_module(os.path.join(ROOT, "perf", "references",
                                     "smallthinker.py"))


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


# ---- the configuration and the cell ----

def test_the_configuration_is_the_published_one_cut_in_depth_alone():
    config = cells.load_cell(CELL, ROOT).config
    perf = config["perf"]
    published = dict(
        head_dim=128, hidden_size=2560, max_position_embeddings=16384,
        moe_ffn_hidden_size=768, moe_num_active_primary_experts=6,
        moe_num_primary_experts=64, num_attention_heads=28,
        num_key_value_heads=4, rms_norm_eps=1e-06, rope_theta=1500000,
        sliding_window_size=4096, vocab_size=151936,
        moe_primary_router_apply_softmax=True, norm_topk_prob=True,
        tie_word_embeddings=False, rope_scaling=None)
    assert {k: config[k] for k in published} == published
    assert config["num_hidden_layers"] == 12
    assert perf["reduced"] == ["num_hidden_layers"]
    # three whole periods of the published pattern
    assert config["sliding_window_layout"] == [0, 1, 1, 1] * 3
    assert config["rope_layout"] == config["sliding_window_layout"]
    assert "four pipeline stages of 13" in perf["deployment"]
    assert len(perf["assumed"]) == 2
    assert any("router" in a for a in perf["assumed"])
    assert any("bias" in a for a in perf["assumed"])
    assert sorted(perf["controls"]) == ["act8", "kv8"]
    assert perf["kernel_families"] == ["decode_attention", "kv_write"]
    assert perf["env"] == {"APHRODITE_SPEC": "0"}
    # what one token multiplies: attention, router and 6 experts a
    # layer, and the head
    hidden, head = config["hidden_size"], config["head_dim"]
    attention = hidden * head * (28 + 2 * 4) + 28 * head * hidden
    expert = 3 * hidden * config["moe_ffn_hidden_size"]
    a_layer = attention + hidden * 64 + 6 * expert
    assert a_layer == 56_524_800
    assert perf["parameters"] == 12 * a_layer + \
        config["vocab_size"] * hidden == 1_067_253_760
    entry = {c["name"]: c for c in _bench()["configs"]}[
        "smallthinker-21ba3b-bf16"]
    assert entry["source"] == perf["source"]
    assert entry["reduced"] == perf["reduced"]


def test_the_references_tree_is_the_programs_at_the_published_widths():
    """What `perf/serve_child.py` checks when the server starts, here
    without a byte of weights: every bucket, leaf, shape and type."""
    from aphrodite_tpu.modeling.models.smallthinker import \
        SmallThinkerForCausalLM
    from aphrodite_tpu.transformers_utils.configs import SmallThinkerConfig
    config = cells.load_cell(CELL, ROOT).config
    hf = SmallThinkerConfig(**{
        k: v for k, v in config.items()
        if k not in ("perf", "architectures", "model_type",
                     "torch_dtype")})
    model = SmallThinkerForCausalLM(hf, jnp.dtype(config["torch_dtype"]))
    have = {b: {n: (tuple(a.shape), a.dtype.name)
                for n, a in leaves.items()}
            for b, leaves in jax.eval_shape(model.init_params).items()}
    tree = ref.tree(config)
    assert have == {b: {n: (tuple(s[0]), s[1]) for n, s in v.items()}
                    for b, v in tree.items()}
    held = sum(int(np.prod(s[0])) for v in tree.values()
               for s in v.values())
    # 12 layers of 398,627,840, the embedding, the head and the last
    # norm: 11.12 GB
    assert held == 12 * 398_627_840 + 2 * 388_956_160 + 2560 == \
        5_561_448_960
    stages = ref.stages(config)
    assert [fn for fn, _ in stages] == ["embed"] + [
        "layer_full", "layer_window", "layer_window",
        "layer_window"] * 3 + ["logits"]


def test_the_traffic_is_8k_prompts_from_24_callers():
    cell = cells.load_cell(CELL, ROOT)
    loop, params = cell.traffic["loop"], cell.traffic["params"]
    assert (loop["kind"], loop["clients"], loop["ramp_groups"],
            loop["journal_callers"]) == ("closed", 24, [2, 1], 1)
    shapes = cell.generator(params, 3000000877, 0, 24, None, 151936)
    assert {len(s["prompt"]) for s in shapes} == {8192}
    outs = sorted(s["max_tokens"] for s in shapes)
    assert 256 <= outs[0] < 300 and 720 < outs[-1] <= 768
    assert not any(s["stream"] for s in shapes)
    assert all(s["sampling"] == {"temperature": 0.0} for s in shapes)
    # two prompts queued stay under the admission limit of 32,768
    assert max(loop["ramp_groups"]) * 8192 < 32768
    assert cell.traffic["canary"]["prompt_lens"] == [8064, 8120, 8176]
    # the longest sequence the reference is run over fits its rows
    assert 8192 + 768 <= 9216
    # and the rows fit the chip: the head's float32 logits of every
    # padded position of every row are one array, 15.65 GiB of a
    # 15.75 GiB chip at three rows (the canary and two replies), so
    # the run keeps the canary and one reply
    rows = 1 + cell.config["perf"]["reference_replies"]
    assert rows == 2 and rows * 9216 * 151936 * 4 < 11 * 2 ** 30


# ---- the reference's own blocks ----

@pytest.mark.parametrize("window", [None, 5, 40])
@pytest.mark.parametrize("tokens", [7, 64, 75])
def test_the_references_blocked_attention_is_plain_attention(
        monkeypatch, window, tokens):
    """A block of queries at a time against the keys it can see is the
    same function as all queries against all keys under the mask."""
    monkeypatch.setattr(ref, "QUERY_BLOCK", 16)
    rng = np.random.default_rng(tokens)
    q = jnp.asarray(rng.normal(size=(2, tokens, 2, 3, 8)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, tokens, 2, 8)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, tokens, 2, 8)), jnp.float32)
    got = np.asarray(ref.attention(q, k, v, window))
    scores = np.einsum("btkgd,bskd->bkgts", q, k) * 8 ** -0.5
    pos = np.arange(tokens)
    seen = pos[None, :] <= pos[:, None]
    if window is not None:
        seen &= pos[None, :] > pos[:, None] - window
    scores = np.where(seen, scores, -np.inf)
    weights = np.exp(scores - scores.max(-1, keepdims=True))
    weights /= weights.sum(-1, keepdims=True)
    want = np.einsum("bkgts,bskd->btkgd", weights, v).reshape(
        2, tokens, -1)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


# ---- the four readers on hand-made runs ----

def _run(samples, trace=None, seconds=10.0, cell=CELL):
    window = loops.Window(t0=100.0, replies=[], t_end=0.0,
                          seconds=seconds * max(1, len(samples) - 1))
    run = perf_run.Run(
        cell=cells.load_cell(cell, ROOT), window=window, t_start=0.0,
        samples=[(100.0 + i * seconds, s) for i, s in enumerate(samples)],
        steady_until=100.0 + window.seconds, log_setup="", log_window="",
        faults=[], trace=trace)
    run.peaks = cells.load_peaks("TPU v5 lite")
    return run


def _totals(**counters):
    return {f"aphrodite:{k}_total": float(v) for k, v in counters.items()}


#: two readings 10 s apart: 500 step programs, 400 of them decode steps
#: of 24 rows and 100 prompt chunks of 2,048 tokens, 12 expert layers
#: each. A decode step routes 24 x 6 x 12 pairs and touches 58 of 64
#: experts a layer; a chunk routes 2,048 x 6 x 12 and touches all 64.
#: A decode step's rows hold 538 pages live in the full group and 258
#: in each of the three window groups, which would hold 538 without a
#: window.
_DECODE, _CHUNKS = 400, 100
STEPS = [
    _totals(sampler_plans=1000, moe_tokens_routed=5e6,
            moe_experts_touched=1e5, moe_decode_experts_touched=7e4,
            moe_decode_expert_slots=9e4, decode_attn_steps=2000,
            kv_pages_live_full=1e6, kv_pages_live_window=2e6,
            window_pages_unwindowed=4e6),
    _totals(sampler_plans=1000 + _DECODE + _CHUNKS,
            moe_tokens_routed=5e6 + 12 * 6 * (
                _DECODE * 24 + _CHUNKS * 2048),
            moe_experts_touched=1e5 + 12 * (_DECODE * 58 + _CHUNKS * 64),
            moe_decode_experts_touched=7e4 + 12 * _DECODE * 58,
            moe_decode_expert_slots=9e4 + 12 * _DECODE * 64,
            decode_attn_steps=2000 + _DECODE,
            kv_pages_live_full=1e6 + _DECODE * 24 * 538,
            kv_pages_live_window=2e6 + _DECODE * 24 * 3 * 258,
            window_pages_unwindowed=4e6 + _DECODE * 24 * 3 * 538)]
#: the traced 2 s: 40 decode steps and 10 chunks, three grouped matmuls
#: and a metadata call a layer; a decode layer's matmuls take 1.6 ms,
#: a chunk layer's 4 ms, and a decode-attention call 0.5 ms
OPS = {
    "ragged-dot-none bf16[144,768] tpu_custom_call": [0.48, 960],
    "ragged-dot-none bf16[144,2560] tpu_custom_call": [0.24, 480],
    "ragged-dot-none bf16[12288,768] tpu_custom_call": [0.3, 240],
    "ragged-dot-none bf16[12288,2560] tpu_custom_call": [0.15, 120],
    "ragged-dot-metadata s32[65] tpu_custom_call": [0.078, 600],
    "_paged_decode_impl bf16[25,1,28,128] tpu_custom_call": [0.24, 480],
    "fusion f32[24,2560]": [0.5, 5000]}
TRACE = dict(busy_s=1.9, window_s=2.0, device_ops=[], idle_gaps=[],
             ops=OPS)
_EXPERT = 3 * 2560 * 768
_PAIRS = 12 * 6 * (_DECODE * 24 + _CHUNKS * 2048)
_TOUCHED = 12 * (_DECODE * 58 + _CHUNKS * 64)
WANT = {
    # the window's least time a layer call, bound by bytes, over the
    # trace's seconds a layer call
    "moe_experts_roofline_pct.batch":
        ((_TOUCHED * _EXPERT + 2 * _PAIRS * 2560) * 2 / 819e9 /
         (500 * 12)) / (1.248 / 600) * 100,
    "moe_experts_touched_pct.batch": 58 / 64 * 100,
    # a step's live pages hold 3 layers each; 2,048 B a token a layer
    "decode_attn_groups_roofline_pct.batch":
        ((24 * (538 + 3 * 258) * 16 * 3 * 2048 +
          12 * 2 * 25 * 28 * 128 * 2) / 819e9) / (0.0005 * 12) * 100,
    "window_kv_held_pct.batch": 258 / 538 * 100}


def _read(metric, run):
    return cells.load_function(
        cells.reader_path(ROOT, "layers", metric), "read")(run)


@pytest.mark.parametrize("metric", NEW)
def test_each_reader_of_pr_33_on_a_hand_made_run(metric):
    got = _read(metric, _run(STEPS, TRACE))
    assert got == pytest.approx(WANT[metric], rel=1e-6)
    assert 0 < got < 100
    entry = {m["name"]: m for m in _bench()["per_layer"]}[metric]
    assert entry["moves"] == "out_tok_s" and entry["unit"] == "%"
    assert entry["workloads"] == [CELL]
    assert entry["source"] == ("device_trace" if "roofline" in metric
                               else "program_counter")


@pytest.mark.parametrize("metric", NEW)
def test_a_reader_of_pr_33_that_finds_nothing_reads_nothing(metric):
    """The parent's program exports none of the counters; a `--trace
    0` run has no trace, a CPU trace none of the kernels' names, an
    unknown device no peaks. None, never 0 and never an exception."""
    old = [_totals(sampler_plans=10, generation_tokens=1),
           _totals(sampler_plans=90, generation_tokens=9)]
    assert _read(metric, _run(old, TRACE)) is None
    assert _read(metric, _run([], TRACE)) is None
    if "roofline" in metric:
        assert _read(metric, _run(STEPS)) is None
        assert _read(metric, _run(STEPS, dict(
            TRACE, ops={"fusion f32[8]": [1.0, 10]}))) is None
        run = _run(STEPS, TRACE)
        run.peaks = None
        assert _read(metric, run) is None


def test_the_roofline_counts_from_the_configurations_shapes():
    config = cells.load_cell(CELL, ROOT).config
    moe = cells.load_function(os.path.join(
        ROOT, "perf", "rooflines", "moe_experts.py"), "count")
    # a decode layer call of 24 rows: 144 pairs over 58 experts
    moved, computed = moe(config, 144, 58)
    assert moved == (58 * 3 * 2560 * 768 + 2 * 144 * 2560) * 2
    assert computed == 2 * 3 * 2560 * 768 * 144
    # bound by the experts' bytes, by far: 0.84 ms against 9 us
    assert moved / 819e9 > 50 * computed / 197e12
    # a chunk of 2,048 tokens touches all 64: the two bounds are close,
    # bytes still the longer (0.92 ms against 0.74 ms)
    moved, computed = moe(config, 2048 * 6, 64)
    assert 1.0 < (moved / 819e9) / (computed / 197e12) < 1.5
    groups = cells.load_module(os.path.join(
        ROOT, "perf", "rooflines", "paged_decode_groups.py"))
    assert groups.layers_per_group(config) == 3
    assert groups.layers_per_group(
        cells.load_cell(OLD_CELL, ROOT).config) == 32
    # one row at 8,960 tokens: 560 pages in the full group, 258 in
    # each window group; 131 MB, the issue's sizing
    moved, computed = groups.count(config, 560 + 3 * 258, 0)
    assert moved == (560 + 3 * 258) * 16 * 3 * 2048
    assert 130e6 < moved < 132e6
    assert computed == 4 * 128 * 28 * (560 + 3 * 258) * 16 * 3


# ---- the manifest's new entries ----

def test_the_manifest_gains_the_cell_and_four_metrics_and_loses_nothing():
    bench = _bench()
    assert [c["name"] for c in bench["configs"]] == [
        "mistral-7b-w4a8", "smallthinker-21ba3b-bf16"]
    assert [w["name"] for w in bench["workloads"]] == [OLD_CELL, CELL]
    new = bench["workloads"][-1]
    assert (new["config"], new["traffic"], new["chips"]) == (
        "smallthinker-21ba3b-bf16", "batch-8k", 1)
    assert len(new["why"]) <= 200
    by_name = {m["name"]: m for m in
               bench["end_to_end"] + bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"]][-4:] == list(NEW)
    assert by_name["out_tok_s"]["workloads"] == [OLD_CELL, CELL]
    # every cell reports these two, with no list of their own
    assert "workloads" not in by_name["setup_s"]
    assert "workloads" not in by_name["programs_warmed"]
    # the metric that divides the live bytes by the layer count stays
    # the dense model's; the new cell brings its own
    assert by_name["decode_attn_roofline_pct.batch"]["workloads"] == \
        [OLD_CELL]
    joined = [m["name"] for m in bench["per_layer"]
              if m.get("workloads") == [OLD_CELL, CELL]]
    assert len(joined) == 21 and set(joined) >= {
        "device_idle_pct.batch", "idle_attributed_pct.batch",
        "model_flops_pct.batch", "kv_used_pct.batch",
        "steps_ahead_pct.batch", "decode_attn_fetch_live_pct.batch",
        "preemptions.batch", "compiles_in_window.batch"}
    reported = {m["name"] for m in cells.load_cell(CELL, ROOT).per_layer}
    assert reported == set(joined) | set(NEW) | {"programs_warmed"}
    for name in NEW:
        assert os.path.isfile(cells.reader_path(ROOT, "layers", name))
