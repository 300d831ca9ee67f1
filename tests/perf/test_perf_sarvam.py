"""Tests of what PR 52 adds to the benchmark as new files and entries:
the configuration `sarvam-105b-bf16` (the catalog row cut to one
chip's share of an 8-way expert-parallel stage), its two parameter
counts against the reference's tree, the reference's stages and both
controls through the harness's own child at a toy size, the traffic
`doc-8k`, the three new per-layer readers and the two older ones the
cell joins on hand-made runs of the new cell, the roofline counts by
hand, and the manifest's new entries. No chip."""
import io
import json
import math
import os
import sys

import numpy as np
import pytest

import jax

from perf import cells, loops, reference_child, weights
from perf import run as perf_run

ROOT = cells.ROOT
CELL = "sarvam-105b-bf16.doc-8k"
OLD_CELLS = ["mistral-7b-w4a8.batch", "smallthinker-21ba3b-bf16.batch-8k",
             "phi-4-mini-flash-bf16.reason-2k", "jamba2-3b-bf16.reason-512",
             "laguna-s-2.1-bf16.agent-4k", "evabyte-6.5b-bf16.doc-5k"]
NEW = ("decode_attn_latent_roofline_pct.batch",
       "mla_prefix_expand_ratio.batch", "mla_cache_read_share_pct.batch")
JOINED = ("moe_experts_touched_pct.batch", "moe_held_roofline_pct.batch")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NOT_PUBLISHED = {"architectures", "torch_dtype", "perf",
                 "num_routed_experts", "first_held_expert",
                 "mlp_layer_types"}
ref = cells.load_module(os.path.join(ROOT, "perf", "references",
                                     "sarvam_mla.py"))


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _config():
    return cells.load_cell(CELL, ROOT).config


# ---- the configuration and the cell ----

@pytest.mark.skipif(not os.path.isfile(CATALOG), reason="no catalog here")
def test_the_configuration_is_the_catalog_row_cut_as_written():
    config, perf = _config(), _config()["perf"]
    with open(CATALOG) as f:
        (row,) = [r for r in map(json.loads, f)
                  if r["name"] == "sarvam-105b"]
    published = row["config"]
    assert row["source_url"] == perf["source"]
    assert set(config) == set(published) | NOT_PUBLISHED
    cut = {"num_hidden_layers": (5, 32), "num_experts": (16, 128),
           "vocab_size": (32768, 262144)}
    for key, value in published.items():
        if key in cut:
            assert (config[key], value) == cut[key]
        else:
            assert config[key] == value, key
    # no width is cut
    assert perf["reduced"] == list(cut)
    assert not any(k.endswith(("_dim", "_rank", "_size")) and
                   k != "vocab_size" for k in perf["reduced"])
    entry = {c["name"]: c for c in _bench()["configs"]}["sarvam-105b-bf16"]
    assert entry["reduced"] == perf["reduced"]
    assert entry["source"] == perf["source"]
    assert sorted(perf["share_keys"]) == sorted(
        NOT_PUBLISHED - {"architectures", "torch_dtype", "perf"})
    assert (config["num_routed_experts"], config["first_held_expert"]) == \
        (128, 0)
    assert config["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    # items (a) to (f), each with its reason
    assert [a[:3] for a in perf["assumed"]] == [f"({c})" for c in "abcdef"]
    for said in ("Eight chips", "experts 0-15", "rows 0-32,767",
                 "2,656,353,280", "5.31 GB", "6,400 B a token",
                 "204,800 B"):
        assert said.lower() in perf["deployment"].lower(), said
    assert perf["engine_args"] == ["--max-model-len", "9216",
                                   "--max-num-seqs", "64"]
    assert perf["env"] == {"APHRODITE_SPEC": "0"}
    assert perf["kernel_families"] == [
        "decode_attention", "kv_write", "prefill_attention",
        "expert_matmul"]
    assert perf["matmul_peak"] == "bf16_flops_per_s"
    assert perf["precision"] == \
        "bfloat16 weights, activations and latent cache"
    assert (perf["reference"], perf["reference_replies"]) == \
        ("sarvam_mla", 2)
    assert sorted(perf["controls"]) == ["act8", "kv8"]
    assert perf["controls"]["kv8"]["kv"] == "float8_e5m2"
    assert "latent" in perf["controls"]["kv8"]["why"]
    assert perf["controls"]["act8"]["act_bits"] == 8
    limits = perf["reference_tolerances"]
    assert set(limits) == {"gap_threshold", "gap_mean", "gap_share",
                           "gap_worst", "why"}
    assert "PLACEHOLDER" not in limits["why"] and len(limits["why"]) > 200


def test_the_parameters_to_the_parameter():
    """2,656,353,280 held, counted from the reference's tree (which
    `perf/serve_child.py` holds to the program's own), and what one
    token multiplies (`perf.parameters`, the routed term an
    expectation of one held pair) by the configuration's widths."""
    config = _config()
    tree = ref.tree(config)
    held = sum(math.prod(shape) for bucket in tree.values()
               for shape, _, _ in bucket.values())
    # (the selection bias, 128 a layer, is in the count)
    attention = 4096 * 64 * 192 + 4096 * 576 + 512 * 64 * 256 + \
        64 * 128 * 4096
    assert attention == 94_633_984
    expert = 3 * 4096 * 2048
    layer0 = attention + 512 + 2 * 4096 + 3 * 4096 * 16384
    sparse = attention + 512 + 2 * 4096 + 4096 * 128 + 128 + 17 * expert
    assert (layer0, sparse) == (295_969_280, 522_986_112)
    assert layer0 + 4 * sparse + 2 * 32768 * 4096 + 4096 == 2_656_353_280
    assert held == 2_656_353_280
    assert config["perf"]["parameters"] == \
        5 * attention + 201_326_592 + \
        4 * (524_288 + expert + 1 * expert) + 134_217_728 == 1_012_137_984
    for said in ("94,633,984", "201,326,592", "134,217,728",
                 "8 x 16/128 = 1"):
        assert said in config["perf"]["parameters_why"], said


def test_the_program_serves_the_references_tree():
    """The tree `perf/serve_child.py` makes the weights from is the
    program's own, name for name, shape for shape, type for type."""
    import jax.numpy as jnp
    from aphrodite_tpu.modeling.models.sarvam_mla import (
        SarvamMLAForCausalLM)
    from aphrodite_tpu.transformers_utils.configs import SarvamMLAConfig
    config = _config()
    hf = SarvamMLAConfig(**{k: v for k, v in config.items() if k not in (
        "perf", "architectures", "model_type", "torch_dtype")})
    model = SarvamMLAForCausalLM(hf, jnp.bfloat16, max_model_len=9216)
    have = {b: {n: (tuple(a.shape), a.dtype.name)
                for n, a in leaves.items()}
            for b, leaves in jax.eval_shape(model.init_params).items()}
    want = {b: {n: (tuple(spec[0]), spec[1]) for n, spec in leaves.items()}
            for b, leaves in ref.tree(config).items()}
    assert have == want
    made = {b for _, buckets in ref.stages(config)
            for b in buckets.values()}
    assert made == set(want)


def test_the_traffic_is_8k_prompts_from_64_callers():
    cell = cells.load_cell(CELL, ROOT)
    traffic = cell.traffic
    assert traffic["generator"] == "stratified"
    assert traffic["loop"] == dict(kind="closed", clients=64,
                                   ramp_groups=[4], journal_callers=1)
    shapes = cell.generator(traffic["params"], 2**31 + 5, 0, 64, None,
                            cell.config["vocab_size"])
    assert {len(s["prompt"]) for s in shapes} == {8192}
    outs = sorted(s["max_tokens"] for s in shapes)
    assert 512 <= outs[0] < 530 and 1010 < outs[-1] <= 1024
    assert all(3 <= t < 32768 for s in shapes for t in s["prompt"])
    assert not any(s["stream"] for s in shapes)
    assert {s["sampling"]["temperature"] for s in shapes} == {0.0}
    # contexts of 8,193-9,216 tokens: 513-576 pages, one table width
    assert 8192 + outs[-1] <= 9216 == int(
        cell.config["perf"]["engine_args"][1])
    canary = traffic["canary"]
    assert canary["prompt_lens"] == [8080, 8128, 8176]
    assert canary["max_tokens"] == 16
    assert (traffic["warm_seconds"], traffic["request_timeout_s"],
            traffic["warm_timeout_s"]) == (10.0, 120.0, 600.0)
    # a group of four queues 32,768 prompt tokens
    assert 4 * 8192 == 32768 < 8 * 9216


# ---- the reference through the harness's child ----

def _tiny():
    return dict(
        architectures=["SarvamMLAForCausalLM"], model_type="sarvam_mla",
        vocab_size=320, hidden_size=128, intermediate_size=256,
        moe_intermediate_size=64, num_hidden_layers=3,
        num_attention_heads=4, kv_lora_rank=64, qk_nope_head_dim=32,
        qk_rope_head_dim=16, q_head_dim=48, v_head_dim=32, head_dim=80,
        max_position_embeddings=512, rms_norm_eps=1e-6, rope_theta=10000,
        rope_scaling={"type": "deepseek_yarn", "factor": 8,
                      "beta_fast": 32, "beta_slow": 1, "mscale": 1,
                      "mscale_all_dim": 1,
                      "original_max_position_embeddings": 32},
        first_k_dense_replace=1, num_experts=4, num_routed_experts=16,
        first_held_expert=0, num_experts_per_tok=4, num_shared_experts=1,
        moe_router_enable_expert_bias=True, routed_scaling_factor=2.5,
        torch_dtype="float32",
        perf=dict(reference="sarvam_mla", controls=dict(
            kv8=dict(kv="float8_e5m2"), act8=dict(act_bits=8))))


def test_the_stages_and_both_controls_through_the_harness_child(
        tmp_path, monkeypatch):
    """`perf/reference_child.py` as the harness starts it, on the CPU at
    a toy size: every stage maps the stream to itself and reports its
    share; a greedy continuation of the reference itself reads no gap
    at all; a control's gaps are none or more, and `kv8` (two bits of
    mantissa in the latent and the rotary key) reads some."""
    config = _tiny()
    params = weights.whole(ref.tree(config), ref.stages(config), 5)
    ids = np.random.default_rng(0).integers(3, 320, 16).tolist()
    steps = 112

    @jax.jit
    def forward(x):
        for fn, buckets in ref.stages(config):
            w = {local: params[b] for local, b in buckets.items()}
            x = getattr(ref, fn)(config, w, x, ref.Precision())
        return x

    with jax.default_matmul_precision("highest"):
        for _ in range(steps):
            x = np.zeros((1, 128), np.int32)
            x[0, :len(ids)] = ids
            ids.append(int(np.asarray(forward(x)[0, len(ids) - 1]).argmax()))
    job = dict(root=ROOT, config=config, name="sarvam_mla", seed=5,
               sequences=[dict(prompt=ids[:16], reply=ids[16:])], rows=2,
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
    assert 0.2 < out["layer_share"] < 3 and len(out["stage_s"]) == 1 + 3
    assert gaps("served").max() <= 1e-5
    for control in ("kv8", "act8"):
        assert (gaps(control) >= -1e-5).all()
    assert gaps("kv8").max() > 1e-3


def test_kv8_rounds_what_the_cache_holds_and_nothing_else():
    """`Precision.kv` meets the normed latent and the rotated key, the
    two things a token leaves in the cache: rounding them moves a
    layer; the queries, which no cache holds, are not rounded."""
    import jax.numpy as jnp
    config = _tiny()
    params = weights.whole(ref.tree(config), ref.stages(config), 7)
    w = {b: params[f"model.layers.1.{b}"] for b in ref.SPARSE_BUCKETS}
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 24, 128))
    seen = []

    def kv(a):
        seen.append(a.shape)
        return a.astype(jnp.float8_e5m2).astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        sound = ref.attend(config, w, x, ref.Precision())
        lowered = ref.attend(config, w, x, ref.Precision(kv=kv))
    assert sorted(seen) == [(1, 24, 1, 16), (1, 24, 64)]
    assert float(jnp.abs(lowered - sound).max()) > 1e-2


# ---- the readers on hand-made runs of the new cell ----

def _run(samples, trace=None, seconds=10.0, cell=CELL, root=ROOT):
    window = loops.Window(t0=100.0, replies=[], t_end=0.0,
                          seconds=seconds * max(1, len(samples) - 1))
    loaded = cells.load_cell(cell, ROOT)
    loaded.root = root
    run = perf_run.Run(
        cell=loaded, window=window, t_start=0.0,
        samples=[(100.0 + i * seconds, s) for i, s in enumerate(samples)],
        steady_until=100.0 + window.seconds, log_setup="",
        log_window="", faults=[], trace=trace)
    run.peaks = cells.load_peaks("TPU v5 lite")
    if trace is not None:
        # (the edges as far apart as the samples, so that one set of
        # totals serves both kinds of rate)
        run.trace_edges = ((200.0, samples[0]),
                           (200.0 + window.seconds, samples[-1])) \
            if samples else None
    return run


def _totals(gauge=6400.0, **counters):
    out = {f"aphrodite:{k}_total": float(v) for k, v in counters.items()}
    out["aphrodite:kv_cache_bytes_per_token"] = gauge
    return out


#: two readings 10 s apart: 700 decode steps of 64 rows at 8,900
#: tokens a row (557 live pages), 58 of the 64 held experts touched a
#: step; 40 prompts of 8,192 tokens written, 12 of them in four chunks
_DECODE, _ROWS, _CTX = 700, 64, 8900
_PAGES = -(-_CTX // 16)
_PAIRS = _DECODE * _ROWS * 4
STEPS = [
    _totals(decode_attn_steps=900, decode_attn_pages_live=1e6,
            mla_latent_tokens_read=2e8, mla_prefix_tokens_expanded=5e5,
            prompt_tokens=3e6, moe_decode_experts_touched=4e4,
            moe_decode_expert_slots=5e4, moe_pairs_held=1e6,
            moe_experts_touched=5e4),
    _totals(decode_attn_steps=900 + _DECODE,
            decode_attn_pages_live=1e6 + _DECODE * _ROWS * _PAGES,
            mla_latent_tokens_read=2e8 + _DECODE * _ROWS * _CTX,
            mla_prefix_tokens_expanded=5e5 + 12 * 12288,
            prompt_tokens=3e6 + 40 * 8192,
            moe_decode_experts_touched=4e4 + _DECODE * 58,
            moe_decode_expert_slots=5e4 + _DECODE * 64,
            moe_pairs_held=1e6 + _PAIRS,
            moe_experts_touched=5e4 + _DECODE * 58 + 40 * 64)]
#: the traced 2 s: 130 decode steps of 5 calls, 1.1 ms a call
OPS = {"paged-decode-latent bf16[65,1,64,512] tpu_custom_call":
       [0.715, 650],
       "ragged-dot-aligned-gate-up bf16[512,2048] tpu_custom_call":
       [0.50, 600],
       "ragged-dot-aligned-down bf16[512,4096] tpu_custom_call":
       [0.30, 600],
       "fusion bf16[64,4096]": [0.3, 5000]}
TRACE = dict(busy_s=1.95, window_s=2.0, device_ops=[], idle_gaps=[],
             ops=OPS)
_EXPERT = 3 * 4096 * 2048
_WEIGHTS = 2 * (5 * 94_633_984 + 201_326_592 +
                4 * (524_288 + _EXPERT) + 58 * _EXPERT + 134_217_728)
_LATENT = _ROWS * _CTX * 6400
WANT = {
    # a call: every live page once (20 KB a page), 64 rows' queries,
    # new rows and outputs; the bytes bind
    "decode_attn_latent_roofline_pct.batch":
        ((_ROWS * _PAGES * 16 * 640 +
          64 * (64 * 640 + 2 * 640 + 64 * 512)) * 2 / 819e9) /
        (0.715 / 650) * 100,
    "mla_prefix_expand_ratio.batch": 12 * 12288 / (40 * 8192),
    "mla_cache_read_share_pct.batch":
        _LATENT / (_LATENT + _WEIGHTS) * 100,
    "moe_experts_touched_pct.batch": 58 / 64 * 100}


def _read(metric, run):
    return cells.load_function(
        cells.reader_path(ROOT, "layers", metric), "read")(run)


@pytest.mark.parametrize("metric", NEW + JOINED[:1])
def test_each_reader_on_a_hand_made_run_of_the_new_cell(metric):
    got = _read(metric, _run(STEPS, TRACE))
    assert got == pytest.approx(WANT[metric], rel=1e-6)
    assert 0 < got < 100
    entry = {m["name"]: m for m in _bench()["per_layer"]}[metric]
    assert entry["moves"] == "out_tok_s" and CELL in entry["workloads"]
    assert entry["unit"] == ("ratio" if "ratio" in metric else "%")
    if metric in NEW:
        assert entry["better"] == ("lower" if "ratio" in metric
                                   else "higher")
    assert entry["source"] == ("device_trace" if "roofline" in metric
                               else "program_counter")


def test_the_held_experts_share_reads_the_new_cell():
    """`moe_held_roofline_pct.batch` (PR 49's reader and count, as they
    are) finds the expert kernels' seconds and Sarvam's widths through
    the keys the configuration states for it."""
    got = _read("moe_held_roofline_pct.batch", _run(STEPS, TRACE))
    held, touched = _PAIRS / 10.0, (_DECODE * 58 + 40 * 64) / 10.0
    moved = (touched * _EXPERT + 2 * held * 4096) * 2
    least = max(moved / 819e9, 2.0 * _EXPERT * held / 197e12)
    assert got == pytest.approx(least / (0.80 / 2.0) * 100, rel=1e-6)
    assert 0 < got < 100


@pytest.mark.parametrize("metric", NEW)
def test_a_new_reader_that_finds_nothing_reads_nothing(metric, tmp_path):
    """The parent's program exports none of the new counters and states
    no name for the latent calls; a `--trace 0` run has no trace, an
    unknown device no peaks, another configuration no `kv_lora_rank`.
    None, never 0 and never an exception."""
    assert _read(metric, _run([], TRACE)) is None
    parent = [{k: v for k, v in s.items()
               if "mla_" not in k and "bytes_per_token" not in k}
              for s in STEPS]
    assert _read(metric, _run(parent, TRACE)) is None
    if "roofline" in metric:
        assert _read(metric, _run(STEPS)) is None
        assert _read(metric, _run(STEPS, dict(
            TRACE, ops={"fusion f32[8]": [1.0, 10]}))) is None
        run = _run(STEPS, TRACE)
        run.peaks = None
        assert _read(metric, run) is None
        # a program whose kernel file states no such constant (the
        # parent's), or that has no such file
        kernels = tmp_path / "aphrodite_tpu" / "ops" / "pallas"
        kernels.mkdir(parents=True)
        assert _read(metric, _run(STEPS, TRACE, root=str(tmp_path))) is None
        (kernels / "paged_attention.py").write_text("X = 1\n")
        assert _read(metric, _run(STEPS, TRACE, root=str(tmp_path))) is None
        (kernels / "paged_attention.py").write_text(
            'LATENT_DEVICE_OP_PREFIXES = ("paged-decode",)\n')
        os.symlink(os.path.join(ROOT, "perf"), tmp_path / "perf")
        assert _read(metric, _run(STEPS, TRACE, root=str(tmp_path))) == \
            pytest.approx(WANT[metric])
    if "ratio" not in metric:
        for cell in OLD_CELLS[:2]:
            assert _read(metric, _run(STEPS, TRACE, cell=cell)) is None


def test_a_prompt_written_whole_reads_zero_and_not_nothing():
    still = [dict(s, **{
        "aphrodite:mla_prefix_tokens_expanded_total": 5e5}) for s in STEPS]
    assert _read("mla_prefix_expand_ratio.batch", _run(still)) == 0.0
    # four chunks of 2,048 read 2,048 + 4,096 + 6,144 back: 1.5
    chunked = [STEPS[0], dict(STEPS[1], **{
        "aphrodite:mla_prefix_tokens_expanded_total": 5e5 + 40 * 12288})]
    assert _read("mla_prefix_expand_ratio.batch",
                 _run(chunked)) == pytest.approx(1.5)


def test_the_roofline_counts_by_hand():
    config = _config()
    module = cells.load_module(os.path.join(
        ROOT, "perf", "rooflines", "paged_decode_latent.py"))
    assert module.lanes(config) == 640
    # 64 rows of 557 live pages (8,900 tokens), 64 rows a call
    moved, computed = module.count(config, 64 * 557, 64 * 8900, 64)
    assert moved == (64 * 557 * 16 * 640 +
                     64 * (64 * 640 + 2 * 640 + 64 * 512)) * 2
    assert computed == 2.0 * 64 * 64 * 8900 * (640 + 512)
    # ISSUE 52's arithmetic: 3.7 GB of latent pages a decode step in
    # five calls, bound by bytes twice over (115 operations a byte)
    assert 3.6e9 < 5 * moved < 3.8e9
    assert 1.8 < (moved / 819e9) / (computed / 197e12) < 2.4
    # the pages are counted ONCE: K/V pairs of the same lanes would be
    # twice the bytes
    assert module.count(config, 64 * 557, 0, 0)[0] == \
        64 * 557 * 16 * 640 * 2
    # a decode step's weights: 5.0 GB with every held expert touched
    weights_all = module.step_weight_bytes(config, 64)
    assert weights_all == 2 * (
        5 * 94_633_984 + 201_326_592 + 4 * (524_288 + _EXPERT) +
        64 * _EXPERT + 134_217_728)
    assert 4.9e9 < weights_all < 5.2e9
    assert module.step_weight_bytes(config, 0) == \
        weights_all - 2 * 64 * _EXPERT


# ---- the manifest's new entries ----

def test_the_manifest_gains_a_configuration_a_cell_and_three_metrics():
    bench = _bench()
    assert [c["name"] for c in bench["configs"]][-1] == "sarvam-105b-bf16"
    names = [w["name"] for w in bench["workloads"]]
    assert names == OLD_CELLS + [CELL]
    new = bench["workloads"][-1]
    assert (new["config"], new["traffic"], new["chips"]) == (
        "sarvam-105b-bf16", "doc-8k", 1)
    for said in ("64 callers", "8,192", "latent pages", "4 pairs",
                 "an eighth"):
        assert said in new["why"], said
    # every `why` and `source` of the file, old and new
    for entry in bench["configs"] + bench["workloads"]:
        assert 0 < len(entry["why"]) <= 200, entry["name"]
    assert all(len(c["source"]) <= 200 for c in bench["configs"])
    by_name = {m["name"]: m for m in
               bench["end_to_end"] + bench["per_layer"]}
    listed = [m["name"] for m in bench["per_layer"]]
    # appended: an entry put in the middle of a list reads as a change
    assert tuple(listed[-3:]) == NEW
    for name in NEW:
        assert set(by_name[name]) == {"name", "unit", "better", "source",
                                      "layer", "moves", "workloads"}
        assert by_name[name]["workloads"] == [CELL]
        assert os.path.isfile(cells.reader_path(ROOT, "layers", name))
    assert by_name[NEW[0]]["layer"] == \
        by_name["decode_attn_roofline_pct.batch"]["layer"]
    assert by_name[NEW[1]]["layer"] == by_name["model_mfu_pct.batch"]["layer"]
    assert by_name[NEW[2]]["layer"] == by_name["kv_used_pct.batch"]["layer"]
    assert by_name["out_tok_s"]["workloads"] == OLD_CELLS + [CELL]
    assert "workloads" not in by_name["setup_s"]
    assert "workloads" not in by_name["programs_warmed"]
    # the 28 metrics every cell reports, and the two it joins
    every = [m["name"] for m in bench["per_layer"]
             if m.get("workloads") == OLD_CELLS + [CELL]]
    assert len(every) == 28
    assert by_name[JOINED[0]]["workloads"] == [
        OLD_CELLS[1], OLD_CELLS[4], CELL]
    assert by_name[JOINED[1]]["workloads"] == [OLD_CELLS[4], CELL]
    # the other kernels' shares stay the older cells'
    for name, metric in by_name.items():
        if ("roofline" in name and name not in (NEW[0], JOINED[1])) or \
                name.startswith(("ssm_", "eva_", "window_")):
            assert CELL not in metric["workloads"], name
    reported = {m["name"] for m in cells.load_cell(CELL, ROOT).per_layer}
    assert reported == set(every) | set(JOINED) | set(NEW) | {
        "programs_warmed"}
    assert len(reported) == 34
    # nothing the older cells report has changed under them: without
    # the new cell the manifest is the parent's, entry for entry
    from conftest import without_cells
    before = without_cells(bench, cells=(CELL,))
    assert [w["name"] for w in before["workloads"]] == OLD_CELLS
    assert len(before["per_layer"]) == len(bench["per_layer"]) - 3
    assert len(before["configs"]) == len(bench["configs"]) - 1
    for cell in OLD_CELLS:
        assert not {m["name"] for m in cells.load_cell(cell, ROOT).per_layer
                    } & set(NEW)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        assert f.read().endswith("}\n")
