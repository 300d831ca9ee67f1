"""Tests of what PR 56 adds to the benchmark as new files and entries:
the configuration `kimi-linear-48b-a3b-bf16` (the catalog row cut to
one chip's share of a four-way expert-parallel stage), its two
parameter counts against the reference's tree, the reference's stages
and both controls through the harness's own child at a toy size, the
traffic `reason-1k`, the four new per-layer readers and the older ones
the cell joins on hand-made runs of the new cell, the roofline counts
by hand, and the manifest's new entries. No chip."""
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
CELL = "kimi-linear-48b-a3b-bf16.reason-1k"
OLD_CELLS = ["mistral-7b-w4a8.batch", "smallthinker-21ba3b-bf16.batch-8k",
             "phi-4-mini-flash-bf16.reason-2k", "jamba2-3b-bf16.reason-512",
             "laguna-s-2.1-bf16.agent-4k", "evabyte-6.5b-bf16.doc-5k",
             "sarvam-105b-bf16.doc-8k"]
NEW = ("kda_update_roofline_pct.batch", "kda_chunk_roofline_pct.batch",
       "kda_state_share_pct.batch",
       "decode_attn_latent_nope_roofline_pct.batch")
JOINED = ("moe_experts_touched_pct.batch", "moe_held_roofline_pct.batch",
          "ssm_slots_used_pct.batch", "ssm_slot_waits.batch",
          "mla_prefix_expand_ratio.batch")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NOT_PUBLISHED = {"architectures", "torch_dtype", "perf",
                 "num_routed_experts", "first_held_expert",
                 "mlp_layer_types", "max_position_embeddings"}
ref = cells.load_module(os.path.join(ROOT, "perf", "references",
                                     "kimi_linear.py"))
kda = cells.load_module(os.path.join(ROOT, "perf", "rooflines", "kda.py"))


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _config():
    return cells.load_cell(CELL, ROOT).config


def _hf(config):
    from aphrodite_tpu.transformers_utils.configs import KimiLinearConfig
    return KimiLinearConfig(**{k: v for k, v in config.items() if k not in (
        "perf", "architectures", "model_type", "torch_dtype")})


# ---- the configuration and the cell ----

@pytest.mark.skipif(not os.path.isfile(CATALOG), reason="no catalog here")
def test_the_configuration_is_the_catalog_row_cut_as_written():
    config, perf = _config(), _config()["perf"]
    with open(CATALOG) as f:
        (row,) = [r for r in map(json.loads, f)
                  if r["name"] == "Kimi-Linear-48B-A3B-Instruct"]
    published = row["config"]
    assert row["source_url"] == perf["source"]
    assert set(config) == set(published) | NOT_PUBLISHED
    cut = {"num_hidden_layers": (8, 27), "num_experts": (64, 256),
           "vocab_size": (40960, 163840)}
    for key, value in published.items():
        if key in cut:
            assert (config[key], value) == cut[key]
        else:
            assert config[key] == value, key
    # no width is cut, and the nested group stands whole
    assert perf["reduced"] == list(cut)
    assert config["linear_attn_config"] == published["linear_attn_config"]
    assert config["max_position_embeddings"] == \
        published["model_max_length"] == 1048576
    entry = {c["name"]: c for c in _bench()["configs"]}[
        "kimi-linear-48b-a3b-bf16"]
    assert entry["reduced"] == perf["reduced"]
    assert entry["source"] == perf["source"]
    assert sorted(perf["share_keys"]) == sorted(
        NOT_PUBLISHED - {"architectures", "torch_dtype", "perf"})
    assert (config["num_routed_experts"], config["first_held_expert"]) == \
        (256, 0)
    assert config["mlp_layer_types"] == ["dense"] + ["sparse"] * 7
    assert ref.layer_kinds(config) == ["kda", "kda", "kda", "mla"] * 2
    assert [a[:3] for a in perf["assumed"]] == [f"({c})" for c in "abcd"]
    for said in ("four pipeline stages", "four ways", "experts 0-63",
                 "rows 0-40,959", "3,772,368,832", "7.54 GB",
                 "13,025,280 B", "2,560 B a token", "6 : 2"):
        assert said.lower() in perf["deployment"].lower(), said
    assert perf["engine_args"][:2] == ["--max-model-len", "2048"]
    assert perf["engine_args"][2] == "--max-num-seqs"
    # (the admission limit, 8 x max_num_batched_tokens = 16,384 queued
    # prompt tokens by default, stated over a group of callers' 24,576)
    assert perf["env"] == {"APHRODITE_SPEC": "0",
                           "APHRODITE_MAX_WAITING_TOKENS": "32768"}
    assert perf["kernel_families"] == [
        "decode_attention", "kv_write", "prefill_attention",
        "expert_matmul", "kda_chunk", "kda_update"]
    assert perf["matmul_peak"] == "bf16_flops_per_s"
    for said in ("bfloat16 weights", "latent pages", "state float32",
                 "tail bfloat16"):
        assert said in perf["precision"], said
    assert (perf["reference"], perf["reference_replies"]) == \
        ("kimi_linear", 2)
    assert sorted(perf["controls"]) == ["act8", "kv8"]
    assert perf["controls"]["kv8"]["kv"] == "float8_e5m2"
    assert "latent" in perf["controls"]["kv8"]["why"]
    assert perf["controls"]["act8"]["act_bits"] == 8
    limits = perf["reference_tolerances"]
    assert set(limits) == {"gap_threshold", "gap_mean", "gap_share",
                           "gap_worst", "why"}
    assert "PLACEHOLDER" not in limits["why"] and len(limits["why"]) > 200


def test_the_parameters_to_the_parameter():
    """3,772,368,832 held, counted from the reference's tree (which
    `perf/serve_child.py` holds to the program's own), and what one
    token multiplies (`perf.parameters`, the routed term an
    expectation of two held pairs) by the configuration's widths."""
    config = _config()
    tree = ref.tree(config)
    held = sum(math.prod(shape) for bucket in tree.values()
               for shape, _, _ in bucket.values())
    width = 32 * 128
    kda_matmuls = 2304 * 3 * width + 4 * 3 * width + 2304 * (256 + 32) + \
        2 * 128 * width + width * 2304
    kda_mixer = kda_matmuls + 32 + width + 128      # A_log, dt_bias, gain
    mla_matmuls = 2304 * 32 * 192 + 2304 * 576 + 512 * 32 * 256 + \
        32 * 128 * 2304
    assert (kda_mixer, mla_matmuls + 512) == (39_514_272, 29_114_880)
    expert = 3 * 2304 * 1024
    dense = 3 * 2304 * 9216
    assert (expert, dense) == (7_077_888, 63_700_992)
    assert 6 * kda_mixer + 2 * (mla_matmuls + 512) + dense + \
        7 * 65 * expert + 7 * (2304 * 256 + 256) + 17 * 2304 + \
        2 * 40960 * 2304 == 3_772_368_832 == held
    assert config["perf"]["parameters"] == \
        6 * kda_matmuls + 2 * mla_matmuls + dense + \
        7 * (2304 * 256 + expert + 2 * expert) + 40960 * 2304 == 606_126_080
    for said in ("39,510,016", "29,114,368", "63,700,992", "94,371,840",
                 "8 x 64/256 = 2"):
        assert said in config["perf"]["parameters_why"], said


def test_the_program_serves_the_references_tree():
    """The tree `perf/serve_child.py` makes the weights from is the
    program's own, name for name, shape for shape, type for type."""
    import jax.numpy as jnp
    from aphrodite_tpu.modeling.models.kimi_linear import (
        KimiLinearForCausalLM)
    config = _config()
    model = KimiLinearForCausalLM(_hf(config), jnp.bfloat16)
    have = {b: {n: (tuple(a.shape), a.dtype.name)
                for n, a in leaves.items()}
            for b, leaves in jax.eval_shape(model.init_params).items()}
    want = {b: {n: (tuple(spec[0]), spec[1]) for n, spec in leaves.items()}
            for b, leaves in ref.tree(config).items()}
    assert have == want
    made = {b for _, buckets in ref.stages(config)
            for b in buckets.values()}
    assert made == set(want)
    assert [fn for fn, _ in ref.stages(config)] == [
        "embed", "layer_kda_dense", "layer_kda_sparse", "layer_kda_sparse",
        "layer_mla_sparse", "layer_kda_sparse", "layer_kda_sparse",
        "layer_kda_sparse", "layer_mla_sparse", "logits"]


def test_the_traffic_is_1k_prompts_from_as_many_callers_as_slots():
    cell = cells.load_cell(CELL, ROOT)
    traffic = cell.traffic
    assert traffic["generator"] == "stratified"
    clients = traffic["loop"]["clients"]
    # ISSUE 56's rule: 192, or the next decode bucket down
    assert clients in (192, 128)
    assert traffic["loop"] == dict(kind="closed", clients=clients,
                                   ramp_groups=[clients // 8],
                                   journal_callers=1)
    assert cell.config["perf"]["engine_args"][3] == str(clients)
    shapes = cell.generator(traffic["params"], 2**31 + 5, 0, clients, None,
                            cell.config["vocab_size"])
    assert {len(s["prompt"]) for s in shapes} == {1024}
    outs = sorted(s["max_tokens"] for s in shapes)
    assert 512 <= outs[0] < 530 and 1010 < outs[-1] <= 1024
    assert all(3 <= t < 40960 for s in shapes for t in s["prompt"])
    assert not any(s["stream"] for s in shapes)
    assert {s["sampling"]["temperature"] for s in shapes} == {0.0}
    # contexts of 1,025-2,048 tokens: 65-128 pages, one table width
    assert 1024 + outs[-1] <= 2048 == int(
        cell.config["perf"]["engine_args"][1])
    canary = traffic["canary"]
    assert canary["prompt_lens"] == [960, 976, 992]
    assert canary["max_tokens"] == 16
    assert (traffic["warm_seconds"], traffic["request_timeout_s"],
            traffic["warm_timeout_s"]) == (10.0, 120.0, 600.0)
    # a group of callers queues less than the server sheds arrivals at
    assert clients // 8 * 1024 < int(
        cell.config["perf"]["env"]["APHRODITE_MAX_WAITING_TOKENS"])


# ---- the reference through the harness's child ----

def _tiny():
    return dict(
        architectures=["KimiLinearForCausalLM"], model_type="kimi_linear",
        vocab_size=320, hidden_size=128, intermediate_size=256,
        moe_intermediate_size=64, num_hidden_layers=4,
        num_attention_heads=4, num_key_value_heads=4, head_dim=32,
        kv_lora_rank=64, qk_nope_head_dim=32, qk_rope_head_dim=16,
        v_head_dim=32, mla_use_nope=True, model_max_length=512,
        max_position_embeddings=512, rms_norm_eps=1e-5,
        linear_attn_config={
            "kda_layers": [1, 2, 3], "full_attn_layers": [4],
            "num_heads": 2, "head_dim": 32, "short_conv_kernel_size": 4},
        first_k_dense_replace=1, num_experts=4, num_routed_experts=16,
        first_held_expert=0, num_experts_per_token=4, num_shared_experts=1,
        routed_scaling_factor=2.446, torch_dtype="float32",
        perf=dict(reference="kimi_linear", controls=dict(
            kv8=dict(kv="float8_e5m2"), act8=dict(act_bits=8))))


def test_the_stages_and_both_controls_through_the_harness_child(
        tmp_path, monkeypatch):
    """`perf/reference_child.py` as the harness starts it, on the CPU at
    a toy size: every stage maps the stream to itself and reports its
    share; a greedy continuation of the reference itself reads no gap
    at all; a control's gaps are none or more, and both read some."""
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
    job = dict(root=ROOT, config=config, name="kimi_linear", seed=5,
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
    assert 0.2 < out["layer_share"] < 3 and len(out["stage_s"]) == 1 + 4
    assert gaps("served").max() <= 1e-5
    for control in ("kv8", "act8"):
        assert (gaps(control) >= -1e-5).all()
        assert gaps(control).max() > 1e-3


def test_the_controls_round_what_they_say_and_nothing_else():
    """`Precision.kv` meets the normed latent and the shared key part of
    an MLA layer, the two things a token leaves in its pages, and
    nothing of a KDA layer (whose state has no control: the harness has
    no such kind); `Precision.act` meets what goes into each of a KDA
    layer's five matmuls."""
    import jax.numpy as jnp
    config = _tiny()
    params = weights.whole(ref.tree(config), ref.stages(config), 7)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 24, 128))
    seen = []

    def note(a):
        seen.append(a.shape)
        return a.astype(jnp.float8_e5m2).astype(jnp.float32)
    w = {b: params[f"model.layers.3.{b}"] for b in ref.MIXER_BUCKETS["mla"]}
    with jax.default_matmul_precision("highest"):
        sound = ref.mla(config, w, x, ref.Precision())
        lowered = ref.mla(config, w, x, ref.Precision(kv=note))
    assert sorted(seen) == [(1, 24, 1, 16), (1, 24, 64)]
    assert float(jnp.abs(lowered - sound).max()) > 1e-2
    del seen[:]
    w = {b: params[f"model.layers.1.{b}"] for b in ref.MIXER_BUCKETS["kda"]}
    with jax.default_matmul_precision("highest"):
        sound = ref.kda(config, w, x, ref.Precision())
        assert np.array_equal(ref.kda(config, w, x, ref.Precision(kv=note)),
                              sound)
        assert not seen
        lowered = ref.kda(config, w, x, ref.Precision(act=note))
    # the normed input (into W_qkv and W_fa | W_ga | W_b), f_a, g_a and
    # the gated output
    assert sorted(seen) == [(1, 24, 32), (1, 24, 32), (1, 24, 64),
                            (1, 24, 128)]
    assert float(jnp.abs(lowered - sound).max()) > 1e-2


def test_the_references_recurrence_against_float64():
    """`delta_rule` (a `lax.scan` over tokens) against the same
    recurrence in numpy's float64, one head."""
    import jax.numpy as jnp
    rng = np.random.default_rng(3)
    t, d = 40, 16
    q, k, v = (rng.standard_normal((t, d)) for _ in range(3))
    g = -rng.uniform(0.001, 0.5, (t, d))
    b = rng.uniform(0, 1, t)
    with jax.default_matmul_precision("highest"):
        got = ref.delta_rule(*(jnp.asarray(a[None, :, None], jnp.float32)
                               for a in (q, k, v, g)),
                             jnp.asarray(b[None, :, None], jnp.float32))
    s, want = np.zeros((d, d)), []
    for i in range(t):
        s = np.exp(g[i])[:, None] * s
        s = s + np.outer(k[i], b[i] * (v[i] - s.T @ k[i]))
        want.append(s.T @ q[i])
    np.testing.assert_allclose(np.asarray(got)[0, :, 0], np.stack(want),
                               rtol=2e-4, atol=2e-4)


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
        run.trace_edges = ((200.0, samples[0]),
                           (200.0 + window.seconds, samples[-1])) \
            if samples else None
    return run


def _totals(**counters):
    return {f"aphrodite:{k}_total": float(v) for k, v in counters.items()}


#: two readings 10 s apart: 300 decode steps of 192 rows at 1,536
#: tokens a row (96 live pages), 440 of the 448 held experts touched a
#: step; 60 prompt steps of one 1,024-token prompt each
_DECODE, _ROWS, _CTX, _PROMPTS = 300, 192, 1536, 60
_PAGES = _CTX // 16
STEPS = [
    _totals(decode_attn_steps=900, decode_attn_pages_live=1e6,
            mla_latent_tokens_read=2e8, mla_prefix_tokens_expanded=0,
            prompt_tokens=3e6, kda_decode_rows=5e4, kda_prompt_tokens=3e6,
            kda_prompt_chunks=5e4, prefill_attn_steps=3000,
            ssm_state_resets=3000, moe_decode_experts_touched=4e4,
            moe_decode_expert_slots=5e4, moe_pairs_held=1e6,
            moe_experts_touched=5e4),
    _totals(decode_attn_steps=900 + _DECODE,
            decode_attn_pages_live=1e6 + _DECODE * _ROWS * _PAGES,
            mla_latent_tokens_read=2e8 + _DECODE * _ROWS * _CTX,
            mla_prefix_tokens_expanded=0,
            prompt_tokens=3e6 + _PROMPTS * 1024,
            kda_decode_rows=5e4 + _DECODE * _ROWS,
            kda_prompt_tokens=3e6 + _PROMPTS * 1024,
            kda_prompt_chunks=5e4 + _PROMPTS * 16,
            prefill_attn_steps=3000 + _PROMPTS,
            ssm_state_resets=3000 + _PROMPTS,
            moe_decode_experts_touched=4e4 + _DECODE * 440,
            moe_decode_expert_slots=5e4 + _DECODE * 448,
            moe_pairs_held=1e6 + _DECODE * _ROWS * 2 * 7,
            moe_experts_touched=5e4 + _DECODE * 440 + _PROMPTS * 448)]
#: the traced 2 s: 60 decode steps of 6 + 2 calls, 12 prompt steps
OPS = {"kda-update f32[24,8,4096] tpu_custom_call": [0.72, 360],
       "kda-chunk f32[1,1024,4096] tpu_custom_call": [0.09, 72],
       "paged-decode-latent bf16[193,1,32,512] tpu_custom_call":
       [0.12, 120],
       "ragged-dot-aligned-gate-up bf16[512,1024] tpu_custom_call":
       [0.40, 500],
       "ragged-dot-aligned-down bf16[512,2304] tpu_custom_call":
       [0.25, 500],
       "fusion bf16[192,2304]": [0.3, 5000]}
TRACE = dict(busy_s=1.95, window_s=2.0, device_ops=[], idle_gaps=[],
             ops=OPS)
_STATE = 32 * 128 * 128 * 4
_ROW = 2 * _STATE + 2 * 3 * 12288 * 2 + 12288 * 2 + (5 * 4096 + 32) * 4
_EXPERT = 3 * 2304 * 1024
_KDA = 2304 * 12288 + 4 * 12288 + 2304 * 288 + 2 * 128 * 4096 + 4096 * 2304
_MLA = 2304 * 32 * 192 + 2304 * 576 + 512 * 32 * 256 + 4096 * 2304
_WEIGHTS = 2 * (6 * _KDA + 2 * _MLA + 3 * 2304 * 9216 +
                7 * (2304 * 256 + _EXPERT) + 440 * _EXPERT + 40960 * 2304)
_STATE_STEP = 6 * _ROWS * 2 * _STATE
_LATENT = _ROWS * _CTX * 2 * 640 * 2
_PRODUCTS = 4 * 64 * 64 * 128 + 11 * 64 ** 3 + 3 * 64 * 128 * 128
WANT = {
    # a call: 192 rows' state and tail both ways and their inputs; the
    # bytes bind
    "kda_update_roofline_pct.batch":
        (_ROWS * _ROW / 819e9) / (0.72 / 360) * 100,
    # a call: 1,024 tokens' rows in and out and one row's state both
    # ways; the bytes bind over the 16 x 32 chunk-heads' products
    "kda_chunk_roofline_pct.batch":
        ((1024 * (5 * 4096 + 32) * 4 + 2 * _STATE) / 819e9) /
        (0.09 / 72) * 100,
    "kda_state_share_pct.batch":
        _STATE_STEP / (_STATE_STEP + _WEIGHTS + _LATENT) * 100,
    "decode_attn_latent_nope_roofline_pct.batch":
        ((_ROWS * _PAGES * 16 * 640 +
          192 * (32 * 640 + 2 * 640 + 32 * 512)) * 2 / 819e9) /
        (0.12 / 120) * 100,
    "moe_experts_touched_pct.batch": 440 / 448 * 100,
    "mla_prefix_expand_ratio.batch": 0.0}


def _read(metric, run):
    return cells.load_function(
        cells.reader_path(ROOT, "layers", metric), "read")(run)


@pytest.mark.parametrize("metric", NEW + (JOINED[0], JOINED[4]))
def test_each_reader_on_a_hand_made_run_of_the_new_cell(metric):
    got = _read(metric, _run(STEPS, TRACE))
    assert got == pytest.approx(WANT[metric], rel=1e-6)
    assert 0 <= got < 100
    entry = {m["name"]: m for m in _bench()["per_layer"]}[metric]
    assert entry["moves"] == "out_tok_s" and CELL in entry["workloads"]
    if metric in NEW:
        assert entry["unit"] == "%" and entry["better"] == "higher"
        assert entry["source"] == ("device_trace" if "roofline" in metric
                                   else "program_counter")


def test_the_chunk_kernels_least_time_is_its_bytes_or_its_products():
    """At the cell's one prompt a step the rows' bytes bind (0.1 ms
    against 0.04 ms of products at the bf16 peak); the products are
    counted once, though the kernel does each in six bfloat16 passes."""
    moved, computed = kda.chunk_count(_config(), 1024, 16, 1)
    assert computed == 2.0 * _PRODUCTS * 32 * 16
    assert moved / 819e9 > computed / 197e12 > 0.3 * moved / 819e9


def test_the_held_experts_share_reads_the_new_cell():
    """`moe_held_roofline_pct.batch` (PR 49's reader and count, as they
    are) finds the expert kernels' seconds and this configuration's
    widths through the keys it states for it (`mlp_layer_types`,
    `moe_intermediate_size`; it reads no experts-per-token key)."""
    got = _read("moe_held_roofline_pct.batch", _run(STEPS, TRACE))
    held = _DECODE * _ROWS * 2 * 7 / 10.0
    touched = (_DECODE * 440 + _PROMPTS * 448) / 10.0
    moved = (touched * _EXPERT + 2 * held * 2304) * 2
    least = max(moved / 819e9, 2.0 * _EXPERT * held / 197e12)
    assert got == pytest.approx(least / (0.65 / 2.0) * 100, rel=1e-6)
    assert 0 < got < 100


def test_the_slot_gauges_read_the_new_cell():
    gauges = [{"aphrodite:ssm_slots_live": 190.0 + i,
               "aphrodite:ssm_slots_total": 192.0,
               "aphrodite:ssm_slot_waits_total": 3.0} for i in range(3)]
    run = _run(gauges)
    assert _read("ssm_slots_used_pct.batch", run) == \
        pytest.approx(191 / 192 * 100)
    assert _read("ssm_slot_waits.batch", run) == 0.0


@pytest.mark.parametrize("metric", NEW)
def test_a_new_reader_that_finds_nothing_reads_nothing(metric, tmp_path):
    """The parent's program exports none of the new counters and has no
    `ops/pallas/kda.py`; a `--trace 0` run has no trace, an unknown
    device no peaks, another configuration no `linear_attn_config`.
    None, never 0 and never an exception."""
    assert _read(metric, _run([], TRACE)) is None
    parent = [{k: v for k, v in s.items() if "kda_" not in k}
              for s in STEPS]
    if "kda" in metric:
        assert _read(metric, _run(parent, TRACE)) is None
    if "roofline" in metric:
        assert _read(metric, _run(STEPS)) is None
        assert _read(metric, _run(STEPS, dict(
            TRACE, ops={"fusion f32[8]": [1.0, 10]}))) is None
        run = _run(STEPS, TRACE)
        run.peaks = None
        assert _read(metric, run) is None
        # a program without the kernels' file (the parent's), or whose
        # file states no such constant
        os.symlink(os.path.join(ROOT, "perf"), tmp_path / "perf")
        assert _read(metric, _run(STEPS, TRACE, root=str(tmp_path))) is None
        kernels = tmp_path / "aphrodite_tpu" / "ops" / "pallas"
        kernels.mkdir(parents=True)
        (kernels / "kda.py").write_text("X = 1\n")
        (kernels / "paged_attention.py").write_text("X = 1\n")
        assert _read(metric, _run(STEPS, TRACE, root=str(tmp_path))) is None
        (kernels / "kda.py").write_text(
            'UPDATE_DEVICE_OP_PREFIXES = ("kda-update",)\n'
            'CHUNK_DEVICE_OP_PREFIXES = ("kda-chunk",)\n')
        (kernels / "paged_attention.py").write_text(
            'LATENT_DEVICE_OP_PREFIXES = ("paged-decode",)\n')
        assert _read(metric, _run(STEPS, TRACE, root=str(tmp_path))) == \
            pytest.approx(WANT[metric])
    for cell in (OLD_CELLS[3], OLD_CELLS[6]):
        assert _read(metric, _run(STEPS, TRACE, cell=cell)) is None


def test_the_roofline_counts_by_hand():
    config = _config()
    assert kda.kda_layers(config) == 6 and kda.state_bytes(config) == _STATE
    moved, computed = kda.update_count(config, 192)
    assert moved == 192 * _ROW and computed == 7.0 * 192 * 32 * 128 * 128
    # ISSUE 56's arithmetic: 5.1 GB of state and tails a decode step
    # in six calls, bound by bytes a hundred times over
    assert 5.0e9 < 6 * moved < 5.2e9
    assert (moved / 819e9) / (computed / 197e12) > 100
    assert kda.latent_lanes(config) == 640
    # (the accepted count would read a row of 128 lanes from head_dim 72)
    other = cells.load_module(os.path.join(
        ROOT, "perf", "rooflines", "paged_decode_latent.py"))
    assert other.lanes(config) == 128
    moved, computed = kda.latent_count(config, 192 * 96, 192 * 1536, 192)
    assert moved == (192 * 96 * 16 * 640 +
                     192 * (32 * 640 + 2 * 640 + 32 * 512)) * 2
    assert computed == 2.0 * 32 * 192 * 1536 * (640 + 512)
    state, everything = kda.step_bytes(config, 192, 192 * 1536, 440)
    assert state == _STATE_STEP
    assert everything == _STATE_STEP + _WEIGHTS + _LATENT
    # 13 GB a step, 37% of it the state
    assert 12.5e9 < everything < 13.5e9 and 0.35 < state / everything < 0.40
    assert kda.step_bytes(config, 192, 0, 0)[1] == \
        everything - _LATENT - 2 * 440 * _EXPERT


# ---- the manifest's new entries ----

def test_the_manifest_gains_a_configuration_a_cell_and_four_metrics():
    bench = _bench()
    assert [c["name"] for c in bench["configs"]][-1] == \
        "kimi-linear-48b-a3b-bf16"
    names = [w["name"] for w in bench["workloads"]]
    assert names == OLD_CELLS + [CELL]
    new = bench["workloads"][-1]
    assert (new["config"], new["traffic"], new["chips"]) == (
        "kimi-linear-48b-a3b-bf16", "reason-1k", 1)
    for said in ("callers", "1,024", "KDA state", "a quarter"):
        assert said in new["why"], said
    for entry in bench["configs"] + bench["workloads"]:
        assert 0 < len(entry["why"]) <= 200, entry["name"]
    assert all(len(c["source"]) <= 200 for c in bench["configs"])
    by_name = {m["name"]: m for m in
               bench["end_to_end"] + bench["per_layer"]}
    listed = [m["name"] for m in bench["per_layer"]]
    # appended: an entry put in the middle of a list reads as a change
    assert tuple(listed[-4:]) == NEW
    for name in NEW:
        assert set(by_name[name]) == {"name", "unit", "better", "source",
                                      "layer", "moves", "workloads"}
        assert by_name[name]["workloads"] == [CELL]
        assert os.path.isfile(cells.reader_path(ROOT, "layers", name))
    assert by_name[NEW[0]]["layer"] == by_name[NEW[1]]["layer"] == \
        "kernels (ops/pallas/kda.py)"
    assert by_name[NEW[2]]["layer"] == by_name["kv_used_pct.batch"]["layer"]
    assert by_name[NEW[3]]["layer"] == \
        by_name["decode_attn_roofline_pct.batch"]["layer"]
    assert by_name["out_tok_s"]["workloads"] == OLD_CELLS + [CELL]
    assert "workloads" not in by_name["setup_s"]
    assert "workloads" not in by_name["programs_warmed"]
    # the 28 metrics every cell reports, and the five it joins
    every = [m["name"] for m in bench["per_layer"]
             if m.get("workloads") == OLD_CELLS + [CELL]]
    assert len(every) == 28
    for name in JOINED:
        assert by_name[name]["workloads"][-1] == CELL
        assert by_name[name]["workloads"][:-1] == [
            c for c in OLD_CELLS if c in by_name[name]["workloads"]]
    # the shares whose counts are wrong or absent here stay the older
    # cells': Sarvam's two read `head_dim`, the Mamba kernels' read
    # `_ssm_*_impl`
    for name in ("decode_attn_latent_roofline_pct.batch",
                 "mla_cache_read_share_pct.batch",
                 "ssm_update_roofline_pct.batch",
                 "ssm_scan_roofline_pct.batch",
                 "decode_attn_roofline_pct.batch",
                 "moe_experts_roofline_pct.batch",
                 "window_kv_held_pct.batch"):
        assert CELL not in by_name[name]["workloads"], name
    reported = {m["name"] for m in cells.load_cell(CELL, ROOT).per_layer}
    assert reported == set(every) | set(JOINED) | set(NEW) | {
        "programs_warmed"}
    assert len(reported) == 38
    # nothing the older cells report has changed under them: without
    # the new cell the manifest is the parent's, entry for entry
    from conftest import without_cells
    before = without_cells(bench, cells=(CELL,))
    assert [w["name"] for w in before["workloads"]] == OLD_CELLS
    assert len(before["per_layer"]) == len(bench["per_layer"]) - 4
    assert len(before["configs"]) == len(bench["configs"]) - 1
    for cell in OLD_CELLS:
        assert not {m["name"] for m in cells.load_cell(cell, ROOT).per_layer
                    } & set(NEW)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        assert f.read().endswith("}\n")
