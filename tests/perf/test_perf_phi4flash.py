"""Tests of what PR 36 adds to the benchmark as new files and entries:
the configuration `phi-4-mini-flash-bf16` (nothing cut), its
reference's tree against the program's at the published widths, the
reference's stages through the harness's own child at a toy size, the
traffic `reason-2k`, the four per-layer readers on hand-made runs,
their roofline counts, and the manifest's new entries. No chip."""
import io
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from perf import cells, loops, reference_child
from perf import run as perf_run

ROOT = cells.ROOT
CELL = "phi-4-mini-flash-bf16.reason-2k"
OLD_CELLS = ["mistral-7b-w4a8.batch", "smallthinker-21ba3b-bf16.batch-8k"]
NEW = ("ssm_update_roofline_pct.batch", "ssm_scan_roofline_pct.batch",
       "decode_attn_shared_roofline_pct.batch", "ssm_slots_used_pct.batch")
ref = cells.load_module(os.path.join(ROOT, "perf", "references",
                                     "phi4flash.py"))
#: the catalog row's `config` (model-configs guide), key for key
PUBLISHED = dict(
    embd_pdrop=0, hidden_act="silu", hidden_size=2560,
    intermediate_size=10240, layer_norm_eps=1e-05,
    max_position_embeddings=262144, mb_per_layer=2, model_type="phi4flash",
    num_attention_heads=40, num_hidden_layers=32, num_key_value_heads=20,
    resid_pdrop=0, sliding_window=512, tie_word_embeddings=True,
    mlp_bias=False, lm_head_bias=False, vocab_size=200064)


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _hf(config):
    from aphrodite_tpu.transformers_utils.configs import Phi4FlashConfig
    return Phi4FlashConfig(**{
        k: v for k, v in config.items()
        if k not in ("perf", "architectures", "model_type", "torch_dtype")})


# ---- the configuration and the cell ----

def test_the_configuration_is_the_published_one_with_nothing_cut():
    config = cells.load_cell(CELL, ROOT).config
    perf = config["perf"]
    assert {k: config[k] for k in PUBLISHED} == PUBLISHED
    assert set(config) == set(PUBLISHED) | {"architectures", "torch_dtype",
                                            "perf"}
    assert config["torch_dtype"] == "bfloat16" and perf["reduced"] == []
    assert "one chip holds the model whole" in perf["deployment"]
    assert perf["engine_args"] == ["--max-model-len", "4096"]
    assert perf["env"] == {"APHRODITE_SPEC": "0"}
    assert perf["kernel_families"] == ["decode_attention", "kv_write",
                                       "ssm_scan"]
    assert perf["matmul_peak"] == "bf16_flops_per_s"
    assert sorted(perf["controls"]) == ["act8", "kv8"]
    assert perf["reference_replies"] == 2
    # every size config.json lacks is listed with its reason
    assumed = " ".join(perf["assumed"])
    for said in ("mamba_d_state 16", "mamba_d_conv 4", "mamba_expand 2",
                 "mamba_dt_rank 160", "convolution has a bias",
                 "attention projections", "no positional encoding",
                 "differential attention", "lambda_init", "sub-norm",
                 "layer-kind rule", "float32"):
        assert said in assumed, said
    # what one token multiplies: the layers and the head (which is the
    # embedding) once; the issue's arithmetic, to the parameter
    tree = ref.tree(config)
    held = sum(int(np.prod(s[0])) for v in tree.values()
               for s in v.values())
    assert perf["parameters"] == held == 3_852_562_944
    assert str(round(held * 2 / 1e9, 2)) in perf["deployment"]
    entry = {c["name"]: c for c in _bench()["configs"]}[
        "phi-4-mini-flash-bf16"]
    assert entry["source"] == perf["source"] and entry["reduced"] == []


def test_what_the_configuration_makes_of_the_cache_layer():
    """The layer kinds, the page groups, what a page and a state slot
    hold: the numbers `PERF.md` section 4 gives."""
    from aphrodite_tpu.common.config import ModelConfig
    config = cells.load_cell(CELL, ROOT).config
    hf = _hf(config)
    kinds = hf.layer_kinds
    assert kinds == ref.kinds(config)
    assert [kinds.count(k) for k in
            ("mamba", "window", "full", "gmu", "cross")] == [9, 8, 1, 7, 7]
    assert kinds[16] == "mamba" and kinds[17] == "full"
    model_config = ModelConfig("x", hf_config=hf, dtype="bfloat16",
                               max_model_len=4096)
    groups = model_config.get_page_groups()
    assert groups.kinds == ("window",) * 8 + ("full",)
    assert groups.layers_per_group == 1 and groups.readers[-1] == 8
    # a differential pair of KV heads is one head of 128: 10 of them,
    # 5,120 B a token a layer, a page of 16 tokens 80 KB
    assert (model_config.get_total_num_kv_heads(),
            model_config.get_head_size()) == (10, 128)
    assert model_config.get_kv_heads_per_slot() == [10]
    # the recurrent state float32, the convolution's tail the model's
    # type: 3.2 MB a slot over the nine Mamba layers
    spec = model_config.get_state_spec()
    assert spec.layers == 9 and spec.arrays == (
        ((3, 5120), "bfloat16"), ((16, 5120), "float32"))
    assert spec.slot_bytes == 9 * (3 * 5120 * 2 + 16 * 5120 * 4) == \
        3_225_600


def test_the_references_tree_is_the_programs_at_the_published_widths():
    """What `perf/serve_child.py` checks when the server starts, here
    without a byte of weights: every bucket, leaf, shape and type."""
    from aphrodite_tpu.modeling.models.phi4flash import \
        Phi4FlashForCausalLM
    config = cells.load_cell(CELL, ROOT).config
    model = Phi4FlashForCausalLM(_hf(config),
                                 jnp.dtype(config["torch_dtype"]))
    have = {b: {n: (tuple(a.shape), a.dtype.name)
                for n, a in leaves.items()}
            for b, leaves in jax.eval_shape(model.init_params).items()}
    tree = ref.tree(config)
    assert have == {b: {n: (tuple(s[0]), s[1]) for n, s in v.items()}
                    for b, v in tree.items()}
    # the issue's count by kind of layer, MLP included
    per = {}
    for bucket, leaves in tree.items():
        if bucket.startswith("model.layers."):
            layer = int(bucket.split(".")[2])
            per[layer] = per.get(layer, 0) + sum(
                int(np.prod(s[0])) for s in leaves.values())
    assert (per[0], per[1], per[17], per[18], per[19]) == (
        119_895_040, 98_322_304, 98_322_304, 104_867_840, 91_766_144)
    fns = [fn for fn, _ in ref.stages(config)]
    assert fns == ["embed"] + ["layer_mamba", "layer_window"] * 8 + [
        "layer_mamba_memory", "layer_full"] + [
        "layer_gmu", "layer_cross"] * 7 + ["logits"]
    # the tied head: the last stage makes the embedding's leaves again
    assert ref.stages(config)[-1][1]["head"] == "model.embed_tokens"


def test_the_traffic_is_2k_prompts_from_48_callers():
    cell = cells.load_cell(CELL, ROOT)
    loop, params = cell.traffic["loop"], cell.traffic["params"]
    assert (loop["kind"], loop["clients"], loop["journal_callers"]) == \
        ("closed", 48, 1)
    shapes = cell.generator(params, 3000000877, 0, 48, None, 200064)
    assert {len(s["prompt"]) for s in shapes} == {2048}
    outs = sorted(s["max_tokens"] for s in shapes)
    assert 512 <= outs[0] < 540 and 1000 < outs[-1] <= 1024
    assert not any(s["stream"] for s in shapes)
    assert all(s["sampling"] == {"temperature": 0.0} for s in shapes)
    # a group of callers queued stays under the admission limit of
    # 8 x max_num_batched_tokens (4,096 at --max-model-len 4096)
    assert max(loop["ramp_groups"]) * 2048 < 8 * 4096
    # the groups add up to the 48 rows of the bucket the window runs in
    assert 48 % sum(loop["ramp_groups"]) == 0
    canary = cell.traffic["canary"]
    assert all(1984 <= n <= 2040 for n in canary["prompt_lens"])
    assert canary["max_tokens"] == 16
    # a canary row stays under 128 pages: one table width, so the
    # canary's one-row decode costs two step programs and not four
    assert max(canary["prompt_lens"]) + canary["max_tokens"] <= 2048
    # the longest sequence fits the reference's rows, the rows the
    # chip: three rows of float32 logits beside the tied embedding
    assert 2048 + 1024 <= reference_child.padded(2048 + 1024) == 3072
    rows = 1 + cell.config["perf"]["reference_replies"]
    assert rows == 3 and rows * 3072 * 200064 * 4 < 7.5e9
    # contexts of 2,049-3,072 tokens: the full layer's table has one
    # width past `_WIDE_TABLE`, a window group's one
    from aphrodite_tpu.executor.model_runner import ModelRunner
    runner = ModelRunner.__new__(ModelRunner)
    runner.pages_bucket = 8
    assert {runner._table_width(-(-ctx // 16))
            for ctx in range(2049, 3073)} == {192}
    assert runner._table_width(512 // 16 + 1) == 40


# ---- the reference through the harness's child ----

def _tiny():
    return dict(
        architectures=["Phi4FlashForCausalLM"], model_type="phi4flash",
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=8, num_attention_heads=8, num_key_value_heads=4,
        max_position_embeddings=512, layer_norm_eps=1e-5,
        sliding_window=24, mb_per_layer=2, tie_word_embeddings=True,
        mlp_bias=False, lm_head_bias=False, hidden_act="silu",
        torch_dtype="float32",
        perf=dict(reference="phi4flash", controls=dict(
            kv8=dict(kv="float8_e5m2"), act8=dict(act_bits=8))))


def test_the_stages_run_through_the_harness_child(tmp_path, monkeypatch):
    """`perf/reference_child.py` as the harness starts it, on the CPU at
    a toy size: the one array it carries from stage to stage is widened
    by the layer's index, the memory and the full layer's K and V; both
    controls run; a greedy continuation of the reference itself has no
    gap, and the controls' tokens have one."""
    from perf import weights
    config = _tiny()
    params = weights.whole(ref.tree(config), ref.stages(config), 5)
    ids = np.random.default_rng(0).integers(3, 256, 24).tolist()

    @jax.jit
    def forward(x):
        for fn, buckets in ref.stages(config):
            w = {local: params[b] for local, b in buckets.items()}
            x = getattr(ref, fn)(config, w, x, ref.Precision())
        return x

    # (causal: what lies behind a position does not reach it)
    with jax.default_matmul_precision("highest"):
        for _ in range(4):
            x = np.zeros((1, 32), np.int32)
            x[0, :len(ids)] = ids
            ids.append(int(np.asarray(forward(x)[0, len(ids) - 1]).argmax()))
    job = dict(root=ROOT, config=config, name="phi4flash", seed=5,
               sequences=[dict(prompt=ids[:24], reply=ids[24:])], rows=2,
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
    served = out["served"]
    assert len(served["chosen"]) == 4
    # its own greedy tokens are its largest logits
    assert np.allclose(served["chosen"], served["best"], atol=1e-4)
    # 6 of the 8 stages keep their shape and report a share
    assert 0.1 < out["layer_share"] < 1.5
    assert len(out["stage_s"]) == 1 + 8
    for control in ("kv8", "act8"):
        gaps = np.asarray(out[control]["best"]) - \
            np.asarray(out[control]["chosen"])
        assert (gaps >= -1e-4).all()


def test_a_control_moves_the_reference_where_it_enters():
    """`kv8` rounds the K and V of the page-holding layers alone (a
    gated unit and a Mamba layer are untouched by it), `act8` every
    matmul's input."""
    import dataclasses
    config = _tiny()
    from perf import weights
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
                per_stage.append(np.asarray(x[..., :64]))
        moved[side] = per_stage
    diff = lambda side, i: float(np.abs(
        moved[side][i] - moved["served"][i]).max())
    # stage 1 is the first Mamba layer: int8 activations move it, an
    # 8-bit cache does not; stage 2, a window layer, is moved by both
    assert diff("kv8", 1) == 0.0 and diff("act8", 1) > 1e-4
    assert diff("kv8", 2) > 1e-4 and diff("act8", 2) > 1e-4
    assert dataclasses.is_dataclass(ref.Precision)


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


def _totals(gauges=None, **counters):
    out = {f"aphrodite:{k}_total": float(v) for k, v in counters.items()}
    out.update({f"aphrodite:{k}": float(v)
                for k, v in (gauges or {}).items()})
    return out


#: two readings 10 s apart: 330 step programs, 300 of them decode steps
#: of 64 rows and 30 prompt steps of one 2,048-token prompt. A decode
#: step's rows hold 160 pages live in the full group, read by 8 layers,
#: and 33 in each of the 8 window groups. 60 of 128 slots are held at
#: the first reading, 64 at the second.
_DECODE, _PROMPTS, _ROWS = 300, 30, 64
STEPS = [
    _totals(dict(ssm_slots_total=128, ssm_slots_live=60),
            sampler_plans=1000, decode_attn_steps=900,
            ssm_decode_rows=5e4, ssm_prefill_tokens=2e5,
            ssm_state_resets=100, kv_page_reads_shared=1e6),
    _totals(dict(ssm_slots_total=128, ssm_slots_live=64),
            sampler_plans=1000 + _DECODE + _PROMPTS,
            decode_attn_steps=900 + _DECODE,
            ssm_decode_rows=5e4 + _DECODE * _ROWS,
            ssm_prefill_tokens=2e5 + _PROMPTS * 2048,
            ssm_state_resets=100 + _PROMPTS,
            kv_page_reads_shared=1e6 + _DECODE * _ROWS * (
                8 * 160 + 8 * 33))]
#: the traced 2 s: 60 decode steps and 6 prompt steps; an update call
#: takes 0.1 ms, a chunk scan 2 ms, a decode-attention call 1 ms (17
#: attention layers and 9 state layers a step)
OPS = {
    "_ssm_update_impl f32[64,1,5120] tpu_custom_call": [0.054, 540],
    "_ssm_scan_impl f32[1,2048,5120] tpu_custom_call": [0.108, 54],
    "_paged_decode_impl bf16[65,2,20,128] tpu_custom_call": [1.02, 1020],
    "fusion f32[64,2560]": [0.5, 5000]}
TRACE = dict(busy_s=1.9, window_s=2.0, device_ops=[], idle_gaps=[],
             ops=OPS)
_ROW = 2 * 16 * 5120 * 4 + 2 * 3 * 5120 * 2 + 5120 * 2 + 3 * 5120 * 4 + \
    2 * 16 * 4
_CALL = 17 * 5120 * 4           # A and D, once a call
WANT = {
    "ssm_update_roofline_pct.batch":
        ((_ROWS * _ROW + _CALL) / 819e9) / 0.0001 * 100,
    "ssm_scan_roofline_pct.batch":
        ((2048 * (3 * 5120 * 4 + 2 * 16 * 4) + 2 * 16 * 5120 * 4 + _CALL)
         / 819e9) / 0.002 * 100,
    # a page read is 16 tokens of one layer's K and V, 5,120 B a token
    "decode_attn_shared_roofline_pct.batch":
        ((_ROWS * (8 * 160 + 8 * 33) * 16 * 5120 +
          16 * 2 * 65 * 40 * 64 * 2) / 819e9) / (0.001 * 16) * 100,
    "ssm_slots_used_pct.batch": (60 + 64) / 2 / 128 * 100}


def _read(metric, run):
    return cells.load_function(
        cells.reader_path(ROOT, "layers", metric), "read")(run)


@pytest.mark.parametrize("metric", NEW)
def test_each_reader_of_pr_36_on_a_hand_made_run(metric):
    got = _read(metric, _run(STEPS, TRACE))
    assert got == pytest.approx(WANT[metric], rel=1e-6)
    assert 0 < got < 100
    entry = {m["name"]: m for m in _bench()["per_layer"]}[metric]
    assert entry["moves"] == "out_tok_s" and entry["unit"] == "%"
    assert entry["workloads"] == [CELL]
    assert entry["source"] == ("device_trace" if "roofline" in metric
                               else "program_counter")


@pytest.mark.parametrize("metric", NEW)
def test_a_reader_of_pr_36_that_finds_nothing_reads_nothing(metric):
    """The parent's program exports none of the counters; a `--trace
    0` run has no trace, a CPU trace none of the kernels' names, an
    unknown device no peaks, a model without state no slots. None,
    never 0 and never an exception."""
    old = [_totals(sampler_plans=10, generation_tokens=1),
           _totals(sampler_plans=90, generation_tokens=9)]
    assert _read(metric, _run(old, TRACE)) is None
    assert _read(metric, _run([], TRACE)) is None
    stateless = [_totals(dict(ssm_slots_total=0, ssm_slots_live=0),
                         sampler_plans=10),
                 _totals(dict(ssm_slots_total=0, ssm_slots_live=0),
                         sampler_plans=90)]
    assert _read(metric, _run(stateless, TRACE)) is None
    if "roofline" in metric:
        assert _read(metric, _run(STEPS)) is None
        assert _read(metric, _run(STEPS, dict(
            TRACE, ops={"fusion f32[8]": [1.0, 10]}))) is None
        run = _run(STEPS, TRACE)
        run.peaks = None
        assert _read(metric, run) is None


def test_the_roofline_counts_from_the_configurations_shapes():
    config = cells.load_cell(CELL, ROOT).config
    ssm = cells.load_module(os.path.join(ROOT, "perf", "rooflines",
                                         "ssm_scan.py"))
    # a decode step of 64 rows: 0.4 GB of state read and written over
    # the nine layers (the issue's sizing), bound by bytes by far
    moved, computed = ssm.update_count(config, 64)
    assert 0.40e9 < 9 * moved < 0.48e9
    assert computed == 7 * 64 * 16 * 5120
    assert moved / 819e9 > 100 * computed / 197e12
    # a chunk of 2,048 tokens: 126 MB of rows in and out; bytes again
    moved, computed = ssm.scan_count(config, 2048, 1)
    assert 126e6 < moved < 128e6
    assert moved / 819e9 > 10 * computed / 197e12
    shared = cells.load_module(os.path.join(
        ROOT, "perf", "rooflines", "paged_decode_shared.py"))
    assert shared.attention_layers(config) == 16
    assert shared.attention_layers(
        cells.load_cell(OLD_CELLS[0], ROOT).config) == 32
    # a row at 2,450 tokens: 154 pages of the full layer read by 8
    # layers, 33 of each window layer: 100 MB and 21.6 MB, the issue's
    moved, _ = shared.count(config, 8 * 154, 0)
    assert 100e6 < moved < 102e6
    moved, computed = shared.count(config, 8 * 33, 0)
    assert 21.5e6 < moved < 21.7e6
    assert computed == 4 * 64 * 40 * 8 * 33 * 16


# ---- the manifest's new entries ----

def test_the_manifest_gains_a_configuration_a_cell_and_four_metrics():
    bench = _bench()
    assert [c["name"] for c in bench["configs"]][-1] == \
        "phi-4-mini-flash-bf16"
    assert len(bench["configs"][-1]["why"]) <= 200
    names = [w["name"] for w in bench["workloads"]]
    assert names[:2] == OLD_CELLS and names[2] == CELL
    new = bench["workloads"][2]
    assert (new["config"], new["traffic"], new["chips"]) == (
        "phi-4-mini-flash-bf16", "reason-2k", 1)
    assert len(new["why"]) <= 200
    by_name = {m["name"]: m for m in
               bench["end_to_end"] + bench["per_layer"]}
    listed = [m["name"] for m in bench["per_layer"]]
    at = listed.index(NEW[0])
    assert listed[at:at + 4] == list(NEW)
    assert by_name["out_tok_s"]["workloads"][:3] == OLD_CELLS + [CELL]
    assert "workloads" not in by_name["setup_s"]
    assert "workloads" not in by_name["programs_warmed"]
    # the shares whose counts are wrong or absent here stay the older
    # cells'; the window's counters mean here what they mean there
    for name in ("decode_attn_roofline_pct.batch",
                 "decode_attn_groups_roofline_pct.batch",
                 "moe_experts_roofline_pct.batch",
                 "moe_experts_touched_pct.batch"):
        assert CELL not in by_name[name]["workloads"]
    assert by_name["window_kv_held_pct.batch"]["workloads"][:2] == \
        [OLD_CELLS[1], CELL]
    joined = [m["name"] for m in bench["per_layer"]
              if m.get("workloads", [])[:3] == OLD_CELLS + [CELL]]
    assert len(joined) == 21
    reported = {m["name"] for m in cells.load_cell(CELL, ROOT).per_layer}
    assert reported == set(joined) | set(NEW) | {
        "programs_warmed", "window_kv_held_pct.batch"}
    for name in NEW:
        assert os.path.isfile(cells.reader_path(ROOT, "layers", name))
    # nothing the older cells report has changed under them
    from conftest import without_cells
    before = without_cells(bench)
    assert [w["name"] for w in before["workloads"]] == OLD_CELLS
    assert len(before["per_layer"]) == len(bench["per_layer"]) - 4
