"""Tests of what PR 41 adds to the benchmark as new files and entries:
the configuration `jamba2-3b-bf16` (the catalog row, nothing cut), its
reference's tree against the program's at the published widths, the
reference's stages through the harness's own child at a toy size, the
traffic `reason-512`, the two new per-layer readers and the three
`ssm_*` ones on hand-made runs of the new cell, the MQA roofline count
by hand, and the manifest's new entries. No chip."""
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
CELL = "jamba2-3b-bf16.reason-512"
OLD_CELLS = ["mistral-7b-w4a8.batch", "smallthinker-21ba3b-bf16.batch-8k",
             "phi-4-mini-flash-bf16.reason-2k"]
NEW = ("decode_attn_mqa_roofline_pct.batch", "ssm_slot_waits.batch")
SSM = ("ssm_update_roofline_pct.batch", "ssm_scan_roofline_pct.batch",
       "ssm_slots_used_pct.batch")
ref = cells.load_module(os.path.join(ROOT, "perf", "references",
                                     "jamba.py"))
#: the catalog row's `config` (model-configs guide,
#: `architectures.jsonl`, AI21-Jamba2-3B), key for key
PUBLISHED = dict(
    attn_layer_offset=7, attn_layer_period=14, expert_layer_offset=1,
    expert_layer_period=2, hidden_act="silu", hidden_size=2560,
    intermediate_size=8192, mamba_conv_bias=True, mamba_d_conv=4,
    mamba_d_state=16, mamba_dt_rank=160, mamba_expand=2,
    mamba_proj_bias=False, max_position_embeddings=262144,
    model_type="jamba", num_attention_heads=20, num_experts=1,
    num_experts_per_tok=1, num_hidden_layers=28, num_key_value_heads=1,
    num_logits_to_keep=1, rms_norm_eps=1e-06, sliding_window=None,
    tie_word_embeddings=True, use_mamba_kernels=True, vocab_size=65536)


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _hf(config):
    from aphrodite_tpu.transformers_utils.configs import JambaConfig
    return JambaConfig(**{
        k: v for k, v in config.items()
        if k not in ("perf", "architectures", "model_type", "torch_dtype")})


# ---- the configuration and the cell ----

def test_the_configuration_is_the_catalog_row_with_nothing_cut():
    config = cells.load_cell(CELL, ROOT).config
    perf = config["perf"]
    assert len(PUBLISHED) == 26
    assert {k: config[k] for k in PUBLISHED} == PUBLISHED
    assert set(config) == set(PUBLISHED) | {"architectures", "torch_dtype",
                                            "perf"}
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.isfile(catalog):
        with open(catalog) as f:
            (row,) = [r for r in map(json.loads, f)
                      if r["name"] == "AI21-Jamba2-3B"]
        assert row["config"] == PUBLISHED
        assert row["source_url"] == perf["source"]
    assert config["architectures"] == ["JambaForCausalLM"]
    assert config["torch_dtype"] == "bfloat16" and perf["reduced"] == []
    assert "one chip holds the model whole" in perf["deployment"]
    assert perf["engine_args"] == ["--max-model-len", "4096",
                                   "--max-num-seqs", "128"]
    assert perf["env"] == {"APHRODITE_SPEC": "0"}
    assert perf["kernel_families"] == ["decode_attention", "kv_write",
                                       "ssm_scan"]
    assert perf["matmul_peak"] == "bf16_flops_per_s"
    assert sorted(perf["controls"]) == ["act8", "kv8"]
    assert perf["reference"] == "jamba" and perf["reference_replies"] == 2
    assumed = " ".join(perf["assumed"])
    for said in ("float32", "ranges", "nothing else"):
        assert said in assumed, said
    # what one token multiplies: the layers and the head (which is the
    # embedding) once; the issue's arithmetic, to the parameter
    tree = ref.tree(config)
    held = sum(int(np.prod(s[0])) for v in tree.values()
               for s in v.values())
    assert perf["parameters"] == held == 3_029_337_472
    assert str(round(held * 2 / 1e9, 2)) in perf["deployment"]
    entry = {c["name"]: c for c in _bench()["configs"]}["jamba2-3b-bf16"]
    assert entry["source"] == perf["source"] and entry["reduced"] == []
    assert entry["file"] == "perf/configs/jamba2-3b-bf16.json"


def test_what_the_configuration_makes_of_the_cache_layer():
    """The layer kinds, the page group, what a page and a state slot
    hold: the numbers `PERF.md` section 4 gives."""
    from aphrodite_tpu.common.config import ModelConfig
    from aphrodite_tpu.executor.cache_engine import CacheEngine
    from aphrodite_tpu.common.config import CacheConfig, ParallelConfig
    config = cells.load_cell(CELL, ROOT).config
    hf = _hf(config)
    kinds = hf.layer_kinds
    assert kinds == ref.kinds(config)
    assert [l for l, k in enumerate(kinds) if k == "attention"] == [7, 21]
    assert kinds.count("mamba") == 26
    model_config = ModelConfig("x", hf_config=hf, dtype="bfloat16",
                               max_model_len=4096)
    groups = model_config.get_page_groups()
    # one group of the two attention layers: two pairs of page arrays
    assert groups.kinds == ("full",) and groups.stateful
    assert groups.layers_per_group == 2 and groups.readers == (2,)
    assert (model_config.get_total_num_kv_heads(),
            model_config.get_head_size()) == (1, 128)
    assert model_config.get_kv_heads_per_slot() == [1, 1]
    spec = model_config.get_state_spec()
    assert spec.layers == 26 and spec.arrays == (
        ((3, 5120), "bfloat16"), ((16, 5120), "float32"))
    assert spec.slot_bytes == 26 * (3 * 5120 * 2 + 16 * 5120 * 4) == \
        9_318_400
    # a token's K and V over both layers are 1,024 B, a page 16,384 B
    cache_config = CacheConfig(16, 0.9, 0.01, "auto", page_groups=groups,
                               state_spec=spec)
    assert CacheEngine.get_cache_block_size(
        cache_config, model_config, ParallelConfig(1, 1)) == 16_384


def test_the_references_tree_is_the_programs_at_the_published_widths():
    """What `perf/serve_child.py` checks when the server starts, here
    without a byte of weights: every bucket, leaf, shape and type."""
    from aphrodite_tpu.modeling.models.jamba import JambaForCausalLM
    config = cells.load_cell(CELL, ROOT).config
    model = JambaForCausalLM(_hf(config), jnp.dtype(config["torch_dtype"]))
    shapes = jax.eval_shape(model.init_params)
    have = {b: {n: (tuple(a.shape), a.dtype.name)
                for n, a in leaves.items()}
            for b, leaves in shapes.items()}
    tree = ref.tree(config)
    assert have == {b: {n: (tuple(s[0]), s[1]) for n, s in v.items()}
                    for b, v in tree.items()}
    assert sum(int(np.prod(a.shape)) for leaves in shapes.values()
               for a in leaves.values()) == 3_029_337_472
    # the issue's count by kind of layer, MLP and norms included
    per = {}
    for bucket, leaves in tree.items():
        if bucket.startswith("model.layers."):
            layer = int(bucket.split(".")[2])
            per[layer] = per.get(layer, 0) + sum(
                int(np.prod(s[0])) for s in leaves.values())
    assert (per[0], per[7]) == (104_161_472, 76_682_240)
    assert set(per.values()) == {104_161_472, 76_682_240}
    fns = [fn for fn, _ in ref.stages(config)]
    period = ["layer_mamba"] * 7 + ["layer_attention"] + ["layer_mamba"] * 6
    assert fns == ["embed"] + period * 2 + ["logits"]
    # the tied head: the last stage makes the embedding's leaves again
    assert ref.stages(config)[-1][1]["head"] == "model.embed_tokens"


def test_the_traffic_is_512_token_prompts_from_128_callers():
    cell = cells.load_cell(CELL, ROOT)
    loop, params = cell.traffic["loop"], cell.traffic["params"]
    assert (loop["kind"], loop["clients"], loop["journal_callers"]) == \
        ("closed", 128, 1)
    assert loop["ramp_groups"] == [32]
    # the callers are the slots the server is started with
    args = cell.config["perf"]["engine_args"]
    assert int(args[args.index("--max-num-seqs") + 1]) == loop["clients"]
    shapes = cell.generator(params, 3000000877, 0, 128, None, 65536)
    assert len(shapes) == 128
    assert {len(s["prompt"]) for s in shapes} == {512}
    assert all(3 <= t < 65536 for s in shapes for t in s["prompt"])
    outs = sorted(s["max_tokens"] for s in shapes)
    assert 256 <= outs[0] < 262 and 1018 < outs[-1] <= 1024
    assert 630 < sum(outs) / 128 < 650
    # another seed holds the same work in another order
    other = cell.generator(params, 12345, 0, 128, None, 65536)
    assert sorted(s["max_tokens"] for s in other) == outs
    assert not any(s["stream"] for s in shapes)
    assert all(s["sampling"] == {"temperature": 0.0} for s in shapes)
    # a group of callers queued stays under the admission limit of
    # 8 x max_num_batched_tokens (4,096 at --max-model-len 4096)
    assert max(loop["ramp_groups"]) * 512 < 8 * 4096
    assert 128 % sum(loop["ramp_groups"]) == 0
    assert (cell.traffic["warm_seconds"], cell.traffic["request_timeout_s"],
            cell.traffic["warm_timeout_s"]) == (10.0, 90.0, 400.0)
    canary = cell.traffic["canary"]
    assert canary["prompt_lens"] == [448, 464, 480]
    assert canary["max_tokens"] == 16
    # a canary row stays within one 512-token work item and one table
    assert max(canary["prompt_lens"]) + canary["max_tokens"] <= 512
    # the longest sequence fits the reference's rows: three rows of
    # float32 logits beside the tied embedding
    assert reference_child.padded(512 + 1024) == 1536
    rows = 1 + cell.config["perf"]["reference_replies"]
    assert rows == 3 and rows * 1536 * 65536 * 4 < 1.3e9


# ---- the reference through the harness's child ----

def _tiny():
    return dict(
        architectures=["JambaForCausalLM"], model_type="jamba",
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=6, num_attention_heads=4, num_key_value_heads=1,
        max_position_embeddings=512, rms_norm_eps=1e-6, sliding_window=None,
        attn_layer_period=3, attn_layer_offset=1, expert_layer_period=2,
        expert_layer_offset=1, num_experts=1, num_experts_per_tok=1,
        mamba_d_state=16, mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=4,
        mamba_conv_bias=True, mamba_proj_bias=False,
        tie_word_embeddings=True, hidden_act="silu", torch_dtype="float32",
        perf=dict(reference="jamba", controls=dict(
            kv8=dict(kv="float8_e5m2"), act8=dict(act_bits=8))))


def test_the_stages_run_through_the_harness_child(tmp_path, monkeypatch):
    """`perf/reference_child.py` as the harness starts it, on the CPU at
    a toy size: every stage maps the stream to itself and reports its
    share; both controls run; a greedy continuation of the reference
    itself has no gap, and the controls' tokens have none below it."""
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
    job = dict(root=ROOT, config=config, name="jamba", seed=5,
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
    # all 6 layer stages keep their shape and report a share
    assert 0.1 < out["layer_share"] < 1.5
    assert len(out["stage_s"]) == 1 + 6
    for control in ("kv8", "act8"):
        gaps = np.asarray(out[control]["best"]) - \
            np.asarray(out[control]["chosen"])
        assert (gaps >= -1e-4).all()


def test_a_control_moves_the_reference_where_it_enters():
    """`kv8` rounds the K and V of the two attention layers alone (a
    Mamba layer is untouched by it), `act8` every matmul's input."""
    import dataclasses
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
    diff = lambda side, i: float(np.abs(
        moved[side][i] - moved["served"][i]).max())
    # stage 1 is the first Mamba layer: int8 activations move it, an
    # 8-bit cache does not; stage 2, the first attention layer, is
    # moved by both
    assert diff("kv8", 1) == 0.0 and diff("act8", 1) > 1e-4
    assert diff("kv8", 2) > 1e-4 and diff("act8", 2) > 1e-4
    assert dataclasses.is_dataclass(ref.Precision)


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


def _totals(gauges=None, **counters):
    out = {f"aphrodite:{k}_total": float(v) for k, v in counters.items()}
    out.update({f"aphrodite:{k}": float(v)
                for k, v in (gauges or {}).items()})
    return out


#: two readings 10 s apart: 480 step programs, 400 of them decode steps
#: of 128 rows and 80 prompt steps of one 512-token prompt. 126 of 128
#: slots are held at the first reading, 128 at the second; 2.4% of the
#: pool is live; 3 admissions waited for a slot.
_DECODE, _PROMPTS, _ROWS = 400, 80, 128
STEPS = [
    _totals(dict(ssm_slots_total=128, ssm_slots_live=126,
                 gpu_cache_usage_perc=0.024),
            sampler_plans=1000, decode_attn_steps=900,
            ssm_decode_rows=5e4, ssm_prefill_tokens=2e5,
            ssm_state_resets=100, ssm_slot_waits=7),
    _totals(dict(ssm_slots_total=128, ssm_slots_live=128,
                 gpu_cache_usage_perc=0.024),
            sampler_plans=1000 + _DECODE + _PROMPTS,
            decode_attn_steps=900 + _DECODE,
            ssm_decode_rows=5e4 + _DECODE * _ROWS,
            ssm_prefill_tokens=2e5 + _PROMPTS * 512,
            ssm_state_resets=100 + _PROMPTS, ssm_slot_waits=10)]
#: the server's start-up line: 400,000 pages of 16,384 B
LOG = "KV cache: 400000 device pages, 64 host pages (6.10 GiB device)\n"
#: the traced 2 s: 80 decode steps and 16 prompt steps; an update call
#: takes 0.16 ms, a chunk scan 0.6 ms, a decode-attention call 1 ms
#: (2 attention layers and 26 state layers a step)
OPS = {
    "_ssm_update_impl f32[128,1,5120] tpu_custom_call": [0.3328, 2080],
    "_ssm_scan_impl f32[1,512,5120] tpu_custom_call": [0.2496, 416],
    "_paged_decode_impl bf16[129,1,20,128] tpu_custom_call": [0.16, 160],
    "fusion f32[128,2560]": [0.5, 5000]}
TRACE = dict(busy_s=1.9, window_s=2.0, device_ops=[], idle_gaps=[],
             ops=OPS)
_ROW = 2 * 16 * 5120 * 4 + 2 * 3 * 5120 * 2 + 5120 * 2 + 3 * 5120 * 4 + \
    2 * 16 * 4
_CALL = 17 * 5120 * 4           # A and D, once a call
#: one attention layer's live K and V: half of 2.4% of 6.10 GiB
_LIVE = 6.10 * 2 ** 30 * 0.024 / 2
WANT = {
    "ssm_update_roofline_pct.batch":
        ((_ROWS * _ROW + _CALL) / 819e9) / 0.00016 * 100,
    "ssm_scan_roofline_pct.batch":
        ((512 * (3 * 5120 * 4 + 2 * 16 * 4) + 2 * 16 * 5120 * 4 + _CALL)
         / 819e9) / 0.0006 * 100,
    "ssm_slots_used_pct.batch": (126 + 128) / 2 / 128 * 100,
    # the rows' queries in and outputs out: 129 x 20 heads of 128, bf16
    "decode_attn_mqa_roofline_pct.batch":
        ((_LIVE + 2 * 129 * 20 * 128 * 2) / 819e9) / 0.001 * 100,
    "ssm_slot_waits.batch": 3.0}


def _read(metric, run):
    return cells.load_function(
        cells.reader_path(ROOT, "layers", metric), "read")(run)


@pytest.mark.parametrize("metric", NEW + SSM)
def test_each_reader_on_a_hand_made_run_of_the_new_cell(metric):
    got = _read(metric, _run(STEPS, TRACE, log_setup=LOG))
    assert got == pytest.approx(WANT[metric], rel=1e-6)
    entry = {m["name"]: m for m in _bench()["per_layer"]}[metric]
    assert entry["moves"] == "out_tok_s" and CELL in entry["workloads"]
    if metric.endswith("_pct.batch"):
        assert 0 < got < 100 and entry["unit"] == "%"
    assert entry["source"] == ("device_trace" if "roofline" in metric
                               else "program_counter")


@pytest.mark.parametrize("metric", NEW)
def test_a_new_reader_that_finds_nothing_reads_nothing(metric):
    """The parent's program exports no such counter; a `--trace 0` run
    has no trace, a CPU trace none of the kernel's names, an unknown
    device no peaks, another configuration no layer rule. None, never
    0 and never an exception."""
    old = [_totals(dict(gpu_cache_usage_perc=0.5), sampler_plans=10),
           _totals(dict(gpu_cache_usage_perc=0.5), sampler_plans=90)]
    assert _read(metric, _run([], TRACE, log_setup=LOG)) is None
    if metric == "ssm_slot_waits.batch":
        assert _read(metric, _run(old, TRACE, log_setup=LOG)) is None
        # a counter that did not grow is the reading 0, not None
        still = [dict(s, **{"aphrodite:ssm_slot_waits_total": 7.0})
                 for s in STEPS]
        assert _read(metric, _run(still)) == 0.0
        return
    assert _read(metric, _run(STEPS, log_setup=LOG)) is None
    assert _read(metric, _run(STEPS, TRACE)) is None         # no pool line
    assert _read(metric, _run(STEPS, dict(
        TRACE, ops={"fusion f32[8]": [1.0, 10]}), log_setup=LOG)) is None
    idle = [dict(s, **{"aphrodite:gpu_cache_usage_perc": 0.0})
            for s in STEPS]
    assert _read(metric, _run(idle, TRACE, log_setup=LOG)) is None
    run = _run(STEPS, TRACE, log_setup=LOG)
    run.peaks = None
    assert _read(metric, run) is None
    # a configuration without the rule's keys (Mistral's)
    assert _read(metric, _run(STEPS, TRACE, log_setup=LOG,
                              cell=OLD_CELLS[0])) is None


def test_the_mqa_roofline_count_by_hand():
    config = cells.load_cell(CELL, ROOT).config
    mqa = cells.load_module(os.path.join(ROOT, "perf", "rooflines",
                                         "paged_decode_mqa.py"))
    assert mqa.page_layers(config) == 2
    assert mqa.page_layers(dict(config, num_hidden_layers=14)) == 1
    # 128 rows at 1,200 tokens: 1,024 B a token over both layers, 512 B
    # in one; the rows' queries and outputs are 20 heads of 128 in bf16
    live = 128 * 1200 * 1024
    moved, computed = mqa.count(config, live, 129)
    assert moved == 128 * 1200 * 512 + 2 * 129 * 20 * 128 * 2
    assert computed == 4.0 * 128 * 20 * 128 * 1200
    # bound by bytes on a v5e, by a factor of twelve
    assert 11 < (moved / 819e9) / (computed / 197e12) < 13
    # the accepted count would spread the pool over all 28 layers
    old = cells.load_function(os.path.join(
        ROOT, "perf", "rooflines", "paged_decode.py"), "count")
    assert old(config, live, 0)[0] == pytest.approx(live / 28)
    # the state layers' counts at this configuration's sizes: a decode
    # step of 128 rows moves 2.6 GB of state over the 26 layers
    ssm = cells.load_module(os.path.join(ROOT, "perf", "rooflines",
                                         "ssm_scan.py"))
    moved, computed = ssm.update_count(config, 128)
    assert 2.5e9 < 26 * moved < 2.7e9
    assert computed == 7 * 128 * 16 * 5120
    moved, _ = ssm.scan_count(config, 512, 1)
    assert 32e6 < moved < 33e6


# ---- the manifest's new entries ----

def test_the_manifest_gains_a_configuration_a_cell_and_two_metrics():
    bench = _bench()
    assert [c["name"] for c in bench["configs"]][-1] == "jamba2-3b-bf16"
    assert len(bench["configs"][-1]["why"]) <= 200
    names = [w["name"] for w in bench["workloads"]]
    assert names == OLD_CELLS + [CELL]
    new = bench["workloads"][-1]
    assert (new["config"], new["traffic"], new["chips"]) == (
        "jamba2-3b-bf16", "reason-512", 1)
    assert len(new["why"]) <= 200
    for said in ("128 callers", "28%", "by nature"):
        assert said in new["why"]
    by_name = {m["name"]: m for m in
               bench["end_to_end"] + bench["per_layer"]}
    listed = [m["name"] for m in bench["per_layer"]]
    # appended: an entry put in the middle of a list reads as a change
    # to what was there
    assert listed[-2:] == list(NEW)
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
        assert os.path.isfile(cells.reader_path(ROOT, "layers", name))
    assert by_name["decode_attn_mqa_roofline_pct.batch"]["layer"] == \
        by_name["decode_attn_roofline_pct.batch"]["layer"]
    assert by_name["ssm_slot_waits.batch"]["layer"] == \
        by_name["ssm_slots_used_pct.batch"]["layer"]
    assert by_name["ssm_slot_waits.batch"]["unit"] == "requests"
    assert by_name["out_tok_s"]["workloads"] == OLD_CELLS + [CELL]
    assert "workloads" not in by_name["setup_s"]
    assert "workloads" not in by_name["programs_warmed"]
    # the 27 metrics every cell reports, and the state's three
    every = [m["name"] for m in bench["per_layer"]
             if m.get("workloads") == OLD_CELLS + [CELL]]
    assert len(every) == 27
    for name in SSM:
        assert by_name[name]["workloads"] == [OLD_CELLS[2], CELL]
    # the shares whose counts are wrong or absent here stay the older
    # cells'
    for name in ("decode_attn_roofline_pct.batch",
                 "decode_attn_groups_roofline_pct.batch",
                 "decode_attn_shared_roofline_pct.batch",
                 "moe_experts_roofline_pct.batch",
                 "moe_experts_touched_pct.batch",
                 "window_kv_held_pct.batch"):
        assert CELL not in by_name[name]["workloads"]
    reported = {m["name"] for m in cells.load_cell(CELL, ROOT).per_layer}
    assert reported == set(every) | set(SSM) | set(NEW) | {
        "programs_warmed"}
    assert len(reported) == 33
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
