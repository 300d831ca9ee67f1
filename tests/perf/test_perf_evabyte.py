"""Tests of what PR 48 adds to the benchmark as new files and entries:
the configuration `evabyte-6.5b-bf16` (the catalog row cut in depth
alone), its two parameter counts against the reference's tree, the
reference's pooling against a dozen lines of NumPy and its ranges at
the published head size (a chunk's pooling neither flat nor one-hot,
the summaries carrying weight), the reference's stages and both
controls through the harness's own child at a toy size, the traffic
`doc-5k`, the three new per-layer readers and the older one the cell
joins on hand-made runs of the new cell, the roofline count by hand,
and the manifest's new entries. No chip."""
import io
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from perf import cells, loops, reference_child, weights
from perf import run as perf_run

ROOT = cells.ROOT
CELL = "evabyte-6.5b-bf16.doc-5k"
OLD_CELLS = ["mistral-7b-w4a8.batch", "smallthinker-21ba3b-bf16.batch-8k",
             "phi-4-mini-flash-bf16.reason-2k", "jamba2-3b-bf16.reason-512",
             "laguna-s-2.1-bf16.agent-4k"]
NEW = ("decode_attn_summary_roofline_pct.batch",
       "eva_summary_pages_pct.batch", "eva_windows_closed.batch")
JOINED = ("window_kv_held_pct.batch",)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
ref = cells.load_module(os.path.join(ROOT, "perf", "references",
                                     "evabyte.py"))


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


# ---- the configuration and the cell ----

@pytest.mark.skipif(not os.path.isfile(CATALOG), reason="no catalog here")
def test_the_configuration_is_the_catalog_row_cut_in_depth_alone():
    config = cells.load_cell(CELL, ROOT).config
    perf = config["perf"]
    with open(CATALOG) as f:
        (row,) = [r for r in map(json.loads, f) if r["name"] == "EvaByte"]
    published = row["config"]
    assert row["source_url"] == perf["source"]
    assert set(config) == set(published) | {"architectures", "torch_dtype",
                                            "perf"}
    for key, value in published.items():
        if key == "num_hidden_layers":
            assert (config[key], value) == (8, 32)
        else:
            assert config[key] == value, key
    assert perf["reduced"] == ["num_hidden_layers"]
    entry = {c["name"]: c for c in _bench()["configs"]}["evabyte-6.5b-bf16"]
    assert entry["reduced"] == perf["reduced"]
    assert entry["source"] == perf["source"]
    # items (a) to (h), each with its reason, and the paper named
    assert [a[:3] for a in perf["assumed"]] == [
        f"({c})" for c in "abcdefgh"]
    assert "Control Variates" in perf["assumed"][0]
    for said in ("four pipeline stages of 8", "BOTH the embedding and the "
                 "head", "1,630,932,992", "6,488,330,240", "32 layers"):
        assert said in perf["deployment"], said
    assert perf["engine_args"] == ["--max-model-len", "8192",
                                   "--max-num-seqs", "24"]
    assert perf["env"] == {"APHRODITE_SPEC": "0"}
    assert perf["kernel_families"] == ["decode_attention", "kv_write",
                                       "prefill_attention"]
    assert perf["matmul_peak"] == "bf16_flops_per_s"
    assert (perf["reference"], perf["reference_replies"]) == ("evabyte", 2)
    assert sorted(perf["controls"]) == ["act8", "kv8"]
    assert perf["controls"]["kv8"]["kv"] == "float8_e5m2"
    assert "pooled" in perf["controls"]["kv8"]["why"]


def test_the_parameters_to_the_parameter():
    """ISSUE 48's arithmetic: what the chip holds (the reference's
    tree, which `tests/models/test_evabyte.py` holds to the program's)
    and what one token multiplies."""
    config = cells.load_cell(CELL, ROOT).config
    tree = ref.tree(config)
    sizes = {b: sum(int(np.prod(s[0])) for s in v.values())
             for b, v in tree.items()}
    assert sum(sizes.values()) == 1_630_932_992

    def layer(i, part=""):
        return sum(n for b, n in sizes.items()
                   if b.startswith(f"model.layers.{i}.{part}"))
    assert layer(0) == layer(7) == 202_391_552
    assert layer(0, "mlp") == 135_266_304
    assert layer(0, "self_attn.") == 67_108_864      # four projections
    assert sizes["model.layers.0.self_attn"] == 2 * 32 * 128   # phi, mu
    assert sizes["model.embed_tokens"] == 1_310_720
    assert sizes["lm_head"] == 8 * 1_310_720
    assert sizes["model.norm"] == 4096
    assert 32 * 202_391_552 + 1_310_720 + 10_485_760 + 4096 == \
        6_488_330_240
    assert config["perf"]["parameters"] == 1_620_312_064 == \
        8 * (67_108_864 + 135_266_304) + 1_310_720
    assert "SERVED rows" in config["perf"]["parameters_why"]
    # every stage is made by some stage function, all layers by ONE
    assert [fn for fn, _ in ref.stages(config)] == \
        ["embed"] + ["layer"] * 8 + ["logits"]
    assert ref.__doc__.count("aphrodite") == 0


def test_what_the_configuration_makes_of_the_cache_layer():
    from aphrodite_tpu.common.config import ModelConfig
    from aphrodite_tpu.transformers_utils.configs import EvaByteConfig
    config = cells.load_cell(CELL, ROOT).config
    hf = EvaByteConfig(**{k: v for k, v in config.items() if k not in (
        "perf", "architectures", "model_type", "torch_dtype")})
    model = ModelConfig("x", dtype="bfloat16", max_model_len=8192,
                        hf_config=hf)
    groups = model.get_page_groups()
    assert groups.kinds == ("pooled",) and groups.layers_per_group == 8
    assert groups.pooled_pages(16) == (128, 8)
    assert model.get_kv_heads_per_slot() == [32] * 8
    # a page id: 8 pairs of 16 tokens x 32 heads x 128 in bfloat16
    assert 8 * 2 * 16 * 32 * 128 * 2 == 2 << 20
    # the longest need of a row of the cell, at position 6,144
    assert 16 + 128 + 8 == 152 and 0.9 * 4780 / 152 > 24


def test_the_traffic_is_5k_prompts_from_24_callers():
    cell = cells.load_cell(CELL, ROOT)
    loop, params = cell.traffic["loop"], cell.traffic["params"]
    assert (loop["kind"], loop["clients"], loop["journal_callers"],
            loop["ramp_groups"]) == ("closed", 24, 1, [4])
    args = cell.config["perf"]["engine_args"]
    assert int(args[args.index("--max-num-seqs") + 1]) == 24
    vocab = cell.config["vocab_size"]
    shapes = cell.generator(params, 3000000877, 0, 24, None, vocab)
    # two whole windows and 1,280 bytes of a third: byte ids and the
    # 64 specials past 2
    assert {len(s["prompt"]) for s in shapes} == {5376} == \
        {2 * cell.config["window_size"] + 1280}
    assert all(3 <= t < 320 for s in shapes for t in s["prompt"])
    outs = sorted(s["max_tokens"] for s in shapes)
    assert 384 <= outs[0] < 420 and 1120 < outs[-1] <= 1152
    assert 760 < sum(outs) / 24 < 776
    # half the replies pass position 6,144 and close a window
    assert sum(5376 + n > 6144 for n in outs) == 12
    assert max(5376 + n for n in outs) <= 6528 < 8192
    assert not any(s["stream"] for s in shapes)
    assert all(s["sampling"] == {"temperature": 0.0} for s in shapes)
    # a group of callers queued is a third of the admission limit
    assert 4 * 5376 == 21504 and 3 * 21504 < 8 * 8192
    canary = cell.traffic["canary"]
    assert canary["prompt_lens"] == [5296, 5328, 5360] and \
        canary["max_tokens"] == 16
    for n in canary["prompt_lens"]:
        assert 2 * 2048 + 1024 < n < 5376     # the cell's three chunks
    assert reference_child.padded(5376 + 1152) == 6656
    assert 6656 % 16 == 0 and 6656 % ref.QUERY_BLOCK == 0
    assert len(cell.traffic["why"]) > 0


# ---- the reference's pooling, and what its ranges make of it ----

def test_the_pooling_against_a_dozen_lines_of_numpy():
    rng = np.random.default_rng(0)
    k = rng.normal(size=(2, 48, 3, 8))
    v = rng.normal(size=(2, 48, 3, 8))
    phi, mu = rng.normal(size=(3, 8)), rng.normal(size=(3, 8))
    with jax.default_matmul_precision("highest"):
        kbar, vbar, weights_ = ref.pool(
            jnp.asarray(k, jnp.float32), jnp.asarray(v, jnp.float32),
            jnp.asarray(phi, jnp.float32), jnp.asarray(mu, jnp.float32), 16)
    assert kbar.shape == vbar.shape == (2, 3, 3, 8)
    for b in range(2):
        for c in range(3):
            for h in range(3):
                keys = k[b, 16 * c:16 * c + 16, h]
                score = keys @ phi[h] / np.sqrt(8)
                p = np.exp(score - score.max())
                p /= p.sum()
                np.testing.assert_allclose(weights_[b, c, :, h], p,
                                           atol=1e-5)
                np.testing.assert_allclose(kbar[b, c, h],
                                           p @ keys + mu[h], atol=1e-5)
                np.testing.assert_allclose(
                    vbar[b, c, h], p @ v[b, 16 * c:16 * c + 16, h],
                    atol=1e-5)


def test_a_query_sees_its_windows_keys_and_the_chunks_behind_it():
    """`attend` against the definition, position by position, at a
    window of 32 and a chunk of 4: exact keys from the window's first
    position to the query's own, a pooled key for every chunk of the
    windows behind and none of its own, ONE softmax over both."""
    rng = np.random.default_rng(1)
    t, heads, head, window, chunk = 80, 2, 8, 32, 4
    q, k, v = (rng.normal(size=(1, t, heads, head)) for _ in range(3))
    kbar, vbar = (rng.normal(size=(1, t // chunk, heads, head))
                  for _ in range(2))
    with jax.default_matmul_precision("highest"):
        mixed, mass = ref.attend(*(jnp.asarray(a, jnp.float32) for a in
                                   (q, k, v, kbar, vbar)), window, chunk)
    for pos in (0, 5, 31, 32, 33, 63, 64, 79):
        first = pos // window * window
        for h in range(heads):
            keys = np.concatenate([kbar[0, :first // chunk, h],
                                   k[0, first:pos + 1, h]])
            values = np.concatenate([vbar[0, :first // chunk, h],
                                     v[0, first:pos + 1, h]])
            score = keys @ q[0, pos, h] / np.sqrt(head)
            p = np.exp(score - score.max())
            p /= p.sum()
            np.testing.assert_allclose(mixed[0, pos, h], p @ values,
                                       atol=1e-5)
            np.testing.assert_allclose(mass[0, pos, h],
                                       p[:first // chunk].sum(), atol=1e-5)


def test_the_ranges_make_the_pooling_count_at_the_published_head_size():
    """One layer at 2 heads of 128 under the reference's ranges, a
    sequence of the cell's length (two windows behind): a chunk's
    largest pooling weight is between 0.15 and 0.6 on average (neither
    flat, 1/16, nor one-hot), a query past the second edge puts a
    tenth or more of its softmax on pooled keys on average, and
    without `mu` it puts a fiftieth there."""
    config = dict(hidden_size=256, num_attention_heads=2,
                  intermediate_size=512, num_hidden_layers=1,
                  vocab_size=320, num_pred_heads=8, window_size=2048,
                  chunk_size=16, rope_theta=100000, rms_norm_eps=1e-5,
                  torch_dtype="float32")
    tree = ref.tree(config)
    buckets = ref.stages(config)[1][1]
    w = weights.make({l: tree[b] for l, b in buckets.items()},
                     weights.subkeys(tree, weights.all_keys(tree, 7),
                                     buckets))
    # the normed stream: unit spread
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 5632, 256))
    with jax.default_matmul_precision("highest"):
        _, mass, pooling = ref.attention(config, w, x, ref.Precision())
        bare = dict(w, self_attn=dict(
            w["self_attn"],
            adaptive_mu_k=jnp.zeros_like(w["self_attn"]["adaptive_mu_k"])))
        _, without_mu, _ = ref.attention(config, bare, x, ref.Precision())
    largest = float(np.asarray(pooling).max(axis=2).mean())
    assert 0.15 < largest < 0.6
    assert float(np.asarray(mass)[0, :2048].max()) == 0.0
    # the cell's decode rows: 1,280 bytes and more into the third
    # window (a query at a window's start sees summaries and little
    # else, whatever the ranges)
    seen = np.asarray(mass)[0, 4096 + 1280:]
    assert seen.mean() > 0.1
    assert np.asarray(without_mu)[0, 4096 + 1280:].mean() < 0.05


# ---- the reference through the harness's child ----

def _tiny():
    return dict(
        architectures=["EvaByteForCausalLM"], model_type="evabyte",
        vocab_size=320, hidden_size=128, intermediate_size=256,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=4,
        max_position_embeddings=512, rms_norm_eps=1e-5, rope_theta=100000,
        window_size=16, chunk_size=4, num_pred_heads=8,
        torch_dtype="float32",
        perf=dict(reference="evabyte", controls=dict(
            kv8=dict(kv="float8_e5m2"), act8=dict(act_bits=8))))


def test_the_stages_and_both_controls_through_the_harness_child(
        tmp_path, monkeypatch):
    """`perf/reference_child.py` as the harness starts it, on the CPU at
    a toy size (a window of 16, so 16 prompt ids and 112 more pass
    seven edges): every stage maps the stream to itself and reports
    its share; a greedy continuation of the reference itself reads no
    gap at all; a control's gaps are none or more, and `kv8` (two bits
    of mantissa in K, V and the pooled keys) reads some. (A gap is
    read only where the first token changes, and three dense layers
    amplify little: `act8` may read none at this size.)"""
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
    job = dict(root=ROOT, config=config, name="evabyte", seed=5,
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


def test_kv8_rounds_the_pooled_keys_too():
    """`kv8` rounds K, V AND the pooled keys and values (a cache of 8
    bits would hold the summary pages in 8 bits): past an edge it
    moves a layer more than rounding the exact keys alone."""
    config = _tiny()
    tree, buckets = ref.tree(config), ref.stages(config)[1][1]
    w = weights.make({l: tree[b] for l, b in buckets.items()},
                     weights.subkeys(tree, weights.all_keys(tree, 2),
                                     buckets))
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 64, 128))
    fp8 = reference_child.lowered(dict(kv="float8_e5m2"))["kv"]
    seen = []
    with jax.default_matmul_precision("highest"):
        sound = ref.layer(config, w, x, ref.Precision())
        pool = ref.pool
        lowered = ref.layer(config, w, x, ref.Precision(kv=fp8))

        def spy(*args):
            out = pool(*args)
            seen.append(out[0])
            return out
        ref.pool = spy
        try:
            ref.layer(config, w, x, ref.Precision(kv=fp8))
        finally:
            ref.pool = pool
    # what `pool` returns is rounded after it: the pooled keys reach
    # attention in float8's few values
    assert len(seen) == 1
    assert float(jnp.abs(lowered - sound)[0, 16:].max()) > 1e-2
    assert float(jnp.abs(lowered - sound)[0, :16].max()) > 1e-3


# ---- the readers on hand-made runs of the new cell ----

def _run(samples, trace=None, seconds=10.0, cell=CELL):
    window = loops.Window(t0=100.0, replies=[], t_end=0.0,
                          seconds=seconds * max(1, len(samples) - 1))
    run = perf_run.Run(
        cell=cells.load_cell(cell, ROOT), window=window, t_start=0.0,
        samples=[(100.0 + i * seconds, s) for i, s in enumerate(samples)],
        steady_until=100.0 + window.seconds, log_setup="",
        log_window="", faults=[], trace=trace)
    run.peaks = cells.load_peaks("TPU v5 lite")
    return run


def _totals(**counters):
    return {f"aphrodite:{k}_total": float(v) for k, v in counters.items()}


#: two readings 10 s apart: 700 decode steps of 24 rows; a row holds 18
#: summary pages and 90 of its window on average, where every key kept
#: would be 366 pages; 11 windows closed by decode rows, 9 by prompts
_DECODE, _ROWS = 700, 24
_SUMMARY, _WINDOW, _WHOLE = 18 * _ROWS, 90 * _ROWS, 366 * _ROWS
STEPS = [
    _totals(decode_attn_steps=900, kv_pages_live_window=1e6,
            kv_pages_live_summary=2e5, window_pages_unwindowed=9e6,
            eva_windows_closed_decode=40, eva_windows_closed_prompt=100),
    _totals(decode_attn_steps=900 + _DECODE,
            kv_pages_live_window=1e6 + _DECODE * (_SUMMARY + _WINDOW),
            kv_pages_live_summary=2e5 + _DECODE * _SUMMARY,
            window_pages_unwindowed=9e6 + _DECODE * _WHOLE,
            eva_windows_closed_decode=51, eva_windows_closed_prompt=109)]
#: the traced 2 s: 120 decode steps of 8 calls, 0.9 ms a call
OPS = {"_paged_decode_impl bf16[25,4,8,128] tpu_custom_call": [0.864, 960],
       "fusion bf16[24,4096]": [0.3, 5000]}
TRACE = dict(busy_s=1.95, window_s=2.0, device_ops=[], idle_gaps=[],
             ops=OPS)
_PAGE_LAYER = 16 * 32 * 128 * 2 * 2      # a page of one layer, K and V
WANT = {
    # a step's eight calls: every live page once a layer, 25 rows of 32
    # heads in and out; the bytes bind
    "decode_attn_summary_roofline_pct.batch":
        (8 * ((_SUMMARY + _WINDOW) * _PAGE_LAYER +
              2 * 25 * 32 * 128 * 2) / 819e9) / (0.864 / 960 * 8) * 100,
    "eva_summary_pages_pct.batch": 18 / 108 * 100,
    "eva_windows_closed.batch": 1.1,
    "window_kv_held_pct.batch": 108 / 366 * 100}


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
    assert entry["unit"] == ("windows/s" if "closed" in metric else "%")
    assert entry["source"] == ("device_trace" if "roofline" in metric
                               else "program_counter")


@pytest.mark.parametrize("metric", NEW)
def test_a_new_reader_that_finds_nothing_reads_nothing(metric):
    """The parent's program exports none of the new counters; a
    `--trace 0` run has no trace, a CPU trace none of the kernel's
    names, an unknown device no peaks, another configuration no
    `window_size`. None, never 0 and never an exception."""
    assert _read(metric, _run([], TRACE)) is None
    parent = [{k: v for k, v in s.items()
               if "summary" not in k and "eva_" not in k} for s in STEPS]
    assert _read(metric, _run(parent, TRACE)) is None
    if "roofline" not in metric:
        return
    assert _read(metric, _run(STEPS)) is None
    assert _read(metric, _run(STEPS, dict(
        TRACE, ops={"fusion f32[8]": [1.0, 10]}))) is None
    run = _run(STEPS, TRACE)
    run.peaks = None
    assert _read(metric, run) is None
    for cell in OLD_CELLS[:2]:
        assert _read(metric, _run(STEPS, TRACE, cell=cell)) is None


def test_a_cell_that_closes_no_window_reads_zero_and_not_nothing():
    still = [dict(s, **{"aphrodite:eva_windows_closed_decode_total": 40.0})
             for s in STEPS]
    assert _read("eva_windows_closed.batch", _run(still)) == 0.0


def test_the_roofline_count_by_hand():
    config = cells.load_cell(CELL, ROOT).config
    count = cells.load_module(os.path.join(
        ROOT, "perf", "rooflines", "paged_decode_summary.py")).count
    # 24 rows of 18 summary pages and 90 of a window, 25 rows a call
    moved, computed = count(config, 18 * 24, 90 * 24, 25)
    assert moved == 8 * (108 * 24 * 16 * 2 * 32 * 128 * 2 +
                         2 * 25 * 32 * 128 * 2)
    assert computed == 8 * 4.0 * 128 * 32 * 108 * 24 * 16
    # ISSUE 48's arithmetic: some 5.2-5.5 GB of pages a decode step,
    # bound by bytes 240 times over (one query row a KV head: 2
    # operations a byte)
    assert 5.2e9 < moved < 5.6e9
    assert 200 < (moved / 819e9) / (computed / 197e12) < 280
    # a pooled key counts as one key: a summary page costs what a
    # window page costs
    assert count(config, 108 * 24, 0, 25) == count(config, 0, 108 * 24, 25)


# ---- the manifest's new entries ----

def test_the_manifest_gains_a_configuration_a_cell_and_three_metrics():
    bench = _bench()
    assert [c["name"] for c in bench["configs"]][-1] == "evabyte-6.5b-bf16"
    names = [w["name"] for w in bench["workloads"]]
    assert names == OLD_CELLS + [CELL]
    new = bench["workloads"][-1]
    assert (new["config"], new["traffic"], new["chips"]) == (
        "evabyte-6.5b-bf16", "doc-5k", 1)
    for said in ("24 callers", "5,376", "close a window", "host and idle"):
        assert said in new["why"], said
    # every `why` and `source` of the file, old and new
    for entry in bench["configs"] + bench["workloads"]:
        assert 0 < len(entry["why"]) <= 200, entry["name"]
    assert all(len(c["source"]) <= 200 for c in bench["configs"])
    by_name = {m["name"]: m for m in
               bench["end_to_end"] + bench["per_layer"]}
    listed = [m["name"] for m in bench["per_layer"]]
    # appended: an entry put in the middle of a list reads as a change
    assert listed[-3:] == list(NEW)
    for name in NEW:
        assert set(by_name[name]) == {"name", "unit", "better", "source",
                                      "layer", "moves", "workloads"}
        assert by_name[name]["workloads"] == [CELL]
        assert os.path.isfile(cells.reader_path(ROOT, "layers", name))
    assert by_name[NEW[0]]["layer"] == \
        by_name["decode_attn_roofline_pct.batch"]["layer"]
    assert by_name[NEW[1]]["layer"] == by_name[NEW[2]]["layer"] == \
        by_name["window_kv_held_pct.batch"]["layer"]
    assert by_name["out_tok_s"]["workloads"] == OLD_CELLS + [CELL]
    assert "workloads" not in by_name["setup_s"]
    assert "workloads" not in by_name["programs_warmed"]
    # the 28 metrics every cell reports, and the one it joins
    every = [m["name"] for m in bench["per_layer"]
             if m.get("workloads") == OLD_CELLS + [CELL]]
    assert len(every) == 28
    assert by_name[JOINED[0]]["workloads"] == [
        OLD_CELLS[1], OLD_CELLS[2], OLD_CELLS[4], CELL]
    # the shares whose counts are wrong or absent here stay the older
    # cells'
    for name, metric in by_name.items():
        if ("roofline" in name and name != NEW[0]) or \
                name.startswith(("moe_", "ssm_")):
            assert CELL not in metric["workloads"], name
    reported = {m["name"] for m in cells.load_cell(CELL, ROOT).per_layer}
    assert reported == set(every) | set(JOINED) | set(NEW) | {
        "programs_warmed"}
    assert len(reported) == 33
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
