"""Tests of what decides `correct` by arithmetic: the plain reference
(`perf/references/llama.py`), the weights made from the seed
(`perf/weights.py`), the numbers compared (`perf/reference.py`), the
child that runs them, its control, and a run whose timed path is
broken underneath. Also what a later PR may add as files and entries
alone. No chip; the program is imported only to be compared with."""
import argparse
import copy
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from perf import cells, loops, reference, trace, weights
from perf import reference_child
from perf import run as perf_run
from perf.client import Reply

ROOT = cells.ROOT
REHEARSAL = "perf/rehearse/manifest.json"
llama = cells.load_module(os.path.join(ROOT, "perf", "references",
                                       "llama.py"))


def _tiny(**perf):
    with open(os.path.join(ROOT, "perf", "rehearse", "tiny.json")) as f:
        config = json.load(f)
    config["perf"].update(perf)
    return config


#: a quantised toy: one group of 128 needs 128 input rows
GPTQ = dict(_tiny(reference_quant={"bits": 4, "group_size": 128}),
            hidden_size=128, intermediate_size=256,
            torch_dtype="bfloat16")


def _program_model(config):
    from transformers import LlamaConfig
    from aphrodite_tpu.modeling.layers.quantization.gptq import GPTQConfig
    from aphrodite_tpu.modeling.models.llama import LlamaForCausalLM
    hf = LlamaConfig(**{k: v for k, v in config.items()
                        if k not in ("perf", "architectures",
                                     "model_type", "torch_dtype")})
    method = GPTQConfig().get_linear_method() \
        if config["perf"].get("reference_quant") else None
    return LlamaForCausalLM(hf, jnp.dtype(config["torch_dtype"]), method)


# ---- the weights are data, and the benchmark makes them ----

def _served_weights(config, seed, monkeypatch):
    """What the server holds: the program's loader, called for
    `--load-format dummy` after `perf/serve_child.py` has put the
    benchmark's recipe in the place of the program's."""
    from aphrodite_tpu.modeling import loader
    from perf import serve_child
    monkeypatch.setattr(loader, "initialize_dummy_params",
                        loader.initialize_dummy_params)
    serve_child.serve_weights_of(config)
    return loader.initialize_dummy_params(_program_model(config), seed=seed)


@pytest.mark.parametrize("config", [_tiny(), GPTQ],
                         ids=["float32", "gptq-bfloat16"])
def test_the_server_holds_the_weights_the_reference_makes(config,
                                                          monkeypatch):
    seed = 852516373
    theirs = _served_weights(config, seed, monkeypatch)
    tree = llama.tree(config)
    assert {b: {n: (tuple(a.shape), a.dtype.name) for n, a in w.items()}
            for b, w in theirs.items()} == {
        b: {n: spec[:2] for n, spec in w.items()} for b, w in tree.items()}
    keys = weights.all_keys(tree, seed)
    # stage by stage, as the child makes them; every bucket is in one
    seen = set()
    for _, buckets in llama.stages(config):
        made = weights.make({local: tree[b] for local, b in buckets.items()},
                            weights.subkeys(tree, keys, buckets))
        for local, w in made.items():
            seen.add(buckets[local])
            for name, mine in w.items():
                np.testing.assert_array_equal(
                    np.asarray(mine), np.asarray(theirs[buckets[local]]
                                                 [name]))
    assert seen == set(theirs)
    gains = np.asarray(theirs["model.norm"]["weight"], np.float32)
    assert 0.75 <= gains.min() < gains.max() <= 1.25
    if config is GPTQ:
        leaves = theirs["model.layers.0.mlp.down_proj"]
        assert set(leaves) == {"qweight", "qzeros", "scales", "g_idx"}
        assert not np.asarray(leaves["g_idx"]).any()
        assert np.asarray(leaves["qweight"]).any()
        # dequantised, a projection has the mean 0 and gives its
        # output the spread that the reference states for it
        for name, spread in llama.SPREAD.items() | {("self_attn.o_proj",
                                                     1.0)}:
            w = np.asarray(llama.dequantize(
                theirs["model.layers.0." + name], 4, 128))
            assert abs(w.mean()) < 0.1 * w.std()
            assert w.std() * w.shape[0] ** 0.5 == pytest.approx(spread,
                                                                rel=0.1)


def test_on_a_mesh_each_leaf_is_put_where_the_program_shards_it(monkeypatch):
    from jax.sharding import Mesh, NamedSharding
    from aphrodite_tpu.common.config import ParallelConfig
    from aphrodite_tpu.modeling import loader
    from perf import serve_child
    if len(jax.devices()) < 2:
        pytest.skip("one device")
    config = _tiny()
    mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(1, 1, 1, 2),
                ParallelConfig.MESH_AXES)
    model = _program_model(config)
    monkeypatch.setattr(loader, "initialize_dummy_params",
                        loader.initialize_dummy_params)
    serve_child.serve_weights_of(config)
    whole = loader.initialize_dummy_params(model, seed=9)
    split = loader.initialize_dummy_params(model, seed=9, mesh=mesh)
    specs = model.param_specs()
    for bucket, leaves in split.items():
        for name, leaf in leaves.items():
            assert leaf.sharding == NamedSharding(
                mesh, specs[bucket][name]), (bucket, name)
            np.testing.assert_array_equal(np.asarray(leaf),
                                          np.asarray(whole[bucket][name]))
    qkv = split["model.layers.0.self_attn.qkv_proj"]["weight"]
    assert len({s.index for s in qkv.addressable_shards}) == 2


def test_a_tree_that_is_not_the_programs_ends_the_server(monkeypatch):
    config = _tiny()
    config["intermediate_size"] += 64   # the model is built from the copy
    with pytest.raises(SystemExit, match="mlp.gate_up_proj"):
        from aphrodite_tpu.modeling import loader
        from perf import serve_child
        monkeypatch.setattr(loader, "initialize_dummy_params",
                            loader.initialize_dummy_params)
        serve_child.serve_weights_of(_tiny())
        loader.initialize_dummy_params(_program_model(config), seed=1)


def _reference_logits(config, params, ids, skip=None, shares=None):
    """The reference over one sequence; `skip` leaves a stage out,
    `shares` collects what each layer added to the residual stream."""
    x = jnp.asarray([ids])
    p = llama.Precision()
    for i, (fn_name, buckets) in enumerate(llama.stages(config)):
        if i == skip:
            continue
        w = {local: params[b] for local, b in buckets.items()}
        y = getattr(llama, fn_name)(config, w, x, p)
        if shares is not None and fn_name == "layer":
            shares.append(float(jnp.linalg.norm(y - x) /
                                jnp.linalg.norm(x)))
        x = y
    return np.asarray(x[0])


def test_every_layer_counts_under_the_benchmarks_weights():
    """A layer adds a good share of the residual stream (under the
    program's own dummy weights it added 1e-5 of it at this size and
    1.4e-3 at Mistral's), so a fault inside a layer reaches the
    logits: the second layer left out moves them by more than a third
    of their spread."""
    config = _tiny()
    params = weights.whole(llama.tree(config), llama.stages(config), 3)
    ids = np.random.default_rng(0).integers(3, 512, 40).tolist()
    shares = []
    whole = _reference_logits(config, params, ids, shares=shares)
    assert len(shares) == 2 and min(shares) > 0.2, shares
    without = _reference_logits(config, params, ids, skip=2)
    assert np.abs(whole - without).max() > 0.3 * whole.std()


def test_gptq_dequantisation_against_the_programs_on_a_random_group():
    from aphrodite_tpu.modeling.layers.quantization.gptq import GPTQConfig
    rng = np.random.default_rng(7)
    n_in, n_out = 256, 64
    w = dict(
        qweight=jnp.asarray(rng.integers(-2 ** 31, 2 ** 31, (n_in // 8,
                                                             n_out)),
                            jnp.int32),
        qzeros=jnp.asarray(rng.integers(-2 ** 31, 2 ** 31, (2, n_out // 8)),
                           jnp.int32),
        scales=jnp.asarray(rng.uniform(-1e-3, 1e-3, (2, n_out)),
                           jnp.bfloat16),
        # the program reads the group of a row from `g_idx`; without
        # act-order the format puts row i in group i // 128
        g_idx=jnp.arange(n_in, dtype=jnp.int32) // 128)
    theirs = GPTQConfig().get_linear_method().dequantize(w, jnp.float32)
    mine = llama.dequantize(w, 4, 128)
    np.testing.assert_array_equal(np.asarray(mine), np.asarray(theirs))
    # both groups are in play, and codes run over all sixteen values
    assert len(np.unique(np.asarray(mine / w["scales"].astype(
        jnp.float32)[np.arange(n_in) // 128]).round())) > 16


# ---- the reference against the engine's own logits ----

PAGE, PAGES = 16, 8


@pytest.mark.parametrize("kv_heads", [2, 4], ids=["gqa", "mha"])
def test_reference_against_the_engine_prefill_then_decode(kv_heads):
    """Float32 on both sides, so the only difference is the order of
    sums: 1e-4 of the logits' spread is some hundred times float32's
    rounding over sums of 64 to 128 terms, and a ten-thousandth of what
    a dropped rotation or another rope base moves (tried: 1.7 and 1.4 of
    the spread; `rms_norm_eps` 1e-2 for 1e-6 moves 0.04)."""
    from aphrodite_tpu.modeling.input_metadata import InputMetadata
    from aphrodite_tpu.ops.kv_cache import padded_head_size
    config = dict(_tiny(), num_key_value_heads=kv_heads)
    model = _program_model(config)
    # the benchmark's own weights, as the server holds them: every
    # layer counts, so a wrong layer would show in the logits
    params = weights.whole(llama.tree(config), llama.stages(config), 0)
    head = padded_head_size(config["hidden_size"] //
                            config["num_attention_heads"])
    caches = [(jnp.zeros((PAGES, PAGE, kv_heads * head), jnp.float32),) * 2
              for _ in range(config["num_hidden_layers"])]
    rng = np.random.default_rng(1)
    ids = rng.integers(3, config["vocab_size"], 19).tolist()
    table = jnp.asarray([[0, 1, PAGES, PAGES]], jnp.int32)
    s = len(ids)
    hidden, caches = model(
        params, jnp.asarray([ids], jnp.int32),
        jnp.arange(s, dtype=jnp.int32)[None], caches,
        InputMetadata(slot_mapping=jnp.arange(s, dtype=jnp.int32),
                      block_tables=table,
                      context_lens=jnp.zeros((1,), jnp.int32),
                      prompt_lens=jnp.asarray([s], jnp.int32),
                      is_prompt=True))
    served = [np.asarray(model.compute_logits(params, hidden)[0, -1])]
    for _ in range(4):
        ids.append(int(served[-1].argmax()))
        cur = len(ids) - 1
        hidden, caches = model(
            params, jnp.asarray([[ids[-1]]], jnp.int32),
            jnp.asarray([[cur]], jnp.int32), caches,
            InputMetadata(slot_mapping=jnp.asarray([cur], jnp.int32),
                          block_tables=table,
                          context_lens=jnp.asarray([cur + 1], jnp.int32),
                          is_prompt=False))
        served.append(np.asarray(
            model.compute_logits(params, hidden)[0, 0]))
    mine = _reference_logits(config, params, ids)
    assert np.abs(mine - _reference_logits(
        config, weights.whole(llama.tree(config), llama.stages(config), 1),
        ids)).max() > 0.3 * mine.std()
    for step, logits in enumerate(served):
        want = mine[s - 1 + step]
        assert np.abs(logits - want).max() <= 1e-4 * want.std(), step
        assert int(logits.argmax()) == int(want.argmax())


# ---- the numbers compared ----

def _facts(logits, chosen):
    got = reference_child.position_facts(jnp.asarray(logits, jnp.float32),
                                         jnp.asarray(chosen))
    return [np.asarray(a).tolist() for a in got]


@pytest.mark.parametrize("case,chosen,mean,share,worst", [
    # the system chose the reference's first token everywhere
    ("agree", [3, 0, 1], 0.0, 0.0, 0.0),
    # a tie at position 1: either token costs nothing
    ("tie", [3, 2, 1], 0.0, 0.0, 0.0),
    # a flipped near-tie at position 2 costs what the tie was worth
    ("flip", [3, 0, 2], 0.01 / 3, 0.0, 0.01),
    # a wrong token at position 0 is most of the spread away
    ("wrong", [1, 0, 1], 2.5 / 3, 1 / 3, 2.5)])
def test_the_gap_statistic_on_hand_made_logits(case, chosen, mean, share,
                                               worst):
    logits = np.zeros((3, 6))
    logits[0, 3], logits[0, 1] = 3.0, 0.5
    logits[1, 0] = logits[1, 2] = 2.0
    logits[2, 1], logits[2, 2] = 2.0, 1.99
    picked, best, std = _facts(logits, chosen)
    # every position's spread made 1, so that a gap reads in logits
    stats = reference.gap_stats(picked, best, [1.0] * 3, threshold=1.0)
    assert stats["positions"] == 3
    assert stats["gap_mean"] == pytest.approx(mean, abs=1e-6)
    assert stats["gap_share"] == pytest.approx(share)
    assert stats["gap_worst"] == pytest.approx(worst, abs=1e-6)
    assert std == pytest.approx(np.std(logits, axis=-1).tolist())
    limits = dict(gap_mean=0.01, gap_share=0.0, gap_worst=0.05)
    lines, faults = reference.judge(stats, limits)
    assert len(lines) == 3 and all("limit" in ln for ln in lines)
    assert bool(faults) == (case == "wrong")
    assert len(faults) == (3 if case == "wrong" else 0)


def test_nothing_compared_is_a_fault_and_a_flat_position_costs_nothing():
    assert reference.gap_stats([], [], [], 1.0) == dict(positions=0)
    assert reference.judge(dict(positions=0), {})[1]
    flat = reference.gap_stats([0.0, 0.0], [0.0, 1.0], [0.0, 0.0], 1.0)
    assert flat["gap_worst"] == float("inf") and flat["gap_mean"] > 1e9


def _reply(sent, ended, n, streamed=True, ok=True):
    return Reply(due=sent, sent=sent, max_tokens=n, prompt_tokens=4,
                 block=0, done=ended if ok else None, ended=ended,
                 tokens=n, ids=list(range(n)) if streamed else None,
                 prompt=[9, 9, 9, sent] if streamed else None,
                 error=None if ok else "HTTP 500")


def test_the_replies_kept_are_the_canary_and_the_windows_streamed_ones():
    canary = [_reply(0.5, 0.8, 2, ok=False), _reply(0.0, 1.0, 2),
              _reply(1.0, 2.0, 2)]
    replies = [_reply(8.0, 12.0, 50),            # began before the window
               _reply(12.0, 14.0, 5),
               _reply(11.0, 13.0, 7, streamed=False),
               _reply(14.0, 17.0, 9),            # the longest inside
               _reply(17.0, 19.0, 6),
               _reply(19.0, 22.0, 30),           # still open at the close
               _reply(10.5, 11.0, 3, ok=False)]
    seqs, kept = reference.pick(canary, replies, 10.0, 20.0, 2)
    assert kept == 2 and len(seqs) == 3
    assert seqs[0] == dict(prompt=[9, 9, 9, 0.0], reply=[0, 1])
    assert [s["prompt"][-1] for s in seqs[1:]] == [14.0, 12.0]
    assert len(seqs[1]["reply"]) == 9
    # rows to spare are filled by those that began before it opened
    seqs, kept = reference.pick([], replies, 10.0, 20.0, 5)
    assert kept == 4
    assert [s["prompt"][-1] for s in seqs] == [14.0, 12.0, 17.0, 8.0]
    # where nothing began and ended inside, one that ended inside does
    seqs, kept = reference.pick([], replies[:1] + replies[5:], 10.0, 20.0,
                                2)
    assert kept == 1 and seqs[0]["prompt"][-1] == 8.0
    assert reference.pick(canary, [], 10.0, 20.0, 2) == (seqs[:0] + [
        dict(prompt=[9, 9, 9, 0.0], reply=[0, 1])], 0)


def test_the_reduction_counts_the_calls_of_every_operation():
    call = ('%_paged_decode_impl.{} = bf16[49,1,32,128]{{3,2,1,0}} '
            'custom-call(bf16[48,32,128]{{2,1,0}} %q), '
            'custom_call_target="tpu_custom_call"')
    events = [(call.format(i), i * 1e6, i * 1e6 + 4e5) for i in range(32)]
    events += [("%fusion.7 = f32[8]{0} fusion(f32[8]{0} %p)", 40e6, 41e6)]
    got = trace.reduce(dict(devices={"/device:TPU:0": events}, host=[]))
    name = "_paged_decode_impl bf16[49,1,32,128] tpu_custom_call"
    assert got["ops"][name] == [pytest.approx(32 * 4e-4), 32]
    assert got["ops"]["fusion f32[8]"] == [pytest.approx(1e-3), 1]
    assert len(got["ops"]) == 2
    # the line's breakdown keeps its shape: name and seconds, ten at most
    assert got["device_ops"][0] == [name, pytest.approx(32 * 4e-4)]
    two = trace.reduce(dict(devices={"/device:TPU:0": events,
                                     "/device:TPU:1": events}, host=[]))
    assert two["ops"][name] == [pytest.approx(32 * 4e-4), 32]


# ---- the child, its control, and a run broken underneath ----

@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """A checkout of its own, so that its `perf/.work` and compile
    cache are no other test's: the program linked, the benchmark
    copied."""
    root = tmp_path_factory.mktemp("checkout")
    os.symlink(os.path.join(ROOT, "aphrodite_tpu"), root / "aphrodite_tpu")
    shutil.copytree(os.path.join(ROOT, "perf"), root / "perf",
                    ignore=shutil.ignore_patterns(".cache", ".work",
                                                  "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    return str(root)


def _sequences(seed, n, prompt, reply, vocab=512):
    rng = np.random.default_rng(seed)
    return [dict(prompt=rng.integers(3, vocab, prompt).tolist(),
                 reply=rng.integers(3, vocab, reply).tolist())
            for _ in range(n)]


@pytest.mark.parametrize("seed", [5, 2_147_483_659, 3_000_000_019])
def test_the_control_comes_out_as_not_correct(checkout, seed):
    """The reference with the keys and values held at the nearest
    precision below the tiny configuration's (bfloat16 for float32),
    put in the program's place over 1,500 positions: the tokens it
    puts first lie below the reference's best by more than the limits
    allow. Nothing but what attention reads is lowered, so it is the
    layers that the limits hold. It need not decode, so the tokens in
    between are any."""
    cell = cells.load_cell("tiny.batch", checkout, REHEARSAL)
    seqs = _sequences(seed, 6, 6, 250)
    check = reference.start(cell, seed % 2 ** 31, seqs, 6, rows=6, cpu=True,
                            controls=["kv16"])
    stats, _, faults = check.finish()
    # the reference against itself, on any tokens: only the control
    # is judged here
    assert stats["positions"] == 1500
    numbers, lines, faults = check.controls["kv16"]
    assert numbers["positions"] == 1500
    assert faults and any("gap_worst" in f for f in faults), lines
    limits = cell.config["perf"]["reference_tolerances"]
    assert numbers["gap_worst"] > 3 * limits["gap_worst"]
    assert any("EXCEEDED" in ln for ln in lines)


def test_more_sequences_than_rows_go_through_in_blocks(checkout):
    """A builder who keeps more sequences than a run's `rows` (many
    journal callers, to read a limit's tail from one run) gets them
    compared block after block through the same programs: every
    position reads what it reads in one block, under the control too."""
    cell = cells.load_cell("tiny.batch", checkout, REHEARSAL)
    seqs = _sequences(11, 5, 6, 20)

    def read(rows):
        check = reference.start(cell, 11, seqs, 5, rows=rows, cpu=True,
                                controls=["kv16"])
        stats, _, _ = check.finish()
        with open(check.path_out) as f:
            return stats, json.load(f)
    (one, whole), (two, blocks) = read(5), read(2)
    assert one["positions"] == two["positions"] == 100
    assert blocks["positions"] == whole["positions"]
    for side in ("served", "kv16"):
        for k in ("chosen", "best", "std"):
            assert blocks[side][k] == pytest.approx(whole[side][k],
                                                    rel=1e-5, abs=1e-6)


@pytest.fixture(scope="module")
def rehearsed(checkout):
    """One rehearsal run of the real server at a toy size on the CPU,
    the reference behind `correct`; what it compared is kept."""
    out = subprocess.run(
        [sys.executable, os.path.join(checkout, "perf", "run.py"),
         "--rehearse", "--workload", "tiny.batch", "--seed", "3000000029",
         "--seconds", "3", "--trace", "0"],
        cwd=checkout, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    with open(os.path.join(checkout, "perf", ".work", "tiny.batch",
                           "reference_in.json")) as f:
        job = json.load(f)
    return out, json.loads(out.stdout.splitlines()[-1]), job


def test_the_rehearsal_holds_the_served_tokens_to_the_reference(rehearsed):
    out, line, job = rehearsed
    got = line["reference"]
    assert line["correct"] is True
    assert got["window_replies"] >= 2
    assert got["sequences"] == got["window_replies"] + 1
    assert got["positions"] == sum(len(s["reply"])
                                   for s in job["sequences"]) > 40
    # float32 on both sides: the served token is the reference's
    # first, but for a tie within float32's rounding (the two sides
    # sum in another order, and a host with other cores in yet another)
    limits = cells.load_cell("tiny.batch", ROOT, REHEARSAL).config[
        "perf"]["reference_tolerances"]
    assert got["gap_share"] == 0.0
    assert got["gap_worst"] <= 1e-5 < limits["gap_worst"]
    assert got["gap_mean"] <= limits["gap_mean"]
    # a layer adds a good share of the residual stream, so the
    # verdict covers attention and the MLP
    assert got["layer_share"] > 0.2
    # the journal caller's replies are rows of the full batch's steps
    assert any(len(s["prompt"]) == 64 for s in job["sequences"])
    assert job["seed"] == 3000000029 % 2 ** 31
    # each number beside its limit: the last lines of standard error
    tail = out.stderr.splitlines()[-3:]
    assert [ln.split()[2] for ln in tail] == list(reference.NUMBERS)
    assert all("(limit" in ln for ln in tail)


def _as_run(cell, job, alter=None):
    """What `measure()` would hand on, had the server sent the kept
    replies: the first alone, the others inside the window. `alter`
    is `(sequence, position)` of a reply token to alter, or a function
    of the sequences."""
    def reply(i, s):
        ids = list(s["reply"])
        if isinstance(alter, tuple) and i == alter[0]:
            ids[alter[1]] = (ids[alter[1]] + 1) % 500 + 3
        return Reply(due=10.0 + i, sent=10.0 + i, max_tokens=len(ids),
                     prompt_tokens=len(s["prompt"]), block=0,
                     done=11.0 + i, ended=11.0 + i, tokens=len(ids),
                     ids=ids, prompt=s["prompt"])
    sequences = alter(job["sequences"]) if callable(alter) \
        else job["sequences"]
    replies = [reply(i, s) for i, s in enumerate(sequences)]
    window = loops.Window(t0=10.5, seconds=100.0, replies=replies[1:],
                          t_end=120.0)
    return perf_run.Run(cell=cell, window=window, t_start=0.0, samples=[],
                        steady_until=110.5, log_setup="", log_window="",
                        faults=[], canary=replies[:1])


def _another_page(sequences):
    """What a wrong page of the cache does: the second reply was
    produced over a context one page of which (16 tokens) held other
    tokens than its prompt says."""
    out = copy.deepcopy(sequences)
    prompt = out[2]["prompt"]
    prompt[16:32] = [(t + 7) % 500 + 3 for t in prompt[16:32]]
    return out


@pytest.mark.parametrize("alter", [None, (2, 3), (0, 0), _another_page],
                         ids=["as-served", "window-token", "canary-token",
                              "another-page"])
def test_a_token_altered_where_it_is_produced_is_not_correct(
        checkout, rehearsed, alter):
    """The rest of a run after the look for a chip, on what the real
    server produced: as served it is correct; with one token of one
    reply altered, or one page of one context, `correct` comes out
    false."""
    cell = cells.load_cell("tiny.batch", checkout, REHEARSAL)
    run = _as_run(cell, rehearsed[2], alter)
    args = argparse.Namespace(seed=3000000029, rehearse=True,
                              control=False)
    lines = perf_run.finish_reference(
        run, args, perf_run.start_reference(run, args))
    line = perf_run.result_line(run, 0, dict(platform="cpu"))
    assert len(lines) == 3
    assert line["correct"] is (alter is None), run.faults
    if callable(alter):
        assert any("gap_" in f for f in run.faults)
    elif alter is not None:
        assert line["reference"]["gap_worst"] > 0.5
        # the widest gap is in the altered reply (the replies are kept
        # anew, so it need not stand where it stood)
        with open(os.path.join(checkout, "perf", ".work", "tiny.batch",
                               "reference_in.json")) as f:
            worst = json.load(f)["sequences"][
                line["reference"]["worst_at"]["sequence"]]
        assert worst["prompt"] == rehearsed[2]["sequences"][alter[0]][
            "prompt"]
        assert worst not in rehearsed[2]["sequences"]
        assert any("gap_worst" in f for f in run.faults)


def test_a_layers_leaf_that_differs_is_not_correct(checkout, rehearsed):
    """A fault inside a layer shows: a reference whose first layer
    draws its MLP's output projection at twice the range the server
    was given (a new file beside the reference, named by a copy of the
    configuration) puts other tokens first."""
    with open(os.path.join(checkout, "perf", "references",
                           "llama_off.py"), "w") as f:
        f.write(open(os.path.join(checkout, "perf", "references",
                                  "llama.py")).read() + """

_tree = tree


def tree(config):
    out = _tree(config)
    shape, dtype, (low, high) = out["model.layers.0.mlp.down_proj"]["weight"]
    out["model.layers.0.mlp.down_proj"] = {
        "weight": (shape, dtype, [2 * low, 2 * high])}
    return out
""")
    cell = cells.load_cell("tiny.batch", checkout, REHEARSAL)
    cell.config = copy.deepcopy(cell.config)
    cell.config["perf"]["reference"] = "llama_off"
    job = rehearsed[2]
    stats, _, faults = reference.start(
        cell, job["seed"], job["sequences"], 2, rows=job["rows"],
        cpu=True).finish()
    assert faults and stats["gap_worst"] > 0.1, stats


def test_a_configuration_without_a_reference_is_not_correct(checkout):
    cell = cells.load_cell("tiny.batch", checkout, REHEARSAL)
    cell.config = copy.deepcopy(cell.config)
    del cell.config["perf"]["reference"]
    run = _as_run(cell, dict(sequences=_sequences(1, 5, 4, 4)))
    args = argparse.Namespace(seed=1, rehearse=True, control=False)
    perf_run.finish_reference(run, args,
                              perf_run.start_reference(run, args))
    assert run.faults == ["the configuration names no reference"]
    assert run.reference is None


# ---- what a later PR may add: files and entries alone ----

MANIFEST_TESTS = ("test_every_workload_names_files_that_exist or "
                  "test_names_and_units or test_each_layer_metric_moves "
                  "or test_the_manifest_takes_the_key or "
                  "test_each_reader_of_pr_27")


def test_a_configuration_with_its_reference_comes_as_new_files_alone(
        tmp_path):
    """The statement of what the next `model_config` PR may do: on a
    copy of the real manifest and harness it adds a configuration file
    that names a reference, the reference, a traffic file, a cell, a
    per-layer metric with its reader and a roofline count, and puts
    the cell on the `workloads` of `out_tok_s` and of three metrics
    that are there. It edits no file that is there, the cell loads, and
    every test that reads the manifest passes on the copy."""
    root = tmp_path / "checkout"
    ignore = shutil.ignore_patterns(".cache", ".work", "__pycache__")
    shutil.copytree(os.path.join(ROOT, "perf"), root / "perf",
                    ignore=ignore)
    shutil.copytree(os.path.join(ROOT, "tests", "perf"),
                    root / "tests" / "perf", ignore=ignore)
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}

    config = _tiny(reference="other", source="https://example.org/other")
    (root / "perf/configs/other-model.json").write_text(json.dumps(config))
    (root / "perf/references/other.py").write_text(
        (root / "perf/references/llama.py").read_text())
    traffic = json.loads((root / "perf/traffic/batch.json").read_text())
    traffic["loop"]["clients"] = 8
    (root / "perf/traffic/few.json").write_text(json.dumps(traffic))
    (root / "perf/rooflines/other_kernel.py").write_text(
        "def count(config, tokens):\n"
        "    return 2.0 * tokens, 4.0 * tokens\n")
    (root / "perf/layers/other_kernel_roofline.py").write_text(
        "def read(run):\n    return None\n")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = "other-model.few"
    bench["configs"].append(dict(
        name="other-model", source=config["perf"]["source"],
        file="perf/configs/other-model.json", reduced=[], why="x"))
    bench["workloads"].append(dict(name=cell, config="other-model",
                                   traffic="few", chips=1, why="x"))
    shared = ("round_ms.batch", "kv_used_pct.batch", "device_wait_ms.batch")
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] == "out_tok_s" or m["name"] in shared:
            m["workloads"].append(cell)
    bench["per_layer"].append(dict(
        name="other_kernel_roofline", unit="%", better="higher",
        source="device_trace", layer="kernels (ops/pallas/other.py)",
        moves="out_tok_s", workloads=[cell]))
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    assert all(p.read_bytes() == data for p, data in before.items())
    loaded = cells.load_cell(cell, str(root))
    assert loaded.config["perf"]["reference"] == "other"
    assert {m["name"] for m in loaded.per_layer} >= set(shared) | {
        "other_kernel_roofline", "programs_warmed"}
    assert callable(cells.load_module(
        str(root / "perf/references/other.py")).layer)
    out = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-p", "no:randomly", "tests/perf", "-k", MANIFEST_TESTS],
        cwd=root, env=dict(os.environ, JAX_PLATFORMS="cpu",
                           PYTHONPATH=f"{root}{os.pathsep}"
                                      f"{root / 'tests' / 'perf'}"),
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-1000:]
    assert " passed" in out.stdout and "failed" not in out.stdout
