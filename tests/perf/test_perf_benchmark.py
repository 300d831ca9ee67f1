"""Tests of the benchmark's yardstick and layout (`perf/`): no chip, no
topology call, nothing here uses JAX except the trace reader."""
import asyncio
import json
import os
import re
import shutil
import subprocess
import sys

import aiohttp
import pytest

from perf import cells, probes, server as srv, stats, trace
from perf import run as perf_run
from perf.client import Reply
from perf_stub import Stub

ROOT = cells.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
MANIFESTS = ["BENCHMARK.json", "perf/rehearse/manifest.json"]


def _manifest(path):
    with open(os.path.join(ROOT, path)) as f:
        return json.load(f)


# ---- the layout is data, found by name ----

@pytest.mark.parametrize("path", MANIFESTS)
def test_every_workload_names_files_that_exist(path):
    bench = _manifest(path)
    for w in bench["workloads"]:
        cell = cells.load_cell(w["name"], ROOT, path)
        assert callable(cell.generator)
        assert cell.config["perf"]["chips"] == w["chips"]
        for kind, entries in (("end_to_end", cell.end_to_end),
                              ("layers", cell.per_layer)):
            for e in entries:
                assert os.path.isfile(cells.reader_path(
                    ROOT, kind, e["name"])), e["name"]
    for c in bench["configs"]:
        assert any(w["config"] == c["name"] for w in bench["workloads"])
        assert c["file"].startswith("perf/")


@pytest.mark.parametrize("path", MANIFESTS)
def test_names_and_units_use_only_the_allowed_characters(path):
    bench = _manifest(path)
    assert set(bench) - {"trace_in_run"} == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for w in bench["workloads"]:
        assert all(NAME.match(w[k]) for k in ("name", "config", "traffic"))
        assert w["chips"] in (1, 4) and 0 < len(w["why"]) <= 200
    for c in bench["configs"]:
        assert NAME.match(c["name"]) and all(map(NAME.match, c["reduced"]))


@pytest.mark.parametrize("path", MANIFESTS)
def test_each_layer_metric_moves_a_metric_its_cells_report(path):
    bench = _manifest(path)
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    every = [w["name"] for w in bench["workloads"]]
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in bench["per_layer"]:
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", every):
            assert cell in moved.get("workloads", every), (m["name"], cell)
    for w in every:
        reported = [m for m in e2e.values()
                    if w in m.get("workloads", every)]
        assert len(reported) >= 2
        assert any(w in m.get("workloads", every)
                   for m in bench["per_layer"])


def test_peaks_table_is_keyed_by_device_kind():
    v5e = cells.load_peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["int8_ops_per_s"] == 393e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(cells.CellError):
        cells.load_peaks("TPU v9 imaginary")


# ---- the generator ----

def _chat_params():
    return cells.load_cell("tiny.chat", ROOT, MANIFESTS[1]).traffic["params"]


def _block(seed, index=0, n=40, span=10.0, params=None):
    gen = cells.load_function(os.path.join(
        ROOT, "perf", "generators", "stratified.py"), "block")
    return gen(params or _chat_params(), seed, index, n, span, 512)


def test_schedule_and_lengths_are_a_pure_function_of_the_seed():
    big = 3_000_000_019          # more than 32 signed bits hold
    assert _block(big) == _block(big)
    a, b = _block(big), _block(big + 1)
    assert a != b
    assert sorted(s["max_tokens"] for s in a) == \
        sorted(s["max_tokens"] for s in b)
    assert sorted(len(s["prompt"]) for s in a) == \
        sorted(len(s["prompt"]) for s in b)

    def gaps(blk):      # the last gap closes the block (span 10 s)
        offsets = [s["offset"] for s in blk] + [10.0]
        return sorted(y - x for x, y in zip(offsets, offsets[1:]))
    assert gaps(a) == pytest.approx(gaps(b))    # in another order
    assert _block(big, index=1) != a


def test_gaps_fill_the_span_and_lengths_stay_inside_the_clips():
    blk = _block(5, n=60, span=12.0)
    offsets = [s["offset"] for s in blk]
    assert offsets[0] == 0.0 and offsets == sorted(offsets)
    assert offsets[-1] < 12.0
    p = _chat_params()
    assert all(p["prompt_len"]["min"] <= len(s["prompt"])
               <= p["prompt_len"]["max"] for s in blk)
    assert all(p["output_len"]["min"] <= s["max_tokens"]
               <= p["output_len"]["max"] for s in blk)
    assert all(3 <= t < 512 for s in blk for t in s["prompt"])
    sampled = [s for s in blk if "seed" in s["sampling"]]
    assert len(sampled) == 30 and all(
        isinstance(s["sampling"]["seed"], int) for s in sampled)


def test_a_closed_loop_block_has_no_schedule_and_a_split_has_one_reader():
    blk = _block(9, n=20, span=None)
    assert all(s["offset"] is None for s in blk)
    with pytest.raises(ValueError):
        _block(9, params=dict(_chat_params(), gaps={"kind": "gamma"}))
    shared = cells.reader_path(ROOT, "layers", "running_mean.batch")
    assert shared == cells.reader_path(ROOT, "layers", "running_mean.chat")
    assert shared.endswith(os.path.join("layers", "running_mean.py"))
    assert cells.reader_path(ROOT, "layers", "programs_warmed").endswith(
        "programs_warmed.py")


# ---- the arithmetic ----

def test_ttft_counts_from_the_due_time_when_a_send_is_late():
    r = Reply(due=10.0, sent=10.4, max_tokens=2, prompt_tokens=5, block=0,
              arrivals=[(10.9, 1), (11.0, 1)])
    assert r.ttft == pytest.approx(0.9)       # not 0.5
    assert Reply(due=1.0, sent=1.0, max_tokens=1, prompt_tokens=1,
                 block=0).ttft is None


def test_pooled_gaps_with_several_tokens_in_one_chunk():
    gaps = stats.pooled_gaps([(1.0, 1), (1.5, 3), (1.5, 0), (1.75, 1)])
    assert gaps == pytest.approx([0.5, 0.0, 0.0, 0.25])
    # a first chunk of three tokens: two of them follow at once
    assert stats.pooled_gaps([(2.0, 3), (2.2, 1)]) == \
        pytest.approx([0.0, 0.0, 0.2])
    assert stats.pooled_gaps([]) == []


def test_a_reply_counts_by_the_share_of_its_time_inside_the_window():
    def reply(sent, ended, tokens, error=None):
        return Reply(due=sent, sent=sent, max_tokens=tokens,
                     prompt_tokens=1, block=0, done=ended, ended=ended,
                     tokens=tokens, error=error)
    replies = [reply(8.0, 12.0, 100),       # half of it before the window
               reply(11.0, 13.0, 60),       # whole
               reply(19.0, 23.0, 200),      # a quarter inside
               reply(2.0, 9.0, 70),         # before, and (25, 30) after
               reply(25.0, 30.0, 70),
               reply(12.0, 14.0, 999, error="HTTP 500")]
    assert stats.tokens_inside(replies, 10.0, 20.0) == \
        pytest.approx(50 + 60 + 50)
    assert stats.tokens_inside(replies, 0.0, 40.0) == pytest.approx(500)
    assert stats.tokens_inside([], 0.0, 1.0) == 0.0


@pytest.mark.parametrize("values,q,want", [
    ([1, 2, 3, 4, 5], 50, 3.0), ([1, 2, 3, 4], 50, 2.5),
    (list(range(1, 101)), 95, 95.05), ([7.0], 95, 7.0)])
def test_percentile_arithmetic(values, q, want):
    assert stats.percentile(values, q) == pytest.approx(want)


def test_percentile_of_nothing_raises_and_mean_of_nothing_is_none():
    with pytest.raises(ValueError):
        stats.percentile([], 50)
    assert stats.mean([]) is None and stats.mean([1, 2]) == 1.5


def test_prometheus_text_is_read_without_the_buckets():
    got = probes.parse_prometheus(
        '# HELP x\nx{a="1"} 2.0\nx{a="2"} 3.0\ny_bucket{le="1"} 9\n'
        'y_sum 1.5\ny_count 4.0\nz 1e3\n')
    assert got == {"x": 5.0, "y_sum": 1.5, "y_count": 4.0, "z": 1000.0}


# ---- the log readers, on recorded lines ----

LOG = """INFO 04:00:00 [aphrodite_tpu.engine] Initializing engine on platform=tpu device_kind='TPU v5 lite' device_count=1
INFO 04:00:01 [x] KV cache: 3459 device pages, 512 host pages (6.76 GiB device)
INFO 04:00:02 [x] kernel path: decode_attention = pallas (fused-write)
INFO 04:00:02 [x] kernel path: kv_write = pallas (prefill page writer)
INFO 04:00:02 [x] kernel path: quant_matmul = reference (tp>1)
Finished tracing + transforming _step for pjit in 1.5 sec
Finished jaxpr to MLIR module conversion jit(_step) in 2.5 sec
Finished XLA compilation of jit(_step) in 8.0 sec
Finished tracing + transforming _step_sample for pjit in 0.5 sec
Finished XLA compilation of jit(_gather) in 0.1 sec
perf: device memory peak_bytes_in_use=[13958643712, 12000000000]
"""


def test_log_readers_on_recorded_lines():
    assert srv.parse_device(LOG) == dict(platform="tpu", kind="TPU v5 lite",
                                         count=1)
    assert srv.parse_kv_pool(LOG) == (3459, 6.76)
    assert srv.parse_memory_peak(LOG) == 13958643712
    facts = srv.compile_facts(LOG)
    assert (facts["programs"], facts["compiled"]) == (2, 1)
    assert facts["trace_s"] == 2.0 and facts["compile_s"] == 8.0
    assert srv.check_kernel_paths(LOG, ["decode_attention", "kv_write"]) \
        == []
    faults = srv.check_kernel_paths(LOG, ["quant_matmul", "moe"])
    assert len(faults) == 2 and "reference" in faults[0]
    with pytest.raises(srv.RunFailure):
        srv.parse_device("nothing here")


# ---- the harness, end to end against a stub server ----

def _measure(cell, tmp_path, seconds=1.5, **stub_kw):
    async def go():
        stub = Stub(str(tmp_path / "server.log"), **stub_kw)
        await stub.start()
        try:
            async with aiohttp.ClientSession() as session:
                run = await perf_run.measure(
                    cell, stub, session, seed=3_000_000_019,
                    seconds=seconds, trace_dir=None, model="stub")
                run.stub = stub
                return run
        finally:
            await stub.stop()
    return asyncio.run(go())


def test_open_loop_end_to_end_against_the_stub(tmp_path):
    cell = cells.load_cell("tiny.chat", ROOT, MANIFESTS[1])
    run = _measure(cell, tmp_path, chunk=2)
    assert run.faults == [] and run.window.failed == 0
    assert run.window.attempted == round(
        cell.traffic["loop"]["rate_per_s"] * 1.5)
    assert all(r.block == 0 and r.tokens == r.max_tokens
               for r in run.window.replies)
    got = perf_run.read_metrics(run, cell.end_to_end, "end_to_end")
    assert set(got) == {"ttft_p50_ms", "gap_p95_ms",
                        "setup_s"}
    assert all(m["value"] > 0 for m in got.values())
    layers = perf_run.read_metrics(run, cell.per_layer, "layers")
    assert layers["compiles_in_window.chat"]["value"] == 0
    assert layers["programs_warmed"]["value"] >= 1
    assert layers["gen_late_p95_ms"]["value"] < 200
    assert "device_idle_pct.chat" not in layers      # nothing traced


def test_closed_loop_end_to_end_against_the_stub(tmp_path):
    cell = cells.load_cell("tiny.batch", ROOT, MANIFESTS[1])
    run = _measure(cell, tmp_path, seconds=1.0)
    assert run.faults == [] and run.window.attempted > 0
    assert run.window.failed_before == 0
    # the callers joined 4, 2 and 1 at a time, never all 40 at once;
    # a journal caller streams its later requests too, and one of
    # those may wait for its first token beside a joining group
    assert 1 <= run.stub.most_streams_queued <= \
        4 + cell.traffic["loop"]["journal_callers"]
    t0, t1 = run.window.t0, run.window.t0 + 1.0
    # every request open at some time in the window, each waited for
    assert all(r.ended >= t0 and r.sent < t1 for r in run.window.replies)
    assert sum(1 for r in run.window.replies if r.ended >= t1) == \
        cell.traffic["loop"]["clients"]
    assert run.window.t_end >= t1
    got = perf_run.read_metrics(run, cell.end_to_end, "end_to_end")
    ended_inside = sum(r.tokens for r in run.window.replies
                       if t0 <= r.ended < t1)
    assert got["out_tok_s"]["value"] == pytest.approx(
        stats.tokens_inside(run.window.replies, t0, t1))
    assert got["out_tok_s"]["value"] == pytest.approx(ended_inside,
                                                      rel=0.25)
    assert sum(perf_run.parts(run.window)) == pytest.approx(
        got["out_tok_s"]["value"], abs=20)
    layers = perf_run.read_metrics(run, cell.per_layer, "layers")
    assert layers["kv_used_pct.batch"]["value"] == pytest.approx(25.0)
    assert 0 < layers["running_mean.batch"]["value"] <= 40


def test_failures_are_counted_against_attempted(tmp_path):
    cell = cells.load_cell("tiny.chat", ROOT, MANIFESTS[1])
    run = _measure(cell, tmp_path, fail_every=3)
    assert 0 < run.window.failed < run.window.attempted
    assert any("HTTP 500" in f for f in run.faults)
    ok = [r for r in run.window.replies if r.ok]
    assert len(ok) == run.window.attempted - run.window.failed


def test_a_cell_made_of_new_files_is_found_and_run(tmp_path):
    """What a later PR does: new files and one `workloads` entry."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "perf"), root / "perf",
                    ignore=shutil.ignore_patterns(".cache", ".work",
                                                  "__pycache__"))
    traffic = json.loads((root / "perf/traffic/rehearse-batch.json")
                         .read_text())
    traffic["loop"]["clients"] = 2
    (root / "perf/traffic/two-callers.json").write_text(json.dumps(traffic))
    (root / "perf/layers/served_count.py").write_text(
        "def read(run):\n    return len(run.window.replies)\n")
    bench = _manifest(MANIFESTS[1])
    bench["workloads"].append(dict(name="tiny.two", config="tiny",
                                   traffic="two-callers", chips=1, why="x"))
    for m in bench["end_to_end"]:
        if m["name"] == "out_tok_s":
            m["workloads"].append("tiny.two")
    bench["per_layer"].append(dict(
        name="served_count", unit="requests", better="higher",
        source="program_counter", layer="load generator",
        moves="out_tok_s", workloads=["tiny.two"]))
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = cells.load_cell("tiny.two", str(root))
    run = _measure(cell, tmp_path, seconds=0.8)
    layers = perf_run.read_metrics(run, cell.per_layer, "layers")
    assert layers["served_count"]["value"] == run.window.attempted > 0
    assert "out_tok_s" in perf_run.read_metrics(run, cell.end_to_end,
                                                "end_to_end")
    with pytest.raises(cells.CellError):
        cells.load_cell("tiny.none", str(root))


# ---- the trace reduction ----

def test_union_and_reduction_on_a_handmade_trace():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    planes = dict(
        devices={"/device:TPU:0": [("fusion.1", 0.0, 2e9),
                                   ("fusion.1", 1e9, 3e9),
                                   ("custom-call.2", 6e9, 7e9)]},
        host=[("$sched.py:1 schedule", 3.1e9, 5.9e9),
              ("$loop.py:1 run", 0.0, 10e9)])
    got = trace.reduce(planes)
    assert got["busy_s"] == pytest.approx(4.0)
    # first to last device operation: the host's tracer ran longer
    assert got["window_s"] == pytest.approx(7.0)
    assert got["device_ops"][0] == ["fusion.1", pytest.approx(4.0)]
    assert dict(map(tuple, got["idle_gaps"])) == {
        "$sched.py:1 schedule": pytest.approx(3.0)}
    # a host tracer that started late or stopped early clips the window
    late = trace.reduce(dict(devices=planes["devices"],
                             host=[("$loop.py:1 run", 1e9, 6.5e9)]))
    assert late["window_s"] == pytest.approx(5.5)
    assert late["busy_s"] == pytest.approx(2.5)
    with pytest.raises(ValueError):
        trace.reduce(dict(devices={}, host=[]))


def test_reduction_of_the_recorded_chip_trace():
    """A slice of the first traced chip run (one TPU v5 lite, Mistral-7B
    W4A8 chat): 1,800 device operations of one decode step, back to
    back, and the host events over them. The host events span 29.8 ms;
    the window is the 5.07 ms in which the device's tracer was on."""
    with open(os.path.join(ROOT, "perf", "fixtures", "trace_cut.json")) as f:
        cut = json.load(f)
    planes = dict(devices=cut["devices"],
                  host=[tuple(h) for h in cut["host"]])
    got = trace.reduce(planes)
    assert got["busy_s"] == pytest.approx(0.005066276, rel=1e-6)
    assert got["window_s"] == pytest.approx(0.005074041, rel=1e-6)
    assert 0 < got["busy_s"] < got["window_s"]
    ops = dict(map(tuple, got["device_ops"]))
    assert len(ops) == 10
    # the 32 layers' calls of one kernel at one shape are one entry
    assert got["device_ops"][0][0] == "gptq_matmul_a8 bf16[16,28672]"
    assert "_paged_decode_impl bf16[9,1,32,128]" in ops
    assert sum(ops.values()) <= got["busy_s"]
    gaps = dict(map(tuple, got["idle_gaps"]))
    assert gaps["$array.py:631 _value"] == pytest.approx(5.834e-06)
    assert sum(gaps.values()) <= got["window_s"] - got["busy_s"] + 1e-9
    assert trace.short_name(
        '%gptq_matmul_a8.4 = bf16[16,6144]{1,0:T(8,128)(2,1)S(1)} '
        'custom-call(bf16[1,16,4096]{2,1,0} %p), '
        'custom_call_target="tpu_custom_call"') == \
        "gptq_matmul_a8 bf16[16,6144] tpu_custom_call"
    sliced = trace.cut(planes, span_ns=5e6)
    assert 0 < len(sliced["devices"]["/device:TPU:0"]) < 1800


# ---- the command itself ----

def _run_py(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "perf", "run.py"), "--workload",
         "mistral-7b-w4a8.batch", "--seed", "1", "--seconds", "1", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_without_a_chip_the_command_fails_and_prints_no_result():
    out = _run_py(ROOT)
    assert out.returncode != 0
    assert '"metrics"' not in out.stdout and "FAILED" in out.stderr


def test_outside_a_checkout_the_command_fails(tmp_path):
    shutil.copytree(os.path.join(ROOT, "perf"), tmp_path / "perf",
                    ignore=shutil.ignore_patterns(".cache", ".work",
                                                  "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    out = _run_py(str(tmp_path))
    assert out.returncode != 0 and '"metrics"' not in out.stdout
