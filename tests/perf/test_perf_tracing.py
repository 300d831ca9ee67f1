"""Tests of what reads the engine round's counters and spans
(`perf/layers/`, `perf/rounds.py`) and of the run that measures first
and traces afterwards (`--trace 2`): no chip."""
import asyncio
import json
import os
import subprocess
import sys

import aiohttp
import pytest

from perf import cells, loops, probes
from perf import run as perf_run
from perf.client import Reply
from perf_stub import Stub

ROOT = cells.ROOT
REHEARSAL = "perf/rehearse/manifest.json"
CELL = "mistral-7b-w4a8.batch"


def _run(samples, trace=None, seconds=10.0):
    """A hand-made `Run`: `samples` are `/metrics` readings `seconds`
    apart, the first at the window's opening."""
    cell = cells.load_cell(CELL, ROOT)
    window = loops.Window(t0=100.0, replies=[], t_end=0.0,
                          seconds=seconds * max(1, len(samples) - 1))
    return perf_run.Run(
        cell=cell, window=window, t_start=0.0,
        samples=[(100.0 + i * seconds, s) for i, s in enumerate(samples)],
        steady_until=100.0 + window.seconds, log_setup="", log_window="",
        faults=[], trace=trace)


def _counters(rounds, **seconds):
    out = {"aphrodite:engine_rounds_total": float(rounds)}
    out.update({f"aphrodite:{k}_total": float(v)
                for k, v in seconds.items()})
    return out


#: two readings 10 s apart: 160 rounds of 62.5 ms, each 2.5 ms between
#: steps, 1.25 scheduling, 12.5 preparing, 40 with a step in flight,
#: 5 processing; 30 requests first scheduled after 0.9 s of waiting in
#: all; 7,200 tokens from 180 syncs; 1 preemption
WINDOW = [
    _counters(1000, host_between_steps_seconds=5.0,
              host_schedule_seconds=1.0, host_prepare_seconds=20.0,
              device_wait_seconds=70.0, host_process_seconds=9.0,
              queue_wait_seconds=4.0, requests_first_scheduled=50,
              preemptions=2, generation_tokens=10_000, host_syncs=1100),
    _counters(1160, host_between_steps_seconds=5.4,
              host_schedule_seconds=1.2, host_prepare_seconds=22.0,
              device_wait_seconds=76.4, host_process_seconds=9.8,
              queue_wait_seconds=4.9, requests_first_scheduled=80,
              preemptions=3, generation_tokens=17_200, host_syncs=1280)]
TRACE = dict(busy_s=1.2, window_s=2.0, device_ops=[["fusion", 1.0]],
             idle_gaps=[["aph.engine.step", 0.5],
                        ["aph.runner.prepare", 0.2],
                        ["PjitFunction(_step_sample)", 0.06],
                        ["unattributed", 0.04]])
WANT = {
    "round_ms.batch": 62.5, "host_between_ms.batch": 2.5,
    "host_schedule_ms.batch": 1.25, "queue_wait_ms.batch": 30.0,
    "preemptions.batch": 1.0, "host_prepare_ms.batch": 12.5,
    "tokens_per_sync.batch": 40.0, "device_wait_ms.batch": 40.0,
    "host_process_ms.batch": 5.0, "no_step_in_flight_pct.batch": 36.0,
    "idle_attributed_pct.batch": 87.5}


def _read(metric, run):
    return cells.load_function(
        cells.reader_path(ROOT, "layers", metric), "read")(run)


@pytest.mark.parametrize("metric", sorted(WANT))
def test_each_new_reader_on_a_hand_made_run(metric):
    assert _read(metric, _run(WINDOW, TRACE)) == pytest.approx(WANT[metric])


@pytest.mark.parametrize("metric", sorted(WANT))
def test_a_program_without_the_counter_or_span_reads_nothing(metric):
    """The parent's program: `/metrics` has none of the new counters and
    a `--trace 0` run has no trace. The reader returns None and the
    line leaves the metric out; it does not raise."""
    old = [{"aphrodite:generation_tokens_total": 1.0},
           {"aphrodite:generation_tokens_total": 9.0}]
    assert _read(metric, _run(old)) is None
    assert _read(metric, _run([])) is None
    if metric == "idle_attributed_pct.batch":
        # a trace whose gaps carry no program span reads 0, not nothing
        frames = dict(TRACE, idle_gaps=[["$sched.py:1 schedule", 0.3]])
        assert _read(metric, _run(WINDOW, frames)) == 0.0
        assert _read(metric, _run(WINDOW, dict(TRACE, idle_gaps=[]))) \
            is None


def test_the_five_stages_of_the_hand_made_window_sum_to_its_round():
    stages = ("host_between_ms.batch", "host_schedule_ms.batch",
              "host_prepare_ms.batch", "device_wait_ms.batch",
              "host_process_ms.batch")
    assert sum(WANT[m] for m in stages) == pytest.approx(
        WANT["round_ms.batch"] * 0.98)


def test_the_manifest_takes_the_key_and_appends_the_new_metrics():
    """PR 25's eleven entries are found by name, and a later PR may
    append metrics and put a new cell on the `workloads` of these."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert bench["trace_in_run"] is True
    names = [m["name"] for m in bench["per_layer"]]
    assert names[:7] == [
        "waiting_mean.batch", "running_mean.batch", "kv_used_pct.batch",
        "programs_warmed", "compiles_in_window.batch",
        "model_flops_pct.batch", "device_idle_pct.batch"]
    assert set(names[7:]) >= set(WANT)
    layers = {m["layer"] for m in bench["per_layer"][:7]}
    for m in bench["per_layer"]:
        if m["name"] not in WANT:
            continue
        assert m["moves"] == "out_tok_s" and CELL in m["workloads"]
        assert m["source"] == ("device_trace" if m["name"].startswith(
            "idle_attributed") else "program_counter")
        # a layer the manifest already names keeps its name
        assert m["layer"] in layers or m["layer"].startswith(
            ("core engine", "HTTP front end"))


# ---- PR 27's three readers: the sampling plan, the decode kernel ----

#: `WINDOW` again, with 320 plans of which 144 reused, 0.112 s in all
PLANS = [dict(WINDOW[0], **{"aphrodite:sampler_plan_seconds_total": 1.0,
                            "aphrodite:sampler_plans_total": 2000.0,
                            "aphrodite:sampler_plan_reuses_total": 900.0,
                            "aphrodite:gpu_cache_usage_perc": 0.5}),
         dict(WINDOW[1], **{"aphrodite:sampler_plan_seconds_total": 1.112,
                            "aphrodite:sampler_plans_total": 2320.0,
                            "aphrodite:sampler_plan_reuses_total": 1044.0,
                            "aphrodite:gpu_cache_usage_perc": 0.5})]
POOL_LINE = ("INFO [x] KV cache: 5000 device pages, 512 host pages "
             "(8.00 GiB device)\n")
#: 64 calls of the decode kernel in 0.032 s (0.5 ms each) and one of
#: another shape; half of an 8 GiB pool live over 32 layers is 128 MiB
#: a call and the 49 rows 0.8 MB more, 0.16486 ms at 819 GB/s: 32.97%
KERNEL_TRACE = dict(TRACE, ops={
    "_paged_decode_impl bf16[49,1,32,128] tpu_custom_call": [0.031, 62],
    "_paged_decode_impl bf16[2,1,32,128] tpu_custom_call": [0.001, 2],
    "gptq_matmul_a8 bf16[48,4096] tpu_custom_call": [0.5, 256]})
NEW_WANT = {"sampler_plan_ms.batch": 0.35, "plan_reuse_pct.batch": 45.0,
            "decode_attn_roofline_pct.batch": 32.972}


def _run_27(samples, trace=None):
    run = _run(samples, trace)
    run.log_setup = POOL_LINE
    run.peaks = cells.load_peaks("TPU v5 lite")
    return run


@pytest.mark.parametrize("metric", sorted(NEW_WANT))
def test_each_reader_of_pr_27_on_a_hand_made_run(metric):
    got = _read(metric, _run_27(PLANS, KERNEL_TRACE))
    assert got == pytest.approx(NEW_WANT[metric], rel=1e-4)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = {m["name"]: m for m in json.load(f)["per_layer"]}[metric]
    assert entry["moves"] == "out_tok_s" and CELL in entry["workloads"]
    assert entry["layer"].startswith(
        "kernels" if "roofline" in metric else "model runner")


@pytest.mark.parametrize("metric", sorted(NEW_WANT))
def test_a_reader_of_pr_27_that_finds_nothing_reads_nothing(metric):
    """The parent's program exports no plan counter; an untraced run
    has no trace, an old reduction no `ops`, a CPU trace no such
    kernel. None, never 0 and never an exception."""
    assert _read(metric, _run_27(WINDOW, TRACE)) is None
    assert _read(metric, _run_27([])) is None
    assert _read(metric, _run_27(PLANS, dict(
        TRACE, ops={"fusion f32[8]": [1.0, 10]}))) is None or \
        "roofline" not in metric
    if "roofline" in metric:
        # no pool line in the log, or no table of peaks: nothing to read
        run = _run_27(PLANS, KERNEL_TRACE)
        run.log_setup = ""
        assert _read(metric, run) is None
        run = _run_27(PLANS, KERNEL_TRACE)
        run.peaks = None
        assert _read(metric, run) is None


def test_the_roofline_count_of_the_decode_kernel_from_its_shapes():
    count = cells.load_function(os.path.join(
        ROOT, "perf", "rooflines", "paged_decode.py"), "count")
    config = dict(num_hidden_layers=32, num_attention_heads=32,
                  num_key_value_heads=8, hidden_size=4096)
    # 48 rows of 1,024 tokens: 128 KiB of K and V a token over 32 layers
    live = 48 * 1024 * 131072
    moved, computed = count(config, live, 48)
    assert moved == live / 32 + 2 * 48 * 32 * 128 * 2
    assert computed == 4 * 128 * 32 * 48 * 1024
    # at these sizes the bytes bound it, by far: 0.25 ms against 4 us
    assert moved / 819e9 > 50 * computed / 197e12


# ---- `--trace 2` against the stub ----

def _measure(cell, tmp_path, trace_mode, seconds=1.0, **stub_kw):
    async def go():
        stub = Stub(str(tmp_path / "server.log"), **stub_kw)
        await stub.start()
        try:
            async with aiohttp.ClientSession() as session:
                run = await perf_run.measure(
                    cell, stub, session, seed=2_147_483_659,
                    seconds=seconds,
                    trace_dir=str(tmp_path / "trace")
                    if trace_mode else None,
                    model="stub", trace_mode=trace_mode)
                run.stub = stub
                return run
        finally:
            await stub.stop()
    return asyncio.run(go())


@pytest.fixture
def short_trace(monkeypatch):
    monkeypatch.setattr(probes, "TRACE_SECONDS", 0.3)


@pytest.mark.parametrize("workload", ["tiny.batch", "tiny.chat"])
def test_trace_2_traces_after_the_window_with_the_load_still_going(
        tmp_path, short_trace, workload):
    cell = cells.load_cell(workload, ROOT, REHEARSAL)
    run = _measure(cell, tmp_path, trace_mode=2, chunk=2)
    assert run.faults == [] and run.window.failed_after == 0
    w, calls = run.window, run.stub.profile_calls
    # started and stopped once for nothing, then the traced seconds
    assert [c[0] for c in calls] == ["/start_profile", "/stop_profile"] * 2
    trace_dir = str(tmp_path / "trace")
    assert calls[0][1] == {"trace_dir": trace_dir + ".first"}
    assert calls[2][1] == {"trace_dir": trace_dir}
    # after every request of the sample had ended: nothing of the
    # window's numbers can move any more
    assert all(r.ended is not None for r in w.replies)
    # the gauges and counters are the whole window's
    assert run.steady_until == pytest.approx(w.t0 + w.seconds)
    assert run.samples[-1][0] > run.steady_until
    # the load went on while the profiler ran: requests were served
    # between its start and its stop
    assert calls[3][3] > calls[2][3]
    if workload == "tiny.batch":
        assert calls[2][2] > 0          # the callers were still there


def test_trace_2_prints_both_kinds_and_the_same_end_to_end_as_trace_0(
        tmp_path, short_trace):
    cell = cells.load_cell("tiny.batch", ROOT, REHEARSAL)
    run = _measure(cell, tmp_path, trace_mode=2)
    device = dict(platform="cpu", kind="cpu", count=1)
    run.trace = None
    plain = perf_run.result_line(run, 0, dict(device))
    run.trace = TRACE
    both = perf_run.result_line(run, 2, dict(device))
    layers = perf_run.result_line(run, 1, dict(device))
    end_to_end = {m["name"] for m in cell.end_to_end}
    # (no table of peaks goes with the stub, and it counts no sampling
    # plan: the readers that need them find nothing to read and are
    # left out of the line)
    per_layer = {m["name"] for m in cell.per_layer} - {
        "model_flops_pct.batch", "sampler_plan_ms.batch",
        "plan_reuse_pct.batch"}
    assert set(plain) == {"correct", "attempted", "failed", "metrics",
                          "device"}
    assert set(both) == set(layers) == set(plain) | {"breakdown"}
    assert set(plain["metrics"]) == end_to_end == {"out_tok_s", "setup_s"}
    assert set(layers["metrics"]) == per_layer
    assert set(both["metrics"]) == end_to_end | per_layer
    for name in end_to_end:
        assert both["metrics"][name] == plain["metrics"][name]
    assert both["metrics"]["device_idle_pct.batch"]["value"] == \
        pytest.approx(40.0)
    assert both["breakdown"]["idle_gaps"] == TRACE["idle_gaps"]
    assert (both["correct"], both["attempted"], both["failed"]) == \
        (plain["correct"], plain["attempted"], plain["failed"])
    json.dumps(both)


@pytest.mark.parametrize("workload", ["tiny.batch", "tiny.chat"])
def test_a_failed_request_in_the_traced_tail_is_a_fault(
        tmp_path, short_trace, workload):
    cell = cells.load_cell(workload, ROOT, REHEARSAL)
    run = _measure(cell, tmp_path, trace_mode=2,
                   fail_while_profiling=True)
    assert run.window.failed_after > 0
    assert any("traced seconds" in f for f in run.faults)
    # the window itself was clean, and its numbers stand
    assert run.window.failed == 0 and run.window.attempted > 0


def test_trace_0_and_1_keep_their_behaviour(tmp_path, short_trace):
    cell = cells.load_cell("tiny.batch", ROOT, REHEARSAL)
    run = _measure(cell, tmp_path, trace_mode=0, seconds=0.8)
    assert run.stub.profile_calls == [] and run.faults == []
    assert run.steady_until == pytest.approx(run.window.t0 + 0.8)
    # `--trace 1`: the trace is taken inside the window, and the
    # samples that count end where the profiler starts
    run = _measure(cell, tmp_path, trace_mode=1, seconds=0.8)
    assert [c[0] for c in run.stub.profile_calls] == [
        "/start_profile", "/stop_profile"]
    assert run.stub.profile_calls[0][1] == {
        "trace_dir": str(tmp_path / "trace")}
    assert run.steady_until == pytest.approx(run.window.t0)


def test_a_window_counts_failures_after_it_apart():
    def reply(sent, ended, ok=True):
        return Reply(due=sent, sent=sent, max_tokens=1, prompt_tokens=1,
                     block=0, done=ended if ok else None, ended=ended,
                     tokens=1, error=None if ok else "HTTP 500")
    w = loops.Window(t0=0.0, seconds=1.0, replies=[reply(0.1, 0.5)],
                     t_end=1.0, failed_after=2)
    assert (w.attempted, w.failed, w.failed_after) == (1, 0, 2)
    assert loops.Window(t0=0.0, seconds=1.0, replies=[],
                        t_end=1.0).failed_after == 0


# ---- the command itself, on the CPU ----

def test_rehearsal_of_trace_2_prints_one_line_with_both_kinds(tmp_path):
    """The real server on the CPU at a toy size: one process measures,
    then traces, and the program's own spans name the idle gaps."""
    log = tmp_path / "server.log"
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perf", "run.py"),
         "--rehearse", "--workload", "tiny.batch", "--seed", "3000000019",
         "--seconds", "3", "--trace", "2", "--keep-log", str(log)],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [ln for ln in out.stdout.splitlines() if ln.startswith("{")]
    assert len(lines) == 1
    line = json.loads(lines[0])
    assert line["correct"] and line["failed"] == 0
    assert "rehearsal" in line["device"]
    metrics = line["metrics"]
    assert {"out_tok_s", "setup_s", "running_mean.batch",
            "kv_used_pct.batch", "device_idle_pct.batch",
            "programs_warmed", "sampler_plan_ms.batch",
            "plan_reuse_pct.batch"} <= set(metrics)
    assert 0 < metrics["sampler_plan_ms.batch"]["value"] < 50
    # the served tokens against the float32 reference, after the exit
    assert line["reference"]["positions"] > 20
    assert line["reference"]["gap_worst"] <= 1e-4
    assert "reference: gap_worst" in out.stderr.splitlines()[-1]
    assert metrics["out_tok_s"]["value"] > 0
    assert 0 < line["device"]["busy_s"] < line["device"]["window_s"] < 3
    gaps = dict(map(tuple, line["breakdown"]["idle_gaps"]))
    assert any(name.startswith("aph.") for name in gaps), gaps
    # the profiler ran with the Python tracer off: no frame names a gap
    assert not any(name.startswith("$") for name in gaps), gaps
    text = log.read_text()
    assert text.count("Started jax.profiler trace") == 2
    assert "python tracer off" in text
    # the trace is deleted once it is reduced
    work = os.path.join(ROOT, "perf", ".work", "tiny.batch")
    assert not os.path.exists(os.path.join(work, "trace"))
    assert not os.path.exists(os.path.join(work, "trace.first"))
    assert "traced 2 s after the window" in out.stdout
