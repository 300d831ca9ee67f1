"""Serving load-gen harness smoke test (benchmarks/serving.py is the
p50-TTFT artifact BASELINE.md tracks)."""
import argparse
import asyncio
import os
import sys

import pytest


def _forget_fault_env():
    """The harness writes the fault spec into `os.environ` itself. It
    is popped here, not with `monkeypatch.delenv`: that would record
    the harness's value as the original and put it back at teardown,
    into every later test of this worker."""
    os.environ.pop("APHRODITE_FAULT", None)
    os.environ.pop("APHRODITE_FAULT_SEED", None)


def _args(tiny_model_dir, **kw):
    defaults = dict(
        model=tiny_model_dir, load_format="dummy", dtype="float32",
        quantization=None, kv_cache_dtype="auto", max_num_seqs=4,
        max_model_len=256, multi_step=4, request_rate=float("inf"),
        num_requests=6, prompt_len=12, output_len=5, warmup=0)
    defaults.update(kw)
    return argparse.Namespace(**defaults)


def test_serving_harness(tiny_model_dir):
    sys.path.insert(0, "benchmarks")
    from serving import run

    result = asyncio.run(run(_args(tiny_model_dir)))
    assert result["metric"] == "serving_p50_ttft_s"
    d = result["detail"]
    assert d["ttft_p50"] > 0 and d["ttft_p99"] >= d["ttft_p50"]
    assert d["e2e_p50"] >= d["ttft_p50"]
    assert d["throughput_out_tok_s"] > 0
    assert d["mesh"] is None        # single device: topology recorded
    assert "chaos" not in d


def test_serving_harness_tp_mesh(tiny_model_dir):
    """--tp 2 serves through the async engine on the virtual mesh and
    records the (dp, pp, sp, tp) topology + backend in the JSON, so a
    capture can never silently drop its mesh provenance."""
    sys.path.insert(0, "benchmarks")
    from serving import run

    result = asyncio.run(run(_args(tiny_model_dir, tp=2,
                                   num_requests=4, output_len=4)))
    d = result["detail"]
    assert d["mesh"] == [1, 1, 1, 2]
    assert d["backend"] == "cpu"
    assert d["throughput_out_tok_s"] > 0


def test_serving_harness_chaos_mode(tiny_model_dir, monkeypatch):
    """--chaos JSON artifact: injected transient faults are retried
    (requests still survive), the abort storm is accounted, and the
    chaos counters ride alongside the usual percentiles."""
    sys.path.insert(0, "benchmarks")
    from serving import run
    from aphrodite_tpu.common import faultinject

    monkeypatch.delenv("APHRODITE_FAULT", raising=False)
    faultinject.reset()
    try:
        result = asyncio.run(run(_args(
            tiny_model_dir, num_requests=8, chaos=True,
            chaos_fault="executor.execute_model:transient:1:2",
            chaos_abort_rate=0.3, chaos_seed=3)))
    finally:
        _forget_fault_env()
        faultinject.reset()
    c = result["detail"]["chaos"]
    assert c["engine_state"] == "RUNNING"
    assert c["steps_recovered"] >= 1
    assert c["steps_retried"] >= 2
    assert c["faults_fired"] == {
        "executor.execute_model:transient": 2}
    assert c["requests_survived"] >= 1
    assert (c["requests_survived"] + c["requests_aborted"]
            + c["requests_failed"]) == 8
    assert c["degraded_ttft_p99"] >= 0


def test_serving_harness_overload_mode(tiny_model_dir, monkeypatch):
    """--overload JSON artifact: the offered rate doubles, deadlines
    and the disconnect storm are applied, and the `overload` section
    reports goodput, shed/expired/served/disconnected counts, shed
    rejection latency, and a zero KV leak (free pages == free0)."""
    sys.path.insert(0, "benchmarks")
    from serving import run

    monkeypatch.delenv("APHRODITE_PAGE_LOW_WATERMARK", raising=False)
    # A 2-deep queue cap forces real shedding even on the tiny model.
    monkeypatch.setenv("APHRODITE_MAX_QUEUE_DEPTH", "2")
    result = asyncio.run(run(_args(
        tiny_model_dir, num_requests=10, max_num_seqs=2,
        request_rate=float("inf"), overload=True, overload_mult=2.0,
        deadline_s=30.0, disconnect_rate=0.3, chaos_seed=1)))
    o = result["detail"]["overload"]
    assert (o["requests_served"] + o["requests_shed"]
            + o["requests_expired"] + o["requests_disconnected"]
            + o["requests_failed"]) == 10
    assert o["requests_shed"] >= 1, o
    assert o["requests_served"] >= 1, o
    assert o["rejection_ms_max"] < 100, o
    assert o["kv_leak_pages"] == 0, o
    assert o["goodput_out_tok_s"] > 0
    assert o["sheds_total"] >= o["requests_shed"]


def test_serving_harness_chaos_kill_mode(tiny_model_dir, monkeypatch):
    """--chaos-kill JSON artifact: a FATAL fault armed at measurement
    start forces one reincarnation (every request still completes —
    zero unaccounted, zero KV leak on the REBUILT pool), then the
    drain storm proves in-flight work completes while late arrivals
    get the typed draining rejection and the replica drains clean."""
    sys.path.insert(0, "benchmarks")
    from serving import run
    from aphrodite_tpu.common import faultinject

    monkeypatch.delenv("APHRODITE_FAULT", raising=False)
    monkeypatch.setenv("APHRODITE_REINCARNATIONS", "2")
    monkeypatch.setenv("APHRODITE_REINCARNATION_BACKOFF_S", "0.01")
    faultinject.reset()
    try:
        result = asyncio.run(run(_args(
            tiny_model_dir, num_requests=8, chaos_kill=True,
            kill_fault="executor.execute_model:fatal:1:1",
            chaos_seed=0)))
    finally:
        _forget_fault_env()
        faultinject.reset()
    ck = result["detail"]["chaos_kill"]
    assert ck["reincarnations"] == 1
    assert ck["requests_restored"] >= 1
    assert ck["requests_lost_typed"] == 0
    assert ck["recovery_s"] > 0
    assert ck["requests_unaccounted"] == 0
    assert ck["kv_leak_pages"] == 0, ck
    assert ck["faults_fired"] == {"executor.execute_model:fatal": 1}
    d = ck["drain"]
    assert d["inflight_completed"] == d["inflight_offered"] == 4
    assert d["late_rejected_draining"] == d["late_offered"] == 4
    assert d["clean_exit"] is True


@pytest.mark.slow
def test_serving_harness_fleet_smoke():
    """--fleet smoke (slow: spawns real replica server processes):
    two replicas behind the router, a mid-run rolling deploy, every
    request served with zero unaccounted and zero pre-stream 5xx.
    Excluded from tier-1; CI runs it in the dedicated fleet job."""
    sys.path.insert(0, "benchmarks")
    from serving import run_fleet, synthetic_tiny_dir

    args = argparse.Namespace(
        model=synthetic_tiny_dir(), load_format="dummy",
        dtype="float32", quantization=None, kv_cache_dtype="auto",
        max_num_seqs=4, max_model_len=256, multi_step=4,
        request_rate=4.0, num_requests=12, prompt_len=32,
        output_len=6, warmup=1, fleet=2, session_turns=3,
        rollout_at=0.5, kill_at=-1.0, chaos_kill=False)
    result = asyncio.run(run_fleet(args))
    assert result["metric"] == "fleet_goodput_out_tok_s"
    d = result["detail"]
    assert d["requests_unaccounted"] == 0
    assert d["outcomes"]["client_5xx_prestream"] == 0
    assert d["outcomes"]["served"] == 12
    assert d["goodput_out_tok_s"] > 0
    assert d["rollout"]["status"] == 200
    assert d["rollout"]["report"]["ok"] is True
    assert d["affinity_hit_rate"] is not None


def test_serving_harness_chaos_fault_free_matches_baseline(
        tiny_model_dir, monkeypatch):
    """A fault-free --chaos run (no spec, no aborts) must report every
    request survived — pure accounting, no semantic drift."""
    sys.path.insert(0, "benchmarks")
    from serving import run
    from aphrodite_tpu.common import faultinject

    monkeypatch.delenv("APHRODITE_FAULT", raising=False)
    faultinject.reset()
    result = asyncio.run(run(_args(
        tiny_model_dir, chaos=True, chaos_fault="none",
        chaos_abort_rate=0.0)))
    c = result["detail"]["chaos"]
    assert c["fault_spec"] == "none"
    assert c["requests_survived"] == 6
    assert c["requests_aborted"] == c["requests_failed"] == 0
    assert c["steps_retried"] == 0
    assert c["faults_fired"] == {}
    assert result["detail"]["throughput_out_tok_s"] > 0
