"""Engine lifecycle suite: crash-safe reincarnation (FATAL ->
REBUILDING -> RUNNING) and graceful drain (RUNNING -> DRAINING ->
exit).

The headline invariants, mirroring `benchmarks/serving.py
--chaos-kill`:

- a FATAL fault mid-serving yields a reincarnated engine whose
  surviving greedy outputs are BIT-EQUAL to a fault-free run, with
  free pages back at `free0` and zero silently-lost requests (every
  request completes or receives a typed error);
- draining a live replica completes all in-flight requests before the
  loop goes idle while new requests get the typed 503-class rejection
  (kept distinct from overload's 429), and a missed drain deadline
  force-aborts stragglers with typed errors instead of hanging the
  exit.
"""
import asyncio
import gc
import time

import pytest

from aphrodite_tpu.common import faultinject
from aphrodite_tpu.common.sampling_params import SamplingParams
from aphrodite_tpu.engine.supervisor import (EngineState, HealthMonitor,
                                             StaleEngineStepError)
from aphrodite_tpu.processing.admission import (EngineDrainingError,
                                                RequestRejectedError,
                                                RequestTimeoutError)

PROMPTS = [[(i * 7 + j * 3) % 90 + 5 for j in range(12)]
           for i in range(3)]
SP = dict(temperature=0.0, max_tokens=6, ignore_eos=True)

_LIFECYCLE_FLAGS = ("APHRODITE_REINCARNATIONS",
                    "APHRODITE_REINCARNATION_BACKOFF_S",
                    "APHRODITE_DRAIN_DEADLINE_S",
                    "APHRODITE_MAX_QUEUE_DEPTH",
                    "APHRODITE_FAULT", "APHRODITE_FAULT_SEED")


@pytest.fixture(autouse=True)
def _clean_lifecycle_state(monkeypatch):
    for name in _LIFECYCLE_FLAGS:
        monkeypatch.delenv(name, raising=False)
    faultinject.reset()
    yield
    faultinject.reset()


def _prompt(i, n=12):
    return [(i * 7 + j * 3) % 90 + 5 for j in range(n)]


def _async_engine(tiny_model_dir, **kw):
    from aphrodite_tpu.engine.args_tools import AsyncEngineArgs
    from aphrodite_tpu.engine.async_aphrodite import AsyncAphrodite
    defaults = dict(model=tiny_model_dir, load_format="dummy",
                    dtype="float32", block_size=16, max_model_len=256,
                    max_num_seqs=8, swap_space=0.01,
                    disable_log_stats=True, disable_log_requests=True)
    defaults.update(kw)
    return AsyncAphrodite.from_engine_args(AsyncEngineArgs(**defaults))


def _sync_engine(tiny_model_dir, **kw):
    from aphrodite_tpu.engine.args_tools import EngineArgs
    from aphrodite_tpu.engine.aphrodite_engine import AphroditeEngine
    defaults = dict(model=tiny_model_dir, load_format="dummy",
                    dtype="float32", block_size=16, max_model_len=256,
                    max_num_seqs=8, swap_space=0.01,
                    disable_log_stats=True, skip_tokenizer_init=True)
    defaults.update(kw)
    return AphroditeEngine(
        *EngineArgs(**defaults).create_engine_configs())


async def _generate_all(engine, prompts, sp):
    async def one(i, p):
        final = None
        async for out in engine.generate(None, sp, f"req-{i}",
                                         prompt_token_ids=list(p)):
            final = out
        return final

    return await asyncio.gather(
        *(one(i, p) for i, p in enumerate(prompts)),
        return_exceptions=True)


def _run_async(tiny_model_dir, monkeypatch, spec):
    if spec:
        monkeypatch.setenv("APHRODITE_FAULT", spec)
    else:
        monkeypatch.delenv("APHRODITE_FAULT", raising=False)
    faultinject.reset()
    from aphrodite_tpu.engine.async_aphrodite import AsyncAphrodite

    state = {}

    async def go():
        engine = _async_engine(tiny_model_dir)
        outs = await _generate_all(engine, PROMPTS,
                                   SamplingParams(**SP))
        state["engine"] = engine
        return outs

    return asyncio.run(go()), state


# ---------------------------------------------------------------------
# reincarnation: FATAL -> REBUILDING -> RUNNING
# ---------------------------------------------------------------------

def test_fatal_fault_reincarnates_bit_equal(tiny_model_dir,
                                            monkeypatch):
    """The chaos-kill acceptance invariant: a FATAL fault mid-serving
    is survived by one reincarnation — every request completes with
    outputs BIT-EQUAL to a fault-free run (restored requests re-prefill
    to identical KV), free pages return to free0 on the rebuilt pool,
    and health reports RUNNING with the rebuild counted."""
    clean, _ = _run_async(tiny_model_dir, monkeypatch, "")
    assert not any(isinstance(o, Exception) for o in clean)

    monkeypatch.setenv("APHRODITE_REINCARNATIONS", "1")
    monkeypatch.setenv("APHRODITE_REINCARNATION_BACKOFF_S", "0.01")
    faulty, state = _run_async(tiny_model_dir, monkeypatch,
                               "executor.execute_model:fatal:1:1")
    assert not any(isinstance(o, Exception) for o in faulty), faulty
    assert [tuple(o.outputs[0].token_ids) for o in faulty] == \
        [tuple(o.outputs[0].token_ids) for o in clean]
    engine = state["engine"]
    health = engine.health
    assert not health.is_dead
    assert health.report().state == "RUNNING"
    assert health.reincarnations_total == 1
    assert health.requests_restored_total >= 1
    assert health.requests_lost_total == 0
    assert health.last_rebuild_s is not None
    # The rebuilt pool is byte-for-byte as large as the original and
    # fully free after the run (zero-leak across the rebuild).
    bm = engine.engine.scheduler.block_manager
    assert bm.get_num_free_gpu_blocks() == \
        engine.engine.cache_config.num_gpu_blocks
    assert not bm.block_tables


def test_reincarnation_budget_exhaustion_goes_dead(tiny_model_dir,
                                                   monkeypatch):
    """A persistent FATAL fault burns the reincarnation budget and
    then lands in today's terminal DEAD — bounded recovery, not a
    rebuild loop."""
    from aphrodite_tpu.engine.async_aphrodite import AsyncEngineDeadError
    monkeypatch.setenv("APHRODITE_REINCARNATIONS", "1")
    monkeypatch.setenv("APHRODITE_REINCARNATION_BACKOFF_S", "0.01")
    faulty, state = _run_async(tiny_model_dir, monkeypatch,
                               "executor.execute_model:fatal:1:0")
    assert all(isinstance(o, AsyncEngineDeadError) for o in faulty), \
        faulty
    health = state["engine"].health
    assert health.report().state == "DEAD"
    assert health.reincarnations_total == 1


def test_sync_reincarnate_restores_waiting_fcfs(tiny_model_dir,
                                                monkeypatch):
    """Engine-level unit: after a FATAL step failure, reincarnate()
    rebuilds the executor + scheduler, restores every rolled-back
    request to `waiting` in FCFS order with zero casualties, and the
    fresh pool starts at free0; stepping on produces the fault-free
    outputs."""
    def run(spec):
        if spec:
            monkeypatch.setenv("APHRODITE_FAULT", spec)
        else:
            monkeypatch.delenv("APHRODITE_FAULT", raising=False)
        faultinject.reset()
        engine = _sync_engine(tiny_model_dir)
        sp = SamplingParams(**SP)
        free0 = engine.scheduler.block_manager.\
            get_num_free_gpu_blocks()
        for i, p in enumerate(PROMPTS):
            engine.add_request(f"r{i}", None, sp,
                               prompt_token_ids=list(p))
        results, reincarnated = {}, False
        while engine.has_unfinished_requests():
            try:
                outs = engine.step()
            except faultinject.InjectedFatalFault:
                outcome = engine.reincarnate()
                reincarnated = True
                assert outcome.restored == len(PROMPTS)
                assert outcome.lost == []
                assert not engine.drain_step_faults()
                assert [g.request_id
                        for g in engine.scheduler.waiting] == \
                    [f"r{i}" for i in range(len(PROMPTS))]
                assert engine.scheduler.block_manager.\
                    get_num_free_gpu_blocks() == free0
                continue
            for o in outs:
                if o.finished:
                    results[o.request_id] = [tuple(c.token_ids)
                                             for c in o.outputs]
        assert engine.scheduler.block_manager.\
            get_num_free_gpu_blocks() == free0
        return results, reincarnated

    clean, hit0 = run("")
    assert not hit0
    faulty, hit = run("executor.execute_model:fatal:1:1")
    assert hit, "the fatal fault never fired"
    assert faulty == clean


def test_reincarnate_clears_stale_prefix_pins(tiny_model_dir):
    """reincarnate() routes the torn-down scheduler's prefix pins
    through the free seam (`clear_prefixes`): the old pool's
    accounting ends exact (free pages back to boot, pinned gauge 0),
    the rebuilt pool starts pin-free, and the re-keyed prefix simply
    recomputes — no stale pin can be resurrected."""
    engine = _sync_engine(tiny_model_dir)
    sp = SamplingParams(**SP)
    free0 = engine.scheduler.block_manager.get_num_free_gpu_blocks()
    engine.add_request("pfx", None, sp,
                       prompt_token_ids=_prompt(0, n=40),
                       prefix_pos=32)     # 2 pinned pages at bs=16
    engine.step()                         # allocates + pins
    assert engine.scheduler.prefix_pinned_pages() == 2

    old_sched = engine.scheduler
    outcome = engine.reincarnate()
    assert outcome.restored == 1 and outcome.lost == []
    assert old_sched.prefix_pinned_pages() == 0
    assert old_sched.block_manager.get_num_free_gpu_blocks() == free0
    assert engine.scheduler.prefix_pinned_pages() == 0
    assert engine.scheduler.block_manager.\
        get_num_free_gpu_blocks() == free0
    (group,) = list(engine.scheduler.waiting)
    assert group.request_id == "pfx"
    assert group.prefix is not None and not group.prefix.allocated

    while engine.has_unfinished_requests():
        engine.step()
    # the restored request re-pinned its recomputed prefix; releasing
    # it lands the pool exactly at boot — the zero-leak invariant with
    # pins accounted, not fuzzed
    assert engine.scheduler.prefix_pinned_pages() == 2
    assert engine.scheduler.clear_prefixes() == 2
    assert engine.scheduler.block_manager.\
        get_num_free_gpu_blocks() == free0


def test_seeded_partial_request_restores_bit_equal(tiny_model_dir):
    """Seeded-sampling determinism through reincarnation restore: a
    seeded request killed MID-GENERATION (several tokens already
    emitted) and restored must emit its remaining tokens bit-equal to
    the fault-free run — the restored outputs re-enter as output
    tokens, so the sampler's output-position PRNG salt continues at n
    (the same seam mid-stream failover resumes through)."""
    sp = SamplingParams(temperature=1.0, seed=31337, max_tokens=12,
                        ignore_eos=True)

    def run(kill_at_output_len):
        engine = _sync_engine(tiny_model_dir,
                              skip_tokenizer_init=False)
        engine.add_request("seeded", None, sp,
                           prompt_token_ids=_prompt(0))
        emissions = []          # token_ids of every emitted output
        killed = False
        final = None
        while engine.has_unfinished_requests():
            if not killed and kill_at_output_len is not None:
                groups = list(engine.scheduler.running)
                if groups and groups[0].get_seqs()[0].get_output_len() \
                        >= kill_at_output_len:
                    outcome = engine.reincarnate()
                    assert outcome.restored == 1
                    assert outcome.lost == []
                    killed = True
                    continue
            for out in engine.step():
                emissions.append(list(out.outputs[0].token_ids))
                if out.finished:
                    final = out
        assert killed == (kill_at_output_len is not None)
        return final, emissions

    clean, _ = run(None)
    faulty, emissions = run(4)
    assert list(faulty.outputs[0].token_ids) == \
        list(clean.outputs[0].token_ids)
    assert faulty.outputs[0].text == clean.outputs[0].text
    # No duplicate emission across the rebuild: every successive
    # output's token_ids strictly extend the previous one's (the
    # restore continues from the emitted tokens; it never re-emits).
    for prev, cur in zip(emissions, emissions[1:]):
        assert cur[:len(prev)] == prev
        assert len(cur) > len(prev)


def test_disagg_reincarnation_rebuilds_split_bit_equal(tiny_model_dir):
    """Reincarnation THROUGH the disaggregated path: a (2,2)-split
    engine killed mid-generation rebuilds BOTH submeshes, both KV
    pools, and the disagg-aware scheduler; the restored request's KV
    re-prefills on the NEW prefill group, hands off across the new
    seam, and the joint output is bit-equal to the fault-free split
    run — with the shared ownership ledger back at free0."""
    sp = SamplingParams(temperature=1.0, seed=31337, max_tokens=12,
                        ignore_eos=True)
    kw = dict(tensor_parallel_size=4, disagg_split="2,2")

    def run(kill_at_output_len):
        engine = _sync_engine(tiny_model_dir, **kw)
        assert engine.executor.disagg
        free0 = engine.scheduler.block_manager.get_num_free_gpu_blocks()
        engine.add_request("seeded", None, sp,
                           prompt_token_ids=_prompt(0))
        killed = False
        final = None
        while engine.has_unfinished_requests():
            if not killed and kill_at_output_len is not None:
                groups = list(engine.scheduler.running)
                if groups and groups[0].get_seqs()[0].get_output_len() \
                        >= kill_at_output_len:
                    flushes_before = \
                        engine.executor.cache_engine.handoff_flushes
                    assert flushes_before > 0, \
                        "no handoff before the kill"
                    outcome = engine.reincarnate()
                    assert outcome.restored == 1
                    assert outcome.lost == []
                    # The rebuilt executor is a fresh split: both
                    # submeshes present, pools zeroed, counters reset,
                    # scheduler still chunk-throttle-free.
                    assert engine.executor.disagg
                    assert engine.executor.prefill_mesh.size == 2
                    assert engine.executor.mesh.size == 2
                    assert engine.executor.cache_engine \
                        .handoff_flushes == 0
                    assert engine.scheduler.disagg
                    killed = True
                    continue
            for out in engine.step():
                if out.finished:
                    final = out
        assert killed == (kill_at_output_len is not None)
        if kill_at_output_len is not None:
            # The restored request re-prefilled on the NEW prefill
            # group and handed off across the new seam.
            assert engine.executor.cache_engine.handoff_flushes > 0
        assert engine.scheduler.block_manager \
            .get_num_free_gpu_blocks() == free0
        return final

    clean = run(None)
    faulty = run(4)
    assert list(faulty.outputs[0].token_ids) == \
        list(clean.outputs[0].token_ids)


def test_async_restore_no_duplicate_chunks(tiny_model_dir,
                                           monkeypatch):
    """The stream-level half of the same invariant: a FATAL fault
    mid-generation reincarnates the engine, and the client stream's
    successive RequestOutputs never regress or re-deliver a token —
    the delta stream a frontend derives has no duplicate chunks."""
    monkeypatch.setenv("APHRODITE_REINCARNATIONS", "1")
    monkeypatch.setenv("APHRODITE_REINCARNATION_BACKOFF_S", "0.01")
    from aphrodite_tpu.common.faultinject import InjectedFatalFault

    async def go():
        engine = _async_engine(tiny_model_dir)
        sp = SamplingParams(temperature=1.0, seed=7, max_tokens=12,
                            ignore_eos=True)
        armed = {"fire": False, "fired": False}
        real = engine.engine.executor.dispatch_steps

        def maybe_fail(*a, **kw):
            # One-shot fatal, armed by the watcher once tokens have
            # streamed (same executor object: survives until the
            # rebuild replaces it).
            if armed["fire"] and not armed["fired"]:
                armed["fired"] = True
                raise InjectedFatalFault("mid-generation kill")
            return real(*a, **kw)

        engine.engine.executor.dispatch_steps = maybe_fail
        emissions = []
        async for out in engine.generate(None, sp, "r0",
                                         prompt_token_ids=_prompt(0)):
            emissions.append(list(out.outputs[0].token_ids))
            if len(emissions[-1]) >= 4:
                armed["fire"] = True
        assert armed["fired"], "the mid-generation fault never fired"
        assert engine.health.reincarnations_total == 1
        assert len(emissions[-1]) == 12
        for prev, cur in zip(emissions, emissions[1:]):
            assert cur[:len(prev)] == prev, \
                "stream re-delivered tokens after the rebuild"
        return emissions[-1]

    faulty = asyncio.run(go())

    async def clean_go():
        engine = _async_engine(tiny_model_dir)
        sp = SamplingParams(temperature=1.0, seed=7, max_tokens=12,
                            ignore_eos=True)
        final = None
        async for out in engine.generate(None, sp, "r0",
                                         prompt_token_ids=_prompt(0)):
            final = out
        return list(final.outputs[0].token_ids)

    assert faulty == asyncio.run(clean_go())


def test_stale_step_cannot_commit_after_reincarnation(tiny_model_dir,
                                                      monkeypatch):
    """The epoch guard: a step that was in flight when reincarnate()
    ran (the watchdog-abandoned-thread scenario) must raise
    StaleEngineStepError instead of committing tokens or rollbacks
    against the rebuilt scheduler."""
    engine = _sync_engine(tiny_model_dir)
    sp = SamplingParams(**SP)
    engine.add_request("r0", None, sp,
                       prompt_token_ids=list(PROMPTS[0]))
    engine.step()                       # prefill: r0 now decoding
    (group,) = engine.scheduler.running
    seq = group.get_seqs()[0]
    len_before = seq.get_output_len()

    real = engine.executor.dispatch_steps

    def bump_then_run(*a, **kw):
        # Simulate a reincarnation landing while this step is on the
        # device: the epoch moves under the step thread's feet.
        engine._epoch += 1
        return real(*a, **kw)

    monkeypatch.setattr(engine.executor, "dispatch_steps",
                        bump_then_run)
    with pytest.raises(StaleEngineStepError):
        engine.step()
    # No token was committed by the stale step.
    assert seq.get_output_len() == len_before


# ---------------------------------------------------------------------
# graceful drain: RUNNING -> DRAINING -> idle
# ---------------------------------------------------------------------

def test_drain_completes_inflight_rejects_new(tiny_model_dir):
    """start_drain(): the in-flight request runs to completion, a new
    request is rejected with the typed EngineDrainingError, drained()
    resolves True, and /health-level reporting says DRAINING."""
    engine = _async_engine(tiny_model_dir)

    async def go():
        async def long_req():
            final = None
            async for out in engine.generate(
                    None,
                    SamplingParams(temperature=0.0, max_tokens=32,
                                   ignore_eos=True),
                    "long", prompt_token_ids=_prompt(0)):
                final = out
            return final

        long_task = asyncio.create_task(long_req())
        await asyncio.sleep(0.2)          # admitted and running
        assert engine.engine.has_unfinished_requests()
        engine.start_drain(deadline_s=30.0, reason="test drain")
        assert engine.is_draining
        with pytest.raises(EngineDrainingError) as ei:
            async for _ in engine.generate(
                    None, SamplingParams(**SP), "late",
                    prompt_token_ids=_prompt(1)):
                pass
        assert ei.value.retry_after_s >= 1.0
        clean = await asyncio.wait_for(engine.drained(), timeout=30)
        assert clean is True
        final = await long_task
        assert len(final.outputs[0].token_ids) == 32
        report = await engine.check_health()
        assert report.state == "DRAINING"
        assert report.draining
        assert report.drain_deadline_remaining_s is not None

    asyncio.run(go())
    bm = engine.engine.scheduler.block_manager
    assert not bm.block_tables


def test_drain_deadline_force_aborts_with_typed_error(tiny_model_dir):
    """A missed drain deadline aborts the stragglers with the typed
    EngineDrainingError (the process can exit; nothing hangs, nothing
    is silently lost) and their KV pages free."""
    engine = _async_engine(tiny_model_dir)
    bm = engine.engine.scheduler.block_manager
    free0 = bm.get_num_free_gpu_blocks()

    async def go():
        async def long_req():
            async for _ in engine.generate(
                    None,
                    SamplingParams(temperature=0.0, max_tokens=200,
                                   ignore_eos=True),
                    "straggler", prompt_token_ids=_prompt(0)):
                pass

        long_task = asyncio.create_task(long_req())
        await asyncio.sleep(0.1)
        engine.start_drain(deadline_s=0.2, reason="deadline test")
        clean = await asyncio.wait_for(engine.drained(), timeout=30)
        assert clean is False
        with pytest.raises(EngineDrainingError):
            await long_task
        # The abort drains through the engine loop; wait for idle.
        for _ in range(200):
            gc.collect()
            await asyncio.sleep(0.02)
            if not engine.engine.has_unfinished_requests() and \
                    not bm.block_tables:
                break
        assert not engine.engine.has_unfinished_requests()

    asyncio.run(go())
    assert not bm.block_tables
    assert bm.get_num_free_gpu_blocks() == free0


def test_expiry_still_fires_during_drain(tiny_model_dir):
    """Drain x overload interplay: a request admitted BEFORE the drain
    whose TTFT deadline passes while queued must still expire with the
    typed RequestTimeoutError (408) during the drain — draining stops
    ADMISSION, not the deadline machinery."""
    engine = _async_engine(tiny_model_dir, max_num_seqs=1)

    async def go():
        async def long_req():
            final = None
            async for out in engine.generate(
                    None,
                    SamplingParams(temperature=0.0, max_tokens=48,
                                   ignore_eos=True),
                    "long", prompt_token_ids=_prompt(0)):
                final = out
            return final

        long_task = asyncio.create_task(long_req())
        await asyncio.sleep(0.1)          # long occupies the seq slot

        async def doomed():
            async for _ in engine.generate(
                    None, SamplingParams(ttft_slo_s=0.02, **SP),
                    "doomed", prompt_token_ids=_prompt(1)):
                pass

        doomed_task = asyncio.create_task(doomed())
        await asyncio.sleep(0.01)         # admitted, queued
        engine.start_drain(deadline_s=30.0, reason="expiry test")
        with pytest.raises(RequestTimeoutError):
            await doomed_task
        clean = await asyncio.wait_for(engine.drained(), timeout=30)
        assert clean is True
        final = await long_task
        assert len(final.outputs[0].token_ids) == 48

    asyncio.run(go())


# ---------------------------------------------------------------------
# HTTP semantics: 503 (draining) vs 429 (overload), /admin/drain auth,
# and the shared /health probe on every frontend
# ---------------------------------------------------------------------

def test_http_drain_503_stays_distinct_from_overload_429(
        tiny_model_dir, monkeypatch):
    """While the PR-7 admission controller is actively shedding
    (429 + Retry-After), an authed /admin/drain flips the replica to
    DRAINING — from then on rejections are 503 + Retry-After with the
    draining_error type, and /health turns 503/DRAINING."""
    monkeypatch.setenv("APHRODITE_MAX_QUEUE_DEPTH", "2")
    from aiohttp.test_utils import TestClient, TestServer
    from aphrodite_tpu.endpoints.openai.api_server import build_app

    async def go():
        engine = _async_engine(tiny_model_dir, max_num_seqs=2)
        client = TestClient(TestServer(build_app(
            engine, "tiny", admin_keys=["sekret"])))
        await client.start_server()
        try:
            async def post():
                r = await client.post("/v1/completions", json={
                    "model": "tiny", "prompt": "hello world " * 4,
                    "max_tokens": 8, "ignore_eos": True})
                return r.status, dict(r.headers), await r.json()

            # Overload burst: sheds are 429s while admitted serve 200.
            results = await asyncio.gather(*(post() for _ in range(8)))
            statuses = [s for s, _, _ in results]
            assert 429 in statuses and 200 in statuses, statuses
            for status, headers, body in results:
                if status == 429:
                    assert int(headers["Retry-After"]) >= 1
                    assert body["type"] == "overloaded_error"

            # Admin drain: unauthed 401, authed 200.
            r = await client.post("/admin/drain")
            assert r.status == 401
            r = await client.post(
                "/admin/drain", json={"deadline_s": 30.0},
                headers={"Authorization": "Bearer sekret"})
            assert r.status == 200
            body = await r.json()
            assert body["state"] == "DRAINING"
            assert body["drain_deadline_s"] == 30.0

            # New work now gets 503 draining_error — NOT 429.
            status, headers, body = await post()
            assert status == 503, body
            assert int(headers["Retry-After"]) >= 1
            assert body["type"] == "draining_error"

            # /health: 503 + DRAINING so balancers eject the replica.
            r = await client.get("/health")
            assert r.status == 503
            body = await r.json()
            assert body["state"] == "DRAINING"
            assert body["draining"] is True
            assert "Retry-After" in r.headers
        finally:
            await client.close()

    asyncio.run(go())


def test_kobold_and_ooba_serve_health_report(tiny_model_dir):
    """Satellite: the Kobold and Ooba frontends serialize the SAME
    HealthReport JSON via the shared endpoint helper — 200/RUNNING on
    a fresh replica (lazy loop included), 503/DRAINING once draining —
    and expose the /admin/drain endpoint (403 when no key is
    configured)."""
    from aiohttp.test_utils import TestClient, TestServer
    from aphrodite_tpu.endpoints.kobold.api_server import \
        build_app as kobold_app
    from aphrodite_tpu.endpoints.ooba.api_server import \
        build_app as ooba_app

    async def go():
        engine = _async_engine(tiny_model_dir)
        for build in (kobold_app, ooba_app):
            client = TestClient(TestServer(build(engine, "tiny")))
            await client.start_server()
            try:
                r = await client.get("/health")
                assert r.status == 200
                body = await r.json()
                assert body["state"] == "RUNNING"
                assert "reincarnations_total" in body
                r = await client.post("/admin/drain")
                assert r.status == 403   # no admin key configured
            finally:
                await client.close()

        engine.start_drain(deadline_s=30.0, reason="probe test")
        client = TestClient(TestServer(kobold_app(engine, "tiny")))
        await client.start_server()
        try:
            r = await client.get("/health")
            assert r.status == 503
            body = await r.json()
            assert body["state"] == "DRAINING"
            assert "Retry-After" in r.headers
        finally:
            await client.close()

    asyncio.run(go())


# ---------------------------------------------------------------------
# supervisor units: state precedence + lifecycle report plumbing
# ---------------------------------------------------------------------

def test_health_lifecycle_state_precedence():
    h = HealthMonitor()
    h.begin_rebuild()
    assert h.state() is EngineState.REBUILDING
    h.record_failure(RuntimeError("x"))   # degraded under rebuild
    assert h.state() is EngineState.REBUILDING
    h.end_rebuild(success=True, restored=3, lost=1, duration_s=1.5)
    # end_rebuild clears the fault streak with the old executor.
    assert h.state() is EngineState.RUNNING
    r = h.report()
    assert r.reincarnations_total == 1
    assert r.requests_restored == 3 and r.requests_lost == 1
    assert r.last_rebuild_s == 1.5

    h.mark_draining(time.monotonic() + 5.0)
    h.begin_rebuild()
    assert h.state() is EngineState.DRAINING   # outranks REBUILDING
    assert 0 < h.drain_remaining_s <= 5.0
    assert h.state().code == 2

    h.mark_dead(RuntimeError("boom"))
    assert h.state() is EngineState.DEAD
    body = h.report().to_json()
    assert body["draining"] is True
    assert body["state"] == "DEAD"


def test_failed_rebuild_counts_nothing():
    h = HealthMonitor()
    h.begin_rebuild()
    h.end_rebuild(success=False)
    assert h.reincarnations_total == 0
    assert h.state() is EngineState.RUNNING

    h2 = HealthMonitor()
    h2.mark_draining(None)                 # unbounded drain
    assert h2.is_draining
    assert h2.drain_remaining_s is None
    assert h2.report().drain_deadline_remaining_s is None


# ---------------------------------------------------------------------
# two-world regression tests (PR 11, aphrorace): the engine must be
# drivable from a worker thread's event loop (get_running_loop, not the
# deprecated get_event_loop), and drained() must be event-driven — it
# resolves the moment in-flight hits zero, with no poll timer.
# ---------------------------------------------------------------------

def test_engine_loop_from_worker_thread(tiny_model_dir):
    """Fleet mode runs each replica's asyncio loop on a worker thread:
    generate + drain + drained() must work end-to-end off the main
    thread (the deprecated get_event_loop() grabbed — or failed to
    create — the wrong loop there)."""
    import threading

    engine = _async_engine(tiny_model_dir)
    result, errors = {}, []

    def worker():
        async def go():
            final = None
            async for out in engine.generate(
                    None, SamplingParams(**SP), "threaded",
                    prompt_token_ids=_prompt(0)):
                final = out
            result["tokens"] = list(final.outputs[0].token_ids)
            engine.start_drain(deadline_s=10.0, reason="thread test")
            result["drained"] = await asyncio.wait_for(
                engine.drained(), timeout=20)

        try:
            asyncio.run(go())
        except BaseException as e:   # surface into the main thread
            errors.append(e)

    t = threading.Thread(target=worker)
    t.start()
    t.join(timeout=120)
    assert not t.is_alive(), "worker-thread engine loop hung"
    assert not errors, errors
    assert len(result["tokens"]) == SP["max_tokens"]
    assert result["drained"] is True


def test_drained_is_event_driven(tiny_model_dir):
    """drained() resolves via the tracker-fed idle event, not a poll
    loop: an idle replica resolves immediately, and after the last
    in-flight request finishes the waiter wakes without any sleep
    cadence (asserted by resolving well inside the old 50 ms poll)."""
    engine = _async_engine(tiny_model_dir)

    async def go():
        # Idle from the start: resolves without the loop ever running.
        assert await asyncio.wait_for(engine.drained(), timeout=1) \
            is True

        final = None
        async for out in engine.generate(
                None, SamplingParams(**SP), "one",
                prompt_token_ids=_prompt(1)):
            final = out
        assert final is not None
        # The event must already be set by the round that finished the
        # request — drained() resolves with no timer in the path.
        t0 = time.monotonic()
        assert await asyncio.wait_for(engine.drained(), timeout=5) \
            is True
        assert time.monotonic() - t0 < 0.05
        assert engine._idle_event.is_set()

        # New arrivals flip the replica busy again.
        stream = await engine.add_request(
            "two", None, SamplingParams(**SP),
            prompt_token_ids=_prompt(2))
        assert not engine._idle_event.is_set()
        async for _ in stream:
            pass

    asyncio.run(go())
