"""Unit tests for the supervision layer: fault classification, the
health state machine, the fault-injection spec parser, and the
RequestTracker races the supervised loop must survive."""
import asyncio

import pytest

from aphrodite_tpu.common import faultinject
from aphrodite_tpu.engine import supervisor
from aphrodite_tpu.engine.supervisor import (EngineState, FaultClass,
                                             HealthMonitor,
                                             StepTimeoutError,
                                             classify_failure)


@pytest.fixture(autouse=True)
def _fresh_fault_state(monkeypatch):
    monkeypatch.delenv("APHRODITE_FAULT", raising=False)
    monkeypatch.delenv("APHRODITE_FAULT_SEED", raising=False)
    faultinject.reset()
    yield
    faultinject.reset()


# ---------------------------------------------------------------------
# classification
# ---------------------------------------------------------------------

def test_classify_injected_faults():
    assert classify_failure(
        faultinject.InjectedTransientFault("engine.step")) is \
        FaultClass.TRANSIENT
    assert classify_failure(
        faultinject.InjectedRequestFault("tokenizer.decode")) is \
        FaultClass.REQUEST
    assert classify_failure(
        faultinject.InjectedFatalFault("engine.step")) is \
        FaultClass.FATAL


def test_classify_timeout_is_always_fatal():
    # Even though the message mentions a transient-looking marker, a
    # watchdog timeout means a wedged step thread: never retried.
    assert classify_failure(
        StepTimeoutError("deadline exceeded: step wedged")) is \
        FaultClass.FATAL


def test_classify_transient_markers_and_default():
    assert classify_failure(
        RuntimeError("DEADLINE_EXCEEDED: collective timed out")) is \
        FaultClass.TRANSIENT
    assert classify_failure(ValueError("nonsense")) is FaultClass.FATAL
    assert classify_failure(
        ValueError("nonsense"),
        default=FaultClass.REQUEST) is FaultClass.REQUEST


def test_classify_compile_and_resource_errors_are_fatal():
    """What the compiler or the allocator refuses, it refuses again on
    retry, even when the text also carries a transient-looking
    marker."""
    for text in (
            "INTERNAL: Mosaic failed to compile TPU kernel: Slice "
            "shape along dimension 1 must be aligned to tiling (2)",
            "RESOURCE_EXHAUSTED: Ran out of memory in memory space "
            "vmem while allocating on stack; exceeded scoped vmem "
            "limit by 1.44M",
            "RESOURCE_EXHAUSTED: Out of memory while trying to "
            "allocate 6442450944 bytes",
            "DEADLINE_EXCEEDED while compiling"):
        assert classify_failure(RuntimeError(text)) is FaultClass.FATAL


# ---------------------------------------------------------------------
# health state machine
# ---------------------------------------------------------------------

def test_health_running_degraded_dead_transitions():
    h = HealthMonitor()
    assert h.state() is EngineState.RUNNING
    h.beat()
    assert h.state() is EngineState.RUNNING

    h.record_failure(RuntimeError("x"))
    assert h.state() is EngineState.DEGRADED
    r = h.report()
    assert r.state == "DEGRADED"
    assert r.consecutive_failures == 1 and r.retries_total == 1

    h.record_recovery()
    h.beat()                   # successful step clears the failures
    assert h.state() is EngineState.RUNNING
    assert h.recovered_steps == 1

    h.mark_dead(RuntimeError("boom"))
    assert h.is_dead
    assert h.state() is EngineState.DEAD
    # DEAD is terminal and keeps the FIRST reason.
    h.mark_dead(RuntimeError("later"))
    assert "boom" in h.dead_reason
    h.beat()
    assert h.state() is EngineState.DEAD


def test_health_stale_heartbeat_degrades_with_watchdog(monkeypatch):
    monkeypatch.setenv("APHRODITE_STEP_TIMEOUT_S", "0.01")
    h = HealthMonitor()
    h.beat()
    import time
    time.sleep(0.03)
    # Stale only matters while work is in flight.
    assert h.state(in_flight=False) is EngineState.RUNNING
    assert h.state(in_flight=True) is EngineState.DEGRADED
    assert h.report(in_flight=True).last_step_age_s > 0


def test_retry_policy_reads_flags(monkeypatch):
    monkeypatch.setenv("APHRODITE_STEP_RETRIES", "7")
    monkeypatch.setenv("APHRODITE_STEP_BACKOFF_S", "0.5")
    assert supervisor.retry_policy() == (7, 0.5)


# ---------------------------------------------------------------------
# fault-injection spec parsing / determinism
# ---------------------------------------------------------------------

def test_fire_noop_when_unset():
    faultinject.fire("engine.step")
    assert faultinject.stats() == {}


def test_count_bounds_fires(monkeypatch):
    monkeypatch.setenv("APHRODITE_FAULT", "engine.step:transient:1:2")
    faultinject.reset()
    for _ in range(2):
        with pytest.raises(faultinject.InjectedTransientFault):
            faultinject.fire("engine.step")
    faultinject.fire("engine.step")        # exhausted: recovers
    assert faultinject.stats() == {"engine.step:transient": 2}
    faultinject.fire("executor.execute_model")   # other points quiet


def test_probability_draws_are_seed_deterministic(monkeypatch):
    def schedule(seed):
        monkeypatch.setenv("APHRODITE_FAULT",
                           "engine.step:transient:0.5:0")
        monkeypatch.setenv("APHRODITE_FAULT_SEED", str(seed))
        faultinject.reset()
        fired = []
        for i in range(64):
            try:
                faultinject.fire("engine.step")
                fired.append(False)
            except faultinject.InjectedTransientFault:
                fired.append(True)
        return fired

    a, b, c = schedule(0), schedule(0), schedule(1)
    assert a == b, "same (spec, seed) must replay the same schedule"
    assert a != c, "different seeds must differ somewhere"
    assert any(a) and not all(a)


def test_malformed_specs_warn_and_noop(monkeypatch):
    for bad in ("engine.step:transient:1",          # missing count
                "nosuch.point:transient:1:1",       # unknown point
                "engine.step:nosuchkind:1:1",       # unknown kind
                "engine.step:transient:banana:1",   # bad prob
                "engine.step:transient:2.0:1"):     # prob out of range
        monkeypatch.setenv("APHRODITE_FAULT", bad)
        faultinject.reset()
        with pytest.warns(RuntimeWarning):
            faultinject.fire("engine.step")
        faultinject.fire("engine.step")    # parsed state: no rules


def test_multi_rule_spec(monkeypatch):
    monkeypatch.setenv(
        "APHRODITE_FAULT",
        "engine.step:transient:1:1,tokenizer.decode:request:1:1")
    faultinject.reset()
    with pytest.raises(faultinject.InjectedTransientFault):
        faultinject.fire("engine.step")
    with pytest.raises(faultinject.InjectedRequestFault):
        faultinject.fire("tokenizer.decode")
    faultinject.fire("engine.step")
    faultinject.fire("tokenizer.decode")


# ---------------------------------------------------------------------
# RequestTracker races (satellite: propagate/abort)
# ---------------------------------------------------------------------

def test_propagate_exception_to_untracked_request_is_silent():
    """Regression: an abort racing a step error used to KeyError inside
    propagate_exception — killing the loop it was trying to save."""
    from aphrodite_tpu.engine.async_aphrodite import RequestTracker

    async def go():
        tracker = RequestTracker()
        tracker.init_event()
        stream = tracker.add_request("r1")
        tracker.get_new_and_finished_requests()   # r1 now tracked
        tracker.abort_request("r1")
        tracker.get_new_and_finished_requests()   # r1 now UNtracked
        # Must not raise, must not resurrect the stream:
        tracker.propagate_exception(RuntimeError("late error"), "r1")
        assert stream.finished

    asyncio.run(go())


def test_fail_all_covers_queued_requests():
    """A request enqueued but not yet pumped into the engine must still
    receive the terminal error (no silent hang on a dead engine)."""
    from aphrodite_tpu.engine.async_aphrodite import RequestTracker

    async def go():
        tracker = RequestTracker()
        tracker.init_event()
        tracked = tracker.add_request("tracked")
        tracker.get_new_and_finished_requests()
        queued = tracker.add_request("queued")    # never pumped
        boom = RuntimeError("engine died")
        tracker.fail_all(boom)
        for stream in (tracked, queued):
            with pytest.raises(RuntimeError, match="engine died"):
                await stream.__anext__()

    asyncio.run(go())
