"""OpenAI API server integration tests over a real aiohttp app
(reference strategy: `tests/async_engine/test_openai_server.py`, but
in-process instead of a subprocess uvicorn)."""
import asyncio
import json

import pytest
from aiohttp.test_utils import TestClient, TestServer

from aphrodite_tpu.engine.args_tools import AsyncEngineArgs
from aphrodite_tpu.engine.async_aphrodite import AsyncAphrodite
from aphrodite_tpu.endpoints.openai.api_server import build_app

MODEL_KEY = "tiny"


@pytest.fixture(scope="module")
def server_ctx(tiny_model_dir):
    """One engine + app per module; each test drives it via asyncio.run
    on a dedicated loop owned by the module."""
    loop = asyncio.new_event_loop()

    async def setup():
        engine = AsyncAphrodite.from_engine_args(AsyncEngineArgs(
            model=tiny_model_dir, load_format="dummy", dtype="float32",
            max_model_len=256, max_num_seqs=8, swap_space=0.01,
            disable_log_stats=False, disable_log_requests=True))
        app = build_app(engine, MODEL_KEY)
        client = TestClient(TestServer(app))
        await client.start_server()
        return engine, client

    engine, client = loop.run_until_complete(setup())
    yield loop, client
    loop.run_until_complete(client.close())
    loop.close()


def run(server_ctx, coro_fn):
    loop, client = server_ctx
    return loop.run_until_complete(coro_fn(client))


def test_health(server_ctx):
    async def go(client):
        # Health requires a running background loop; trigger it with a
        # first tiny request if needed.
        r = await client.post("/v1/completions", json={
            "model": MODEL_KEY, "prompt": "hi", "max_tokens": 1,
            "ignore_eos": True})
        assert r.status == 200, await r.text()
        r = await client.get("/health")
        assert r.status == 200
        body = await r.json()
        assert body["state"] == "RUNNING"
        assert body["steps_completed"] >= 1
        assert body["last_step_age_s"] >= 0
        assert body["consecutive_failures"] == 0
        assert body["dead_reason"] is None
    run(server_ctx, go)


def test_health_probe_fast_path(server_ctx):
    """GET /health?probe=1 serializes ONLY lifecycle state + overload
    snapshot (the fleet router's poll payload); the full report stays
    the default."""
    async def go(client):
        r = await client.get("/health", params={"probe": "1"})
        assert r.status == 200
        body = await r.json()
        assert set(body) == {"state", "draining", "inflight",
                             "overload"}
        assert body["state"] in ("RUNNING", "DEGRADED")
        assert body["draining"] is False
        assert isinstance(body["inflight"], int)
        assert "queue_depth" in body["overload"]
        assert "ewma_prefill_tok_s" in body["overload"]
        # The probe must NOT carry the full report's counters...
        assert "steps_completed" not in body
        # ...which the default /health still does.
        r = await client.get("/health")
        full = await r.json()
        assert "steps_completed" in full and "retries_total" in full
    run(server_ctx, go)


def test_health_reports_dead_after_fatal_fault(tiny_model_dir,
                                               monkeypatch):
    """An unrecoverable injected fault must flip /health to 503/DEAD
    (load balancers eject the replica) while requests fail fast."""
    from aphrodite_tpu.common import faultinject
    monkeypatch.setenv("APHRODITE_REINCARNATIONS", "0")
    monkeypatch.setenv("APHRODITE_FAULT",
                       "executor.execute_model:fatal:1:1")
    faultinject.reset()

    async def go():
        engine = AsyncAphrodite.from_engine_args(AsyncEngineArgs(
            model=tiny_model_dir, load_format="dummy", dtype="float32",
            max_model_len=256, max_num_seqs=4, swap_space=0.01,
            disable_log_stats=True, disable_log_requests=True))
        client = TestClient(TestServer(build_app(engine, MODEL_KEY)))
        await client.start_server()
        try:
            r = await client.post("/v1/completions", json={
                "model": MODEL_KEY, "prompt": "hi", "max_tokens": 2,
                "ignore_eos": True})
            assert r.status >= 500   # the engine died mid-request
            r = await client.get("/health")
            assert r.status == 503
            body = await r.json()
            assert body["state"] == "DEAD"
            assert "fatal" in body["error"] or \
                "fatal" in (body["dead_reason"] or "")
            # Subsequent requests fail fast, not hang.
            r = await asyncio.wait_for(
                client.post("/v1/completions", json={
                    "model": MODEL_KEY, "prompt": "hi",
                    "max_tokens": 2, "ignore_eos": True}),
                timeout=10)
            assert r.status >= 500
        finally:
            await client.close()
            faultinject.reset()

    asyncio.run(go())


def test_models(server_ctx):
    async def go(client):
        r = await client.get("/v1/models")
        body = await r.json()
        assert r.status == 200
        assert body["data"][0]["id"] == MODEL_KEY
    run(server_ctx, go)


def test_tokenize(server_ctx):
    async def go(client):
        r = await client.post("/v1/tokenize",
                              json={"prompt": "hello world"})
        body = await r.json()
        assert r.status == 200
        assert body["count"] == len(body["tokens"]) > 0
        assert body["max_model_len"] == 256
    run(server_ctx, go)


def test_completion_basic(server_ctx):
    async def go(client):
        r = await client.post("/v1/completions", json={
            "model": MODEL_KEY, "prompt": "the quick brown",
            "max_tokens": 6, "temperature": 0.0, "ignore_eos": True})
        body = await r.json()
        assert r.status == 200, body
        assert body["object"] == "text_completion"
        assert len(body["choices"]) == 1
        assert body["choices"][0]["finish_reason"] == "length"
        assert body["usage"]["completion_tokens"] == 6
    run(server_ctx, go)


def test_completion_wrong_model_404(server_ctx):
    async def go(client):
        r = await client.post("/v1/completions", json={
            "model": "nope", "prompt": "x", "max_tokens": 1})
        assert r.status == 404
    run(server_ctx, go)


def test_completion_n_choices(server_ctx):
    async def go(client):
        r = await client.post("/v1/completions", json={
            "model": MODEL_KEY, "prompt": "hello", "max_tokens": 4,
            "n": 2, "best_of": 2, "seed": 5, "ignore_eos": True})
        body = await r.json()
        assert r.status == 200, body
        assert len(body["choices"]) == 2
    run(server_ctx, go)


def test_completion_logprobs(server_ctx):
    async def go(client):
        r = await client.post("/v1/completions", json={
            "model": MODEL_KEY, "prompt": "hello", "max_tokens": 3,
            "temperature": 0.0, "logprobs": 2, "ignore_eos": True})
        body = await r.json()
        assert r.status == 200, body
        lp = body["choices"][0]["logprobs"]
        assert len(lp["tokens"]) == 3
        assert len(lp["top_logprobs"]) == 3
        assert all(len(d) >= 2 for d in lp["top_logprobs"])
    run(server_ctx, go)


def test_completion_streaming(server_ctx):
    async def go(client):
        r = await client.post("/v1/completions", json={
            "model": MODEL_KEY, "prompt": "the quick", "max_tokens": 5,
            "temperature": 0.0, "stream": True, "ignore_eos": True})
        assert r.status == 200
        assert r.headers["Content-Type"].startswith("text/event-stream")
        chunks, done = [], False
        async for line in r.content:
            line = line.decode().strip()
            if not line.startswith("data: "):
                continue
            payload = line[len("data: "):]
            if payload == "[DONE]":
                done = True
                break
            chunks.append(json.loads(payload))
        assert done
        assert chunks
        assert chunks[-1]["choices"][0]["finish_reason"] == "length"
    run(server_ctx, go)


def test_chat_completion(server_ctx):
    async def go(client):
        r = await client.post("/v1/chat/completions", json={
            "model": MODEL_KEY,
            "messages": [{"role": "user", "content": "say hi"}],
            "max_tokens": 5, "temperature": 0.0, "ignore_eos": True})
        body = await r.json()
        assert r.status == 200, body
        msg = body["choices"][0]["message"]
        assert msg["role"] == "assistant"
        assert isinstance(msg["content"], str)
    run(server_ctx, go)


def test_chat_streaming(server_ctx):
    async def go(client):
        r = await client.post("/v1/chat/completions", json={
            "model": MODEL_KEY,
            "messages": [{"role": "user", "content": "hello"}],
            "max_tokens": 4, "stream": True, "ignore_eos": True})
        assert r.status == 200
        saw_role = saw_done = False
        async for line in r.content:
            line = line.decode().strip()
            if not line.startswith("data: "):
                continue
            payload = line[len("data: "):]
            if payload == "[DONE]":
                saw_done = True
                break
            chunk = json.loads(payload)
            delta = chunk["choices"][0]["delta"]
            if delta.get("role") == "assistant":
                saw_role = True
        assert saw_role and saw_done
    run(server_ctx, go)


def test_logit_bias_forces_token(server_ctx):
    async def go(client):
        r = await client.post("/v1/completions", json={
            "model": MODEL_KEY, "prompt": "hello", "max_tokens": 3,
            "temperature": 0.0, "logit_bias": {"42": 100.0},
            "logprobs": 0, "ignore_eos": True})
        body = await r.json()
        assert r.status == 200, body
        # +100 bias must make token 42 win every greedy step; logprobs
        # tokens echo the sampled token strings.
        lp = body["choices"][0]["logprobs"]
        # All three sampled tokens identical (token id 42's string).
        assert len(set(lp["tokens"])) == 1
    run(server_ctx, go)


def test_logit_bias_out_of_vocab_rejected(server_ctx):
    async def go(client):
        r = await client.post("/v1/completions", json={
            "model": MODEL_KEY, "prompt": "hello", "max_tokens": 2,
            "logit_bias": {"99999999": 5.0}})
        assert r.status == 400
        # Engine must still be alive afterwards.
        r = await client.post("/v1/completions", json={
            "model": MODEL_KEY, "prompt": "hi", "max_tokens": 1,
            "ignore_eos": True})
        assert r.status == 200
    run(server_ctx, go)


def test_metrics_endpoint(server_ctx):
    async def go(client):
        r = await client.get("/metrics")
        assert r.status == 200
        text = await r.text()
        assert "aphrodite" in text
    run(server_ctx, go)


def test_grammar_constrained_completion(server_ctx):
    """The `grammar` field must constrain output (reference accepts it
    in the protocol and feeds GrammarLogitsProcessor); invalid grammars
    must 400 instead of being silently dropped."""
    grammar = '\nstart: "(" NUMBER ")"\nNUMBER: /[0-9]+/\n'

    async def go(client):
        r = await client.post("/v1/completions", json={
            "model": MODEL_KEY, "prompt": "the", "max_tokens": 8,
            "temperature": 0.0, "grammar": grammar})
        assert r.status == 200, await r.text()
        body = await r.json()
        text = body["choices"][0]["text"]
        from aphrodite_tpu.common.grammar import GrammarMatcher
        m = GrammarMatcher(grammar)
        state = m.root
        for ch in text:
            state = m.advance(state, ch)
            assert state is not None, f"output {text!r} broke grammar"

        r = await client.post("/v1/completions", json={
            "model": MODEL_KEY, "prompt": "the", "max_tokens": 4,
            "grammar": "start: !!not a grammar"})
        assert r.status == 400
    run(server_ctx, go)


def test_profile_endpoints(server_ctx, tmp_path):
    """POST /start_profile + /stop_profile wrap a jax.profiler trace
    around live requests (SURVEY §5 tracing/profiling)."""
    trace_dir = str(tmp_path / "trace")

    async def go(client):
        r = await client.post("/start_profile",
                              json={"trace_dir": trace_dir})
        assert r.status == 200, await r.text()
        r = await client.post("/v1/completions", json={
            "model": MODEL_KEY, "prompt": "hi", "max_tokens": 2,
            "ignore_eos": True})
        assert r.status == 200
        r = await client.post("/stop_profile", json={})
        assert r.status == 200
        # Double-stop errors cleanly.
        r = await client.post("/stop_profile", json={})
        assert r.status == 400
    run(server_ctx, go)
    import glob
    assert glob.glob(trace_dir + "/**/*.pb", recursive=True) or \
        glob.glob(trace_dir + "/**/*.xplane.pb", recursive=True) or \
        glob.glob(trace_dir + "/*", recursive=False)


def test_settle_collector_freezes_and_spaces_full_collections():
    """A frontend's `main()` settles the collector once its engine is
    built: what start-up left is frozen (a full collection no longer
    walks it), the young thresholds stay Python's and the third rises.
    Objects made afterwards are still collected, cycles included."""
    import gc
    import weakref

    from aphrodite_tpu.endpoints import utils

    before, frozen = gc.get_threshold(), gc.get_freeze_count()
    try:
        utils.settle_collector()
        assert gc.get_freeze_count() > frozen + 10_000
        assert gc.get_threshold() == utils.COLLECTOR_THRESHOLDS
        assert utils.COLLECTOR_THRESHOLDS[:2] == before[:2]
        assert utils.COLLECTOR_THRESHOLDS[2] >= 100 * before[2]

        class Node:
            pass
        a, b = Node(), Node()
        a.other, b.other = b, a
        gone = weakref.ref(a)
        del a, b
        gc.collect()
        assert gone() is None
    finally:
        gc.unfreeze()
        gc.set_threshold(*before)


@pytest.mark.parametrize("frontend", ["openai", "kobold", "ooba"])
def test_every_frontend_main_settles_the_collector(frontend):
    """Each server entry point calls `settle_collector` once, between
    the engine's construction and the sockets; nothing else in the
    package does (an engine built inside a test or `endpoints/llm.py`
    leaves the collector alone)."""
    import ast
    import importlib
    import inspect

    module = importlib.import_module(
        f"aphrodite_tpu.endpoints.{frontend}.api_server")
    calls = [n for n in ast.walk(ast.parse(inspect.getsource(module)))
             if isinstance(n, ast.Call) and
             getattr(n.func, "id", None) == "settle_collector"]
    assert len(calls) == 1
    main_src = inspect.getsource(module.main) if hasattr(module, "main") \
        else inspect.getsource(module)
    assert "settle_collector()" in main_src
