"""A stub of the program's OpenAI server for the harness's tests: it
speaks `/v1/completions` (JSON and SSE with journal records),
`/health`, `/metrics` and the profile routes (which it only records),
and writes a log with
JAX_LOG_COMPILES-style lines the first time it meets a prompt bucket.
"""
from __future__ import annotations

import asyncio
import json
import os

from aiohttp import web


class Stub:
    def __init__(self, log_path: str, token_delay: float = 0.002,
                 chunk: int = 1, fail_every: int = 0,
                 fail_while_profiling: bool = False) -> None:
        self.log_path = log_path
        self.token_delay = token_delay
        self.chunk = chunk              # tokens per streamed chunk
        self.fail_every = fail_every    # every n-th request gets a 500
        #: requests that arrive while the profiler runs get a 500
        self.fail_while_profiling = fail_while_profiling
        self.profiling = False
        #: one (route, request body, requests open, requests served so
        #: far) per call of a profile route
        self.profile_calls = []
        self.inflight = 0
        #: streamed requests that wait for their first token, and the
        #: most there were at once
        self.streams_queued = self.most_streams_queued = 0
        self.served = 0
        self.ttft_sum = 0.0
        self.seen_buckets = set()
        self.url = None
        self._runner = None
        open(log_path, "w").close()

    # -- what perf.run.measure() needs of a server --
    def log_size(self) -> int:
        return os.path.getsize(self.log_path)

    def read_log(self, start: int = 0, end=None) -> str:
        with open(self.log_path, "rb") as f:
            f.seek(start)
            data = f.read() if end is None else f.read(end - start)
        return data.decode()

    def _log(self, line: str) -> None:
        with open(self.log_path, "a") as f:
            f.write(line + "\n")

    async def start(self) -> None:
        app = web.Application()
        app.router.add_get("/health", self.health)
        app.router.add_get("/metrics", self.metrics)
        app.router.add_post("/v1/completions", self.completions)
        app.router.add_post("/start_profile", self.profile)
        app.router.add_post("/stop_profile", self.profile)
        self._runner = web.AppRunner(app)
        await self._runner.setup()
        site = web.TCPSite(self._runner, "127.0.0.1", 0)
        await site.start()
        port = self._runner.addresses[0][1]
        self.url = f"http://127.0.0.1:{port}"

    async def stop(self) -> None:
        await self._runner.cleanup()

    async def profile(self, request):
        self.profiling = request.path == "/start_profile"
        self.profile_calls.append((request.path, await request.json(),
                                   self.inflight, self.served))
        return web.json_response({"status": "ok"})

    async def health(self, request):
        if "probe" in request.query:
            return web.json_response({"state": "RUNNING", "draining": False,
                                      "inflight": self.inflight})
        return web.json_response({
            "state": "RUNNING", "retries_total": 0, "recovered_steps": 0,
            "reincarnations_total": 0, "requests_lost": 0, "sheds_total": 0})

    async def metrics(self, request):
        h = "aphrodite:time_to_first_token_seconds"
        return web.Response(text="\n".join([
            "# HELP aphrodite:num_requests_running running",
            f"aphrodite:num_requests_running {float(self.inflight)}",
            "aphrodite:num_requests_waiting 0.0",
            "aphrodite:gpu_cache_usage_perc 0.25",
            f'{h}_bucket{{le="0.1"}} {self.served}.0',
            f"{h}_sum {self.ttft_sum}", f"{h}_count {float(self.served)}",
            f"aphrodite:prompt_tokens_total {self.served * 10.0}",
            f"aphrodite:generation_tokens_total {self.served * 5.0}", ""]))

    @staticmethod
    def tokens(prompt, n):
        """Deterministic ids from the prompt, never one id repeated."""
        base = sum(prompt) % 97
        return [(base + 7 * i) % 500 + 3 for i in range(n)]

    async def completions(self, request):
        body = await request.json()
        self.served += 1
        if (self.fail_every and self.served % self.fail_every == 0) or \
                (self.fail_while_profiling and self.profiling):
            return web.json_response({"message": "stub fault"}, status=500)
        prompt, n = body["prompt"], body["max_tokens"]
        bucket = max(16, 1 << (len(prompt) - 1).bit_length())
        if bucket not in self.seen_buckets:
            self.seen_buckets.add(bucket)
            self._log("Finished tracing + transforming _step for pjit "
                      "in 0.010 sec")
            self._log("Finished XLA compilation of jit(_step) in 0.020 sec")
        ids = self.tokens(prompt, n)
        self.inflight += 1
        streamed = bool(body.get("stream"))
        self.streams_queued += streamed
        self.most_streams_queued = max(self.most_streams_queued,
                                       self.streams_queued)
        try:
            try:
                await asyncio.sleep(self.token_delay)
            finally:
                self.streams_queued -= streamed
            self.ttft_sum += self.token_delay
            if not body.get("stream"):
                await asyncio.sleep(self.token_delay * n)
                return web.json_response({
                    "choices": [{"text": "", "finish_reason": "length"}],
                    "usage": {"prompt_tokens": len(prompt),
                              "completion_tokens": n}})
            resp = web.StreamResponse(headers={
                "Content-Type": "text/event-stream"})
            await resp.prepare(request)
            sent = 0
            while sent < n:
                part = ids[sent:sent + self.chunk]
                sent += len(part)
                rec = {"t": part, "n": sent}
                if sent == n:
                    rec["fin"] = "length"
                await resp.write(b": aphrodite-journal " +
                                 json.dumps(rec).encode() + b"\n")
                await resp.write(b"data: " + json.dumps(
                    {"choices": [{"text": "x"}]}).encode() + b"\n\n")
                await asyncio.sleep(self.token_delay * len(part))
            await resp.write(b"data: [DONE]\n\n")
            return resp
        finally:
            self.inflight -= 1
