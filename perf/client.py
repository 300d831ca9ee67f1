"""One request over HTTP: send it, time it, check the reply."""
from __future__ import annotations

import asyncio
import dataclasses
import json
import time
from typing import Callable, List, Optional, Tuple

import aiohttp

_JOURNAL = ": aphrodite-journal "
clock = time.monotonic


@dataclasses.dataclass
class Reply:
    """What one request did. Times are on `clock`; `due` is when the
    schedule wanted it sent (for a closed loop, when it was sent)."""
    due: float
    sent: float
    max_tokens: int
    prompt_tokens: int
    block: int
    done: Optional[float] = None      # when a correct reply was complete
    ended: Optional[float] = None     # when the request ended, either way
    #: one (arrival time, tokens) per streamed chunk that held tokens
    arrivals: List[Tuple[float, int]] = dataclasses.field(
        default_factory=list)
    tokens: int = 0
    ids: Optional[List[int]] = None
    #: the prompt's ids, kept with a streamed reply (which has `ids`):
    #: the two together are what the reference is run over
    prompt: Optional[List[int]] = None
    error: Optional[str] = None

    @property
    def ok(self) -> bool:
        return self.error is None and self.done is not None

    @property
    def ttft(self) -> Optional[float]:
        """Seconds from when the request was due to its first token."""
        return self.arrivals[0][0] - self.due if self.arrivals else None


async def sleep_until(when: float) -> None:
    delay = when - clock()
    if delay > 0:
        await asyncio.sleep(delay)


async def complete(session: aiohttp.ClientSession, url: str, model: str,
                   shape: dict, vocab: int, due: Optional[float],
                   block: int, timeout: float,
                   on_first: Optional[Callable[[], None]] = None) -> Reply:
    """Send `shape` at `due` (now, if None) and read the whole reply.
    A fault of any kind is recorded in `Reply.error`, never raised: a
    failed request is counted, not fatal. `on_first` is called once,
    when the first token arrives or, failing that, when the request
    ends."""
    if due is not None:
        await sleep_until(due)
    sent = clock()
    reply = Reply(due=sent if due is None else due, sent=sent,
                  max_tokens=shape["max_tokens"],
                  prompt_tokens=len(shape["prompt"]), block=block,
                  prompt=shape["prompt"] if shape["stream"] else None)
    body = dict(model=model, prompt=shape["prompt"],
                max_tokens=shape["max_tokens"], ignore_eos=True,
                stream=shape["stream"], **shape["sampling"])
    # Journal records carry the token ids of every streamed chunk.
    headers = {"X-Aphrodite-Stream-Journal": "1"} if shape["stream"] else {}
    try:
        async with session.post(
                url + "/v1/completions", json=body, headers=headers,
                timeout=aiohttp.ClientTimeout(total=timeout)) as resp:
            if resp.status != 200:
                text = (await resp.text())[:300]
                reply.error = f"HTTP {resp.status}: {text}"
            elif shape["stream"]:
                await _read_stream(resp, reply, on_first)
            else:
                _read_body(await resp.json(), reply)
    except (aiohttp.ClientError, asyncio.TimeoutError, ValueError,
            KeyError) as e:
        reply.error = f"{type(e).__name__}: {e}"
    reply.ended = clock()
    if on_first is not None and not reply.arrivals:
        on_first()
    if reply.error is None:
        reply.done = reply.ended
        _check(reply, vocab)
    return reply


async def _read_stream(resp: aiohttp.ClientResponse, reply: Reply,
                       on_first: Optional[Callable[[], None]]) -> None:
    ids: List[int] = []
    finish = last_data = None
    async for raw in resp.content:
        line = raw.decode().rstrip("\r\n")
        if line.startswith(_JOURNAL):
            rec = json.loads(line[len(_JOURNAL):])
            if rec["t"]:
                if on_first is not None and not reply.arrivals:
                    on_first()
                reply.arrivals.append((clock(), len(rec["t"])))
                ids.extend(rec["t"])
            finish = rec.get("fin", finish)
        elif line.startswith("data: "):
            last_data = line[len("data: "):]
            if last_data != "[DONE]" and "error" in json.loads(last_data):
                reply.error = f"in-band {last_data[:300]}"
                return
    reply.ids, reply.tokens = ids, len(ids)
    if last_data != "[DONE]":
        reply.error = f"stream ended with {last_data!r}, not [DONE]"
    elif finish != "length":
        reply.error = f"finish_reason {finish!r}, wanted 'length'"


def _read_body(out: dict, reply: Reply) -> None:
    reply.tokens = out["usage"]["completion_tokens"]
    reason = out["choices"][0]["finish_reason"]
    if reason != "length":
        reply.error = f"finish_reason {reason!r}, wanted 'length'"


def _check(reply: Reply, vocab: int) -> None:
    if reply.tokens != reply.max_tokens:
        reply.error = (f"{reply.tokens} tokens, wanted exactly "
                       f"{reply.max_tokens}")
    elif reply.ids is not None and \
            not all(0 <= t < vocab for t in reply.ids):
        reply.error = "a token id outside the vocabulary"
    if reply.error is not None:
        reply.done = None


async def get_json(session: aiohttp.ClientSession, url: str,
                   timeout: float = 10.0):
    async with session.get(
            url, timeout=aiohttp.ClientTimeout(total=timeout)) as resp:
        return resp.status, await resp.json(content_type=None)


async def get_text(session: aiohttp.ClientSession, url: str,
                   timeout: float = 10.0) -> str:
    async with session.get(
            url, timeout=aiohttp.ClientTimeout(total=timeout)) as resp:
        return await resp.text()


async def post_json(session: aiohttp.ClientSession, url: str, body: dict,
                    timeout: float = 60.0):
    async with session.post(
            url, json=body,
            timeout=aiohttp.ClientTimeout(total=timeout)) as resp:
        return resp.status, await resp.text()
