"""The two ways load is offered, the warm-up and the canary.

Open loop: a schedule of due times is drawn from the seed before the
window; each request is sent by its own task at its due time and timed
from that due time. Closed loop: N callers, each sends its next
request when its last reply is complete; the callers join a few at a
time, and the window opens once each has a reply and the server has
stopped compiling.
"""
from __future__ import annotations

import asyncio
import contextlib
import dataclasses
import itertools
from typing import Awaitable, Callable, List, Optional

import aiohttp
import numpy as np

from perf.client import Reply, clock, complete, get_json, sleep_until


@dataclasses.dataclass
class Target:
    """Where requests go and what they are drawn from."""
    session: aiohttp.ClientSession
    url: str
    model: str
    vocab: int
    traffic: dict           # the traffic file
    generator: Callable     # its generator's block()
    timeout: float          # per request, seconds

    def block(self, seed: int, index: int, n: int,
              span: Optional[float]) -> List[dict]:
        return self.generator(self.traffic["params"], seed, index, n,
                              span, self.vocab)

    def send(self, shape: dict, due: Optional[float], block: int,
             on_first: Optional[Callable] = None,
             timeout: Optional[float] = None) -> Awaitable[Reply]:
        return complete(self.session, self.url, self.model, shape,
                        self.vocab, due, block, timeout or self.timeout,
                        on_first)


@dataclasses.dataclass
class Window:
    """The measured window: its sample of requests and its clock."""
    t0: float
    seconds: float
    replies: List[Reply]
    #: when the last request of the sample ended (>= t0 + seconds:
    #: requests open when the window closes are waited for)
    t_end: float
    #: requests that failed before the window opened (closed loop: the
    #: callers' joining is part of the same stream)
    failed_before: int = 0
    #: requests that failed after the sample's last one had ended: the
    #: load goes on while `after_close` runs (a `--trace 2` run traces
    #: then), and those replies enter no metric, but a failure is one
    failed_after: int = 0

    @property
    def attempted(self) -> int:
        return len(self.replies)

    @property
    def failed(self) -> int:
        return sum(1 for r in self.replies if not r.ok)


async def _cancel(tasks) -> None:
    for t in tasks:
        t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)


async def wait_idle(target: Target, timeout: float = 300.0) -> None:
    """Until the engine owns no unfinished request (cancelled ones are
    aborted within a step or two)."""
    deadline = clock() + timeout
    while clock() < deadline:
        _, body = await get_json(target.session,
                                 target.url + "/health?probe=1")
        if body.get("inflight") == 0:
            return
        await asyncio.sleep(0.2)
    raise TimeoutError(f"server still busy {timeout:.0f} s after the "
                       "load stopped")


async def open_loop(target: Target, seed: int, seconds: float,
                    on_open: Optional[Callable] = None,
                    inflight_cap: Optional[int] = None,
                    tail: bool = True,
                    after_close: Optional[Callable] = None) -> Window:
    """Offer the traffic at its fixed rate. Block 0 of the seed's
    stream, due in [t0, t0 + seconds), is the sample; the last
    `lead_seconds` of block -1 run before it so that the window opens
    on a loaded server, and blocks 1, 2, ... go on at the same rate
    until the sample's last request has ended, so that the tail is
    measured under load, and on through `after_close()`. With an
    `inflight_cap` (warm-up only) a send waits while that many
    requests are open."""
    loop = target.traffic["loop"]
    n, span = max(1, round(loop["rate_per_s"] * seconds)), float(seconds)
    lead = min(float(loop.get("lead_seconds", 0.0)), span)
    before = [s for s in target.block(seed, -1, n, span)
              if s["offset"] >= span - lead]
    sample = target.block(seed, 0, n, span)
    gate = asyncio.Semaphore(inflight_cap) if inflight_cap \
        else contextlib.nullcontext()

    async def one(shape: dict, due: float, index: int) -> Reply:
        await sleep_until(due)
        async with gate:
            return await target.send(shape, due, index)

    t0 = clock() + lead + 0.25
    opened = asyncio.ensure_future(on_open(t0)) if on_open else None
    others = [asyncio.ensure_future(one(s, t0 - span + s["offset"], -1))
              for s in before]
    tasks = [asyncio.ensure_future(one(s, t0 + s["offset"], 0))
             for s in sample]

    async def keep_sending() -> None:
        for index in itertools.count(1):
            await sleep_until(t0 + index * span - 1.0)
            for s in target.block(seed, index, n, span):
                others.append(asyncio.ensure_future(
                    one(s, t0 + index * span + s["offset"], index)))

    sender = asyncio.ensure_future(keep_sending()) if tail else None
    try:
        replies = await asyncio.gather(*tasks)
        t_end = max(r.ended for r in replies)
        if after_close is not None:
            await after_close()
        later = [t.result() for t in others
                 if t.done() and not t.cancelled()]
    finally:
        await _cancel(([sender] if sender else []) + others)
    if opened is not None:
        await opened
    return Window(t0=t0, seconds=span, replies=list(replies), t_end=t_end,
                  failed_after=sum(1 for r in later
                                   if not r.ok and r.ended >= t_end))


async def closed_loop(target: Target, seed: int, seconds: float,
                      on_open: Optional[Callable] = None,
                      settled: Optional[Callable] = None,
                      after_close: Optional[Callable] = None) -> Window:
    """`clients` callers in a closed loop, which is its own warm-up.

    The callers join in groups of `ramp_groups` (cycled). A group's
    first requests are streamed, and the next group joins when each of
    them has its first token: the server's waiting queue then never
    holds more prompts than one group, however long a step program
    takes to compile (the server sheds arrivals past 8 x
    max_num_batched_tokens of queued prompt), and the group sizes are
    the prefill batches that callers finishing in one step make now and
    then. The window opens when every caller has a complete first
    reply (the callers have then drifted apart and the pool is as full
    as it gets) and `settled()` has returned (the server has compiled
    nothing for a while), and lasts `seconds`. The callers go on until
    every request that was open when it closed has ended, so that the
    work the window did on them can be counted, and on through
    `after_close()`; the sample is every request that was open at some
    time inside the window. Requests
    sent before it opens may wait for compiles and get
    `warm_timeout_s`. Every block of `clients` requests holds the same
    lengths, so what is open at any time is the same work in every
    run. The first `journal_callers` callers stream every request with
    the journal header, so their replies carry token ids: rows of the
    full decode batch that the reference can be run over."""
    loop = target.traffic["loop"]
    clients = int(loop["clients"])
    journal = int(loop.get("journal_callers", 0))
    groups = itertools.cycle(loop.get("ramp_groups", [1]))
    patient = float(target.traffic.get("warm_timeout_s", target.timeout))

    def shapes():
        for index in itertools.count(0):
            for s in target.block(seed, index, clients, None):
                yield index, s

    stream = shapes()
    replies: List[Reply] = []
    started = [asyncio.Event() for _ in range(clients)]
    replied = [asyncio.Event() for _ in range(clients)]
    closed = [asyncio.Event() for _ in range(clients)]
    t0: Optional[float] = None
    t1: Optional[float] = None

    async def caller(j: int) -> None:
        index, shape = next(stream)
        replies.append(await target.send(
            dict(shape, stream=True), None, index, started[j].set, patient))
        replied[j].set()
        while True:
            index, shape = next(stream)
            replies.append(await target.send(
                dict(shape, stream=True) if j < journal else shape, None,
                index, timeout=patient if t0 is None else None))
            if t1 is not None:      # the request open at t1 has ended
                closed[j].set()

    callers: List[asyncio.Task] = []
    opened = None
    try:
        while len(callers) < clients:
            first = len(callers)
            for j in range(first, min(first + next(groups), clients)):
                callers.append(asyncio.ensure_future(caller(j)))
            await asyncio.gather(
                *(e.wait() for e in started[first:len(callers)]))
        await asyncio.gather(*(e.wait() for e in replied))
        if settled is not None:
            await settled()
        t0 = clock()
        if on_open:
            opened = asyncio.ensure_future(on_open(t0))
        await sleep_until(t0 + seconds)
        t1 = t0 + seconds
        await asyncio.wait_for(
            asyncio.gather(*(e.wait() for e in closed)), target.timeout)
        if after_close is not None:
            await after_close()
    finally:
        await _cancel(callers)
    if opened is not None:
        await opened
    sample = [r for r in replies if r.ended >= t0 and r.sent < t1]
    return Window(t0=t0, seconds=float(seconds), replies=sample,
                  t_end=max([t1] + [r.ended for r in sample]),
                  failed_before=sum(1 for r in replies
                                    if r.ended < t0 and not r.ok),
                  failed_after=sum(1 for r in replies
                                   if r.sent >= t1 and not r.ok))


LOOPS = {"open": open_loop, "closed": closed_loop}


async def warm_waves(target: Target, seed: int) -> int:
    """The traffic file's `warm_waves`, e.g. [[9, 8, 6], [3], [5]]:
    groups of bursts of the cell's own requests. A group starts on an
    idle server; each burst of a group is sent at once, when every
    request of the burst before it has its first token, so the bursts
    pile up into the larger decode batches without ever queueing more
    prompts than one burst holds. When a group's last request has
    ended the server has decayed through every decode batch size below
    the group's sum. The batch shapes that arrivals reach only now and
    then are so met here and not in the window. Returns how many
    requests failed."""
    failed, index = 0, 100
    for group in target.traffic.get("warm_waves", []):
        tasks = []
        for size in group:
            index += 1
            firsts = []
            for shape in target.block(seed, index, size, None):
                firsts.append(asyncio.Event())
                tasks.append(asyncio.ensure_future(
                    target.send(shape, None, -3, firsts[-1].set)))
            await asyncio.gather(*(e.wait() for e in firsts))
        failed += sum(1 for r in await asyncio.gather(*tasks) if not r.ok)
        await wait_idle(target)
    return failed


async def warm_pass(target: Target, seed: int) -> Window:
    """One pass of an open loop's own traffic for `warm_seconds`,
    outside any window: no lead-in, no tail, and sends held back while
    `warm_inflight` requests are open, so that a server stalled on a
    compile is not flooded into batch shapes the window never has."""
    loop = target.traffic["loop"]
    warm = dict(target.traffic, loop=dict(loop, lead_seconds=0.0))
    window = await open_loop(
        dataclasses.replace(target, traffic=warm), seed,
        float(target.traffic["warm_seconds"]),
        inflight_cap=target.traffic.get("warm_inflight"), tail=False)
    await wait_idle(target)
    return window


async def canary(target: Target, seed: int) -> List[Reply]:
    """A few short prompts, each alone on the server, greedy or with a
    fixed sampling seed. Sent before and after the window, they must
    return the same ids."""
    spec = target.traffic.get("canary", {})
    rng = np.random.default_rng([int(seed), 0])
    out = []
    for n in spec.get("prompt_lens", [40, 50, 60]):
        shape = dict(prompt=rng.integers(3, target.vocab, n).tolist(),
                     max_tokens=spec.get("max_tokens", 16), stream=True,
                     sampling=spec.get("sampling", {"temperature": 0.0}))
        out.append(await target.send(shape, None, -2))
    return out
