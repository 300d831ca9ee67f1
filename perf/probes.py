"""What is read from the server while the window is open: the gauges
of `/metrics`, the server log's position, and the device trace."""
from __future__ import annotations

import asyncio
import dataclasses
import re
from typing import Dict, List, Optional, Tuple

from perf.client import clock, get_text, post_json, sleep_until

_SAMPLE = re.compile(r"^([A-Za-z_:][\w:]*)(?:\{[^}]*\})? ([-+.\deEinfa]+)$")
#: seconds of device trace taken. The program's `stop_profile` then
#: stalls the server while the trace is written (some 15 s with the
#: Python tracer on). In a `--trace 1` run of an open loop the trace
#: is the window's last seconds, so that the stall falls behind the
#: window and no arrival piles up in it; a closed loop's callers leave
#: when the window closes, so its trace ends `CLOSED_LOOP_MARGIN`
#: seconds earlier, under load. A `--trace 2` run traces after the
#: window (`trace_after`), with the load still going.
TRACE_SECONDS = 2.0
CLOSED_LOOP_MARGIN = 3.0
SAMPLE_PERIOD = 0.25


def parse_prometheus(text: str) -> Dict[str, float]:
    """name -> value, summed over label sets; histogram buckets are
    left out (their `_sum` and `_count` are kept)."""
    out: Dict[str, float] = {}
    for line in text.splitlines():
        m = _SAMPLE.match(line)
        if m and not m.group(1).endswith("_bucket"):
            out[m.group(1)] = out.get(m.group(1), 0.0) + float(m.group(2))
    return out


@dataclasses.dataclass
class Probe:
    """Started when the window opens (`open(t0)`), ended by `close()`."""
    session: object
    url: str
    server: object                    # .log_size()
    trace_dir: Optional[str] = None   # set: take a device trace
    trace_at: float = 0.0             # seconds after t0 to start it
    python_tracer: bool = False       # ask for Python frames as well
    samples: List[Tuple[float, Dict[str, float]]] = dataclasses.field(
        default_factory=list)
    log_open: Optional[int] = None
    log_close: Optional[int] = None
    trace_span: Optional[Tuple[float, float]] = None
    _tasks: list = dataclasses.field(default_factory=list)

    async def open(self, t0: float) -> None:
        await sleep_until(t0)
        self.log_open = self.server.log_size()
        self._tasks.append(asyncio.ensure_future(self._sample()))
        if self.trace_dir is not None:
            self._tasks.append(asyncio.ensure_future(self._trace(t0)))

    async def _sample(self) -> None:
        while True:
            text = await get_text(self.session, self.url + "/metrics")
            self.samples.append((clock(), parse_prometheus(text)))
            await asyncio.sleep(SAMPLE_PERIOD)

    async def _trace(self, t0: float) -> None:
        await sleep_until(t0 + self.trace_at)
        await self._profile(self.trace_dir, TRACE_SECONDS)

    async def _profile(self, trace_dir: str, seconds: float) -> float:
        """Trace `seconds` into `trace_dir`; returns how long the
        server took to answer `/stop_profile` (it writes the trace
        before it does)."""
        body = {"trace_dir": trace_dir}
        if self.python_tracer:
            body["python_tracer"] = True
        status, text = await post_json(
            self.session, self.url + "/start_profile", body)
        if status != 200:
            raise RuntimeError(f"/start_profile: HTTP {status}: {text}")
        started = clock()
        await asyncio.sleep(seconds)
        stopping = clock()
        status, text = await post_json(
            self.session, self.url + "/stop_profile", {}, timeout=120.0)
        if status != 200:
            raise RuntimeError(f"/stop_profile: HTTP {status}: {text}")
        self.trace_span = (started, clock())
        return clock() - stopping

    async def trace_after(self, trace_dir: str) -> float:
        """A `--trace 2` run's traced seconds, taken when the window's
        numbers are complete and the load still goes on. The profiler
        is first started and stopped once into a directory that is
        thrown away, so that what its first start costs falls into no
        number; then `TRACE_SECONDS` are traced into `trace_dir`.
        Returns the seconds the second `/stop_profile` took."""
        await self._profile(trace_dir + ".first", 0.0)
        return await self._profile(trace_dir, TRACE_SECONDS)

    async def close(self) -> None:
        """Take the last sample, stop sampling, and wait for the trace
        to be written if one is being taken."""
        self.log_close = self.server.log_size()
        sampler, tracers = self._tasks[0], self._tasks[1:]
        sampler.cancel()
        await asyncio.gather(sampler, return_exceptions=True)
        text = await get_text(self.session, self.url + "/metrics")
        self.samples.append((clock(), parse_prometheus(text)))
        for t in tracers:
            await t
