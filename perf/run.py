#!/usr/bin/env python3
"""Run one cell of the benchmark, once.

    python perf/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1|2>

Starts the program's OpenAI server as the one child that holds the
chip, warms up the cell's own traffic, measures for `--seconds`, drains
the server, and prints one JSON object as the last line of its output:
the end-to-end metrics (`--trace 0`), the per-layer metrics of a run
whose window is traced (`--trace 1`), or both (`--trace 2`: a
`--trace 0` run that, once its window's numbers are complete, traces a
few seconds of the same traffic).
It exits non-zero, and prints no result, when the server does not come
up on `tpu` with the chips the cell asks for. `--rehearse` runs a tiny
cell of `perf/rehearse/` on the CPU, says so in `device`, and is named
by no manifest.

This process never imports JAX while the server lives.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse            # noqa: E402
import asyncio             # noqa: E402
import contextlib          # noqa: E402
import dataclasses         # noqa: E402
import json                # noqa: E402
import os                  # noqa: E402
import shutil              # noqa: E402
import sys                 # noqa: E402
from typing import List, Optional   # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import aiohttp             # noqa: E402

from perf import (cells, loops, probes, reference, server as srv,  # noqa: E402
                  stats, trace)
from perf.client import clock, get_json   # noqa: E402

HEALTH_COUNTERS = ("retries_total", "recovered_steps",
                   "reincarnations_total", "requests_lost", "sheds_total")
MAX_WARM_PASSES = 3


def say(msg: str) -> None:
    print(f"[perf] {msg}", flush=True)


@dataclasses.dataclass
class Run:
    """Everything one run observed; what the metric readers read."""
    cell: cells.Cell
    window: loops.Window
    t_start: float                   # process start, on `clock`
    #: (time, {metric name: value}) of `/metrics`, four times a second
    #: from the window's opening to its close
    samples: list
    #: the samples that count end here: at the window's close or, in a
    #: `--trace 1` run, when the profiler was started (it slows the
    #: host, and writing the trace stalls the server for seconds)
    steady_until: float
    log_setup: str                   # server log before the window
    log_window: str                  # server log while it was open
    faults: List[str]
    #: what the reference found (`perf/reference.py::check`)
    reference: Optional[dict] = None
    #: the canary's replies of before the window, each served alone
    canary: list = dataclasses.field(default_factory=list)
    peaks: Optional[dict] = None     # perf/peaks.json for this device
    trace: Optional[dict] = None     # trace.reduce(), traced runs only

    def _steady(self, name: str) -> List[tuple]:
        return [(t, s[name]) for t, s in self.samples
                if t <= self.steady_until and name in s]

    def gauge(self, name: str) -> List[float]:
        """The window's samples of one gauge."""
        return [v for _, v in self._steady(name)]

    def rate(self, name: str) -> Optional[float]:
        """Growth of a counter per second, first to last sample."""
        have = self._steady(name)
        if len(have) < 2 or have[-1][0] <= have[0][0]:
            return None
        return (have[-1][1] - have[0][1]) / (have[-1][0] - have[0][0])


async def wait_ready(server: srv.Server, session, deadline: float) -> None:
    while clock() < deadline:
        if server.exit_code() is not None:
            raise srv.RunFailure(
                f"server exited with code {server.exit_code()} before "
                f"it was ready: {server.last_error()}")
        try:
            status, _ = await get_json(
                session, server.url + "/health?probe=1", 2.0)
            if status == 200:
                return
        except (aiohttp.ClientError, asyncio.TimeoutError, ValueError):
            pass
        await asyncio.sleep(1.0)
    raise srv.RunFailure("server not ready before the deadline")


async def warm_up(target: loops.Target, server, seed: int) -> None:
    """An open loop's warm-up: the traffic file's bursts, then passes
    of the cell's own traffic, on a seed derived from `seed` but not
    the window's, until a whole pass adds no compile line."""
    traffic = target.traffic
    warm_seed = (seed * 2654435761 + 1) % 2 ** 32
    warming = dataclasses.replace(
        target, timeout=float(traffic.get("warm_timeout_s", 900.0)))
    mark, t = server.log_size(), clock()
    failed = await loops.warm_waves(warming, warm_seed)
    say(f"warm-up waves {traffic.get('warm_waves', [])}: {failed} failed, "
        f"{clock() - t:.1f} s, {programs(server.read_log(mark))}")
    for i in range(MAX_WARM_PASSES if traffic["warm_seconds"] > 0 else 0):
        mark, t = server.log_size(), clock()
        warm = await loops.warm_pass(warming, warm_seed + i + 1)
        facts = srv.compile_facts(server.read_log(mark))
        say(f"warm-up pass {i + 1}: {warm.attempted} requests, "
            f"{warm.failed} failed, {clock() - t:.1f} s, "
            f"{facts['programs']} step programs traced, "
            f"{facts['compiled']} compiled or loaded")
        if facts["programs"] == 0 and facts["compiled"] == 0:
            break


def programs(log: str) -> str:
    facts = srv.compile_facts(log)
    return (f"{facts['programs']} step programs traced "
            f"({facts['trace_s'] + facts['lower_s']:.0f} s tracing and "
            f"lowering), {facts['compiled']} compiled or loaded "
            f"({facts['compile_s']:.0f} s), {facts['cache_hits']} of them "
            "from the persistent cache")


def parts(window: loops.Window, n: int = 30) -> List[int]:
    """Output tokens in each of `n` equal parts of the window: shows
    how evenly its work fell, and what a shorter window would read."""
    step = window.seconds / n
    return [round(stats.tokens_inside(window.replies, window.t0 + i * step,
                                      window.t0 + (i + 1) * step))
            for i in range(n)]


async def settled(server, quiet: float) -> None:
    """Returns when every step program the server has begun to trace is
    compiled and its log has gained no compile line for `quiet`
    seconds (more than the longest stage of a program that is in the
    cache: tracing, lowering and loading each end with a line)."""
    last, since = None, clock()
    while True:
        facts = srv.compile_facts(server.read_log())
        seen = (facts["programs"], facts["compiled"])
        if seen != last:
            last, since = seen, clock()
        if seen[0] <= seen[1] and clock() - since >= quiet:
            return
        await asyncio.sleep(0.5)


async def measure(cell: cells.Cell, server, session, seed: int,
                  seconds: float, trace_dir: Optional[str],
                  model: str, trace_mode: int = 1,
                  python_tracer: bool = False) -> Run:
    """Warm-up, canary, window, canary, health: everything between the
    server being ready and its drain. `server` needs `.url`,
    `.log_size()` and `.read_log(start, end)`. With a `trace_dir` the
    profiler's trace goes there: taken inside the window
    (`trace_mode` 1) or after it, the load still going (2)."""
    traffic = cell.traffic
    target = loops.Target(
        session=session, url=server.url, model=model,
        vocab=int(cell.config["vocab_size"]), traffic=traffic,
        generator=cell.generator,
        timeout=float(traffic["request_timeout_s"]))
    faults: List[str] = []

    is_open = traffic["loop"]["kind"] == "open"
    if is_open:
        await warm_up(target, server, seed)
    before = await loops.canary(target, seed)

    in_window = trace_dir if trace_mode == 1 else None
    probe = probes.Probe(
        session=session, url=server.url, server=server,
        trace_dir=in_window, trace_at=max(0.0, seconds - (
            probes.TRACE_SECONDS if is_open else
            probes.TRACE_SECONDS + probes.CLOSED_LOOP_MARGIN)),
        python_tracer=python_tracer)
    if is_open:
        # Writing the trace stalls the server for seconds. In an open
        # loop the arrivals of that time would pile into batch shapes
        # the cell never has, and their compiles into the rest of the
        # window; so a traced window holds sends back as the warm-up
        # does. Its end-to-end numbers are not reported.
        how = dict(inflight_cap=traffic.get("warm_inflight")) \
            if in_window else {}
    else:
        # A closed loop warms itself up: its callers join, and the
        # window opens when the server has stopped compiling.
        how = dict(settled=lambda: settled(
            server, float(traffic["warm_seconds"])))
    if trace_dir and trace_mode == 2:
        async def trace_after() -> None:
            t = clock()
            stall = await probe.trace_after(trace_dir)
            say(f"traced {probes.TRACE_SECONDS:g} s after the window, "
                f"the load still going: {clock() - t:.1f} s in all, "
                f"{stall:.1f} s of it the server writing the trace")
            # What the profiler costs the host: the engine's rounds
            # while it ran, against `round_ms` of the window.
            began, name = probe.trace_span[0], "aphrodite:engine_rounds_total"
            seen = [(at, s[name]) for at, s in probe.samples if name in s
                    and began <= at <= began + probes.TRACE_SECONDS]
            if len(seen) > 1 and seen[-1][1] > seen[0][1]:
                say("a round under the profiler: %.1f ms (%d rounds)" % (
                    (seen[-1][0] - seen[0][0]) * 1e3 /
                    (seen[-1][1] - seen[0][1]), seen[-1][1] - seen[0][1]))
        how["after_close"] = trace_after
    window = await loops.LOOPS[traffic["loop"]["kind"]](
        target, seed, seconds, on_open=probe.open, **how)
    await probe.close()
    say(f"before the window: {programs(server.read_log(0, probe.log_open))}")
    say(f"window: {window.attempted} requests, {window.failed} failed, "
        f"last one ended {window.t_end - window.t0:.1f} s after it opened")
    if not is_open:
        say(f"output tokens in each thirtieth of the window: {parts(window)}")
    for r in [r for r in window.replies if not r.ok][:5]:
        faults.append(f"request failed: {r.error}")
    if window.failed_before:
        faults.append(f"{window.failed_before} requests failed while the "
                      "callers joined")
    if trace_mode == 2 and window.failed_after:
        faults.append(f"{window.failed_after} requests failed in the "
                      "traced seconds after the window")

    await loops.wait_idle(target)
    after = await loops.canary(target, seed)
    if not all(a.ok and b.ok and a.ids == b.ids
               for a, b in zip(before, after)):
        faults.append("the canary's ids differ before and after the "
                      "window: " + "; ".join(
                          str(r.error or r.ids[:6]) for r in before + after))
    # That a reply is no constant is the reference's to say (the first
    # canary reply is among the sequences it is run over): under
    # weights whose layers count, greedy text settles into a few
    # tokens, and a sound reply of 16 can be one token 16 times.

    status, health = await get_json(session, server.url + "/health")
    counters = {k: health.get(k) for k in HEALTH_COUNTERS}
    say(f"/health: HTTP {status}, state {health.get('state')}, {counters}")
    if status != 200 or any(v != 0 for v in counters.values()):
        faults.append(f"the supervisor absorbed a fault: {counters}")

    return Run(cell=cell, window=window, t_start=T_START,
               samples=probe.samples,
               steady_until=window.t0 + (probe.trace_at if in_window
                                         else seconds),
               log_setup=server.read_log(0, probe.log_open),
               log_window=server.read_log(probe.log_open, probe.log_close),
               faults=faults, canary=before)


def read_metrics(run: Run, entries: list, kind: str) -> dict:
    """Each manifest entry's reader, found by the metric's name. A
    reader that finds nothing to read returns None, and the metric is
    left out of the line."""
    out = {}
    for entry in entries:
        value = cells.load_function(cells.reader_path(
            run.cell.root, kind, entry["name"]), "read")(run)
        if value is not None:
            out[entry["name"]] = {"value": value, "unit": entry["unit"]}
    return out


@contextlib.asynccontextmanager
async def serving(cell: cells.Cell, seed: int, rehearse: bool,
                  keep_log: Optional[str] = None):
    """The cell's configuration served by the one child process:
    yields `(server, session, device)` once the server is ready on the
    platform and chips the cell needs, and ends the child whatever
    happens."""
    cfg = cell.config["perf"]
    work = os.path.join(cell.root, "perf", ".work", cell.name)
    shutil.rmtree(work, ignore_errors=True)
    model_dir = os.path.join(work, "model")
    srv.write_model_dir(model_dir, {k: v for k, v in cell.config.items()
                                    if k != "perf"})
    # the whole configuration, for the child that makes the weights
    config_path = os.path.join(work, "cell_config.json")
    with open(config_path, "w") as f:
        json.dump(cell.config, f)
    # The compile cache is the benchmark's own, at a fixed path inside
    # the checkout: two checkouts share nothing, and nothing is written
    # to a directory the machine owns.
    cache = os.path.join(cell.root, "perf", ".cache", "jax")
    server = srv.Server(
        root=cell.root, model_dir=model_dir, config_path=config_path,
        engine_args=cfg["engine_args"], env=cfg["env"],
        device="cpu" if rehearse else "tpu", seed=seed % 2 ** 31,
        cache_dir=cache, log_path=os.path.join(work, "server.log"))
    say("server flags: " + " ".join(server.args[2:]))
    say("server environment: " + " ".join(
        f"{k}={v}" for k, v in sorted(server.env.items())))
    try:
        connector = aiohttp.TCPConnector(limit=0)
        async with aiohttp.ClientSession(connector=connector) as session:
            await wait_ready(server, session,
                             clock() + cfg["ready_timeout_s"])
            device = srv.parse_device(server.read_log())
            say(f"serving process reports {device}")
            want = "cpu" if rehearse else "tpu"
            if device["platform"] != want or \
                    (not rehearse and device["count"] != cell.chips):
                raise srv.RunFailure(
                    f"the server runs on {device}; this cell needs "
                    f"{cell.chips} device(s) of platform {want!r}")
            say(f"KV pool: {srv.parse_kv_pool(server.read_log())} "
                f"(pages, GiB); ready {clock() - T_START:.1f} s after start")
            yield server, session, device
    finally:
        server.kill()
        if keep_log:
            os.makedirs(os.path.dirname(os.path.abspath(keep_log)),
                        exist_ok=True)
            shutil.copyfile(server.log_path, keep_log)


async def serve_and_measure(cell: cells.Cell, args):
    async with serving(cell, args.seed, args.rehearse,
                       args.keep_log) as (server, session, device):
        run = await measure(
            cell, server, session, args.seed, args.seconds,
            os.path.join(os.path.dirname(server.log_path), "trace")
            if args.trace else None, server.args[1],
            trace_mode=args.trace, python_tracer=args.python_tracer)
        # The reference's child starts now and stays off the chip: it
        # imports and prepares while the server drains.
        checking = start_reference(run, args)
        try:
            # Drain while the session is still open: its connections
            # are idle.
            code = server.drain(180.0)
        except BaseException:
            if checking is not None:
                checking.abandon()
            raise
        # The server has exited, and its log holds its peak: the chip
        # is free for the child (some 10 s to reach it; the trace is
        # reduced meanwhile).
        if checking is not None:
            checking.release()
        log = server.read_log()
        if code != 0 or "Drain complete; exiting." not in log:
            run.faults.append(f"the server did not drain cleanly "
                              f"(exit code {code})")
        return run, device, log, checking


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1, 2), default=0)
    p.add_argument("--python-tracer", action="store_true",
                   help="ask the server's profiler for Python frames "
                        "as well (slows the host it times)")
    p.add_argument("--rehearse", action="store_true",
                   help="run a tiny cell of perf/rehearse/ on the CPU")
    p.add_argument("--keep-log", default=None, metavar="PATH",
                   help="copy the server's log to PATH at the end")
    p.add_argument("--control", action="store_true",
                   help="run the configuration's controls too (the "
                        "reference with what each entry of "
                        "`perf.controls` lowers) and print their "
                        "numbers; each has to exceed a limit. The "
                        "builder's and the tests', never the driver's")
    p.add_argument("--keep-trace", default=None, metavar="DIR",
                   help="write a short slice of the trace to "
                        "DIR/cut.json")
    args = p.parse_args(argv)
    root = cells.ROOT
    if not os.path.isdir(os.path.join(root, "aphrodite_tpu")):
        print("perf/run.py: FAILED: no aphrodite_tpu package in "
              f"{root}; run it from a checkout", file=sys.stderr)
        return 1
    try:
        cell = cells.load_cell(
            args.workload, root,
            "perf/rehearse/manifest.json" if args.rehearse else None)
        say(f"cell {cell.name}: config {cell.config_name} (source "
            f"{cell.config['perf']['source']}), traffic "
            f"{cell.traffic_name}, seed {args.seed}, {args.seconds:g} s")
        run, device, log, checking = asyncio.run(
            serve_and_measure(cell, args))
    except (srv.RunFailure, cells.CellError, TimeoutError,
            asyncio.TimeoutError, aiohttp.ClientError) as e:
        print(f"perf/run.py: FAILED: {type(e).__name__}: {e}",
              file=sys.stderr, flush=True)
        return 1

    if not args.rehearse:
        run.faults += srv.check_kernel_paths(
            log, cell.config["perf"]["kernel_families"])
        run.peaks = cells.load_peaks(device["kind"], root)
    if args.rehearse:
        device["rehearsal"] = "CPU rehearsal at a tiny size: no number " \
                              "here is a device metric"
    device["memory_peak_bytes"] = srv.parse_memory_peak(log)
    # The peak holds the whole reserved KV pool; say how much of the
    # pool the window's requests filled, so that reservation does not
    # pass for use.
    pool, used = srv.parse_kv_pool(log), stats.mean(
        run.gauge("aphrodite:gpu_cache_usage_perc"))
    if pool is not None and used is not None:
        device["kv_pool_bytes"] = int(pool[1] * 2 ** 30)
        device["kv_live_bytes"] = int(pool[1] * 2 ** 30 * used)
        say(f"device memory: peak {device['memory_peak_bytes'] / 1e9:.2f} "
            f"GB; KV pool {pool[0]} pages, {pool[1]:.2f} GiB reserved, "
            f"{used * 100:.1f}% of it live on average over the window")
    if args.trace:
        trace_dir = os.path.join(root, "perf", ".work", cell.name, "trace")
        path, t = trace.find_xplane(trace_dir), clock()
        try:
            planes = trace.load(path, args.rehearse)
            if args.keep_trace:
                os.makedirs(args.keep_trace, exist_ok=True)
                with open(os.path.join(args.keep_trace, "cut.json"),
                          "w") as f:
                    json.dump(trace.cut(planes), f)
            run.trace = trace.reduce(planes)
        except ValueError as e:
            print(f"perf/run.py: FAILED: {e}", file=sys.stderr, flush=True)
            if checking is not None:
                checking.finish()       # leave no process behind
            return 1
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        say(f"trace {os.path.getsize(path) / 1e6:.1f} MB, read and "
            f"reduced in {clock() - t:.1f} s")
        if args.trace == 2:
            # The trace is reduced: a run keeps none (a `--trace 1`
            # run's is overwritten by the cell's next run).
            shutil.rmtree(trace_dir, ignore_errors=True)
            shutil.rmtree(trace_dir + ".first", ignore_errors=True)
    compared = finish_reference(run, args, checking)
    for fault in run.faults:
        say(f"FAULT: {fault}")
    say(f"total {clock() - T_START:.1f} s")
    # each number compared beside its limit, last on standard error too
    for line in compared + [f"FAULT: {f}" for f in run.faults]:
        print(f"[perf] {line}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result_line(run, args.trace, device)), flush=True)
    return 0


def start_reference(run: Run, args):
    """Start the configuration's reference over the replies kept
    (`perf/reference.py`): one of the canary's, served alone, and the
    journal callers' of the window."""
    cell, w = run.cell, run.window
    cfg = cell.config["perf"]
    if "reference" not in cfg:
        return None
    most = int(cfg.get("reference_replies", 2))
    sequences, kept = reference.pick(run.canary, w.replies, w.t0,
                                     w.t0 + w.seconds, most)
    return reference.start(
        cell, args.seed % 2 ** 31,       # the seed the server was given
        sequences, kept, rows=1 + most, cpu=args.rehearse,
        controls=sorted(cfg.get("controls", {})) if args.control else ())


def finish_reference(run: Run, args, checking) -> List[str]:
    """Wait for the reference; a number over its limit is a fault.
    Returns one line for each number compared."""
    if checking is None:
        run.faults.append("the configuration names no reference")
        return []
    cfg = run.cell.config["perf"]
    run.reference, lines, faults = checking.finish()
    run.faults += faults
    got = run.reference
    if got.get("positions"):
        say(f"reference {cfg['reference']}: {got['sequences']} sequences "
            f"({got['window_replies']} of the window), {got['tokens']} "
            f"tokens, {got['positions']} positions compared in "
            f"{got['seconds']:.1f} s ({got['child_start_s']:.1f} s to "
            f"start, {got['compute_s']:.1f} s computing); a layer adds "
            f"{got['layer_share']:.3g} of the residual stream's norm")
    for name, read in checking.controls.items():
        numbers, under, failed = read or ({}, [], ["gave no number"])
        lines += [f"control {name} " + ln for ln in under]
        lines.append(f"control {name}: " + (
            "not correct, as it has to be" if failed
            else "PASSED: the limits do not hold it off"))
        run.reference.setdefault("controls", {})[name] = {
            k: numbers.get(k) for k in reference.NUMBERS}
    for line in lines:
        say(line)
    return lines


def result_line(run: Run, trace_mode: int, device: dict) -> dict:
    """The run's one line: the end-to-end metrics (`--trace 0`), the
    per-layer metrics (1), or both side by side (2)."""
    cell, metrics = run.cell, {}
    if trace_mode != 1:
        metrics.update(read_metrics(run, cell.end_to_end, "end_to_end"))
    if trace_mode:
        metrics.update(read_metrics(run, cell.per_layer, "layers"))
    result = dict(correct=not run.faults, attempted=run.window.attempted,
                  failed=run.window.failed, metrics=metrics, device=device)
    if run.reference is not None:
        result["reference"] = run.reference
    if trace_mode:
        result["breakdown"] = dict(device_ops=run.trace["device_ops"],
                                   idle_gaps=run.trace["idle_gaps"])
    return result


if __name__ == "__main__":
    sys.exit(main())
