"""Spans of the engine round: one instrumentation, read two ways.

A `Tracer` belongs to one engine, which hands it to its scheduler,
executor and model runners. `tracer.span(name, **facts)` times a stage
of a round. It always adds its duration to a per-stage accumulator
(`seconds`, `counts`: plain numbers on two dicts, written by the thread
that runs the stage, no lock), from which `engine/metrics.py` exports
the per-stage Prometheus counters. While the profiler runs
(`annotate(True)`, set by `AphroditeEngine.start_profile`) it also
enters a `jax.profiler.TraceAnnotation("aph." + name, ...)`, so the
same span lands in the profiler's `.xplane.pb` on the device trace's
clock, under its parent on the same thread, with the round's facts.
The profiler's trace is the span store: there is no second buffer or
exporter.

The names are a contract (PERF.md lists them with the metric each is
for): an unknown name raises. With the profiler off a span costs two
clock reads and two adds.

Set-up is accounted for the same way. The entry point makes the tracer
before the engine exists and the phases from process start to ready
are spans of it (`Tracer.phase`, `SETUP_PHASES`, `Tracer.ready`). Every
program the process builds is filed under the `program.*` names by
listeners of `jax.monitoring`, which JAX calls on the thread that
builds, at the start and at the end of a trace, a lowering and a
compile (`install_listeners`): the seconds are the process's
(`BUILDS`), the tracer bound to the building thread supplies the
round's facts, and `Tracer.fold_builds` brings them into the
accumulators the counters are exported from. A built program that is
called again fires no listener.
"""
from __future__ import annotations

import functools
import os
import threading
import time
from typing import Callable, Dict, List, Optional

from jax import monitoring
from jax.profiler import TraceAnnotation

from aphrodite_tpu.common.logger import init_logger

logger = init_logger(__name__)

#: Every span; the events that are counted but not timed as a span
#: (`Tracer.add`): a request's wait from arrival to the round that
#: first schedules it, a preemption, a sampling plan that built and
#: sent nothing because the batch had not changed, a step program
#: dispatched while the round before had not been pulled, and the
#: KV pages a decode step's attention copies and those of them that
#: are live, by page group where the model has several; the pages
#: window groups let go of; what a model's expert layers count in
#: the step program (pairs routed, experts touched); what the model
#: runner counts where it builds a step of a model with state slots
#: (rows started from zeros, decode rows, prompt tokens scanned, page
#: reads with every reading layer counted); the seconds in
#: which a dispatched step had not been pulled yet (`Tracer.flight`);
#: and the host's lead over the device, counted where the round turns
#: (`Tracer.add_split`: a name's `.prompt` and `.decode` twins); the
#: phases from process start to ready (`SETUP_PHASES`) and the stages of
#: the programs the process builds (`BUILD_NAMES`).
NAMES = (
    "async.between_steps",  # engine.step returning -> the next entering
    "async.step_call",      # the loop's call of engine.step: the hop to
                            # the step thread, the step, the hop back
    "engine.step",          # one AphroditeEngine.step()
    "sched.schedule",       # deadline expiry + Scheduler.schedule()
    "runner.prepare",       # host batch build, up to the dispatch call
    "sampler.plan",         # Sampler.plan (inside prepare)
    "runner.dispatch",      # the jitted call returning
    "runner.device_wait",   # the one blocking pull of the results
    "sampler.finalize",     # unpacking the pulled results
    "engine.process",       # detokenise, stop checks, outputs, stats
    "cache.kv_handoff",     # disagg: prefill pool -> decode pool
    "cache.window_release",  # window groups let go of passed pages
                             # (inside sched.schedule)
    "cache.state_assign",   # state slots given to admitted prompts
                            # (inside sched.schedule)
    "cache.window_close",   # a pooled group closes a window: its
                            # summary pages taken, its pages let go
                            # (inside cache.window_release or the
                            # decode rows' slots)
    "runner.summarise",     # the dispatch of the program that pools
                            # the closed windows into their summaries
    "queue_wait",
    "preemptions",
    "sampler.plan_reuse",
    "runner.ahead",         # a step program dispatched while the round
                            # before had not been pulled (`runner.starved`
                            # says whether the device had drained)
    "round.ahead",          # rounds dispatched with a round in flight
    "round.ahead.prompt",   # of them, those that carry a prompt step
    "runner.starved",       # of them, those whose round in flight had
                            # finished at the dispatch: nothing queued
    "runner.starved.prompt",
    "runner.prompt_late",   # of `round.ahead.prompt`, the rounds whose
                            # decode program had already finished when
                            # their prompt program went out behind it
    "pull.blocked",         # the blocking pull after a round went out
                            # ahead: the device's work still to do when
                            # the host had none left (seconds, pulls)
    "pull.blocked.decode",  # a decode-only round under a decode-only step
    "attn.pages_fetched",   # pages the decode kernel copies, a step
    "attn.pages_live",      # pages below the rows' context lengths
    "attn.decode_steps",    # decode steps those two were summed over
    "attn.pages_live.full",    # of them, the full groups' (a group's
    "attn.pages_live.window",  # layers share a page), the window groups'
    "attn.window_pages_unwindowed",  # what the window groups' rows
                            # would hold live without a window
    "cache.window_pages_freed",  # pages the window groups let go of
    "attn.summary_pages_live",  # of `attn.pages_live.window`, a pooled
                            # group's summary pages (its tables count
                            # there whole: summaries and window)
    "attn.windows_closed_prompt",  # windows pooled groups closed as a
    "attn.windows_closed_decode",  # prompt chunk, a decode row passed
                            # their edge
    "moe.tokens_routed",    # token-expert pairs of the expert layers
    "moe.experts_touched",  # held experts with a pair, over layers and
                            # steps
    "moe.pairs_held",       # of the pairs routed, those whose expert the
                            # layer holds (counted by a model that may
                            # hold a share of its experts)
    "moe.rows_walked",      # rows of the row tiles the expert kernels
                            # visited (`ops/pallas/grouped_matmul.py`):
                            # over `moe.pairs_held`, the padding a tile
                            # taller than its group costs the MXU
    "moe.kernel_steps",     # steps whose expert layers took those
                            # kernels (`fused_moe.takes_expert_kernel`)
    "moe.decode_experts_touched",  # of them, the decode steps'
    "moe.decode_expert_slots",     # experts x expert layers, a decode step
    "mla.latent_tokens_read",  # a decode step's rows' context lengths,
                            # summed: the latent rows each layer's
                            # absorbed decode attention reads (host)
    "mla.prefix_tokens_expanded",  # prefix tokens a prompt step read
                            # back from latent pages and up-projected
                            # to K and V (a layer's; in the program)
    "ssm.state_resets",     # prompt rows that start at position 0: the
                            # step's program starts them from zeros
    "ssm.decode_rows",      # decode rows of a model with state slots
    "ssm.prefill_tokens",   # prompt tokens its chunk scans went over
    "ssm.slot_waits",       # admission verdicts of "later" for want of a
                            # state slot while the pages were there
    "kda.decode_rows",      # decode rows of a model with delta-rule
                            # (KDA) layers: one-token state updates a
                            # layer (`ops/pallas/kda.py::kda_update`)
    "kda.prompt_tokens",    # live prompt tokens its chunk kernel went
                            # over (a layer's), padding apart
    "kda.prompt_chunks",    # and the chunks of `kda.CHUNK` tokens that
                            # held at least one of them
    "attn.page_reads_shared",  # a decode step's live pages, each group's
                            # times the layers that read them
    "attn.prefill_tiles_visited",  # (query block, key block) tiles the
                            # blocked prompt attention visits, over a
                            # step's attention layers
    "attn.prefill_tiles_padded",   # the tiles of its padded rectangles
    "attn.prefill_steps",   # prompt steps built (`_prepare_prompt`)
    "attn.prefill_kernel_steps",   # of them, those whose attention is
                            # the Pallas flash kernel
                            # (`layers/attention.py::takes_prefill_kernel`)
    "runner.in_flight",
    "setup.import",         # process start to the entry point's first
                            # line: the interpreter and the imports
    "setup.backend",        # the first jax.devices(): the runtime
    "setup.tokenizer",      # AphroditeEngine._init_tokenizer
    "setup.weights",        # get_model (and the prefill group's copy)
    "setup.kv_pool",        # the pool and the state slots sized, the
                            # cache engine's arrays allocated
    "setup.runner",         # ModelRunner.__init__, the LoRA manager
    "setup.frontend",       # engine built -> the server's start-up
                            # hooks done, its sockets about to open
    "program.trace",        # OUTERMOST traces of jitted functions (a
                            # nested jit's trace lies inside its
                            # caller's): seconds, count
    "program.lower",        # jaxpr -> MLIR module: seconds, count
    "program.compile",      # the backend's compile, or on a hit in the
                            # persistent cache the load in its place:
                            # seconds, count (programs built)
    "program.cache_load",   # of `program.compile`, the seconds reading
                            # executables from the persistent cache
    "program.cache_hit",    # compile requests the persistent cache
    "program.cache_miss",   # answered, and those it could not
    "program.store_hit",    # step programs the program store had
                            # (`executor/program_store.py`): loaded, never
                            # traced or lowered; each also one
                            # `program.compile` and one `program.cache_hit`
    "program.store_miss",   # step programs it had not: built, and kept
    "program.store_load",   # seconds reading and loading the hits (also
                            # in `program.compile` and `program.cache_load`)
)

#: the phases from process start to ready, in the order a server
#: enters them; together they should tile `Tracer.startup_seconds`
SETUP_PHASES = tuple(n for n in NAMES if n.startswith("setup."))
#: what the listeners of `jax.monitoring` file, for the whole process
BUILD_NAMES = tuple(n for n in NAMES if n.startswith("program."))
#: nested jits whose traces are kept by function as well (`BUILDS
#: .by_function`): the kernels' own, inside a step program's trace
KERNEL_JITS = frozenset((
    "_paged_decode_impl", "_prefill_flash_impl", "_ssm_update_impl",
    "_ssm_scan_impl", "grouped_ffn", "gptq_matmul_a8"))

_clock = time.perf_counter


def process_age() -> float:
    """Seconds since this process started, from `/proc/self/stat` (its
    start in clock ticks after boot, as `prometheus_client` reads
    `process_start_time_seconds`) and `/proc/uptime`; 0.0 where there
    is no such file."""
    try:
        with open("/proc/self/stat", "rb") as f:
            # the fields after the command, which may hold spaces
            started = float(f.read().rsplit(b")", 1)[1].split()[19])
        with open("/proc/uptime", "rb") as f:
            up = float(f.read().split()[0])
        return max(0.0, up - started / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return 0.0


class Builds:
    """What the process spent building programs, whichever engine and
    thread asked: seconds and counts under `BUILD_NAMES`, the same by
    function (an outermost function under the name its compile
    carries, `jit(_step_sample)`; a nested one of `KERNEL_JITS` under
    its own), and the nested traces that were only counted."""

    def __init__(self) -> None:
        self.seconds: Dict[str, float] = dict.fromkeys(BUILD_NAMES, 0.0)
        self.counts: Dict[str, int] = dict.fromkeys(BUILD_NAMES, 0)
        #: function -> [programs built, seconds tracing, lowering,
        #: compiling or loading]; a nested jit's traces and their
        #: seconds in the first two
        self.by_function: Dict[str, List[float]] = {}
        self.nested_traces = 0
        # Two threads may build at once (a compile releases the GIL);
        # held on the outermost stages only, never on a nested trace.
        self.lock = threading.Lock()

    def add(self, name: str, fun: str, secs: float,
            nested: int = 0) -> None:
        """An outermost stage `name` of `fun` ended after `secs`, with
        `nested` traces inside it."""
        with self.lock:
            self.seconds[name] += secs
            self.counts[name] += 1
            row = self.by_function.setdefault(fun, [0, 0.0, 0.0, 0.0])
            row[_COLUMN[name]] += secs
            if name == "program.compile":
                row[0] += 1
            self.nested_traces += nested

    def count(self, name: str, secs: float = 0.0) -> None:
        """One more of `name`, which is no stage of a build."""
        with self.lock:
            self.seconds[name] += secs
            self.counts[name] += 1

    def add_nested(self, fun: str, secs: float) -> None:
        """A trace of `fun`, one of `KERNEL_JITS`, inside another."""
        with self.lock:
            row = self.by_function.setdefault(fun, [0, 0.0, 0.0, 0.0])
            row[0] += 1
            row[1] += secs

    def summary(self, most: int = 12) -> str:
        """One line: the totals, then each function as `<fun> <builds>
        x trace/lower/compile seconds`, the `most` costliest outermost
        functions and every nested one, the rest summed."""
        with self.lock:
            s, c = dict(self.seconds), dict(self.counts)
            funs = sorted(self.by_function.items(),
                          key=lambda kv: -sum(kv[1][1:]))
        nested = [(f, v) for f, v in funs if f in KERNEL_JITS]
        outer = [(f, v) for f, v in funs if f not in KERNEL_JITS]
        rest = [sum(col) for col in zip(*(v for _, v in outer[most:]))]
        parts = [f"{f} {int(v[0])} x {v[1]:.3f}/{v[2]:.3f}/{v[3]:.3f}"
                 for f, v in outer[:most]]
        parts += [f"{f} (nested) {int(v[0])} x {v[1]:.3f}"
                  for f, v in nested]
        if rest:
            parts.append(f"{len(outer) - most} other functions "
                         f"{int(rest[0])} x {rest[1]:.3f}/{rest[2]:.3f}/"
                         f"{rest[3]:.3f}")
        return (
            f"programs: {c['program.compile']} built, "
            f"{c['program.trace']} traced (trace "
            f"{s['program.trace']:.3f} s, lower {s['program.lower']:.3f} "
            f"s, compile or load {s['program.compile']:.3f} s, of it "
            f"{s['program.cache_load']:.3f} s reading the cache: "
            f"{c['program.cache_hit']} hits, {c['program.cache_miss']} "
            f"misses; the program store {c['program.store_hit']} hits "
            f"in {s['program.store_load']:.3f} s, "
            f"{c['program.store_miss']} misses), {self.nested_traces} "
            "nested traces; by function, builds x trace/lower/compile s: "
            + "; ".join(parts))


#: the one account of the process's builds
BUILDS = Builds()

_TRACE = "/jax/core/compile/jaxpr_trace_duration"
#: the stages JAX announces (a scalar at the start, a duration at the
#: end, both with `fun_name`) -> the name each is filed under
_STAGES = {
    _TRACE: "program.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "program.lower",
    "/jax/core/compile/backend_compile_duration": "program.compile",
}
_CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "program.cache_hit",
    "/jax/compilation_cache/cache_misses": "program.cache_miss",
}
_COLUMN = {"program.trace": 1, "program.lower": 2, "program.compile": 3}


class _Building:
    """One thread's side of the builds: how many stages it is inside
    (a trace inside any is nested), the build it is in the middle of,
    and the tracer whose round it works for."""

    __slots__ = ("depth", "nested", "generation", "tracer", "trace_s",
                 "lower_s", "cache", "last_cache", "open")

    def __init__(self) -> None:
        self.depth = 0
        self.nested = 0
        self.generation = _generation
        self.tracer: Optional["Tracer"] = None
        self.trace_s = self.lower_s = 0.0
        #: what the persistent cache said of the build in the middle,
        #: and of the last one that ended
        self.cache = self.last_cache = "off"
        #: (stage, annotation) of the stages open under the profiler
        self.open: list = []


_thread = threading.local()
#: bumped when the listeners had to be put back: a depth counted while
#: the closing listener was missing is void
_generation = 0


def _building() -> _Building:
    try:
        return _thread.building
    except AttributeError:
        _thread.building = _Building()
        return _thread.building


def _stage_started(event: str, _value=None, fun_name: str = "",
                   **_) -> None:
    """`jax.monitoring`'s scalar listener: a stage of a build begins
    on this thread. A trace inside another stage is a depth bump and a
    return: inside a trace (every `jnp` function is a jit of its own,
    tens of thousands a step program) or inside a lowering, whose
    rules trace too; its seconds are its caller's."""
    name = _STAGES.get(event)
    if name is None:
        return
    b = _building()
    if b.generation != _generation:
        b.depth, b.generation = 0, _generation
    b.depth += 1
    if event == _TRACE:
        if b.depth > 1:
            b.nested += 1
            return
    tracer = b.tracer
    if tracer is not None and tracer.annotating:
        annotation = TraceAnnotation("aph." + name, fun=fun_name,
                                     **tracer.facts)
        annotation.__enter__()
        b.open.append((name, annotation))


def _stage_ended(event: str, secs: float, fun_name: str = "",
                 **_) -> None:
    """The duration listener: the stage that `_stage_started` saw ends
    (JAX reports it from a context manager's `__exit__`, so an
    exception inside the stage ends it all the same)."""
    name = _STAGES.get(event)
    if name is None:
        if event == _CACHE_LOAD:
            with BUILDS.lock:
                BUILDS.seconds["program.cache_load"] += secs
        return
    b = _building()
    depth = b.depth
    b.depth = max(0, depth - 1)
    if event == _TRACE:
        if depth > 1:
            if fun_name in KERNEL_JITS:
                BUILDS.add_nested(fun_name, secs)
            return
        b.trace_s = secs
        # the compile's name, so that a function's stages share a row
        fun_name = f"jit({fun_name})"
    if b.open and b.open[-1][0] == name:
        b.open.pop()[1].__exit__(None, None, None)
    nested, b.nested = b.nested, 0
    BUILDS.add(name, fun_name, secs, nested)
    if name == "program.lower":
        b.lower_s = secs
    elif name == "program.compile":
        _log_built(fun_name, b.tracer.facts if b.tracer is not None
                   else {}, b.trace_s, b.lower_s, secs, b.cache)
        b.trace_s = b.lower_s = 0.0
        b.last_cache, b.cache = b.cache, "off"


def _log_built(fun: str, facts: dict, trace_s: float, lower_s: float,
               compile_s: float, cache: str) -> None:
    logger.info(
        "program built: fun=%s round=%s path=%s rows=%s "
        "prompt_tokens=%s trace=%.3f lower=%.3f compile=%.3f "
        "cache=%s", fun, facts.get("round", "-"),
        facts.get("path", "-"), facts.get("rows", "-"),
        facts.get("prompt_tokens", "-"), trace_s, lower_s, compile_s,
        cache)


def last_build_cache() -> str:
    """What the persistent cache said of the last program this thread
    built: "hit", "miss", or "off" where none is configured."""
    return _building().last_cache


def store_loaded(fun: str, secs: float) -> None:
    """This thread took `fun` from the program store in `secs`: no
    trace, no lowering. Filed under the store's own names and as what
    it stands in the place of, a program made ready by a load from
    disk (`program.compile`, `program.cache_load`, `program.cache_hit`),
    and logged as a build is: `JAX_LOG_COMPILES` says nothing of it."""
    BUILDS.add("program.compile", fun, secs)
    with BUILDS.lock:
        for name in ("program.cache_load", "program.store_load"):
            BUILDS.seconds[name] += secs
        for name in ("program.cache_hit", "program.store_hit"):
            BUILDS.counts[name] += 1
    tracer = _building().tracer
    _log_built(fun, tracer.facts if tracer is not None else {}, 0.0, 0.0,
               secs, "store")


def _cache_answered(event: str, **_) -> None:
    """The event listener: the persistent cache had, or had not, the
    executable this thread's compile stage asked for."""
    name = _CACHE_EVENTS.get(event)
    if name is not None:
        with BUILDS.lock:
            BUILDS.counts[name] += 1
        _building().cache = name.rsplit("_", 1)[1]


_LISTENERS = (
    (monitoring.register_scalar_listener,
     monitoring.unregister_scalar_listener, _stage_started),
    (monitoring.register_event_duration_secs_listener,
     monitoring.unregister_event_duration_listener, _stage_ended),
    (monitoring.register_event_listener,
     monitoring.unregister_event_listener, _cache_answered),
)


def install_listeners() -> None:
    """Register the three listeners, each once however often this is
    called (every `Tracer` calls it). One that somebody's
    `jax.monitoring.clear_event_listeners()` took away is put back,
    and the depths counted without it are void."""
    global _generation
    for register, unregister, listener in _LISTENERS:
        try:
            unregister(listener)
        except (AssertionError, ValueError):    # it was not there
            _generation += 1
        register(listener)


class Tracer:
    """The accumulators of one engine, and whether its spans annotate
    the profiler's trace."""

    def __init__(self) -> None:
        install_listeners()
        #: cumulative seconds and occurrences of each name
        self.seconds: Dict[str, float] = dict.fromkeys(NAMES, 0.0)
        self.counts: Dict[str, int] = dict.fromkeys(NAMES, 0)
        self.annotating = False
        #: what every annotation of the current round carries: `round`,
        #: and once the round is scheduled `path`, `rows`,
        #: `prompt_tokens`; of a round dispatched ahead with a round in
        #: flight, also `pulls`: that one is `combined` or `decode`
        self.facts: Dict[str, object] = {}
        #: step programs dispatched and not pulled yet, and the clock
        #: at the last change of that number
        self.in_flight = 0
        self._flight_mark = 0.0
        #: process start to ready (`ready`); 0.0 until then
        self.startup_seconds = 0.0
        # what the thread that makes the tracer builds before the
        # first round is this tracer's
        _building().tracer = self

    @classmethod
    def at_entry(cls) -> "Tracer":
        """The tracer an entry point makes at its first line, before
        the engine exists: everything the process did until now, the
        interpreter and the imports, is its first phase."""
        tracer = cls()
        tracer.add("setup.import", process_age())
        return tracer

    def annotate(self, on: bool) -> None:
        """Switch the TraceAnnotation half of the spans on or off. A
        span that is open at the switch ends the way it began."""
        self.annotating = on

    def set_round(self, **facts) -> None:
        """The facts of the round the step thread is in; a program
        this thread builds from now on is this tracer's round's."""
        self.facts = facts
        _building().tracer = self

    def phase(self, name: str) -> "Span":
        """The span of a set-up phase, one of `SETUP_PHASES`: its name
        is the `path` of the programs built inside it."""
        self.facts = {"path": name}
        return self.span(name)

    def ready(self) -> None:
        """The process is ready for work: fixes `startup_seconds` and
        logs the phases beside it."""
        self.startup_seconds = process_age()
        total = sum(self.seconds[n] for n in SETUP_PHASES)
        logger.info(
            "startup: %s total=%.3f (process start to ready %.3f)",
            " ".join(f"{n[len('setup.'):]}={self.seconds[n]:.3f}"
                     for n in SETUP_PHASES), total, self.startup_seconds)

    def fold_builds(self) -> None:
        """Bring the `program.*` accumulators up to the process's
        (`BUILDS`), from which they are exported like any stage's."""
        self.seconds.update(BUILDS.seconds)
        self.counts.update(BUILDS.counts)

    def add(self, name: str, secs: float = 0.0, count: int = 1) -> None:
        """Count `count` occurrences of `name` that lasted `secs`."""
        self.seconds[name] += secs
        self.counts[name] += count

    def add_split(self, name: str, secs: float = 0.0,
                  count: int = 1) -> None:
        """`add` to `name` and to the twin of it that the round's
        facts call for, where `NAMES` has that twin: `<name>.prompt`
        when the round carries a prompt step (`path` is `combined`),
        `<name>.decode` when the round and the round in flight are
        both decode-only: an ordinary round under an ordinary step."""
        self.add(name, secs, count)
        path = self.facts.get("path")
        if path == "combined":
            twin = name + ".prompt"
        elif path == "decode" and self.facts.get("pulls") == "decode":
            twin = name + ".decode"
        else:
            return
        if twin in self.counts:
            self.add(twin, secs, count)

    def flight(self, steps: int) -> None:
        """`steps` step programs were dispatched (positive) or their
        results pulled (negative). `seconds["runner.in_flight"]` grows
        by the time since the last call if a step was in flight over
        it: the union of the steps' intervals from dispatch entered to
        result on the host, not their sum, and current at every call."""
        now = _clock()
        if self.in_flight > 0:
            self.seconds["runner.in_flight"] += now - self._flight_mark
        self._flight_mark = now
        self.in_flight = max(0, self.in_flight + steps)

    def grounded(self) -> None:
        """Nothing is in flight any more: the steps that were are
        abandoned (a failed round, a rebuild)."""
        self.flight(-self.in_flight)

    def span(self, name: str, **facts) -> "Span":
        return Span(self, name, facts)


class Span:
    """`with tracer.span("runner.prepare"): ...`; entered and left on
    one thread. Also usable by hand (`__enter__` returns the span)
    where the stage does not fit a block. Once left it keeps its
    duration (`seconds`), for a caller that files it under a second
    name without reading the clock again."""

    __slots__ = ("tracer", "name", "facts", "t0", "annotation",
                 "seconds")

    def __init__(self, tracer: Tracer, name: str, facts: dict) -> None:
        self.tracer = tracer
        self.name = name
        self.facts = facts

    def __enter__(self) -> "Span":
        self.annotation = None
        if self.tracer.annotating:
            self.annotation = TraceAnnotation(
                "aph." + self.name, **self.tracer.facts, **self.facts)
            self.annotation.__enter__()
        self.t0 = _clock()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = _clock() - self.t0
        self.tracer.add(self.name, self.seconds)
        if self.annotation is not None:
            self.annotation.__exit__(*exc)


def spanned(name: str) -> Callable:
    """Decorator for a method of an object that has a `tracer`: the
    whole call is one span."""
    def wrap(method: Callable) -> Callable:
        @functools.wraps(method)
        def inner(self, *args, **kwargs):
            with self.tracer.span(name):
                return method(self, *args, **kwargs)
        return inner
    return wrap
