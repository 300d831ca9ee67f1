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
"""
from __future__ import annotations

import functools
import time
from typing import Callable, Dict

from jax.profiler import TraceAnnotation

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
#: (`Tracer.add_split`: a name's `.prompt` and `.decode` twins).
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
)

_clock = time.perf_counter


class Tracer:
    """The accumulators of one engine, and whether its spans annotate
    the profiler's trace."""

    def __init__(self) -> None:
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

    def annotate(self, on: bool) -> None:
        """Switch the TraceAnnotation half of the spans on or off. A
        span that is open at the switch ends the way it began."""
        self.annotating = on

    def set_round(self, **facts) -> None:
        """The facts of the round the step thread is in."""
        self.facts = facts

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
