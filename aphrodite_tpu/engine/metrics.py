"""Prometheus metrics + periodic stdout throughput log.

Reference: `aphrodite/engine/metrics.py` (Metrics `:18`, Stats `:90`,
StatLogger `:110`); same metric names under the `aphrodite:` namespace so
existing Grafana dashboards (reference `examples/monitoring/`) work
unchanged.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Dict, List, Optional

from prometheus_client import Counter, Gauge, Histogram, REGISTRY

from aphrodite_tpu.common.logger import init_logger

logger = init_logger(__name__)

_LOCAL_LOGGING_INTERVAL_SEC = 5.0

_LATENCY_BUCKETS = [
    0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.075, 0.1, 0.15, 0.2, 0.3,
    0.4, 0.5, 0.75, 1.0, 2.5
]


def _get_or_create(cls, name, documentation, labelnames=(), **kw):
    """Idempotent metric creation (tests build multiple engines)."""
    try:
        return cls(name, documentation, labelnames=labelnames, **kw)
    except ValueError:
        return REGISTRY._names_to_collectors[name]


#: (metric, help, cumulative total from the span accumulators
#: `seconds` and `counts` of common/tracing.py). One metric name per
#: quantity and no label that carries meaning: readers sum over label
#: sets.
_STAGE_COUNTERS = [
    ("aphrodite:engine_rounds_total",
     "Engine rounds (AphroditeEngine.step calls).",
     lambda s, c: c["engine.step"]),
    ("aphrodite:engine_step_seconds_total",
     "Seconds inside AphroditeEngine.step.",
     lambda s, c: s["engine.step"]),
    ("aphrodite:host_syncs_total",
     "Blocking pulls of step results from the device.",
     lambda s, c: c["runner.device_wait"]),
    ("aphrodite:host_schedule_seconds_total",
     "Seconds scheduling rounds (deadline expiry, scheduler, block "
     "manager).", lambda s, c: s["sched.schedule"]),
    ("aphrodite:host_prepare_seconds_total",
     "Seconds building a step's host batch, sampling plan included, "
     "up to the dispatch.", lambda s, c: s["runner.prepare"]),
    ("aphrodite:device_wait_seconds_total",
     "Seconds in which a dispatched step had not been pulled yet: "
     "from a step's dispatch entered to its result on the host, "
     "overlapping steps counted once.",
     lambda s, c: s["runner.in_flight"]),
    ("aphrodite:host_process_seconds_total",
     "Seconds unpacking sampled results and processing outputs "
     "(detokenise, stop checks, stats).",
     lambda s, c: s["sampler.finalize"] + s["engine.process"]),
    ("aphrodite:host_between_steps_seconds_total",
     "Seconds of the async loop between one engine step returning "
     "and the next entering, idle waits left out.",
     lambda s, c: s["async.between_steps"]),
    ("aphrodite:queue_wait_seconds_total",
     "Seconds requests waited from arrival to the round that first "
     "scheduled them.", lambda s, c: s["queue_wait"]),
    ("aphrodite:requests_first_scheduled_total",
     "Requests scheduled for the first time.",
     lambda s, c: c["queue_wait"]),
    ("aphrodite:preemptions_total",
     "Preemptions of running requests (recompute and swap).",
     lambda s, c: c["preemptions"]),
    ("aphrodite:sampler_plan_seconds_total",
     "Seconds building the steps' sampling plans (inside the prepare "
     "seconds).", lambda s, c: s["sampler.plan"]),
    ("aphrodite:sampler_plans_total",
     "Sampling plans built, one a step program enqueued.",
     lambda s, c: c["sampler.plan"]),
    ("aphrodite:sampler_plan_reuses_total",
     "Sampling plans that built and sent nothing: the batch and its "
     "parameters were the step before's.",
     lambda s, c: c["sampler.plan_reuse"]),
    ("aphrodite:steps_ahead_total",
     "Step programs dispatched while the round before had not been "
     "pulled (of aphrodite:sampler_plans_total dispatched); whether "
     "the device had drained by then is "
     "aphrodite:dispatches_starved_total.",
     lambda s, c: c["runner.ahead"]),
    ("aphrodite:rounds_ahead_total",
     "Rounds dispatched while the round before had not been pulled.",
     lambda s, c: c["round.ahead"]),
    ("aphrodite:rounds_ahead_prompt_total",
     "Of aphrodite:rounds_ahead_total, the rounds that carry a prompt "
     "step.", lambda s, c: c["round.ahead.prompt"]),
    ("aphrodite:dispatches_starved_total",
     "Of aphrodite:rounds_ahead_total, the rounds whose round in "
     "flight had finished when they were dispatched: the device had "
     "nothing queued and waited for the host.",
     lambda s, c: c["runner.starved"]),
    ("aphrodite:dispatches_starved_prompt_total",
     "Of aphrodite:dispatches_starved_total, the rounds that carry a "
     "prompt step (of aphrodite:rounds_ahead_prompt_total).",
     lambda s, c: c["runner.starved.prompt"]),
    ("aphrodite:dispatches_prompt_late_total",
     "Of aphrodite:rounds_ahead_prompt_total, the rounds whose decode "
     "program had already finished when their prompt program was "
     "enqueued behind it: the host prepared the prompt step for "
     "longer than the device ran.",
     lambda s, c: c["runner.prompt_late"]),
    ("aphrodite:pull_blocked_seconds_total",
     "Seconds the step thread blocked pulling the round in flight "
     "after it had dispatched the next: the host's lead over the "
     "device (the result's transfer included).",
     lambda s, c: s["pull.blocked"]),
    ("aphrodite:pulls_ahead_total",
     "Pulls that aphrodite:pull_blocked_seconds_total was summed "
     "over.", lambda s, c: c["pull.blocked"]),
    ("aphrodite:pull_blocked_decode_seconds_total",
     "Of aphrodite:pull_blocked_seconds_total, the pulls of a "
     "decode-only round behind a decode-only dispatch: the slack of "
     "an ordinary round.", lambda s, c: s["pull.blocked.decode"]),
    ("aphrodite:pulls_ahead_decode_total",
     "Pulls that aphrodite:pull_blocked_decode_seconds_total was "
     "summed over.", lambda s, c: c["pull.blocked.decode"]),
    ("aphrodite:host_dispatch_seconds_total",
     "Seconds in the jitted calls that enqueue step programs, up to "
     "their return (a program built inside one lies here too; the "
     "aphrodite:program_*_seconds_total counters say how long).",
     lambda s, c: s["runner.dispatch"]),
    ("aphrodite:step_call_seconds_total",
     "Seconds of the async loop's calls of AphroditeEngine.step: the "
     "hop to the step thread, the step and the hop back (less "
     "aphrodite:engine_step_seconds_total: the two hops).",
     lambda s, c: s["async.step_call"]),
    ("aphrodite:decode_attn_pages_fetched_total",
     "KV pages the decode-attention kernel copied from the pool, "
     "summed over decode steps (one layer's; host arithmetic).",
     lambda s, c: c["attn.pages_fetched"]),
    ("aphrodite:decode_attn_pages_live_total",
     "KV pages below the rows' context lengths, summed over decode "
     "steps: what aphrodite:decode_attn_pages_fetched_total cannot "
     "go under.", lambda s, c: c["attn.pages_live"]),
    ("aphrodite:decode_attn_steps_total",
     "Decode steps that aphrodite:decode_attn_pages_live_total and the "
     "page-group counters below were summed over.",
     lambda s, c: c["attn.decode_steps"]),
    ("aphrodite:prefill_attn_tiles_visited_total",
     "Tiles of key_block queries x key_block keys that the blocked "
     "prompt attention visited, summed over the attention layers of "
     "the prompt steps that took it (host arithmetic, by "
     "ops/attention.py::count_prefill_tiles).",
     lambda s, c: c["attn.prefill_tiles_visited"]),
    ("aphrodite:prefill_attn_tiles_padded_total",
     "Tiles of the same steps' padded rectangles, queries x keys as "
     "the step program pads them: what "
     "aphrodite:prefill_attn_tiles_visited_total would read if every "
     "key block were scored for every query.",
     lambda s, c: c["attn.prefill_tiles_padded"]),
    ("aphrodite:prefill_attn_steps_total",
     "Prompt steps built (a chunk of one or more rows each).",
     lambda s, c: c["attn.prefill_steps"]),
    ("aphrodite:prefill_attn_kernel_steps_total",
     "Of aphrodite:prefill_attn_steps_total, the steps whose attention "
     "is the Pallas flash kernel (ops/pallas/prefill_attention.py): one "
     "TPU, K and V in bfloat16 or float32, no ALiBi; the rest take the "
     "jnp functions.",
     lambda s, c: c["attn.prefill_kernel_steps"]),
    ("aphrodite:kv_pages_live_full_total",
     "Of aphrodite:decode_attn_pages_live_total, the pages of the full "
     "page groups (a page holds a group's layers).",
     lambda s, c: c["attn.pages_live.full"]),
    ("aphrodite:kv_pages_live_window_total",
     "Of aphrodite:decode_attn_pages_live_total, the pages of the "
     "window page groups, and every page of a pooled group's table "
     "(its window's pages behind its summary pages).",
     lambda s, c: c["attn.pages_live.window"]),
    ("aphrodite:kv_pages_live_summary_total",
     "Of aphrodite:kv_pages_live_window_total, the summary pages of "
     "the pooled page groups (a pooled key a page of tokens of the "
     "windows behind), summed over decode steps.",
     lambda s, c: c["attn.summary_pages_live"]),
    ("aphrodite:window_pages_unwindowed_total",
     "Pages the window and pooled groups' rows would hold live with "
     "every key kept, summed over decode steps "
     "(aphrodite:kv_pages_live_window_total is what they hold).",
     lambda s, c: c["attn.window_pages_unwindowed"]),
    ("aphrodite:eva_windows_closed_prompt_total",
     "Windows that pooled page groups closed as a prompt chunk passed "
     "their edge: summary pages taken, the window's pages let go.",
     lambda s, c: c["attn.windows_closed_prompt"]),
    ("aphrodite:eva_windows_closed_decode_total",
     "Windows that pooled page groups closed as a decode row passed "
     "their edge.", lambda s, c: c["attn.windows_closed_decode"]),
    ("aphrodite:window_pages_freed_total",
     "KV pages that window page groups let go of, to the free list.",
     lambda s, c: c["cache.window_pages_freed"]),
    ("aphrodite:kv_page_reads_shared_total",
     "Live KV pages of each page group times the layers whose "
     "attention reads them (a group's own and those that read "
     "theirs), summed over decode steps.",
     lambda s, c: c["attn.page_reads_shared"]),
    ("aphrodite:ssm_state_resets_total",
     "Prompt rows that started at position 0, where the step's "
     "program starts the row's state slot from zeros.",
     lambda s, c: c["ssm.state_resets"]),
    ("aphrodite:ssm_decode_rows_total",
     "Decode rows of a model with state slots: one-token state "
     "updates a state layer, summed over decode steps.",
     lambda s, c: c["ssm.decode_rows"]),
    ("aphrodite:ssm_prefill_tokens_total",
     "Prompt tokens the chunk scans of a model with state slots went "
     "over (a state layer's), summed over prompt steps.",
     lambda s, c: c["ssm.prefill_tokens"]),
    ("aphrodite:ssm_slot_waits_total",
     "Admission verdicts that put a prompt off for want of a state "
     "slot while its pages were free (one each round the prompt at "
     "the head of the queue waits so).",
     lambda s, c: c["ssm.slot_waits"]),
    ("aphrodite:kda_decode_rows_total",
     "Decode rows of a model with delta-rule (KDA) layers: one-token "
     "updates of a row's matrix state a KDA layer, summed over decode "
     "steps.",
     lambda s, c: c["kda.decode_rows"]),
    ("aphrodite:kda_prompt_tokens_total",
     "Live prompt tokens the chunk kernel of a model with KDA layers "
     "went over (a KDA layer's), padding apart, summed over prompt "
     "steps.",
     lambda s, c: c["kda.prompt_tokens"]),
    ("aphrodite:kda_prompt_chunks_total",
     "Chunks of the KDA chunk kernel's size (64 tokens) that held at "
     "least one live prompt token (a KDA layer's), summed over prompt "
     "steps.",
     lambda s, c: c["kda.prompt_chunks"]),
    ("aphrodite:mla_latent_tokens_read_total",
     "Context lengths of the decode rows of a model whose pages are "
     "latent (multi-head latent attention), summed over decode steps: "
     "the latent rows every layer's absorbed decode attention reads "
     "(host arithmetic, from what the step is given).",
     lambda s, c: c["mla.latent_tokens_read"]),
    ("aphrodite:mla_prefix_tokens_expanded_total",
     "Prefix tokens that prompt steps of such a model read back from "
     "the latent pages and up-projected to keys and values (a "
     "layer's), counted in the step programs; over "
     "aphrodite:prompt_tokens_total it says what chunking a prompt "
     "costs the up-projection.",
     lambda s, c: c["mla.prefix_tokens_expanded"]),
    ("aphrodite:moe_tokens_routed_total",
     "Token-expert pairs the expert layers computed, counted in the "
     "step programs.", lambda s, c: c["moe.tokens_routed"]),
    ("aphrodite:moe_experts_touched_total",
     "Held experts with at least one pair, summed over expert layers "
     "and steps, counted in the step programs.",
     lambda s, c: c["moe.experts_touched"]),
    ("aphrodite:moe_pairs_held_total",
     "Of aphrodite:moe_tokens_routed_total, the pairs whose expert "
     "the layer holds and computes, counted by a model that may hold "
     "a share of its experts (the rest is left out, not stood in for).",
     lambda s, c: c["moe.pairs_held"]),
    ("aphrodite:moe_rows_walked_total",
     "Rows of the row tiles the expert layers' Pallas kernels visited "
     "(ops/pallas/grouped_matmul.py: every tile belongs to one expert), "
     "counted in the step programs; over aphrodite:moe_pairs_held_total "
     "it says how much of the matmuls' work is a tile's padding.",
     lambda s, c: c["moe.rows_walked"]),
    ("aphrodite:moe_kernel_steps_total",
     "Steps whose expert layers took the Pallas kernels: one TPU, "
     "weights in bfloat16 or float32; the rest take jax.lax.ragged_dot.",
     lambda s, c: c["moe.kernel_steps"]),
    ("aphrodite:moe_decode_experts_touched_total",
     "Of aphrodite:moe_experts_touched_total, the decode steps'.",
     lambda s, c: c["moe.decode_experts_touched"]),
    ("aphrodite:moe_decode_expert_slots_total",
     "Experts times expert layers, summed over decode steps: what "
     "aphrodite:moe_decode_experts_touched_total cannot pass.",
     lambda s, c: c["moe.decode_expert_slots"]),
    ("aphrodite:program_trace_seconds_total",
     "Seconds the process spent tracing jitted functions, outermost "
     "traces alone (a nested jit's trace lies inside its caller's), "
     "whichever thread built; from jax.monitoring.",
     lambda s, c: s["program.trace"]),
    ("aphrodite:program_lower_seconds_total",
     "Seconds the process spent lowering traced functions to MLIR "
     "modules.", lambda s, c: s["program.lower"]),
    ("aphrodite:program_compile_seconds_total",
     "Seconds in the backend's compile stage: compiling, or on a hit in "
     "the persistent cache loading the executable.",
     lambda s, c: s["program.compile"]),
    ("aphrodite:program_cache_load_seconds_total",
     "Of aphrodite:program_compile_seconds_total, the seconds reading "
     "executables from the persistent compilation cache.",
     lambda s, c: s["program.cache_load"]),
    ("aphrodite:programs_built_total",
     "Programs the process built (compile stages ended), every jitted "
     "function and every one-operation program of an eager call; one "
     "that grows under load is a recompile, logged as `program "
     "built:` with the round that met it.",
     lambda s, c: c["program.compile"]),
    ("aphrodite:program_cache_hits_total",
     "Compile requests the persistent compilation cache answered.",
     lambda s, c: c["program.cache_hit"]),
    ("aphrodite:program_cache_misses_total",
     "Compile requests the persistent compilation cache could not "
     "answer: the backend compiled.",
     lambda s, c: c["program.cache_miss"]),
    ("aphrodite:program_store_hits_total",
     "Step programs the program store had (executor/program_store.py): "
     "loaded, never traced or lowered. Each is also one of "
     "aphrodite:programs_built_total and of "
     "aphrodite:program_cache_hits_total.",
     lambda s, c: c["program.store_hit"]),
    ("aphrodite:program_store_misses_total",
     "Step programs the program store had not: built as before, and "
     "kept for the next process.",
     lambda s, c: c["program.store_miss"]),
    ("aphrodite:program_store_load_seconds_total",
     "Seconds reading and loading the store's hits (also in "
     "aphrodite:program_compile_seconds_total and "
     "aphrodite:program_cache_load_seconds_total).",
     lambda s, c: s["program.store_load"]),
    ("aphrodite:setup_import_seconds_total",
     "Set-up phase, seconds: from the start of the process to the "
     "entry point's first line: the interpreter and the imports.",
     lambda s, c: s["setup.import"]),
    ("aphrodite:setup_backend_seconds_total",
     "Set-up phase, seconds: the first jax.devices(): the "
     "accelerator's runtime coming up.",
     lambda s, c: s["setup.backend"]),
    ("aphrodite:setup_tokenizer_seconds_total",
     "Set-up phase, seconds: loading the tokenizer.",
     lambda s, c: s["setup.tokenizer"]),
    ("aphrodite:setup_weights_seconds_total",
     "Set-up phase, seconds: loading or making the weights (and the "
     "prefill group's copy of them).",
     lambda s, c: s["setup.weights"]),
    ("aphrodite:setup_kv_pool_seconds_total",
     "Set-up phase, seconds: sizing the KV pool and the state slots "
     "and allocating their arrays.",
     lambda s, c: s["setup.kv_pool"]),
    ("aphrodite:setup_runner_seconds_total",
     "Set-up phase, seconds: building the model runner (and the LoRA "
     "manager).",
     lambda s, c: s["setup.runner"]),
    ("aphrodite:setup_frontend_seconds_total",
     "Set-up phase, seconds: from the engine built to the server's "
     "start-up hooks done, its sockets about to open.",
     lambda s, c: s["setup.frontend"]),
]


class Metrics:

    def __init__(self, labelnames: List[str]):
        self.gauge_scheduler_running = _get_or_create(
            Gauge, "aphrodite:num_requests_running",
            "Number of requests currently running on TPU.", labelnames)
        self.gauge_scheduler_swapped = _get_or_create(
            Gauge, "aphrodite:num_requests_swapped",
            "Number of requests swapped to CPU.", labelnames)
        self.gauge_scheduler_waiting = _get_or_create(
            Gauge, "aphrodite:num_requests_waiting",
            "Number of requests waiting to be processed.", labelnames)
        self.gauge_gpu_cache_usage = _get_or_create(
            Gauge, "aphrodite:gpu_cache_usage_perc",
            "Device KV-cache usage. 1 means 100 percent usage.",
            labelnames)
        self.gauge_cpu_cache_usage = _get_or_create(
            Gauge, "aphrodite:cpu_cache_usage_perc",
            "CPU KV-cache usage. 1 means 100 percent usage.", labelnames)
        self.counter_prompt_tokens = _get_or_create(
            Counter, "aphrodite:prompt_tokens_total",
            "Number of prefill tokens processed.", labelnames)
        self.counter_generation_tokens = _get_or_create(
            Counter, "aphrodite:generation_tokens_total",
            "Number of generation tokens processed.", labelnames)
        self.histogram_time_to_first_token = _get_or_create(
            Histogram, "aphrodite:time_to_first_token_seconds",
            "Histogram of time to first token in seconds.", labelnames,
            buckets=_LATENCY_BUCKETS)
        self.histogram_time_per_output_token = _get_or_create(
            Histogram, "aphrodite:time_per_output_token_seconds",
            "Histogram of time per output token in seconds.", labelnames,
            buckets=_LATENCY_BUCKETS)
        self.histogram_e2e_request_latency = _get_or_create(
            Histogram, "aphrodite:e2e_request_latency_seconds",
            "Histogram of end to end request latency in seconds.",
            labelnames,
            buckets=[1.0, 2.5, 5.0, 10.0, 15.0, 20.0, 30.0, 40.0, 50.0,
                     60.0])
        # Overload-control gauges/counters (processing/admission.py):
        # the same numbers ride in the /health report so load
        # balancers can act on DEGRADED-while-shedding before DEAD.
        self.gauge_waiting_prefill_tokens = _get_or_create(
            Gauge, "aphrodite:queued_prefill_tokens",
            "Prefill tokens queued across the waiting queue.",
            labelnames)
        self.gauge_ewma_prefill = _get_or_create(
            Gauge, "aphrodite:ewma_prefill_tokens_per_s",
            "EWMA prefill throughput driving admission TTFT "
            "prediction.", labelnames)
        self.gauge_ewma_decode = _get_or_create(
            Gauge, "aphrodite:ewma_decode_tokens_per_s",
            "EWMA decode throughput.", labelnames)
        self.gauge_prefix_pinned = _get_or_create(
            Gauge, "aphrodite:prefix_pinned_pages",
            "KV pages pinned by the prefix cache (held on purpose; "
            "subtracted by the zero-leak accounting).", labelnames)
        self.gauge_ssm_slots_total = _get_or_create(
            Gauge, "aphrodite:ssm_slots_total",
            "State slots of a model that keeps recurrent state beside "
            "its KV pages (0: it keeps none).", labelnames)
        self.gauge_kv_bytes_per_token = _get_or_create(
            Gauge, "aphrodite:kv_cache_bytes_per_token",
            "Bytes a token takes of the KV pool, all layers: K/V pairs "
            "of every KV head, or the one latent row a layer of a model "
            "with latent pages.", labelnames)
        self.gauge_startup = _get_or_create(
            Gauge, "aphrodite:startup_seconds",
            "Seconds from the start of the process to the server ready "
            "for connections (an offline engine: to the end of its "
            "construction); 0 until then. The "
            "aphrodite:setup_*_seconds_total counters are its phases.",
            labelnames)
        self.gauge_ssm_slots_live = _get_or_create(
            Gauge, "aphrodite:ssm_slots_live",
            "State slots that sequences hold.", labelnames)
        self.counter_requests_shed = _get_or_create(
            Counter, "aphrodite:num_requests_shed",
            "Requests rejected at admission by overload control.",
            labelnames)
        self.counter_requests_expired = _get_or_create(
            Counter, "aphrodite:num_requests_expired",
            "Requests expired in the waiting queue past their TTFT "
            "deadline.", labelnames)
        # Lifecycle gauges/counters (engine/supervisor.py): drain and
        # reincarnation state, mirrored in the /health report so load
        # balancers and dashboards see the same numbers.
        self.gauge_engine_state = _get_or_create(
            Gauge, "aphrodite:engine_lifecycle_state",
            "Engine lifecycle state code (0=RUNNING 1=DEGRADED "
            "2=DRAINING 3=REBUILDING 4=DEAD).", labelnames)
        self.gauge_inflight = _get_or_create(
            Gauge, "aphrodite:num_requests_inflight",
            "Unfinished requests owned by the engine (waiting + "
            "prefilling + running + swapped).", labelnames)
        self.gauge_drain_remaining = _get_or_create(
            Gauge, "aphrodite:drain_deadline_remaining_seconds",
            "Seconds before a draining engine force-aborts in-flight "
            "work (-1 = no drain deadline ticking).", labelnames)
        self.counter_reincarnations = _get_or_create(
            Counter, "aphrodite:reincarnations_total",
            "Engine rebuilds (executor/KV teardown + restore) after "
            "FATAL step faults.", labelnames)
        self.counter_requests_restored = _get_or_create(
            Counter, "aphrodite:requests_restored_total",
            "Requests restored into the waiting queue across engine "
            "rebuilds.", labelnames)
        self.counter_requests_lost = _get_or_create(
            Counter, "aphrodite:requests_lost_on_rebuild_total",
            "Requests an engine rebuild could not restore (typed "
            "errors delivered to their streams).", labelnames)
        # Per-stage counters of the engine round, from the span
        # accumulators of common/tracing.py (see _STAGE_COUNTERS).
        self.stage_counters = [
            (_get_or_create(Counter, name, doc, labelnames), total)
            for name, doc, total in _STAGE_COUNTERS]


@dataclass
class Stats:
    """Snapshot of engine state for one logging tick."""
    now: float
    num_running: int
    num_waiting: int
    num_swapped: int
    gpu_cache_usage: float
    cpu_cache_usage: float
    num_prompt_tokens: int
    num_generation_tokens: int
    time_to_first_tokens: List[float]
    time_per_output_tokens: List[float]
    time_e2e_requests: List[float]
    # Overload-control snapshot (cumulative counters; the logger
    # tracks deltas for the Prometheus counters).
    num_waiting_tokens: int = 0
    prefix_pinned_pages: int = 0
    ssm_slots_total: int = 0
    ssm_slots_live: int = 0
    kv_bytes_per_token: int = 0
    startup_seconds: float = 0.0
    sheds_total: int = 0
    expired_total: int = 0
    ewma_prefill_tok_s: float = 0.0
    ewma_decode_tok_s: float = 0.0
    # Lifecycle snapshot (provided by the async wrapper's
    # lifecycle_source; cumulative counters get delta-exported).
    state_code: int = 0
    inflight: int = 0
    drain_remaining_s: float = -1.0
    reincarnations_total: int = 0
    restored_total: int = 0
    lost_total: int = 0
    # The span accumulators of the engine's common/tracing.py Tracer
    # (cumulative, live references; exported as deltas through
    # _STAGE_COUNTERS).
    stage_seconds: Optional[Dict[str, float]] = None
    stage_counts: Optional[Dict[str, int]] = None


class StatLogger:
    """Aggregates across steps; logs locally every 5 s; drives Prometheus."""

    def __init__(self, local_interval: float = _LOCAL_LOGGING_INTERVAL_SEC,
                 labels: Dict[str, str] = None) -> None:
        self.last_local_log = time.monotonic()
        self.local_interval = local_interval
        self.labels = labels or {}
        self.num_prompt_tokens: List[int] = []
        self.num_generation_tokens: List[int] = []
        # Cumulative totals already exported, by counter, for deltas.
        self._exported: Dict[object, float] = {}
        self._children: Dict[object, object] = {}
        self.metrics = Metrics(labelnames=list(self.labels.keys()))
        for counter, _ in self.metrics.stage_counters:
            # A labelled counter has no sample until it is first
            # touched: one that stays at 0 (preemptions) must read 0.
            (counter.labels(**self.labels) if self.labels
             else counter).inc(0)

    def _labeled(self, metric):
        """`metric` under this logger's labels: looked up once a
        metric, not at each of a round's hundred and more updates."""
        if not self.labels:
            return metric
        child = self._children.get(metric)
        if child is None:
            child = self._children[metric] = metric.labels(**self.labels)
        return child

    def _throughput(self, tracked: List[int], now: float) -> float:
        elapsed = now - self.last_local_log
        return sum(tracked) / elapsed if elapsed > 0 else 0.0

    def log(self, stats: Stats) -> None:
        m = self.metrics
        labeled = self._labeled

        def export(counter, total) -> None:
            """Raise `counter` to the cumulative `total` (a total that
            fell, after a rebuild, exports nothing until it passes the
            old one)."""
            done = self._exported.get(counter, 0)
            if total > done:
                labeled(counter).inc(total - done)
                self._exported[counter] = total
        labeled(m.gauge_scheduler_running).set(stats.num_running)
        labeled(m.gauge_scheduler_swapped).set(stats.num_swapped)
        labeled(m.gauge_scheduler_waiting).set(stats.num_waiting)
        labeled(m.gauge_gpu_cache_usage).set(stats.gpu_cache_usage)
        labeled(m.gauge_cpu_cache_usage).set(stats.cpu_cache_usage)
        labeled(m.counter_prompt_tokens).inc(stats.num_prompt_tokens)
        labeled(m.counter_generation_tokens).inc(
            stats.num_generation_tokens)
        labeled(m.gauge_waiting_prefill_tokens).set(
            stats.num_waiting_tokens)
        labeled(m.gauge_prefix_pinned).set(stats.prefix_pinned_pages)
        labeled(m.gauge_ssm_slots_total).set(stats.ssm_slots_total)
        labeled(m.gauge_ssm_slots_live).set(stats.ssm_slots_live)
        labeled(m.gauge_kv_bytes_per_token).set(stats.kv_bytes_per_token)
        labeled(m.gauge_startup).set(stats.startup_seconds)
        labeled(m.gauge_ewma_prefill).set(stats.ewma_prefill_tok_s)
        labeled(m.gauge_ewma_decode).set(stats.ewma_decode_tok_s)
        export(m.counter_requests_shed, stats.sheds_total)
        export(m.counter_requests_expired, stats.expired_total)
        labeled(m.gauge_engine_state).set(stats.state_code)
        labeled(m.gauge_inflight).set(stats.inflight)
        labeled(m.gauge_drain_remaining).set(stats.drain_remaining_s)
        export(m.counter_reincarnations, stats.reincarnations_total)
        export(m.counter_requests_restored, stats.restored_total)
        export(m.counter_requests_lost, stats.lost_total)
        if stats.stage_seconds is not None:
            for counter, total in m.stage_counters:
                export(counter, total(stats.stage_seconds,
                                      stats.stage_counts))
        for histogram, samples in (
                (m.histogram_time_to_first_token,
                 stats.time_to_first_tokens),
                (m.histogram_time_per_output_token,
                 stats.time_per_output_tokens),
                (m.histogram_e2e_request_latency,
                 stats.time_e2e_requests)):
            observe = labeled(histogram).observe
            for t in samples:
                observe(t)

        self.num_prompt_tokens.append(stats.num_prompt_tokens)
        self.num_generation_tokens.append(stats.num_generation_tokens)

        now = time.monotonic()
        if now - self.last_local_log >= self.local_interval:
            prompt_tps = self._throughput(self.num_prompt_tokens, now)
            gen_tps = self._throughput(self.num_generation_tokens, now)
            logger.info(
                "Avg prompt throughput: %.1f tokens/s, Avg generation "
                "throughput: %.1f tokens/s, Running: %d reqs, Swapped: "
                "%d reqs, Pending: %d reqs, device KV cache usage: %.1f%%, "
                "host KV cache usage: %.1f%%",
                prompt_tps, gen_tps, stats.num_running, stats.num_swapped,
                stats.num_waiting, stats.gpu_cache_usage * 100,
                stats.cpu_cache_usage * 100)
            self.num_prompt_tokens = []
            self.num_generation_tokens = []
            self.last_local_log = now
