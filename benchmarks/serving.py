"""Serving load generator: poisson arrivals against the async engine,
reporting TTFT / per-token / e2e latency percentiles and goodput.

Reference: `tests/benchmarks/serving.py` (HTTP + ShareGPT dataset).
This harness drives AsyncAphrodite in-process (the HTTP layer adds
fixed overhead identical across engines) with synthetic
token-id prompts, so it runs hermetically on any chip — BASELINE.md's
tracked serving metric is p50 TTFT.

Usage:
    python benchmarks/serving.py --model <path-or-id> [--request-rate 4]
        [--num-requests 128] [--prompt-len 128] [--output-len 64]
Prints one JSON line with the percentile table.

Overload mode (`--overload`): multiplies the offered rate
(`--overload-mult`, default 2x), attaches a per-request TTFT deadline
drawn from a distribution around `--deadline-s`, and fires a
disconnect storm (`--disconnect-rate` of requests hang up mid-stream
by dropping their generators — the GeneratorExit abort path, not a
polite abort). The JSON gains an `overload` section: goodput for
admitted requests, shed/expired/served/disconnected counts, rejection
latency (shed requests must observe sub-100 ms rejections), admitted-
request TTFT percentiles, and the post-storm free-page check
(`kv_leak_pages` must be 0 — KV returns to `free0`).

Chaos mode (`--chaos`): injects faults via APHRODITE_FAULT
(`--chaos-fault`, default a low-probability transient executor fault)
and fires an abort storm (`--chaos-abort-rate` of requests aborted at
a random point of their lifetime). The JSON gains a `chaos` section —
recovered-step / retry counters from the engine health monitor,
requests failed vs survived vs aborted, and the injected-fault tally —
alongside the usual TTFT/throughput percentiles, so fault-tolerance
overhead and degradation are measured with the same harness as the
baseline. A `--chaos` run with `--chaos-fault none --chaos-abort-rate
0` measures pure accounting overhead and must match baseline
throughput within noise.

Kill-chaos mode (`--chaos-kill`): the lifecycle proof. A FATAL fault
(`--kill-fault`) is armed AFTER warmup so it fires mid-measurement;
the engine must reincarnate (rebuild executor/KV pool, restore the
waiting queue) and finish the run. The JSON gains a `chaos_kill`
section asserting the zero-lost-requests invariant — every request
either completed or received a typed error (`requests_unaccounted`
must be 0), free pages return to `free0` on the REBUILT pool
(`kv_leak_pages` must be 0) — plus the recovery time
(`recovery_s` = executor+KV rebuild wall time). A drain storm
follows: with requests in flight the replica enters DRAINING, late
arrivals must be rejected with the typed 503-class error while every
in-flight request runs to completion, proving the SIGTERM
rolling-restart contract in-process.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def logger_warn(msg: str, *fmt_args) -> None:
    print("[serving] " + (msg % fmt_args if fmt_args else msg),
          file=sys.stderr, flush=True)


async def poisson_arrivals(n: int, rate: float, rng: np.random.RandomState):
    for i in range(n):
        yield i
        if rate == float("inf"):
            continue
        await asyncio.sleep(rng.exponential(1.0 / rate))


def build_mix(args, vocab: int, rng: np.random.RandomState):
    """Per-request workload shapes for the --mix presets.

    Returns (prompts, out_lens, mix_detail):

    - uniform:      every request is prompt_len/output_len (the
      historical 128/64 shape — zero per-arrival variance, so rate
      sweeps isolate scheduler behavior from workload noise).
    - sharegpt:     ragged conversational shape — lognormal prompt AND
      output lengths with the configured lengths as medians (p90/p50
      ~2x, the long-tail shape ShareGPT-trace benchmarks sample),
      independent token streams.
    - prefix-heavy: multi-turn sessions — every --session-turns'th
      request shares a ~3/4-prompt_len session prefix with a ragged
      fresh suffix. Repeated history is what the prefix cache pins and
      the n-gram drafter mines, so this is the traffic the spec-decode
      A/B criterion is defined on.

    Lengths are clamped so prompt+output+16 fits --max-model-len; the
    summary stats ride in the JSON so a capture is self-describing.
    """
    n = args.num_requests
    p_nom, o_nom = args.prompt_len, args.output_len
    cap = max(32, args.max_model_len - 16)
    mix = getattr(args, "mix", "uniform") or "uniform"
    extra = {}
    if mix == "uniform":
        prompts = [rng.randint(5, vocab - 5, size=p_nom).tolist()
                   for _ in range(n)]
        out_lens = [o_nom] * n
    elif mix == "sharegpt":
        # Lognormal with the nominal length as median, written as
        # median * exp(N(0, sigma)) (equivalent, and it avoids np.log
        # — the race pass's name-resolved call graph would alias a
        # `log` call here onto StatLogger.log and cross-pollute its
        # execution domains).
        plens = np.clip(np.round(
            p_nom * np.exp(rng.normal(0.0, 0.6, size=n))),
            4, cap - 8).astype(int)
        out_lens = np.clip(np.round(
            o_nom * np.exp(rng.normal(0.0, 0.6, size=n))), 1,
            cap - plens).astype(int).tolist()
        prompts = [rng.randint(5, vocab - 5, size=int(pl)).tolist()
                   for pl in plens]
    elif mix == "prefix-heavy":
        turns = max(1, int(getattr(args, "session_turns", 4) or 4))
        n_sessions = max(1, n // turns)
        prefix_len = min(max(8, (3 * p_nom) // 4), cap - o_nom - 8)
        prefixes = {
            s: rng.randint(5, vocab - 5, size=prefix_len).tolist()
            for s in range(n_sessions)
        }
        prompts = []
        for i in range(n):
            sfx_cap = max(2, min(p_nom - prefix_len,
                                 cap - o_nom - prefix_len))
            sfx = int(rng.randint(1, sfx_cap))
            prompts.append(prefixes[i % n_sessions] +
                           rng.randint(5, vocab - 5, size=sfx).tolist())
        out_lens = [o_nom] * n
        extra = {"sessions": n_sessions, "turns": turns,
                 "prefix_len": prefix_len}
    else:
        raise ValueError(f"unknown --mix preset: {mix!r}")
    plens_a = np.asarray([len(p) for p in prompts])
    olens_a = np.asarray(out_lens)
    mix_detail = {
        "preset": mix,
        "prompt_len_p50": int(np.percentile(plens_a, 50)),
        "prompt_len_p90": int(np.percentile(plens_a, 90)),
        "prompt_len_max": int(plens_a.max()),
        "output_len_p50": int(np.percentile(olens_a, 50)),
        "output_len_p90": int(np.percentile(olens_a, 90)),
        "output_len_max": int(olens_a.max()),
        **extra,
    }
    return prompts, out_lens, mix_detail


async def run(args) -> dict:
    from aphrodite_tpu.common import faultinject
    from aphrodite_tpu.common.sampling_params import SamplingParams
    from aphrodite_tpu.engine.args_tools import AsyncEngineArgs
    from aphrodite_tpu.engine.async_aphrodite import AsyncAphrodite
    from aphrodite_tpu.processing.admission import (RequestRejectedError,
                                                    RequestTimeoutError)

    chaos = bool(getattr(args, "chaos", False))
    chaos_fault = str(getattr(args, "chaos_fault", "") or "")
    chaos_abort_rate = float(getattr(args, "chaos_abort_rate", 0.0)
                             or 0.0)
    chaos_kill = bool(getattr(args, "chaos_kill", False))
    kill_fault = str(getattr(args, "kill_fault", "") or
                     "executor.execute_model:fatal:0.05:1")
    overload = bool(getattr(args, "overload", False))
    overload_mult = float(getattr(args, "overload_mult", 2.0) or 2.0)
    deadline_s = float(getattr(args, "deadline_s", 2.0) or 2.0)
    disconnect_rate = float(getattr(args, "disconnect_rate", 0.1)
                            or 0.0)
    if overload:
        # Offered load = mult x the configured rate; the admission
        # layer must shed the excess instead of queueing to death.
        if args.request_rate != float("inf"):
            args.request_rate = args.request_rate * overload_mult
        # Engage the anti-preemption-storm page reserve unless the
        # operator pinned a value (env writes are the sanctioned way
        # for a harness to configure per-call-read flags).
        os.environ.setdefault("APHRODITE_PAGE_LOW_WATERMARK", "0.05")
    if chaos and chaos_fault and chaos_fault != "none":
        # Env WRITES are the sanctioned way for a harness to configure
        # the (per-call-read) fault-injection flags.
        os.environ["APHRODITE_FAULT"] = chaos_fault
        os.environ["APHRODITE_FAULT_SEED"] = str(
            getattr(args, "chaos_seed", 0) or 0)
        faultinject.reset()
    if chaos_kill:
        # Reincarnation must be armed for the kill to be survivable;
        # respect an operator's explicit budget.
        os.environ.setdefault("APHRODITE_REINCARNATIONS", "3")

    engine = AsyncAphrodite.from_engine_args(AsyncEngineArgs(
        model=args.model, load_format=args.load_format,
        dtype=args.dtype, max_num_seqs=args.max_num_seqs,
        max_model_len=args.max_model_len, quantization=args.quantization,
        kv_cache_dtype=args.kv_cache_dtype,
        tensor_parallel_size=int(getattr(args, "tp", 1) or 1),
        skip_tokenizer_init=True, disable_log_stats=True,
        multi_step=args.multi_step))
    vocab = engine.engine.model_config.get_vocab_size()
    rng = np.random.RandomState(0)
    prompts, out_lens, mix_detail = build_mix(args, vocab, rng)
    # Deterministic abort plan: request index -> abort delay fraction.
    abort_rng = np.random.RandomState(
        int(getattr(args, "chaos_seed", 0) or 0) + 99)
    abort_frac = {
        i: float(abort_rng.uniform(0.05, 0.95))
        for i in range(args.num_requests)
        if chaos and abort_rng.uniform() < chaos_abort_rate
    }
    # Deterministic overload plans: per-request TTFT deadline drawn
    # around --deadline-s, and a disconnect storm (hang up after a
    # random number of tokens by DROPPING the generator — the
    # GeneratorExit path, not a polite abort).
    dl_rng = np.random.RandomState(
        int(getattr(args, "chaos_seed", 0) or 0) + 7)
    deadline_of = {
        i: float(dl_rng.uniform(0.5, 1.5) * deadline_s)
        for i in range(args.num_requests)
    } if overload else {}
    disc_rng = np.random.RandomState(
        int(getattr(args, "chaos_seed", 0) or 0) + 17)
    disconnect_after = {
        i: int(disc_rng.randint(1, max(2, out_lens[i])))
        for i in range(args.num_requests)
        if overload and disc_rng.uniform() < disconnect_rate
    }

    ttfts, tpots, e2es = [], [], []
    survived_out_tokens: list = []
    outcomes = {"survived": 0, "aborted": 0, "failed": 0,
                "shed": 0, "expired": 0, "disconnected": 0}
    rejection_ms: list = []

    async def one(i: int, *, measured: bool = True) -> None:
        sp = SamplingParams(temperature=0.0, max_tokens=out_lens[i],
                            ignore_eos=True,
                            ttft_slo_s=deadline_of.get(i))
        rid = f"req-{i}" if measured else f"warm-req-{i}"
        aborter = None
        if measured and i in abort_frac:
            async def fire_abort():
                # Abort at a random point of the request's expected
                # lifetime (prefill included: small fractions hit
                # before the first token).
                await asyncio.sleep(abort_frac[i] *
                                    max(args.output_len * 0.05, 0.2))
                try:
                    await engine.abort(rid)
                except Exception as e:
                    logger_warn("abort %s failed: %s", rid, e)

            aborter = asyncio.create_task(fire_abort())
        t0 = time.perf_counter()
        first = None
        final = None
        hung_up = False
        try:
            async for out in engine.generate(
                    None, sp, rid, prompt_token_ids=prompts[i]):
                if first is None and out.outputs and \
                        out.outputs[0].token_ids:
                    first = time.perf_counter()
                final = out
                if measured and i in disconnect_after and final.outputs \
                        and len(final.outputs[0].token_ids) >= \
                        disconnect_after[i]:
                    # Client hangs up: stop iterating and DROP the
                    # generator (no abort call) — disconnect
                    # propagation must free the KV pages anyway.
                    hung_up = True
                    break
        except RequestRejectedError:
            if measured:
                outcomes["shed"] += 1
                rejection_ms.append((time.perf_counter() - t0) * 1e3)
            return
        except RequestTimeoutError:
            if measured:
                outcomes["expired"] += 1
            return
        except Exception as e:
            if measured:
                outcomes["failed"] += 1
                logger_warn("request %s failed: %s: %s", rid,
                            type(e).__name__, e)
            return
        finally:
            if aborter is not None:
                aborter.cancel()
        t1 = time.perf_counter()
        if hung_up:
            outcomes["disconnected"] += 1
            return
        n_out = len(final.outputs[0].token_ids) if final and \
            final.outputs else 0
        if not measured:
            return
        if n_out < out_lens[i]:
            outcomes["aborted"] += 1
            return                  # partial: excluded from latency
        outcomes["survived"] += 1
        survived_out_tokens.append(n_out)
        ttfts.append((first or t1) - t0)
        if n_out > 1:
            tpots.append((t1 - (first or t1)) / (n_out - 1))
        e2es.append(t1 - t0)

    async def bucket_warmup() -> None:
        """Compile the workload's bucket lattice DETERMINISTICALLY (the
        reference captures CUDA graphs for every batch size at startup,
        model_runner.py:654, for the same reason). Replaying the arrival
        schedule only compiles the buckets the warmup pass's own timing
        happens to walk; the measured pass (different service times)
        walks others and pays their compiles mid-measurement
        (observed as 30 s TTFT p99 tails at request rate 2.0).
        All-at-once batches at each batch bucket cover the prefill
        bucket x table-width x burst-length lattice for this workload
        shape; the persistent compile cache makes later runs ~free."""
        caps = [b for b in (1, 2, 4, 8, 12, 16, 24, 32, 48, 64, 96, 128,
                            192, 256)
                if b <= min(args.max_num_seqs, args.num_requests)]
        done = 0

        async def one_warm(i: int, n_out: int) -> None:
            sp = SamplingParams(temperature=0.0, max_tokens=n_out,
                                ignore_eos=True)
            try:
                async for _ in engine.generate(
                        None, sp, f"warm-{i}",
                        prompt_token_ids=prompts[i % len(prompts)]):
                    pass
            except RequestRejectedError:
                # A big all-at-once warmup batch can overrun the
                # waiting-token admission cap; the shed is the
                # admission layer working, not a warmup failure —
                # the batch that DID admit still compiles the bucket.
                pass

        for b in caps:
            await asyncio.gather(*[
                one_warm(done + j, args.output_len) for j in range(b)])
            done += b
        # Tail burst lengths (4/2/1 appear when every row is near its
        # stop) + the odd-length walk.
        await asyncio.gather(*[one_warm(done + j, 13) for j in range(4)])

    async def drive() -> float:
        # Fresh rng per pass: warmup replays the SAME Poisson arrival
        # schedule as the measured pass, so the batch-size bucket walk
        # a slow arrival rate creates is compiled before measurement
        # (an all-at-once warmup only covers the big-batch buckets —
        # round 4's rate-2.0 runs showed 87 s compile-dominated TTFTs
        # behind a "warmed" flag). ~20 s per shape bucket on this
        # platform; the reference's CUDA-graph capture is likewise
        # excluded from its measurements.
        arrival_rng = np.random.RandomState(1234)
        tasks = []
        t0 = time.perf_counter()
        async for i in poisson_arrivals(args.num_requests,
                                        args.request_rate, arrival_rng):
            tasks.append(asyncio.create_task(one(i)))
        await asyncio.gather(*tasks)
        return time.perf_counter() - t0

    async def drain_to_idle() -> None:
        """Wait until every in-flight request (including disconnect
        casualties whose aborts ride the generator finalizers) has
        fully released its KV pages."""
        import gc
        for _ in range(600):
            gc.collect()        # finalize dropped async generators
            await asyncio.sleep(0.05)
            if not engine.engine.has_unfinished_requests() and \
                    not engine.engine.scheduler.block_manager.\
                    block_tables:
                return
        logger_warn("drain_to_idle: engine still busy after 30 s")

    if int(getattr(args, "warmup", 0) or 0):
        await bucket_warmup()
    for _ in range(int(getattr(args, "warmup", 0) or 0)):
        await drive()
        await drain_to_idle()
        ttfts.clear()
        tpots.clear()
        e2es.clear()
        survived_out_tokens.clear()
        rejection_ms.clear()
        for key in outcomes:
            outcomes[key] = 0

    block_manager = engine.engine.scheduler.block_manager
    free0 = block_manager.get_num_free_gpu_blocks()
    # Exact zero-leak accounting: prefix pins are pages held ON
    # PURPOSE, so the leak check subtracts the pin delta instead of
    # fuzzing the invariant (pinned0 is normally 0 — warmup traffic
    # carries no prefix_pos — but measured traffic may pin).
    pinned0 = engine.engine.scheduler.prefix_pinned_pages()

    def kv_leak(free_now: int, pinned_now: int) -> int:
        """free0 == free_now + newly-pinned pages, else pages leaked
        (positive) or double-freed (negative)."""
        return free0 - free_now - (pinned_now - pinned0)
    if chaos_kill and kill_fault != "none":
        # Armed AFTER warmup so the FATAL fires mid-measurement, not
        # during the compile pass (count=1 spends the rule wherever it
        # first fires).
        os.environ["APHRODITE_FAULT"] = kill_fault
        os.environ["APHRODITE_FAULT_SEED"] = str(
            getattr(args, "chaos_seed", 0) or 0)
        faultinject.reset()
    wall = await drive()
    if overload or chaos_kill:
        await drain_to_idle()

    def pct(xs, p):
        # 0.0 (not None) for empty series: round() downstream.
        return float(np.percentile(np.asarray(xs), p)) if xs else 0.0

    mesh_shape = engine.engine.executor.mesh_shape
    import jax as _jax
    detail = {
        "request_rate": args.request_rate,
        "num_requests": args.num_requests,
        # Topology of record: (dp, pp, sp, tp) of the serving mesh
        # (null = one device) and the backend it ran on, so a
        # virtual-mesh capture is never mistaken for hardware.
        "mesh": list(mesh_shape) if mesh_shape else None,
        "backend": _jax.default_backend(),
        "mix": mix_detail,
        # Ragged mixes finish different token counts per request, so
        # throughput sums what actually completed (uniform reduces to
        # the old survived * output_len).
        "throughput_out_tok_s": round(
            sum(survived_out_tokens) / wall, 1),
        "ttft_p50": round(pct(ttfts, 50), 4),
        "ttft_p90": round(pct(ttfts, 90), 4),
        "ttft_p99": round(pct(ttfts, 99), 4),
        "tpot_p50": round(pct(tpots, 50), 5),
        "e2e_p50": round(pct(e2es, 50), 4),
        "e2e_p99": round(pct(e2es, 99), 4),
    }
    # --- EWMA-based saturation/shed attribution (rate runs only) ---
    # When the served output rate falls short of the offered load,
    # name the binding resource MACHINE-READABLY from the admission
    # controller's throughput EWMAs instead of leaving the residual
    # to eyeballing: demand is what the arrival schedule asked for
    # (prompt and output tokens per second), capacity is what the
    # engine sustained while busy (the EWMAs deliberately exclude
    # idle gaps, admission.py). A shed-free run whose EWMAs clear the
    # offered rates saturated on HOST scheduling, not device
    # throughput — that distinction is the `bottleneck` field the
    # SERVING_r06 rate-8.0 gate reads.
    if args.request_rate != float("inf"):
        admission = engine.engine.admission
        plens = [len(p) for p in prompts]
        offered_out = args.request_rate * float(np.mean(out_lens))
        offered_prefill = args.request_rate * float(np.mean(plens))
        served_frac = (detail["throughput_out_tok_s"] / offered_out
                       if offered_out else 1.0)
        ewma_p = admission.ewma_prefill_tok_s
        ewma_d = admission.ewma_decode_tok_s
        util_p = offered_prefill / ewma_p if ewma_p > 0 else 0.0
        util_d = offered_out / ewma_d if ewma_d > 0 else 0.0
        meets_gate = bool(served_frac >= 0.95 and pct(ttfts, 99) <= 1.0)
        if meets_gate:
            bottleneck = "none"
        elif outcomes["shed"] or admission.sheds_total:
            bottleneck = "admission_shed"
        elif util_d >= 1.0 and util_d >= util_p:
            bottleneck = "decode_throughput"
        elif util_p >= 1.0:
            bottleneck = "prefill_throughput"
        else:
            bottleneck = "host_scheduling"
        detail["saturation"] = {
            "offered_out_tok_s": round(offered_out, 1),
            "offered_prefill_tok_s": round(offered_prefill, 1),
            "served_out_tok_s": detail["throughput_out_tok_s"],
            "served_frac": round(served_frac, 4),
            "ewma_prefill_tok_s": round(ewma_p, 1),
            "ewma_decode_tok_s": round(ewma_d, 1),
            "prefill_utilization": round(util_p, 3),
            "decode_utilization": round(util_d, 3),
            "requests_shed": outcomes["shed"],
            "sheds_total": admission.sheds_total,
            "meets_gate": meets_gate,
            "bottleneck": bottleneck,
        }
    if overload:
        admission = engine.engine.admission
        free_end = block_manager.get_num_free_gpu_blocks()
        detail["overload"] = {
            "offered_rate": args.request_rate,
            "deadline_s": deadline_s,
            "disconnect_rate": disconnect_rate,
            "requests_served": outcomes["survived"],
            "requests_shed": outcomes["shed"],
            "requests_expired": outcomes["expired"],
            "requests_disconnected": outcomes["disconnected"],
            "requests_failed": outcomes["failed"],
            # Goodput: output tokens of fully-served admitted
            # requests over the measured wall time.
            "goodput_out_tok_s": round(
                sum(survived_out_tokens) / wall, 1),
            "rejection_ms_p50": round(pct(rejection_ms, 50), 2),
            "rejection_ms_max": round(max(rejection_ms), 2)
            if rejection_ms else 0.0,
            "admitted_ttft_p50": round(pct(ttfts, 50), 4),
            "admitted_ttft_p90": round(pct(ttfts, 90), 4),
            "admitted_ttft_p99": round(pct(ttfts, 99), 4),
            "free_pages_before": free0,
            "free_pages_after": free_end,
            "prefix_pinned_pages": engine.engine.scheduler.
            prefix_pinned_pages(),
            "kv_leak_pages": kv_leak(
                free_end, engine.engine.scheduler.prefix_pinned_pages()),
            "sheds_total": admission.sheds_total,
            "expired_total": admission.expired_total,
            "ewma_prefill_tok_s": round(
                admission.ewma_prefill_tok_s, 1),
        }
    if chaos:
        health = engine.health.report(
            in_flight=engine.engine.has_unfinished_requests())
        detail["chaos"] = {
            "fault_spec": chaos_fault or "none",
            "abort_rate": chaos_abort_rate,
            "requests_survived": outcomes["survived"],
            "requests_aborted": outcomes["aborted"],
            "requests_failed": outcomes["failed"],
            "steps_retried": health.retries_total,
            "steps_recovered": health.recovered_steps,
            "engine_state": health.state,
            "faults_fired": faultinject.stats(),
            # Degradation headline: p99 TTFT under chaos rides in the
            # shared ttft_p99 field above; survivors only.
            "degraded_ttft_p99": detail["ttft_p99"],
        }
    if chaos_kill:
        from aphrodite_tpu.processing.admission import (
            EngineDrainingError)

        health = engine.health
        # The block manager may be a REBUILT object by now — the
        # zero-leak invariant is that the fresh pool's free count
        # equals the original free0 (same configs size both pools).
        bm_now = engine.engine.scheduler.block_manager
        accounted = sum(outcomes.values())
        detail["chaos_kill"] = {
            "fault_spec": kill_fault,
            "reincarnations": health.reincarnations_total,
            "requests_restored": health.requests_restored_total,
            "requests_lost_typed": health.requests_lost_total,
            "recovery_s": round(health.last_rebuild_s or 0.0, 3),
            "engine_state": health.report(
                in_flight=engine.engine.has_unfinished_requests()
            ).state,
            # Zero-lost invariant: every request completed or got a
            # typed error — nothing silently vanished.
            "requests_unaccounted": args.num_requests - accounted,
            "free_pages_before": free0,
            "free_pages_after": bm_now.get_num_free_gpu_blocks(),
            "prefix_pinned_pages": engine.engine.scheduler.
            prefix_pinned_pages(),
            "kv_leak_pages": kv_leak(
                bm_now.get_num_free_gpu_blocks(),
                engine.engine.scheduler.prefix_pinned_pages()),
            "faults_fired": faultinject.stats(),
        }

        # Drain storm: the SIGTERM rolling-restart contract proven
        # in-process — in-flight requests complete, late arrivals get
        # the typed 503-class rejection, the replica goes idle.
        async def drain_storm(n_inflight=4, n_late=4) -> dict:
            sp = SamplingParams(temperature=0.0,
                                max_tokens=args.output_len,
                                ignore_eos=True)

            async def serve(i: int):
                final = None
                async for out in engine.generate(
                        None, sp, f"drain-{i}",
                        prompt_token_ids=prompts[i]):
                    final = out
                return final

            tasks = [asyncio.create_task(serve(i))
                     for i in range(n_inflight)]
            await asyncio.sleep(0.05)       # let them admit
            t0 = time.perf_counter()
            engine.start_drain(deadline_s=60.0,
                               reason="chaos-kill drain storm")
            rejected = 0
            for j in range(n_late):
                try:
                    async for _ in engine.generate(
                            None, sp, f"late-{j}",
                            prompt_token_ids=prompts[j]):
                        pass
                except EngineDrainingError:
                    rejected += 1
                except Exception as e:
                    logger_warn("late request %d unexpected error: "
                                "%s: %s", j, type(e).__name__, e)
            clean = await engine.drained()
            finals = await asyncio.gather(*tasks,
                                          return_exceptions=True)
            completed = sum(
                1 for f in finals
                if not isinstance(f, BaseException) and f is not None
                and len(f.outputs[0].token_ids) == args.output_len)
            return {
                "inflight_offered": n_inflight,
                "inflight_completed": completed,
                "late_offered": n_late,
                "late_rejected_draining": rejected,
                "clean_exit": bool(clean),
                "drain_s": round(time.perf_counter() - t0, 3),
            }

        detail["chaos_kill"]["drain"] = await drain_storm()
    return {
        "metric": "serving_p50_ttft_s",
        "value": round(pct(ttfts, 50), 4),
        "unit": "s",
        "detail": detail,
    }


async def run_fleet(args) -> dict:
    """Fleet mode: N REAL replica server processes behind the fleet
    router, Poisson load driven through the router over HTTP, with a
    mid-run rolling deploy (`--rollout-at`) and an optional chaos
    SIGKILL of one replica (`--chaos-kill` / `--kill-at`). Reports
    fleet goodput, TTFT percentiles, prefix-affinity hit rate, retry
    counters, per-replica accounting, rollout-window continuity, and
    the zero-lost invariant (`requests_unaccounted == 0`).

    With `--chaos-kill` the SIGKILL is deliberately MID-STREAM (the
    victim is the replica with the most in-flight streams, killed
    only once it has some) and every request is SEEDED: the run
    first drives the identical workload kill-free as a control, then
    asserts in the JSON `failover` section that the router resumed
    every interrupted stream (`truncated_client_streams == 0`,
    `resumed_mid_stream >= 1`) and that each chaos-run stream's
    spliced text is BIT-EQUAL to its kill-free control."""
    import tempfile

    import aiohttp
    from aiohttp import web as aioweb

    from aphrodite_tpu.fleet.launcher import FleetLauncher
    from aphrodite_tpu.fleet.router import FleetRouter

    n = int(args.fleet)
    turns = max(1, int(getattr(args, "session_turns", 4) or 4))
    rollout_at = float(getattr(args, "rollout_at", 0.5))
    kill_at = float(getattr(args, "kill_at", -1.0))
    if bool(getattr(args, "chaos_kill", False)) and kill_at < 0:
        kill_at = 0.3
    admin_key = "fleet-admin"
    log_dir = tempfile.mkdtemp(prefix="fleet-logs-")

    extra = ["--load-format", args.load_format,
             "--dtype", args.dtype,
             "--max-num-seqs", str(args.max_num_seqs),
             "--max-model-len", str(args.max_model_len),
             "--multi-step", str(args.multi_step),
             "--swap-space", "0.01",
             "--disable-log-stats"]
    launcher = FleetLauncher(args.model, n, admin_key=admin_key,
                             served_model_name="fleet",
                             extra_args=extra, log_dir=log_dir)
    logger_warn("fleet: spawning %d replicas (logs in %s)", n, log_dir)
    await launcher.start_all(ready_timeout_s=300.0)

    http = aiohttp.ClientSession(timeout=aiohttp.ClientTimeout(
        total=None, sock_connect=10.0))

    # Deterministic session workload: each session shares a prompt
    # PREFIX (first half of the prompt) so its turns hash to the same
    # affinity key; suffixes make every request distinct.
    rng = np.random.RandomState(0)
    n_sessions = max(1, args.num_requests // turns)
    prefix_len = max(8, args.prompt_len // 2)
    session_prefix = {
        s: rng.randint(5, 400, size=prefix_len).tolist()
        for s in range(n_sessions)
    }
    prompts = []
    for i in range(args.num_requests):
        s = i % n_sessions
        suffix = rng.randint(
            5, 400, size=args.prompt_len - prefix_len).tolist()
        prompts.append((s, session_prefix[s] + suffix))

    async def warm_one(url: str, prompt, out_len: int) -> None:
        body = {"model": "fleet", "prompt": prompt,
                "max_tokens": out_len, "temperature": 0.0,
                "ignore_eos": True}
        try:
            async with http.post(url + "/v1/completions",
                                 json=body) as resp:
                await resp.read()
        except aiohttp.ClientError as e:
            logger_warn("warmup request to %s failed: %s", url, e)

    async def warm_replica(url: str) -> None:
        """Absorb the workload's shape-bucket compiles directly on
        one replica (every replica must compile its own lattice)."""
        for batch in (1, min(2, args.max_num_seqs),
                      min(4, args.max_num_seqs)):
            await asyncio.gather(*(
                warm_one(url, prompts[j % len(prompts)][1],
                         args.output_len)
                for j in range(batch)))
        await warm_one(url, prompts[0][1], max(1, 13 % args.output_len))

    if int(getattr(args, "warmup", 0) or 0):
        logger_warn("fleet: warming %d replicas", n)
        await asyncio.gather(*(warm_replica(h.url)
                               for h in launcher.handles()))

    async def restart_and_warm(handle) -> None:
        """Rollout restart hook: bounce the process, then warm the
        fresh replica BEFORE the router re-admits it, so re-admitted
        capacity serves at speed instead of compiling on live
        traffic."""
        await launcher.restart(handle)
        async with aiohttp.ClientSession() as boot:
            await launcher._wait_ready(boot, handle, 300.0)
        await warm_replica(handle.url)

    router = FleetRouter(launcher.handles(), admin_keys=[admin_key],
                         restart_cb=restart_and_warm)
    await router.start()
    app_runner = aioweb.AppRunner(router.build_app())
    await app_runner.setup()
    site = aioweb.TCPSite(app_runner, "127.0.0.1", 0)
    await site.start()
    base = f"http://127.0.0.1:{app_runner.addresses[0][1]}"

    # Seeded requests in chaos-kill mode: per-request seeds make the
    # bit-equality proof non-trivial (random sampling, not greedy) —
    # a resumed continuation only matches the control if the PRNG
    # salt really continues at the splice position.
    seeded = bool(getattr(args, "chaos_kill", False))

    def body_for(i: int) -> dict:
        _, prompt = prompts[i]
        body = {"model": "fleet", "prompt": prompt,
                "max_tokens": args.output_len, "temperature": 0.0,
                "ignore_eos": True, "stream": True}
        if seeded:
            body["temperature"] = 0.8
            body["seed"] = 9000 + i
        return body

    outcomes = {"served": 0, "failed_mid_stream": 0,
                "client_5xx_prestream": 0, "rejected_429": 0,
                "rejected_other": 0, "transport_errors": 0}
    ttfts, e2es = [], []
    completions = []            # perf_counter stamps of served reqs

    def _sse_text(raw: bytes) -> str:
        text = ""
        for line in raw.split(b"\n"):
            if not line.startswith(b"data: "):
                continue
            payload = line[len(b"data: "):]
            if payload.strip() == b"[DONE]":
                continue
            try:
                text += json.loads(payload)["choices"][0]["text"]
            except (ValueError, KeyError, IndexError):
                pass
        return text

    async def one(i: int, outcomes: dict, ttfts: list, e2es: list,
                  completions: list,
                  texts=None) -> None:
        body = body_for(i)
        t0 = time.perf_counter()
        try:
            async with http.post(base + "/v1/completions",
                                 json=body) as resp:
                if resp.status == 200:
                    first = None
                    buf = bytearray()
                    try:
                        async for chunk in resp.content.iter_any():
                            if first is None and chunk:
                                first = time.perf_counter()
                            buf += chunk
                    except aiohttp.ClientError:
                        pass
                    t1 = time.perf_counter()
                    if b"[DONE]" in bytes(buf):
                        outcomes["served"] += 1
                        ttfts.append((first or t1) - t0)
                        e2es.append(t1 - t0)
                        completions.append(t1)
                        if texts is not None:
                            texts[i] = _sse_text(bytes(buf))
                    else:
                        # Mid-stream casualty past the resume budget:
                        # truthful truncation, never a silent
                        # re-issue.
                        outcomes["failed_mid_stream"] += 1
                    return
                await resp.read()
                if resp.status == 429:
                    outcomes["rejected_429"] += 1
                elif resp.status >= 500:
                    # Forbidden: the router must retry these away
                    # for requests that never began streaming.
                    outcomes["client_5xx_prestream"] += 1
                else:
                    outcomes["rejected_other"] += 1
        except (aiohttp.ClientError, asyncio.TimeoutError) as e:
            outcomes["transport_errors"] += 1
            logger_warn("request %d transport error: %s: %s", i,
                        type(e).__name__, e)

    rollout_result = {}

    async def fire_rollout() -> None:
        t0r = time.perf_counter()
        try:
            async with http.post(
                    base + "/admin/rollout",
                    json={"deadline_s": 60.0,
                          "ready_timeout_s": 300.0},
                    headers={"Authorization":
                             f"Bearer {admin_key}"}) as resp:
                rollout_result["status"] = resp.status
                rollout_result["report"] = await resp.json()
        except aiohttp.ClientError as e:
            rollout_result["status"] = -1
            rollout_result["error"] = f"{type(e).__name__}: {e}"
        rollout_result["window"] = (t0r, time.perf_counter())

    def pick_victim() -> int:
        """The replica with the most in-flight streams (freshest
        snapshots) — a SIGKILL there is guaranteed MID-STREAM."""
        best, best_inflight = n - 1, -1
        for idx, h in enumerate(router.replicas):
            s = h.snapshot
            if s is not None and s.inflight > best_inflight:
                best, best_inflight = idx, s.inflight
        return best

    kill_info = None
    rollout_task = None
    kill_index = (int(kill_at * args.num_requests)
                  if kill_at >= 0 else None)
    rollout_index = (int(rollout_at * args.num_requests)
                     if rollout_at >= 0 else None)

    async def drive_pass(outcomes: dict, ttfts: list, e2es: list,
                         completions: list,
                         texts=None,
                         with_events: bool = False) -> float:
        nonlocal kill_info, rollout_task
        arrival_rng = np.random.RandomState(1234)
        tasks = []
        t_start = time.perf_counter()
        kill_pending = with_events and kill_index is not None
        async for i in poisson_arrivals(args.num_requests,
                                        args.request_rate,
                                        arrival_rng):
            if kill_pending and i >= kill_index:
                victim = pick_victim()
                vs = router.replicas[victim].snapshot
                if (vs is not None and vs.inflight > 0) or \
                        i >= args.num_requests - 1:
                    launcher.kill(victim)
                    kill_pending = False
                    kill_info = {
                        "replica": f"replica-{victim}",
                        "at_request": i,
                        "victim_inflight": (vs.inflight
                                            if vs is not None else
                                            None),
                        "at_s": round(
                            time.perf_counter() - t_start, 3)}
                    logger_warn(
                        "fleet: chaos SIGKILL of replica-%d "
                        "(inflight=%s) at request %d", victim,
                        kill_info["victim_inflight"], i)
            if with_events and rollout_index is not None and \
                    i == rollout_index:
                logger_warn("fleet: firing mid-run rolling deploy at "
                            "request %d", i)
                rollout_task = asyncio.create_task(fire_rollout())
            tasks.append(asyncio.create_task(one(
                i, outcomes, ttfts, e2es, completions, texts)))
        await asyncio.gather(*tasks)
        return time.perf_counter() - t_start, t_start

    # Kill-free CONTROL pass first (chaos-kill mode): the seeded
    # texts every chaos-run stream must match bit-for-bit.
    control_texts = None
    if seeded and kill_index is not None:
        logger_warn("fleet: driving the kill-free seeded control "
                    "pass")
        control_texts = {}
        await drive_pass({k: 0 for k in outcomes}, [], [], [],
                         texts=control_texts, with_events=False)

    chaos_texts = {} if control_texts is not None else None
    wall, t_start = await drive_pass(outcomes, ttfts, e2es,
                                     completions, texts=chaos_texts,
                                     with_events=True)
    if rollout_task is not None:
        await rollout_task

    def pct(xs, p):
        return float(np.percentile(np.asarray(xs), p)) if xs else 0.0

    rollout_detail = None
    if rollout_result:
        w0, w1 = rollout_result.get("window", (0.0, 0.0))
        stamps = sorted(t for t in completions if w0 <= t <= w1)
        edges = [w0] + stamps + [w1]
        max_gap = max((b - a for a, b in zip(edges, edges[1:])),
                      default=0.0) if w1 > w0 else 0.0
        rollout_detail = {
            "status": rollout_result.get("status"),
            "started_at_s": round(w0 - t_start, 3),
            "duration_s": round(w1 - w0, 3),
            "completions_during": len(stamps),
            # Zero-downtime evidence: the longest served-request gap
            # inside the rollout window (never a full outage).
            "max_completion_gap_s": round(max_gap, 3),
            "report": rollout_result.get("report"),
            "error": rollout_result.get("error"),
        }

    stats = router.stats
    accounted = sum(outcomes.values())
    detail = {
        "fleet": n,
        "request_rate": args.request_rate,
        "num_requests": args.num_requests,
        "prompt_len": args.prompt_len,
        "output_len": args.output_len,
        "session_turns": turns,
        "sessions": n_sessions,
        "goodput_out_tok_s": round(
            outcomes["served"] * args.output_len / wall, 1),
        "ttft_p50": round(pct(ttfts, 50), 4),
        "ttft_p90": round(pct(ttfts, 90), 4),
        "ttft_p99": round(pct(ttfts, 99), 4),
        "e2e_p50": round(pct(e2es, 50), 4),
        "e2e_p99": round(pct(e2es, 99), 4),
        "outcomes": dict(outcomes),
        # The fleet-wide zero-lost invariant: every request resolved
        # to exactly one outcome.
        "requests_unaccounted": args.num_requests - accounted,
        "affinity_hit_rate": stats.to_json()["affinity_hit_rate"],
        "retries": {"conn": stats.retries_conn,
                    "status_503": stats.retries_503,
                    "status_5xx": stats.retries_5xx,
                    "total": stats.retries_total},
        "router": stats.to_json(),
        "replicas": {r.name: r.describe()
                     for r in router.replicas},
        "rollout": rollout_detail,
        "chaos_kill": kill_info,
        "replica_logs": log_dir,
    }
    # Mid-stream failover proof: the router journal/splice counters
    # plus the seeded bit-equality check against the kill-free
    # control pass (every stream served in BOTH passes must match
    # byte-for-byte — a resumed splice that lost, duplicated, or
    # diverged a token fails here).
    failover = {
        "failed_mid_stream": stats.failed_mid_stream,
        "resumed_mid_stream": stats.resumed_mid_stream,
        "truncated_client_streams": stats.truncated_client_streams,
    }
    if control_texts is not None:
        both = sorted(set(control_texts) & set(chaos_texts or {}))
        mismatches = [i for i in both
                      if control_texts[i] != chaos_texts[i]]
        failover["seeded_control"] = {
            "control_served": len(control_texts),
            "chaos_served": len(chaos_texts or {}),
            "compared": len(both),
            "bit_equal": not mismatches,
            "mismatched_requests": mismatches[:8],
        }
    detail["failover"] = failover

    await http.close()
    await app_runner.cleanup()
    await router.stop()
    await launcher.shutdown()
    return {
        "metric": "fleet_goodput_out_tok_s",
        "value": detail["goodput_out_tok_s"],
        "unit": "tok/s",
        "detail": detail,
    }


def synthetic_tiny_dir() -> str:
    """Tiny-llama config + offline-trained ByteLevel BPE tokenizer
    (mirrors tests/conftest.py's tiny_model_dir). Fleet replicas
    serve real HTTP with real tokenizers, so unlike synthetic-7b this
    model dir must carry one; it is small enough that N engine
    subprocesses build in seconds on CPU."""
    import json as _json
    import tempfile

    from tokenizers import (Tokenizer, decoders, models,
                            pre_tokenizers, trainers)
    tmp = tempfile.mkdtemp(prefix="serving-tiny-")
    corpus = [
        "the quick brown fox jumps over the lazy dog",
        "hello world this is a tiny tokenizer training corpus",
        "continuous batching over a paged key value cache",
        "fleet routing with prefix affinity and rolling deploys",
        "0123456789 !?.,:;()[]{}",
    ] * 4
    tok = Tokenizer(models.BPE(unk_token=None))
    tok.pre_tokenizer = pre_tokenizers.ByteLevel(add_prefix_space=True)
    tok.decoder = decoders.ByteLevel()
    trainer = trainers.BpeTrainer(
        vocab_size=512, special_tokens=["<s>", "</s>", "<pad>"],
        initial_alphabet=pre_tokenizers.ByteLevel.alphabet())
    tok.train_from_iterator(corpus, trainer)
    tok.save(os.path.join(tmp, "tokenizer.json"))
    with open(os.path.join(tmp, "tokenizer_config.json"), "w") as f:
        _json.dump({"tokenizer_class": "PreTrainedTokenizerFast",
                    "bos_token": "<s>", "eos_token": "</s>",
                    "pad_token": "<pad>",
                    "model_max_length": 512}, f)
    with open(os.path.join(tmp, "config.json"), "w") as f:
        _json.dump({
            "architectures": ["LlamaForCausalLM"],
            "model_type": "llama",
            "vocab_size": tok.get_vocab_size(),
            "hidden_size": 64, "intermediate_size": 128,
            "num_hidden_layers": 2, "num_attention_heads": 4,
            "num_key_value_heads": 2,
            "max_position_embeddings": 512, "rms_norm_eps": 1e-6,
            "rope_theta": 10000.0, "tie_word_embeddings": False,
            "torch_dtype": "float32", "bos_token_id": 0,
            "eos_token_id": 1}, f)
    return tmp


def synthetic_7b_dir() -> str:
    """Mistral-7B-shaped dummy config (bench.py's geometry) so the
    serving artifact runs hermetically (zero egress)."""
    import json as _json
    import os
    import tempfile
    tmp = tempfile.mkdtemp(prefix="serving-7b-")
    with open(os.path.join(tmp, "config.json"), "w") as f:
        _json.dump({
            "architectures": ["LlamaForCausalLM"],
            "model_type": "llama", "vocab_size": 32000,
            "hidden_size": 4096, "intermediate_size": 14336,
            "num_hidden_layers": 32, "num_attention_heads": 32,
            "num_key_value_heads": 8,
            "max_position_embeddings": 4096, "rms_norm_eps": 1e-5,
            "rope_theta": 10000.0, "tie_word_embeddings": False,
            "torch_dtype": "bfloat16", "bos_token_id": 1,
            "eos_token_id": 2}, f)
    return tmp


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--model", required=True,
                        help="path, or 'synthetic-7b' for the dummy "
                             "Mistral-7B-shaped bench model")
    parser.add_argument("--load-format", default="auto")
    parser.add_argument("--dtype", default="bfloat16")
    parser.add_argument("--quantization", default=None)
    parser.add_argument("--kv-cache-dtype", default="auto")
    parser.add_argument("--max-num-seqs", type=int, default=256)
    parser.add_argument("--max-model-len", type=int, default=2048)
    parser.add_argument("--multi-step", type=int, default=8)
    parser.add_argument("--tp", "--tensor-parallel-size", type=int,
                        default=1, dest="tp",
                        help="tensor-parallel degree: shard the "
                             "persistent step over a (1,1,1,tp) mesh "
                             "(requires >= tp visible devices; use "
                             "XLA_FLAGS=--xla_force_host_platform_"
                             "device_count=N for a virtual CPU mesh)")
    parser.add_argument("--request-rate", type=float, default=4.0,
                        help="poisson requests/s (inf = all at once)")
    parser.add_argument("--num-requests", type=int, default=128)
    parser.add_argument("--prompt-len", type=int, default=128)
    parser.add_argument("--output-len", type=int, default=64)
    parser.add_argument("--mix", default="uniform",
                        choices=("uniform", "sharegpt", "prefix-heavy"),
                        help="request-shape preset: 'uniform' (every "
                             "request prompt-len/output-len), "
                             "'sharegpt' (ragged lognormal prompt+"
                             "output lengths around the configured "
                             "medians), 'prefix-heavy' (multi-turn "
                             "sessions sharing a ~3/4-prompt prefix — "
                             "the spec-decode A/B traffic); shape "
                             "stats recorded in the JSON detail")
    parser.add_argument("--warmup", type=int, default=1,
                        help="run the workload once first to absorb "
                             "shape-bucket compiles (0 to disable)")
    parser.add_argument("--overload", action="store_true",
                        help="overload mode: offered load x "
                             "--overload-mult, per-request deadlines, "
                             "disconnect storm; emits an `overload` "
                             "JSON section (goodput, shed/expired "
                             "counts, rejection latency, KV-leak "
                             "check)")
    parser.add_argument("--overload-mult", type=float, default=2.0,
                        help="offered-load multiplier over "
                             "--request-rate in overload mode")
    parser.add_argument("--deadline-s", type=float, default=2.0,
                        help="center of the per-request TTFT deadline "
                             "distribution (uniform 0.5x-1.5x)")
    parser.add_argument("--disconnect-rate", type=float, default=0.1,
                        help="fraction of requests that hang up "
                             "mid-stream by dropping their generator")
    parser.add_argument("--chaos", action="store_true",
                        help="chaos mode: inject faults + abort storm "
                             "and report fault-tolerance counters")
    parser.add_argument("--chaos-fault",
                        default="executor.execute_model:transient"
                                ":0.02:4",
                        help="APHRODITE_FAULT spec to inject "
                             "('none' = abort storm only)")
    parser.add_argument("--chaos-abort-rate", type=float, default=0.15,
                        help="fraction of requests aborted at a random "
                             "point of their lifetime")
    parser.add_argument("--chaos-seed", type=int, default=0,
                        help="seed for the fault RNG and abort plan")
    parser.add_argument("--chaos-kill", action="store_true",
                        help="kill-chaos lifecycle proof: arm a FATAL "
                             "fault mid-run (engine must reincarnate; "
                             "zero-lost-requests + KV-leak invariants "
                             "in a `chaos_kill` JSON section), then a "
                             "drain storm (in-flight completes, late "
                             "arrivals 503, clean drain)")
    parser.add_argument("--kill-fault",
                        default="executor.execute_model:fatal:0.05:1",
                        help="APHRODITE_FAULT spec armed after warmup "
                             "in --chaos-kill mode ('none' = drain "
                             "storm only)")
    parser.add_argument("--fleet", type=int, default=0,
                        help="fleet mode: spawn N replica server "
                             "processes behind the fleet router and "
                             "drive the load through it over HTTP "
                             "(reports goodput, affinity hit rate, "
                             "retries, requests_unaccounted)")
    parser.add_argument("--session-turns", type=int, default=4,
                        help="fleet mode: requests per multi-turn "
                             "session (turns share a prompt prefix, "
                             "driving prefix-affinity routing)")
    parser.add_argument("--rollout-at", type=float, default=0.5,
                        help="fleet mode: fire the zero-downtime "
                             "POST /admin/rollout after this "
                             "fraction of arrivals (-1 = no rollout)")
    parser.add_argument("--kill-at", type=float, default=-1.0,
                        help="fleet mode: SIGKILL the last replica "
                             "after this fraction of arrivals "
                             "(-1 = off; --chaos-kill defaults it "
                             "to 0.3)")
    args = parser.parse_args()
    if args.model == "synthetic-7b":
        args.model = synthetic_7b_dir()
        args.load_format = "dummy"
    elif args.model == "synthetic-tiny":
        args.model = synthetic_tiny_dir()
        args.load_format = "dummy"
        args.dtype = "float32"
        args.max_model_len = min(args.max_model_len, 256)
    if args.fleet > 0:
        print(json.dumps(asyncio.run(run_fleet(args))))
    else:
        print(json.dumps(asyncio.run(run(args))))


if __name__ == "__main__":
    main()
