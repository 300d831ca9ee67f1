"""Throughput benchmark: offline continuous-batching generation.

Prints ONE JSON line:
  {"metric": ..., "value": <median>, "samples": [...], "n_runs": 3,
   "unit": ..., "vs_baseline": N, "platform": "tpu", "device_kind": ...,
   "device_count": N}

`value` is the MEDIAN of `n_runs` (default 3) timed runs, each with
the GC-disable discipline; the per-run samples ride along so the
spread between runs is visible.

It measures only on a chip: without a `tpu` backend, or with `--tp`
above the number of devices JAX finds, it exits non-zero before it
builds anything and prints no rate. Every result names the platform,
the device kind and the device count it ran on.

Baseline: the reference's peak batched output throughput for Mistral-7B
fp16 on RTX 4090 is 5489.3 out-tok/s (reference README.md:59; BASELINE.md).
This harness measures aggregate output tokens/s through the full engine
(scheduler + paged cache + jitted model + fused sampler) on a
Mistral-7B-shaped dummy-weight model: the same matmul/KV shapes and
dtype as the baseline row, so vs_baseline is apples-to-apples
methodology-wise.

Warmup policy: the warmup run uses the SAME batch size and prompt length
as the timed run so every compile bucket the timed region hits (prefill
batch/seq bucket, decode batch bucket) is already cached — compile time
never leaks into the measurement.
"""
from __future__ import annotations

import json
import os
import sys
import time

BASELINE_TOKS = 5489.3     # reference README.md:59 (Mistral-7B fp16)

# Matching reference baseline row per quant method (README.md:59-67) so
# vs_baseline stays apples-to-apples when BENCH_QUANT is set.
BASELINE_BY_QUANT = {
    None: 5489.3,          # fp16
    "gptq": 7850.4,        # GPTQ 4-bit
    "awq": 4078.8,         # AWQ 4-bit
    "int8": 7658.0,        # GPTQ 8-bit is the closest 8-bit row
    "squeezellm": 549.5,
}

# GGUF compares per SOURCE FORMAT (one blended number against the
# wrong reference row is not like-for-like). BENCH_GGUF_FMT
# selects the at-rest form the dummy weights take AND the reference row
# the ratio is computed against; q6_k has no reference row, so its
# vs_baseline is null.
BASELINE_BY_GGUF_FMT = {
    "q4_k": 5815.8,        # reference Q4_K_M row
    "q8_0": 5141.2,        # reference Q8_0 row
    "q6_k": None,          # reference publishes no Q6_K row
}


def _log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def _parse_args():
    import argparse
    parser = argparse.ArgumentParser(
        description="Offline continuous-batching throughput bench "
                    "(BENCH_* env vars hold the remaining knobs)")
    parser.add_argument(
        "--tp", type=int, default=None,
        help="tensor-parallel degree (shards the persistent step over "
             "a (1,1,1,tp) mesh; refused when JAX finds fewer devices)."
             " Overrides BENCH_TP.")
    parser.add_argument(
        "--gguf-fmt", choices=sorted(BASELINE_BY_GGUF_FMT), default=None,
        help="GGUF at-rest source format for BENCH_QUANT=gguf runs "
             "(per-format scoreboard rows). Overrides BENCH_GGUF_FMT.")
    parser.add_argument(
        "--no-roofline-gate", action="store_true",
        help="skip the pre-run aphrocheck ROOF/FOLD gate (use when "
             "deliberately benching a known regression)")
    return parser.parse_args()


def _roofline_gate() -> None:
    """Pre-run static perf gate: the aphrocheck ROOF/FOLD sweep (~2 s)
    catches a kernel whose roofline estimate regressed vs ROOFLINE.json
    BEFORE a 30-minute TPU run is spent measuring the regression.
    Raises SystemExit with the findings; --no-roofline-gate skips."""
    from tools.aphrocheck import run as aphrocheck_run
    report = aphrocheck_run(rule_prefixes=["ROOF", "FOLD"])
    if report.findings:
        for f in report.findings:
            _log(f"roofline gate: {f.render()}")
        raise SystemExit(
            "bench: aphrocheck ROOF/FOLD gate failed — fix the "
            "regression, regenerate ROOFLINE.json (`python -m "
            "tools.aphrocheck --roofline --json > ROOFLINE.json`), or "
            "rerun with --no-roofline-gate")
    _log("roofline gate: clean")


def _require_chip(tp: int) -> dict:
    """The device facts every result carries, or SystemExit when there
    is no chip to measure on: a rate from the CPU backend is not a
    measurement of this system."""
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"bench: no chip: JAX found platform {dev.platform!r} "
            f"({dev.device_kind}); rates are measured on a tpu only")
    if tp > len(devices):
        raise SystemExit(
            f"bench: --tp {tp} exceeds the {len(devices)} "
            f"{dev.device_kind} device(s) JAX found")
    return {"platform": dev.platform, "device_kind": dev.device_kind,
            "device_count": len(devices)}


def main() -> None:
    args = _parse_args()
    # CLI -> env so gguf.py's dummy-weight shaping sees the same
    # configuration.
    if args.tp is not None:
        os.environ["BENCH_TP"] = str(args.tp)
    if args.gguf_fmt is not None:
        os.environ["BENCH_GGUF_FMT"] = args.gguf_fmt
    tp = int(os.environ.get("BENCH_TP", "1"))
    device = _require_chip(tp)
    if not args.no_roofline_gate:
        _roofline_gate()

    # Mistral-7B geometry (reference baseline row). Default quant is
    # GPTQ int4 — the reference's own headline row (7,850 tok/s,
    # README.md:61) and the only way a 16 GiB chip holds useful KV
    # next to 7B weights; vs_baseline compares against the MATCHING
    # reference row (see BASELINE_BY_QUANT). BENCH_QUANT= (empty)
    # selects the bf16 run against the fp16 row.
    hidden, layers, heads, kv_heads, inter = 4096, 32, 32, 8, 14336
    vocab = 32000
    if "BENCH_QUANT" not in os.environ:
        # tp>1 is the bf16 configuration (weights shard tp-ways, so
        # no quantization is needed to fit KV).
        os.environ["BENCH_QUANT"] = "" if tp > 1 else "gptq"
    from aphrodite_tpu.common import flags
    if os.environ.get("BENCH_QUANT") in ("gptq", "awq") and \
            not flags.is_set("APHRODITE_W4A8"):
        # The GPTQ/AWQ bench rows run the int8-activation MXU path
        # (weights stay int4 at rest; activations round to int8
        # per row — the reference's exllama kernel likewise
        # accumulates at reduced precision). BENCH_W4A16=1 /
        # APHRODITE_W4A8=0 selects the bit-exact bf16-activation
        # path.
        if os.environ.get("BENCH_W4A16") != "1":
            os.environ["APHRODITE_W4A8"] = "1"
    default_batch = "512" if os.environ["BENCH_QUANT"] else "112"
    batch = int(os.environ.get("BENCH_BATCH", default_batch))
    steps = int(os.environ.get("BENCH_STEPS", "96"))
    prompt_len = int(os.environ.get("BENCH_PROMPT", "32"))

    import json as _json
    import tempfile
    tmp = tempfile.mkdtemp(prefix="bench-model-")
    with open(os.path.join(tmp, "config.json"), "w") as f:
        _json.dump({
            "architectures": ["LlamaForCausalLM"],
            "model_type": "llama",
            "vocab_size": vocab,
            "hidden_size": hidden,
            "intermediate_size": inter,
            "num_hidden_layers": layers,
            "num_attention_heads": heads,
            "num_key_value_heads": kv_heads,
            "max_position_embeddings": 4096,
            "rms_norm_eps": 1e-5,
            "rope_theta": 10000.0,
            "tie_word_embeddings": False,
            "torch_dtype": "bfloat16",
            "bos_token_id": 1,
            "eos_token_id": 2,
        }, f)

    from aphrodite_tpu.common.sampling_params import SamplingParams
    from aphrodite_tpu.engine.aphrodite_engine import AphroditeEngine
    from aphrodite_tpu.engine.args_tools import EngineArgs

    t0 = time.perf_counter()
    # 64-step bursts halve the per-burst dispatch+sync share vs 32
    # (measured +~1.5% at batch 512; the page reservation still grants
    # the full depth at this geometry).
    multi_step = int(os.environ.get("BENCH_MULTI_STEP", "64"))
    quant = os.environ.get("BENCH_QUANT") or None
    kv_dtype = os.environ.get("BENCH_KV_DTYPE", "auto")
    # 32-token pages halve decode attention's per-cell DMA count (the
    # kernel is DMA-count bound at short contexts: +2.5% bench, round
    # 4) and are required for 8-bit KV anyway (8-bit sublane tile).
    block_size = int(os.environ.get("BENCH_BLOCK", "32"))
    engine = AphroditeEngine.from_engine_args(EngineArgs(
        model=tmp, tokenizer=tmp, load_format="dummy", dtype="bfloat16",
        max_model_len=2048, max_num_seqs=batch, disable_log_stats=True,
        skip_tokenizer_init=True, multi_step=multi_step,
        quantization=quant, kv_cache_dtype=kv_dtype,
        block_size=block_size, tensor_parallel_size=tp,
        # Big prefill rounds: each scheduling round pays a fixed
        # dispatch+sync cost plus host batch building, so batch as
        # many prompt tokens as possible per round (8192 is the
        # largest that fits next to the batch-512 KV pool — 16384
        # OOMs on the gate_up activation).
        max_num_batched_tokens=int(os.environ.get("BENCH_PREFILL_TOKENS",
                                                  "8192"))))

    # Fit the batch to KV capacity: a batch whose total footprint
    # exceeds the device pool just thrashes swap/preemption and measures
    # the scheduler, not the model. Leave the watermark + one burst of
    # headroom.
    page = engine.cache_config.block_size
    pages_per_seq = -(-(prompt_len + steps) // page)
    device_pages = engine.cache_config.num_gpu_blocks
    fit = max(1, int(device_pages * 0.98) // pages_per_seq)
    if fit < batch:
        _log(f"batch {batch} -> {fit} (KV capacity: {device_pages} pages"
             f", {pages_per_seq}/seq)")
        batch = fit
    _log(f"engine up in {time.perf_counter() - t0:.1f}s "
         f"(batch={batch}, steps={steps}, "
         f"prompt={prompt_len}, quant={quant}, kv={kv_dtype})")

    # BENCH_MODE=nonburst measures the DEGRADED path: a repetition
    # penalty makes every group history-dependent, so the engine falls
    # back to one dispatch+sync per token instead of the multi-step
    # burst scan (round-2 verdict: the fast-path-only number must not
    # be the only one quoted).
    mode = os.environ.get("BENCH_MODE", "burst")
    if mode == "nonburst":
        sp = SamplingParams(temperature=0.8, top_p=0.9,
                            repetition_penalty=1.1, max_tokens=steps,
                            ignore_eos=True)
    else:
        sp = SamplingParams(temperature=0.0, max_tokens=steps,
                            ignore_eos=True)
    rng_tokens = [[(7 * i + j) % (vocab - 10) + 5
                   for j in range(prompt_len)] for i in range(batch)]

    # Warmup: identical to the timed run — compiles every prefill and
    # decode-burst bucket (each power-of-two burst length is its own
    # compiled scan program) so no compile lands in the timed region.
    t0 = time.perf_counter()
    _run(engine, sp, rng_tokens, steps)
    _log(f"warmup done in {time.perf_counter() - t0:.1f}s")

    # Median of 3 timed runs (BENCH_RUNS overrides): single runs spread
    # several percent run-to-run. All samples ride in the JSON so the
    # spread is visible. Python GC pauses showed up as
    # ~0.5 s hiccups inside timed runs (millions of small host objects
    # from output processing); collect up front and pause collection for
    # the duration of EACH measurement.
    import gc
    import statistics
    n_runs = max(1, int(os.environ.get("BENCH_RUNS", "3")))
    samples = []
    for r in range(n_runs):
        gc.collect()
        gc.disable()
        try:
            t0 = time.perf_counter()
            total_out = _run(engine, sp, rng_tokens, steps)
            dt = time.perf_counter() - t0
        finally:
            gc.enable()
        samples.append(total_out / dt)
        _log(f"timed run {r + 1}/{n_runs}: {total_out} tokens in "
             f"{dt:.1f}s = {samples[-1]:.1f} tok/s")

    toks = statistics.median(samples)
    gguf_fmt = None
    if quant == "gguf":
        gguf_fmt = os.environ.get("BENCH_GGUF_FMT", "q4_k")
        baseline = BASELINE_BY_GGUF_FMT.get(gguf_fmt)
    else:
        baseline = BASELINE_BY_QUANT.get(quant, BASELINE_TOKS)
    tag = f"_{quant}" if quant else ""
    if gguf_fmt:
        tag += f"_{gguf_fmt}"
    if mode != "burst":
        tag += f"_{mode}"
    if tp > 1:
        tag += f"_tp{tp}"
    # Activation mode rides in the JSON so W4A8 and W4A16 runs can't
    # be conflated round-over-round.
    from aphrodite_tpu.common import flags
    act_mode = "w4a8" if flags.get_bool("APHRODITE_W4A8") else "w4a16"
    act_applies = quant in ("gptq", "awq")
    # quant/batch/kv ride in the JSON so round-over-round comparisons
    # can't conflate differently-configured runs (round-2 advisor).
    mesh_shape = engine.executor.mesh_shape
    print(json.dumps({
        "metric": f"offline_throughput_7b{tag}",
        "value": round(toks, 1),
        "unit": "out_tok/s",
        "samples": [round(s, 1) for s in samples],
        "n_runs": n_runs,
        "vs_baseline": round(toks / baseline, 4) if baseline else None,
        "quant": quant, "batch": batch, "steps": steps,
        "kv_dtype": kv_dtype, "baseline": baseline, "tp": tp,
        # The mesh the engine actually served on ((dp, pp, sp, tp);
        # null = single device) and the devices JAX reported.
        "mesh": list(mesh_shape) if mesh_shape else None,
        **device,
        "gguf_fmt": gguf_fmt,
        "activations": act_mode if act_applies else None,
        "layers": layers,
    }))


def _run(engine, sp, prompts_tokens, steps) -> int:
    from aphrodite_tpu.common.sequence import Sequence, SequenceGroup
    import time as _t
    for i, toks in enumerate(prompts_tokens):
        seq = Sequence(next(engine.seq_counter), None, list(toks),
                       engine.cache_config.block_size)
        group = SequenceGroup(f"bench-{i}-{_t.monotonic_ns()}", [seq], sp,
                              _t.monotonic())
        engine.scheduler.add_seq_group(group)
    total = 0
    while engine.has_unfinished_requests():
        outs = engine.step()
        for o in outs:
            if o.finished:
                total += sum(len(c.token_ids) for c in o.outputs)
    return total


if __name__ == "__main__":
    main()
