"""The host's round at a wide decode batch, on the CPU: a toy Jamba
behind `AsyncAphrodite`, `--rows` callers that do what the OpenAI
server's unstreamed handler does (or, with `--stream`, take an output a
token), prompts and outputs at the lengths of
`jamba2-3b-bf16.reason-512`, the benchmark's tokenizer. Prints the
span accumulators of `common/tracing.py` in ms a round over `--seconds`
of steady decode: COUNTS of host work, never device numbers (the
"device" here is the CPU's XLA, held to one thread so that it does not
fight the step thread for cores; PERF.md §6, PR 41, has a reading and
what it predicted on the chip).

    python benchmarks/host_round.py [--rows 128] [--stream] [--profile]
"""
import argparse
import asyncio
import cProfile
import os
import pstats
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault(
    "XLA_FLAGS",
    "--xla_cpu_multi_thread_eigen=false intra_op_parallelism_threads=1")
os.environ["APHRODITE_SPEC"] = "0"
os.environ["APHRODITE_MAX_WAITING_TOKENS"] = "1000000"
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from perf import server as srv  # noqa: E402

STAGES = ("async.between_steps", "sched.schedule", "runner.prepare",
          "runner.dispatch", "runner.device_wait", "sampler.finalize",
          "engine.process")


def toy_config(vocab: int) -> dict:
    """One Mamba layer and one attention layer of one KV head: the
    host's work a row does not depend on the widths."""
    return dict(
        architectures=["JambaForCausalLM"], model_type="jamba",
        vocab_size=vocab, hidden_size=16, intermediate_size=32,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=1,
        max_position_embeddings=262144, rms_norm_eps=1e-6,
        sliding_window=None, attn_layer_period=2, attn_layer_offset=1,
        expert_layer_period=2, expert_layer_offset=1, num_experts=1,
        num_experts_per_tok=1, mamba_d_state=16, mamba_d_conv=4,
        mamba_expand=2, mamba_dt_rank=4, mamba_conv_bias=True,
        mamba_proj_bias=False, tie_word_embeddings=True,
        hidden_act="silu", torch_dtype="float32")


async def main(args) -> None:
    from aphrodite_tpu.common.sampling_params import SamplingParams
    from aphrodite_tpu.engine.args_tools import AsyncEngineArgs
    from aphrodite_tpu.engine.async_aphrodite import AsyncAphrodite
    from aphrodite_tpu.executor import executor
    # The CPU backend's pool (256 MiB) holds 128 rows of 1,536 tokens
    # of this toy; more rows, or longer ones, never all run, and the
    # wait below for a full batch would not end.
    executor._CPU_CACHE_BYTES = max(executor._CPU_CACHE_BYTES, int(
        1.5 * 2 ** 28 * args.rows * (args.prompt + 1024) / (128 * 1536)))
    model_dir = tempfile.mkdtemp()
    srv.write_model_dir(model_dir, toy_config(args.vocab))
    engine = AsyncAphrodite.from_engine_args(AsyncEngineArgs(
        model=model_dir, load_format="dummy", dtype="float32",
        max_model_len=args.prompt + 1024, max_num_seqs=args.rows,
        swap_space=0.01, disable_log_stats=False,
        disable_log_requests=True, seed=1))
    tracer, scheduler = engine.engine.tracer, engine.engine.scheduler
    rng = np.random.default_rng(0)

    async def caller(name: str, tokens: int) -> None:
        params = SamplingParams(temperature=0.0, max_tokens=tokens,
                                ignore_eos=True)
        ids = rng.integers(3, args.vocab - 8, args.prompt).tolist()
        final = None
        async for out in await engine.add_request(
                name, None, params, prompt_token_ids=ids,
                final_only=not args.stream):
            final = out
        assert len(final.outputs[0].token_ids) == tokens

    await asyncio.gather(*[caller(f"warm-{i}", 8)          # the programs
                           for i in range(args.rows)])
    callers = [asyncio.ensure_future(caller(f"r-{i}", 1000))
               for i in range(args.rows)]
    while len(scheduler.running) < args.rows or scheduler.prefilling:
        await asyncio.sleep(0.05)
    profile, step = cProfile.Profile(), engine.engine.step
    if args.profile:
        engine.engine.step = lambda: profile.runcall(step)
    seconds, counts = dict(tracer.seconds), dict(tracer.counts)
    began = time.perf_counter()
    await asyncio.sleep(args.seconds)
    rounds = tracer.counts["engine.step"] - counts["engine.step"]
    print(f"{len(scheduler.running)} rows, {rounds} rounds, "
          f"{1e3 * (time.perf_counter() - began) / rounds:.2f} ms a round")
    for name in STAGES:
        print(f"  {name:20s} "
              f"{1e3 * (tracer.seconds[name] - seconds[name]) / rounds:7.3f}")
    engine.engine.step = step
    for task in callers:
        task.cancel()
    if args.profile:
        pstats.Stats(profile).sort_stats("tottime").print_stats(40)


if __name__ == "__main__":
    parser = argparse.ArgumentParser()
    parser.add_argument("--rows", type=int, default=128)
    parser.add_argument("--prompt", type=int, default=512)
    parser.add_argument("--vocab", type=int, default=1024)
    parser.add_argument("--seconds", type=float, default=8.0)
    parser.add_argument("--stream", action="store_true")
    parser.add_argument("--profile", action="store_true")
    asyncio.run(main(parser.parse_args()))
