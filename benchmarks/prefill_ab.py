"""A/B harness for the blocked prompt attention alone, at a served
geometry.

Times `ops/attention.py::prefill_attention_blocked` as a step program
calls it (`modeling/layers/attention.py::PagedAttention._prefill`):
one row of `--queries` new tokens behind `--ctx` cached ones, against
`--keys` keys (the chunk's own, or the padded table gathered from the
pages), of which `ctx + queries` are valid. `--cells` runs the eight
calls of the two benchmark cells that take the function (PERF.md §5):

    python benchmarks/prefill_ab.py --cells --check

`phi-4-mini-flash-bf16.reason-2k`: `[1, 2048, 40, 128]` queries on
their own 2,048 keys of 10 KV heads, no window (the full layer and the
seven cross layers) and window 512 (eight layers);
`smallthinker-21ba3b-bf16.batch-8k`: `[1, 2048, 28, 128]` queries on
8,192 gathered keys of 4 KV heads behind 2,048, 4,096 and 6,144 cached
tokens (a full layer's chunks two to four), and under window 4,096 on
7,168 keys behind 2,048 (chunk two), on 7,168 behind 4,096 (chunk
three) and on 6,144 behind 4,096 (chunk four: a window group's table
lets go of the pages its window has passed, and its context counts
from the first page it keeps; the widths are those of the cell's
traces).
`--check` compares the call with `prefill_attention` on the same inputs
first. A call is timed as `profile_step.device_bench` times a kernel (a
loop on the device, the slope between two trip counts), and printed
beside it are the tiles the function visits of the padded rectangle's,
by its own rule (`count_prefill_tiles`) where the tree has one.

It times the tree it is run in: to compare two commits, copy this file
into a `git archive` of the other and run both in one chip call (a
tree before PR 39 scans every key block for all queries and has no
rule to count by). It is no code a benchmark cell runs. On the CPU it
checks and times nothing: `--queries 32 --keys 64 --ctx 16 --block 8
--check` is a rehearsal.
"""
from __future__ import annotations

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks.profile_step import device_bench  # noqa: E402

#: (name, queries, keys, ctx, window, heads, KV heads, scale)
CELLS = (
    ("phi full/cross", 2048, 2048, 0, 0, 40, 10, 0.125),
    ("phi window 512", 2048, 2048, 0, 512, 40, 10, 0.125),
    ("smallthinker full, chunk 2", 2048, 8192, 2048, 0, 28, 4, 0.0884),
    ("smallthinker full, chunk 3", 2048, 8192, 4096, 0, 28, 4, 0.0884),
    ("smallthinker full, chunk 4", 2048, 8192, 6144, 0, 28, 4, 0.0884),
    ("smallthinker window, chunk 2", 2048, 7168, 2048, 4096, 28, 4,
     0.0884),
    ("smallthinker window, chunk 3", 2048, 7168, 4096, 4096, 28, 4,
     0.0884),
    ("smallthinker window, chunk 4", 2048, 6144, 4096, 4096, 28, 4,
     0.0884),
)


def run_one(name, queries, keys, ctx, window, heads, kv_heads, scale,
            args) -> None:
    import jax
    import jax.numpy as jnp
    from aphrodite_tpu.ops import attention as att
    window = window or None
    key = jax.random.PRNGKey(args.seed)
    dtype = jnp.bfloat16
    q = jax.random.normal(key, (1, queries, heads, args.head_dim), dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1),
                          (1, keys, kv_heads, args.head_dim), dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), k.shape, dtype)
    valid = min(ctx + queries, keys)
    block = dict(key_block=args.block) if args.block else {}
    count = getattr(att, "count_prefill_tiles", None)
    tiles = "no rule in this tree" if count is None else \
        "{} of {} tiles".format(*count([ctx], [valid], queries, keys,
                                       window, **block))
    ctx_lens = jnp.full((1,), ctx, jnp.int32)
    valid = jnp.full((1,), valid, jnp.int32)
    print(f"prefill_attn[{name}] q={queries} heads={heads}/{kv_heads}x"
          f"{args.head_dim} keys={keys} ctx={ctx} window={window}: "
          f"{tiles}", flush=True)

    def attend(qq):
        return att.prefill_attention_blocked(
            qq, k, v, ctx_lens, valid, scale, sliding_window=window,
            **block)

    if args.check:
        got = jax.jit(attend)(q).astype(jnp.float32)
        want = jax.jit(lambda qq: att.prefill_attention(
            qq, k, v, ctx_lens, valid, scale,
            sliding_window=window))(q).astype(jnp.float32)
        err = float(jnp.max(jnp.abs(got - want)))
        print(f"  check: max |blocked - plain| = {err:.4g} "
              f"(finite: {bool(jnp.isfinite(got).all())})", flush=True)
    if jax.default_backend() != "tpu":
        return
    # (an output is no query for the next call: the dependency alone)
    s, _ = device_bench(
        lambda qq, i: qq + attend(qq) * jnp.bfloat16(1e-30), q, slow=True)
    print(f"  whole call: {s * 1e3:.3f} ms", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cells", action="store_true",
                    help="the calls of the two benchmark cells that "
                         "take the function, in place of one geometry")
    ap.add_argument("--queries", type=int, default=2048)
    ap.add_argument("--keys", type=int, default=2048)
    ap.add_argument("--ctx", type=int, default=0,
                    help="cached tokens before the chunk, counted from "
                         "the first key")
    ap.add_argument("--window", type=int, default=0)
    ap.add_argument("--heads", type=int, default=40)
    ap.add_argument("--kv-heads", type=int, default=10)
    ap.add_argument("--head-dim", type=int, default=128)
    ap.add_argument("--scale", type=float, default=0.125)
    ap.add_argument("--block", type=int, default=0,
                    help="keys a block (default: the function's)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--check", action="store_true",
                    help="compare the output with prefill_attention")
    args = ap.parse_args()
    cells = CELLS if args.cells else (
        ("one geometry", args.queries, args.keys, args.ctx, args.window,
         args.heads, args.kv_heads, args.scale),)
    for cell in cells:
        run_one(*cell, args)


if __name__ == "__main__":
    main()
