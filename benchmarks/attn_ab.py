"""A/B harness for the decode-attention kernel alone, at a served
geometry.

Times `paged_decode_attention` (the rows' own work list or the call's
dense one, with or without the fused KV write). The geometry is arguments; the defaults are
Mistral-7B's heads at bench.py's old shape (batch 512, one context of
128, pages of 32). A benchmark cell's decode call (PERF.md §5):

    python benchmarks/attn_ab.py --batch 48 --ctx 1024:1408 --page 16 \
        --pool 5077 --fused --ragged --runner-pad --arms --check
    python benchmarks/attn_ab.py --batch 48 --ctx 2049:3072 --page 16 \
        --heads 40 --kv-heads 10 --scale 0.125 --table 192 \
        --pool 20000 --fused --ragged --runner-pad --arms --check
    python benchmarks/attn_ab.py ... --window 512 --table 40

(`mistral-7b-w4a8.batch`; `phi-4-mini-flash-bf16.reason-2k`'s full
layer, and a window layer of it; `--check` holds a second copy of the
pool, so the cell's 87,252 pages do not fit beside it.) Rows with
contexts drawn uniformly from the range, each row's pages taken in
shuffled order from a pool of that many, the table as wide as the
runner's 8-page bucket makes it (or `--table`), the work list padded by
the runner's rule (`ModelRunner._work_length`). Under `--window` a
row's table starts at the page that holds its window's oldest key and
its context counts from there, as the block manager's window group has
it. `--arms` splits a call three ways: as it is, with a live item's
arithmetic skipped (copies and waits only) and with its page copies
skipped (arithmetic on whatever the ring holds); `--check` compares the
whole call with the jnp reference first. `--ppc` pins the item size in
pages and `--hb` the KV heads a grid cell holds (defaults: the shared
policy). Variant knobs are env vars read by
ops/pallas/paged_attention.py so the same binary A/Bs kernel changes
without code edits. It is no code a benchmark cell runs.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks.profile_step import device_bench  # noqa: E402

TABLE_BUCKET = 8            # executor/model_runner.py::_PAGES_BUCKET


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=512)
    ap.add_argument("--ctx", default="128",
                    help="one context length, or LOW:HIGH for rows "
                         "drawn uniformly from the range")
    ap.add_argument("--page", type=int, default=32)
    ap.add_argument("--pool", type=int, default=0,
                    help="pages in the pool (default: what the rows "
                         "need, plus one)")
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--kv-heads", type=int, default=8)
    ap.add_argument("--head-dim", type=int, default=128)
    ap.add_argument("--scale", type=float, default=0.0884)
    ap.add_argument("--window", type=int, default=0,
                    help="a causal window of this many keys: a row's "
                         "table starts at the window's first page")
    ap.add_argument("--table", type=int, default=0,
                    help="table width in pages (default: the widest "
                         "row, in the runner's 8-page buckets)")
    ap.add_argument("--ppc", type=int, default=0,
                    help="pages a work item (default: the policy)")
    ap.add_argument("--hb", type=int, default=0,
                    help="KV heads a grid cell (default: the policy)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--fused", action="store_true")
    ap.add_argument("--ragged", action="store_true",
                    help="hand the kernel the rows' own work list "
                         "(without it the call builds the dense list "
                         "of the table width)")
    ap.add_argument("--runner-pad", action="store_true",
                    help="pad the work list as the model runner does")
    ap.add_argument("--arms", action="store_true",
                    help="also time a call without its arithmetic and "
                         "without its page copies")
    ap.add_argument("--check", action="store_true",
                    help="compare the output with the jnp reference")
    ap.add_argument("--interpret", action="store_true",
                    help="rehearse on the CPU: the check in interpret "
                         "mode, and nothing timed")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from aphrodite_tpu.executor.model_runner import ModelRunner
    from aphrodite_tpu.ops.pallas import paged_attention as pa
    kind = jax.devices()[0].device_kind
    with open(os.path.join(ROOT, "perf", "peaks.json")) as f:
        peaks = json.load(f)["devices"].get(kind)

    B, PAGE = args.batch, args.page
    HEADS, KV_HEADS, HEAD_DIM = args.heads, args.kv_heads, args.head_dim
    window = args.window or None
    low, _, high = args.ctx.partition(":")
    rng = np.random.default_rng(args.seed)
    ctx = rng.integers(int(low), int(high or low) + 1, size=B)
    if window:      # pages wholly before the window are let go of
        ctx = ctx - np.maximum(0, ctx - window) // PAGE * PAGE
    counts = -(-ctx // PAGE)
    width = args.table or (
        -(-int(counts.max()) // TABLE_BUCKET) * TABLE_BUCKET
        if args.ragged else int(counts.max()))
    if width < counts.max():
        raise SystemExit(f"--table {width} is narrower than a row's "
                         f"{int(counts.max())} pages")
    hb = args.hb or pa.head_block(KV_HEADS, HEAD_DIM, jnp.bfloat16)
    ppc = args.ppc or pa.choose_pages_per_chunk(width, PAGE,
                                                hb * HEAD_DIM * 2)
    work = None
    if args.ragged:
        items = int((-(-counts // ppc)).sum())
        work = pa.build_decode_work_list(
            counts, ppc, pad_to=ModelRunner._work_length(
                items, B, width, ppc) if args.runner_pad else None)
    num_pages = args.pool or int(counts.sum()) + 1
    key = jax.random.PRNGKey(args.seed)
    kp = jax.random.normal(
        key, (num_pages, PAGE, KV_HEADS * HEAD_DIM), dtype=jnp.bfloat16)
    vp = jax.random.normal(
        jax.random.fold_in(key, 1), kp.shape, dtype=jnp.bfloat16)
    # Sequence-exclusive pages (the engine's decode contract), in
    # shuffled order; pad entries clamp to a valid page as the layer
    # clamps them.
    perm = rng.permutation(num_pages - 1) + 1
    table = np.full((B, width), num_pages - 1, dtype=np.int32)
    taken = 0
    for i, n in enumerate(counts):
        table[i, :n] = perm[taken:taken + n]
        taken += n
    tables = jnp.asarray(table)
    ctx_lens = jnp.asarray(ctx, dtype=jnp.int32)
    q3 = jax.random.normal(key, (B, HEADS, HEAD_DIM), dtype=jnp.bfloat16)
    kn = jax.random.normal(jax.random.fold_in(key, 2),
                           (B, KV_HEADS, HEAD_DIM), dtype=jnp.bfloat16)
    live_bytes = 2 * int(counts.sum()) * PAGE * KV_HEADS * HEAD_DIM * 2
    tag = "fused" if args.fused else "read-only"
    tag += "/ragged" if args.ragged else "/dense list"
    nw = "" if work is None else f" items={work[1].shape[0]}" \
        f"({int((work[1] >= 0).sum())} live)"
    print(f"decode_attn[{tag}] b={B} heads={HEADS}/{KV_HEADS}x{HEAD_DIM} "
          f"hb={hb} ctx={args.ctx} window={window} page={PAGE} "
          f"table={width} ppc={ppc}{nw} pool={num_pages} "
          f"live={live_bytes / 1e6:.1f} MB", flush=True)

    def attend(qq, kpp, vpp, ablate=None):
        return pa.paged_decode_attention(
            qq, kpp, vpp, tables, ctx_lens, None,
            kn if args.fused else None, kn if args.fused else None,
            scale=args.scale, pages_per_chunk=ppc, work_items=work,
            ablate=ablate, hb=hb, interpret=args.interpret,
            window=window)

    if args.check:
        from aphrodite_tpu.ops.attention import (
            paged_decode_attention_ref)
        from aphrodite_tpu.ops.kv_cache import write_to_kv_cache
        want_k, want_v = kp, vp
        if args.fused:
            pos = ctx - 1
            slots = table[np.arange(B), pos // PAGE] * PAGE + pos % PAGE
            want_k, want_v = write_to_kv_cache(
                kn, kn, kp, vp, jnp.asarray(slots, jnp.int32))
        want = paged_decode_attention_ref(
            q3, want_k, want_v, tables, ctx_lens, args.scale,
            window=window)
        got = attend(q3, kp, vp)
        if args.fused:
            got, got_k, got_v = got
            same = bool(jnp.array_equal(got_k, want_k) &
                        jnp.array_equal(got_v, want_v))
            print(f"  check: pages written as the slot writer writes "
                  f"them: {same}", flush=True)
        err = float(jnp.max(jnp.abs(got.astype(jnp.float32) -
                                    want.astype(jnp.float32))))
        print(f"  check: max |kernel - reference| = {err:.4g} "
              f"(finite: {bool(jnp.isfinite(got).all())})", flush=True)

    if args.interpret:
        return
    if peaks is None:
        raise SystemExit(f"perf/peaks.json has no peaks of {kind!r}")
    least = live_bytes / peaks["hbm_bytes_per_s"]
    # The pages ride in the loop's carry, donated, in both modes: as
    # constants of the jitted loop a pool of gigabytes is compiled in.
    state = (q3, kp, vp)
    for arm in [None] + (["compute", "copies"] if args.arms else []):
        def astep(c, i, arm=arm):
            qq, kpp, vpp = c
            o = attend(qq, kpp, vpp, arm)
            if args.fused:
                o, kpp, vpp = o
            return (qq + o * jnp.bfloat16(1e-30), kpp, vpp)
        # an arm's output is no query for the next
        s, rtt, state = device_bench(
            astep, (q3 + 0,) + state[1:], donate=True)
        name = {None: "whole call", "compute": "no arithmetic",
                "copies": "no page copies"}[arm]
        print(f"  {name}: {s * 1e6:.1f} us/call = "
              f"{live_bytes / s / 1e9:.0f} GB/s of live KV  "
              f"({least / s * 100:.1f}% of the bytes roofline)",
              flush=True)


if __name__ == "__main__":
    main()
