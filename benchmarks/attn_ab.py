"""A/B harness for the decode-attention kernel alone, at a served
geometry.

Times `paged_decode_attention` (ragged or classic grid, with or without
the fused KV write) on Mistral-7B heads. The default is bench.py's old
shape (batch 512, one context of 128, pages of 32). The benchmark
cell's decode step (`mistral-7b-w4a8.batch`, PERF.md §5) is

    python benchmarks/attn_ab.py --batch 48 --ctx 1024:1408 --page 16 \
        --pool 5077 --fused --ragged --runner-pad --arms --check

48 rows with contexts drawn uniformly from the range, each row's pages
taken in shuffled order from a pool of that many, the table as wide as
the runner's 8-page bucket makes it, the work list padded by the
runner's rule (`padded_work_length`). `--arms` splits a call three
ways: as it is, with a live item's arithmetic skipped (copies and waits
only) and with its page copies skipped (arithmetic on whatever the ring
holds); `--check` compares the whole call with the jnp reference first.
`--ppc` pins the item size in pages (default: the shared policy).
Variant knobs are env vars read by ops/pallas/paged_attention.py so the
same binary A/Bs kernel changes without code edits. It is no code a
benchmark cell runs.
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

HEADS, KV_HEADS, HEAD_DIM = 32, 8, 128
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
    ap.add_argument("--ppc", type=int, default=0,
                    help="pages a work item (default: the policy)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--fused", action="store_true")
    ap.add_argument("--ragged", action="store_true",
                    help="use the ragged work-list grid (also "
                         "gated by APHRODITE_ATTN_RAGGED)")
    ap.add_argument("--runner-pad", action="store_true",
                    help="pad the work list as the model runner does")
    ap.add_argument("--arms", action="store_true",
                    help="also time a call without its arithmetic and "
                         "without its page copies (ragged grid)")
    ap.add_argument("--check", action="store_true",
                    help="compare the output with the jnp reference")
    ap.add_argument("--interpret", action="store_true",
                    help="rehearse on the CPU: the check in interpret "
                         "mode, and nothing timed")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from aphrodite_tpu.ops.pallas import paged_attention as pa
    kind = jax.devices()[0].device_kind
    with open(os.path.join(ROOT, "perf", "peaks.json")) as f:
        peaks = json.load(f)["devices"].get(kind)

    B, PAGE = args.batch, args.page
    low, _, high = args.ctx.partition(":")
    rng = np.random.default_rng(args.seed)
    ctx = rng.integers(int(low), int(high or low) + 1, size=B)
    counts = -(-ctx // PAGE)
    width = -(-int(counts.max()) // TABLE_BUCKET) * TABLE_BUCKET \
        if args.ragged else int(counts.max())
    lane_bytes = pa.lane_bytes_of(KV_HEADS, HEAD_DIM, jnp.bfloat16)
    ppc = args.ppc or (pa.choose_pages_per_chunk(width, PAGE, lane_bytes)
                       if args.ragged else
                       next(d for d in (8, 4, 2, 1) if width % d == 0))
    work = None
    if args.ragged:
        items = int((-(-counts // ppc)).sum())
        work = pa.build_decode_work_list(
            counts, ppc, pad_to=pa.padded_work_length(
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
    tag += "/ragged" if args.ragged else "/classic"
    nw = "" if work is None else f" items={work[1].shape[0]}" \
        f"({int((work[1] >= 0).sum())} live)"
    print(f"decode_attn[{tag}] b={B} ctx={args.ctx} page={PAGE} "
          f"table={width} ppc={ppc}{nw} pool={num_pages}", flush=True)

    def attend(qq, kpp, vpp, ablate=None):
        return pa.paged_decode_attention(
            qq, kpp, vpp, tables, ctx_lens, None,
            kn if args.fused else None, kn if args.fused else None,
            scale=0.0884, pages_per_chunk=ppc, work_items=work,
            ablate=ablate, interpret=args.interpret)

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
            q3, want_k, want_v, tables, ctx_lens, 0.0884)
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
    state = (q3, kp, vp)
    for arm in [None] + (["compute", "copies"] if args.arms else []):
        if args.fused:
            def astep(c, i, arm=arm):
                qq, kpp, vpp = c
                o, kpp, vpp = attend(qq, kpp, vpp, arm)
                return (qq + o * jnp.bfloat16(1e-30), kpp, vpp)
            # an arm's output is no query for the next
            s, rtt, state = device_bench(
                astep, (q3 + 0,) + state[1:], donate=True)
        else:
            def astep(c, i, arm=arm):
                return c + attend(c, kp, vp, arm) * jnp.bfloat16(1e-30)
            s, rtt = device_bench(astep, q3)
        name = {None: "whole call", "compute": "no arithmetic",
                "copies": "no page copies"}[arm]
        print(f"  {name}: {s * 1e6:.1f} us/call = "
              f"{s * 32 * 1e3:.2f} ms/step(32L)  "
              f"{live_bytes / s / 1e9:.0f} GB/s of live KV  "
              f"({least / s * 100:.1f}% of the bytes roofline)",
              flush=True)


if __name__ == "__main__":
    main()
