"""Decode-attention kernel microbenchmark (reference
`tests/benchmarks/attention.py:93`): the Pallas kernel (the ragged
work-list grid) vs the XLA gather path across
batch/context shapes, timed inside one jitted lax.scan so per-dispatch
latency doesn't pollute the numbers.

Usage:
    python benchmarks/attention.py [--batch 256] [--ctx 1024]
    python benchmarks/attention.py --ctx-mix 128:0.6,512:0.3,2000:0.1

--ctx-mix assigns each sequence a context drawn (deterministically, by
cumulative weight) from the given ctx:weight list — the ragged serving
shape the work-list grid exists for. Every variant prints one JSON
line in bench.py's round-5 format: {"metric", "value", "samples",
"n_runs", ...} where value is the MEDIAN of n_runs timed runs, so
driver captures and self-measured numbers agree for attention too.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import statistics
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def parse_ctx_mix(spec: str, batch: int) -> np.ndarray:
    """"128:0.6,512:0.3,2000:0.1" -> per-sequence ctx array. Weights
    are normalized; counts are assigned largest-remainder so the batch
    is covered exactly; the mix is interleaved (not sorted) so padded
    table raggedness matches a real serving batch."""
    pairs = []
    for part in spec.split(","):
        ctx_s, _, w_s = part.partition(":")
        pairs.append((int(ctx_s), float(w_s) if w_s else 1.0))
    total_w = sum(w for _, w in pairs)
    counts = [int(batch * w / total_w) for _, w in pairs]
    while sum(counts) < batch:
        counts[int(np.argmax([w for _, w in pairs]))] += 1
    ctxs = np.zeros((batch,), dtype=np.int32)
    order = np.argsort([-w for _, w in pairs])
    i = 0
    for idx in order:
        ctxs[i:i + counts[idx]] = pairs[idx][0]
        i += counts[idx]
    rs = np.random.RandomState(1)
    return ctxs[rs.permutation(batch)]


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--batch", type=int, default=256)
    parser.add_argument("--ctx", type=int, default=1024)
    parser.add_argument("--ctx-mix", type=str, default="",
                        help="ctx:weight list, e.g. 128:0.6,512:0.3,"
                             "2000:0.1 (overrides --ctx)")
    parser.add_argument("--heads", type=int, default=32)
    parser.add_argument("--kv-heads", type=int, default=8)
    parser.add_argument("--head-dim", type=int, default=128)
    parser.add_argument("--page-size", type=int, default=16)
    parser.add_argument("--iters", type=int, default=16)
    parser.add_argument("--runs", type=int, default=3)
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp

    from aphrodite_tpu.ops.attention import paged_decode_attention_ref
    from aphrodite_tpu.ops.pallas.paged_attention import (
        build_decode_work_list, choose_pages_per_chunk, lane_bytes_of,
        paged_decode_attention)

    B, page = args.batch, args.page_size
    Hq, Hkv, d = args.heads, args.kv_heads, args.head_dim
    if args.ctx_mix:
        ctxs = parse_ctx_mix(args.ctx_mix, B)
    else:
        ctxs = np.full((B,), args.ctx, dtype=np.int32)
    pages_i = [-(-int(c) // page) for c in ctxs]
    # Padded table width (the batch max, bucketed by 8 pages — the
    # model runner's discipline); per-row REAL pages stay ragged.
    pps = -(-max(pages_i) // 8) * 8
    num_pages = max(sum(pages_i) + 1, 1024)
    rs = np.random.RandomState(0)
    dtype = jnp.bfloat16 if jax.default_backend() == "tpu" \
        else jnp.float32
    q = jnp.asarray(rs.randn(B, Hq, d) * 0.05, dtype)
    # Token-major pages: [num_pages, page_size, Hkv * d].
    kp = jnp.asarray(rs.randn(num_pages, page, Hkv * d) * 0.05, dtype)
    vp = jnp.asarray(rs.randn(num_pages, page, Hkv * d) * 0.05, dtype)
    # Sequence-exclusive pages; table padding beyond a row's real
    # pages stays 0 (the padded-entry convention the kernels mask).
    bt_np = np.zeros((B, pps), dtype=np.int32)
    perm = rs.permutation(num_pages - 1) + 1
    off = 0
    for b in range(B):
        bt_np[b, :pages_i[b]] = perm[off:off + pages_i[b]]
        off += pages_i[b]
    bt = jnp.asarray(bt_np)
    cl = jnp.asarray(ctxs)
    scale = d ** -0.5
    kv_gb = float(ctxs.sum()) * 2 * Hkv * d * kp.dtype.itemsize / 1e9
    ppc = choose_pages_per_chunk(
        pps, page, lane_bytes_of(Hkv, d, kp.dtype))
    work = build_decode_work_list(pages_i, ppc)

    variants = {
        "xla_gather": lambda c: paged_decode_attention_ref(
            c, kp, vp, bt, cl, scale),
    }
    if jax.default_backend() == "tpu" and d % 128 == 0:
        variants["pallas_ragged"] = lambda c: paged_decode_attention(
            c, kp, vp, bt, cl, scale=scale, pages_per_chunk=ppc,
            work_items=work)

    for name, fn in variants.items():
        @jax.jit
        def many(c):
            def body(x, _):
                return x * 0.999 + 1e-6 * fn(x), ()
            return jax.lax.scan(body, c, None, length=args.iters)[0]

        out = many(q)
        _ = float(jnp.sum(out))                 # force + warm
        samples = []
        for _r in range(max(1, args.runs)):
            gc.collect()
            gc.disable()
            try:
                t0 = time.perf_counter()
                _ = float(jnp.sum(many(q)))
                samples.append((time.perf_counter() - t0) / args.iters)
            finally:
                gc.enable()
        dt = statistics.median(samples)
        print(json.dumps({
            "metric": f"decode_attention_{name}",
            "value": round(dt * 1e3, 3),
            "samples": [round(s * 1e3, 3) for s in samples],
            "n_runs": len(samples),
            "unit": "ms/layer",
            "detail": {"batch": B,
                       "ctx": args.ctx_mix if args.ctx_mix
                       else args.ctx,
                       "pages_per_chunk": ppc,
                       "work_items": int(work[1].shape[0]),
                       "kv_gb_per_call": round(kv_gb, 3),
                       "eff_gb_s": round(kv_gb / dt, 1)},
        }))


if __name__ == "__main__":
    main()
