"""A/B harness for the expert layer alone, at a served geometry.

Times `FusedMoE.__call__` on its grouped (`ragged_dot`) path and splits
a call three ways by where its device operations lie on the trace's
timeline: *dispatch* (routing, the sort, the gather of the pairs' rows,
the group sizes: everything before the first grouped matmul), *the
three matmuls* (first `ragged-dot` to the end of the last, the gate's
activation between them included) and *combine* (everything after: the
rows back to their tokens, weighted and summed). The prompt chunk and
the decode step of `smallthinker-21ba3b-bf16.batch-8k` (PERF.md §5) are

    python benchmarks/moe_ab.py --tokens 2048 --check
    python benchmarks/moe_ab.py --tokens 24 --check

64 ReGLU experts 768 wide, 6 a token, hidden 2560, bfloat16, the
router's logits drawn from `--seed` at SmallThinker's spread and handed
to the layer as its caller hands them. `--check` compares the grouped
path with the dense all-experts combine (`_dense_ffn`) first. The whole
call is timed as `profile_step.device_bench` times a kernel (a loop on
the device, the slope between two trip counts); the split comes from a
profiler trace of `--reps` single calls, and every operation of a call
is listed with its microseconds.

It times the tree it is run in: to compare two commits, copy this file
into a `git archive` of the other and run both in one chip call. It is
no code a benchmark cell runs. On the CPU it checks and times nothing.
"""
from __future__ import annotations

import argparse
import os
import sys
import tempfile

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks.profile_step import device_bench  # noqa: E402
from perf.trace import (OPS_LINE, find_xplane, short_name,  # noqa: E402
                        union)

PARTS = ("dispatch", "three matmuls", "combine")


def split_calls(modules, ops):
    """`modules`: one `(start, end)` a call of the layer's program;
    `ops`: every device operation `(name, start, end)`. Returns, a
    call, the busy nanoseconds of each part (nested events counted
    once) and `{operation: nanoseconds}`."""
    out = []
    for m0, m1 in modules:
        mine = [(n, s, e) for n, s, e in ops if s >= m0 and e <= m1]
        dots = [(s, e) for n, s, e in mine
                if short_name(n).startswith("ragged-dot-none")]
        if not dots:
            continue
        first, last = min(s for s, _ in dots), max(e for _, e in dots)
        edges = (m0, first, last, m1)
        busy = {part: sum(e - s for s, e in union(
                    [(max(s, lo), min(e, hi)) for _, s, e in mine
                     if e > lo and s < hi]))
                for part, lo, hi in zip(PARTS, edges, edges[1:])}
        by_op = {}
        for n, s, e in mine:
            part = PARTS[0] if e <= first else \
                PARTS[2] if s >= last else PARTS[1]
            key = (part, short_name(n))
            by_op[key] = by_op.get(key, 0.0) + (e - s)
        out.append((busy, by_op, m1 - m0))
    return out


def read_trace(trace_dir: str, program: str):
    """The layer's calls and the device's operations from the newest
    trace under `trace_dir`."""
    from jax.profiler import ProfileData
    modules, ops = [], []
    for plane in ProfileData.from_file(find_xplane(trace_dir)).planes:
        if not plane.name.startswith("/device:") or "CPU" in plane.name:
            continue
        for line in plane.lines:
            events = [(e.name, float(e.start_ns),
                       float(e.start_ns + e.duration_ns))
                      for e in line.events]
            if line.name == "XLA Modules":
                modules += [(s, e) for n, s, e in events if program in n]
            elif line.name == OPS_LINE:
                ops += events
        break                                   # one chip
    return sorted(modules), ops


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tokens", type=int, default=2048)
    ap.add_argument("--experts", type=int, default=64)
    ap.add_argument("--top-k", type=int, default=6)
    ap.add_argument("--hidden", type=int, default=2560)
    ap.add_argument("--width", type=int, default=768)
    ap.add_argument("--activation", default="relu")
    ap.add_argument("--spread", type=float, default=3.0,
                    help="standard deviation of the router's logits")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--reps", type=int, default=20,
                    help="single calls in the traced split")
    ap.add_argument("--check", action="store_true",
                    help="compare with the dense all-experts combine")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from aphrodite_tpu.modeling.layers.fused_moe import FusedMoE

    T, E, K, H, W = (args.tokens, args.experts, args.top_k, args.hidden,
                     args.width)
    on_chip = jax.default_backend() == "tpu"
    moe = FusedMoE(E, K, H, W, activation=args.activation,
                   own_router=False)
    keys = jax.random.split(jax.random.PRNGKey(args.seed), 5)

    def draw(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32) /
                np.sqrt(fan_in)).astype(jnp.bfloat16)
    params = {"w_gate": draw(keys[0], (E, H, W), H),
              "w_up": draw(keys[1], (E, H, W), H),
              "w_down": draw(keys[2], (E, W, H), W)}
    x = jax.random.normal(keys[3], (T, H), jnp.float32).astype(
        jnp.bfloat16)
    logits = jax.random.normal(keys[4], (T, E), jnp.float32) * args.spread
    top = np.asarray(jax.lax.top_k(logits, K)[1])
    sizes = np.bincount(top.reshape(-1), minlength=E)
    print(f"moe[{jax.devices()[0].device_kind}] tokens={T} experts={E} "
          f"top_k={K} hidden={H} width={W} {args.activation}: "
          f"{T * K} pairs, {int((sizes > 0).sum())} experts with a pair, "
          f"{sizes.min()}-{sizes.max()} rows an expert", flush=True)

    @jax.jit
    def layer(params, x, logits):
        counts = []
        out = moe(params, x, router_logits=logits, counts=counts)
        return out, counts[0]

    if args.check:
        @jax.jit
        def dense(params, x, logits):
            return moe._dense_ffn(params, x, *moe.route(logits))
        (got, (pairs, touched)), want = layer(params, x, logits), \
            dense(params, x, logits)
        got, want = np.asarray(got, np.float32), np.asarray(want,
                                                            np.float32)
        print(f"  check: max |grouped - dense| = "
              f"{np.abs(got - want).max():.4g} at values up to "
              f"{np.abs(want).max():.3g} (finite: "
              f"{bool(np.isfinite(got).all())}); counted {int(pairs)} "
              f"pairs and {int(touched)} experts, numpy "
              f"{T * K} and {int((sizes > 0).sum())}", flush=True)
    if not on_chip:
        print("  no chip: nothing timed", flush=True)
        return

    def step(c, i):
        # the routing depends on the carry, so that no part of a call
        # is hoisted out of the loop
        moved = logits + c[:, :E].astype(jnp.float32) * 1e-30
        out, _ = layer(params, c, moved)
        return c + out * jnp.bfloat16(1e-30)
    whole, _ = device_bench(step, x, slow=T * K >= 4096)
    print(f"  whole call: {whole * 1e6:.1f} us", flush=True)

    trace_dir = tempfile.mkdtemp(prefix="moe_ab_")
    jax.block_until_ready(layer(params, x, logits))
    with jax.profiler.trace(trace_dir):
        for _ in range(args.reps):
            out = layer(params, x, logits)
        jax.block_until_ready(out)
    calls = split_calls(*read_trace(trace_dir, "layer"))
    if not calls:
        raise SystemExit("the trace holds no call of the layer")
    n = len(calls)
    print(f"  traced {n} single calls, "
          f"{np.mean([c[2] for c in calls]) / 1e3:.1f} us a call on the "
          f"device's clock:", flush=True)
    for part in PARTS:
        print(f"    {part}: "
              f"{sum(c[0][part] for c in calls) / n / 1e3:.1f} us",
              flush=True)
    table = {}
    for _, by_op, _ in calls:
        for key, ns in by_op.items():
            table[key] = table.get(key, 0.0) + ns / n
    for part in PARTS:
        for (p, name), ns in sorted(table.items(), key=lambda kv: -kv[1]):
            if p == part and ns >= 500:
                print(f"      [{part}] {name}: {ns / 1e3:.1f} us",
                      flush=True)


if __name__ == "__main__":
    main()
