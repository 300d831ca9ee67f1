"""A/B harness for the expert layer alone, at a served geometry.

Times `FusedMoE.__call__` on its grouped path and splits a call three
ways by where its device operations lie on the trace's timeline:
*dispatch* (routing, the sort, the layout's index arithmetic, the
gather of the pairs' rows: everything before the first grouped
matmul), *the grouped matmuls* (the first operation the program calls
the layer's own, `grouped_matmul.DEVICE_OP_PREFIXES`, to the end of
the last: the Pallas kernels of `ops/pallas/grouped_matmul.py` on the
chip, or
the three `ragged-dot` calls with the gate's activation between them
for a shape that keeps XLA's call) and *combine* (everything after:
the rows back to their tokens, weighted and summed). The prompt chunk
and the decode step of `smallthinker-21ba3b-bf16.batch-8k` and of
`laguna-s-2.1-bf16.agent-4k` (PERF.md §5) are

    python benchmarks/moe_ab.py --tokens 2048 --check
    python benchmarks/moe_ab.py --tokens 24 --check
    python benchmarks/moe_ab.py --geometry laguna --tokens 2048 --check
    python benchmarks/moe_ab.py --geometry laguna --tokens 64 --check

`--geometry smallthinker`: 64 ReGLU experts 768 wide, 6 a token,
hidden 2560; `laguna`: 128 SiLU experts 1,024 wide held of the 256
the router scores, 10 a token, hidden 3,072 (half of a token's pairs
meet a held expert); bfloat16, the router's logits drawn from `--seed`
at the spread the benchmark's weights give them and handed to the
layer as its caller hands them. `--check` compares the layer (on the
chip: the kernel's path) with the dense all-experts combine first.
The whole call is timed as `profile_step.device_bench` times a kernel
(a loop on the device, the slope between two trip counts); the split
comes from a profiler trace of `--reps` single calls, and every
operation of a call is listed with its microseconds.

`--arms` times the grouped matmuls ALONE, gate, up, the activation and
down over rows that are already sorted, in the forms that cost nothing
to write, beside the bytes' bound of the call (`perf/rooflines/
moe_experts.py`'s count): `ragged` (three `jax.lax.ragged_dot` over
every row, as the layer calls them where it keeps XLA's call),
`ragged-held` (over the rows of held pairs alone: the same where the
layer holds every expert), `gmm` (`jax.experimental.pallas.ops.tpu.
megablox.gmm` as installed, at its own tiles and at one whole-matrix
tile) and, under 1,024 pairs, `einsum` (a plain batched matmul over
`[experts, tokens, hidden]`: every expert at a capacity of every
token, which is exact and reads every held expert). They are ceilings
the kernel is held against, not paths of the program.

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

PARTS = ("dispatch", "grouped matmuls", "combine")

#: the expert layers the cells serve: `routed` experts scored, the
#: first `experts` of them held
GEOMETRIES = {
    "smallthinker": dict(experts=64, routed=64, top_k=6, hidden=2560,
                         width=768, activation="relu"),
    "laguna": dict(experts=128, routed=256, top_k=10, hidden=3072,
                   width=1024, activation="silu"),
}

#: a v5e's HBM, bytes a second (`perf/peaks.json`)
HBM_BYTES_S = 819e9


def layer_op_prefixes() -> tuple:
    """What a device trace calls the layer's grouped matmuls, by the
    start of a name, as the program says it; a tree from before the
    kernels had XLA's `ragged-dot` calls alone."""
    try:
        from aphrodite_tpu.ops.pallas.grouped_matmul import \
            DEVICE_OP_PREFIXES
    except ImportError:
        return ("ragged-dot",)
    return tuple(DEVICE_OP_PREFIXES)


def split_calls(modules, ops, prefixes):
    """`modules`: one `(start, end)` a call of the layer's program;
    `ops`: every device operation `(name, start, end)`; `prefixes`:
    what the layer's own operations are called. Returns, a call, the
    busy nanoseconds of each part (nested events counted once) and
    `{operation: nanoseconds}`."""
    out = []
    for m0, m1 in modules:
        mine = [(n, s, e) for n, s, e in ops if s >= m0 and e <= m1]
        dots = [(s, e) for n, s, e in mine
                if short_name(n).startswith(prefixes)]
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


def time_arms(moe, params, x, top_idx, sizes) -> None:
    """The grouped matmuls alone over sorted rows, in the forms that
    cost nothing to write (the module docstring's `--arms`)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    T, K = top_idx.shape
    E, H, W = moe.num_experts, moe.hidden_size, moe.intermediate_size
    held = int(sizes.sum())
    touched = int((sizes > 0).sum())
    bound = (touched * 3 * H * W + 2 * held * H) * 2 / HBM_BYTES_S
    print(f"  arms: {held} pairs meet {touched} held experts; the "
          f"bytes' bound of the three matmuls is {bound * 1e6:.1f} us",
          flush=True)
    group_sizes = jnp.asarray(sizes, jnp.int32)
    # a pair of an expert held elsewhere sorts behind every group
    order = np.argsort(top_idx.T.reshape(-1), kind="stable")
    rows_sorted = x[jnp.asarray(order % T)]
    act = moe.act

    def three(matmul, rows, weights):
        gate = matmul(rows, weights["w_gate"])
        up = matmul(rows, weights["w_up"])
        mid = (act(gate.astype(jnp.float32)) *
               up.astype(jnp.float32)).astype(rows.dtype)
        return matmul(mid, weights["w_down"])

    def ragged(rows, w):
        return jax.lax.ragged_dot(rows, w, group_sizes)

    def megablox(tiling):
        def matmul(rows, w):
            tm = tiling[0]
            pad = -rows.shape[0] % tm
            out = gmm(jnp.pad(rows, ((0, pad), (0, 0))), w, group_sizes,
                      preferred_element_type=rows.dtype,
                      tiling=(tm, min(tiling[1], w.shape[1]),
                              min(tiling[2], w.shape[2])))
            return out[:rows.shape[0]]
        return matmul

    def capacity(rows, w):
        return jnp.einsum("eth,ehi->eti", rows, w)

    tm = 128 if held >= 1024 else 16
    arms = [("ragged", ragged, rows_sorted),
            ("ragged-held", ragged,
             rows_sorted[:min(T * K, -(-held // 256) * 256)]),
            ("gmm 128x128x128", megablox((128, 128, 128)), rows_sorted),
            (f"gmm {tm} x whole", megablox((tm, 1 << 20, 1 << 20)),
             rows_sorted)]
    if T * K <= 1024:
        arms.append(("einsum", capacity,
                     jnp.broadcast_to(x, (E, T, H))))
    for name, matmul, rows in arms:
        # (the matrices ride in the carry: closed over, they would be
        # gigabytes of constants in the loop's program)
        def step(c, i, matmul=matmul):
            rows, weights = c
            out = three(matmul, rows, weights)
            return rows + out * jnp.asarray(1e-30, rows.dtype), weights
        try:
            took, _ = device_bench(step, (rows, params),
                                   slow=T * K >= 4096)
        except Exception as e:      # an arm the compiler refuses
            print(f"    {name}: refused ({type(e).__name__}: "
                  f"{str(e).splitlines()[0][:120]})", flush=True)
            continue
        print(f"    {name} over {rows.size // H} rows: {took * 1e6:.1f} us "
              f"({100 * bound / took:.1f}% of the bound)", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--geometry", choices=sorted(GEOMETRIES),
                    default="smallthinker")
    ap.add_argument("--tokens", type=int, default=2048)
    for name in ("experts", "routed", "top-k", "hidden", "width"):
        ap.add_argument("--" + name, type=int,
                        help="in the place of the geometry's")
    ap.add_argument("--activation", help="in the place of the geometry's")
    ap.add_argument("--spread", type=float, default=3.0,
                    help="standard deviation of the router's logits")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--reps", type=int, default=20,
                    help="single calls in the traced split")
    ap.add_argument("--check", action="store_true",
                    help="compare with the dense all-experts combine")
    ap.add_argument("--tile", type=int,
                    help="rows a tile of the kernels' layout, in the "
                         "place of `grouped_matmul.row_tile`'s (a sweep)")
    ap.add_argument("--arms", action="store_true",
                    help="time the grouped matmuls alone as XLA's "
                         "ragged_dot, megablox's gmm and a batched "
                         "einsum would do them")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from aphrodite_tpu.modeling.layers.fused_moe import FusedMoE

    if args.tile:
        from aphrodite_tpu.ops.pallas import grouped_matmul
        grouped_matmul.row_tile = lambda pairs, experts: args.tile
    geometry = dict(GEOMETRIES[args.geometry])
    for name in geometry:
        if getattr(args, name) is not None:
            geometry[name] = getattr(args, name)
    T = args.tokens
    E, R, K, H, W = (geometry[n] for n in ("experts", "routed", "top_k",
                                           "hidden", "width"))
    on_chip = jax.default_backend() == "tpu"
    moe = FusedMoE(E, K, H, W, activation=geometry["activation"],
                   own_router=False, routed_experts=R)
    keys = jax.random.split(jax.random.PRNGKey(args.seed), 5)

    def draw(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32) /
                np.sqrt(fan_in)).astype(jnp.bfloat16)
    params = {"w_gate": draw(keys[0], (E, H, W), H),
              "w_up": draw(keys[1], (E, H, W), H),
              "w_down": draw(keys[2], (E, W, H), W)}
    x = jax.random.normal(keys[3], (T, H), jnp.float32).astype(
        jnp.bfloat16)
    logits = jax.random.normal(keys[4], (T, R), jnp.float32) * args.spread
    top = np.asarray(jax.lax.top_k(logits, K)[1])
    top = np.where(top < E, top, E)     # held elsewhere: no group
    sizes = np.bincount(top.reshape(-1), minlength=E + 1)[:E]
    print(f"moe[{jax.devices()[0].device_kind}] tokens={T} experts={E} "
          f"of {R} top_k={K} hidden={H} width={W} "
          f"{geometry['activation']}: {T * K} pairs, {int(sizes.sum())} "
          f"of a held expert, {int((sizes > 0).sum())} experts with a "
          f"pair, {sizes.min()}-{sizes.max()} rows an expert; the "
          f"layer's operations are called {layer_op_prefixes()}",
          flush=True)

    @jax.jit
    def layer(params, x, logits):
        counts = []
        out = moe(params, x, router_logits=logits, counts=counts)
        return out, counts[0]

    if args.check:
        @jax.jit
        def dense(params, x, logits):
            # every held expert for every token, under the router's
            # weights of the pairs it holds (a pair held elsewhere
            # writes past the last column and is dropped)
            _, vals, idx = moe.route(logits)
            return moe._dense_ffn(params, x, jnp.zeros((T, E)), vals,
                                  jnp.where(idx < E, idx, E))
        (got, counted), want = layer(params, x, logits), \
            dense(params, x, logits)
        got, want = np.asarray(got, np.float32), np.asarray(want,
                                                            np.float32)
        print(f"  check: max |grouped - dense| = "
              f"{np.abs(got - want).max():.4g} at values up to "
              f"{np.abs(want).max():.3g} (finite: "
              f"{bool(np.isfinite(got).all())}); counted "
              f"{[int(c) for c in counted]} (pairs, experts with a "
              f"pair, pairs held, then what the tree adds), numpy "
              f"{T * K}, {int((sizes > 0).sum())}, {int(sizes.sum())}",
              flush=True)
    if not on_chip:
        print("  no chip: nothing timed", flush=True)
        return

    def step(c, i):
        # the routing depends on the carry, so that no part of a call
        # is hoisted out of the loop; the matrices ride in the carry
        # (closed over, they are gigabytes of constants to compile)
        rows, weights, logits = c
        moved = logits + rows[:, :R].astype(jnp.float32) * 1e-30
        out, _ = layer(weights, rows, moved)
        return rows + out * jnp.bfloat16(1e-30), weights, logits
    whole, _ = device_bench(step, (x, params, logits),
                            slow=T * K >= 4096)
    print(f"  whole call: {whole * 1e6:.1f} us", flush=True)

    trace_dir = tempfile.mkdtemp(prefix="moe_ab_")
    jax.block_until_ready(layer(params, x, logits))
    with jax.profiler.trace(trace_dir):
        for _ in range(args.reps):
            out = layer(params, x, logits)
        jax.block_until_ready(out)
    calls = split_calls(*read_trace(trace_dir, "layer"),
                        layer_op_prefixes())
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
    if args.arms:
        time_arms(moe, params, x, top, sizes)


if __name__ == "__main__":
    main()
