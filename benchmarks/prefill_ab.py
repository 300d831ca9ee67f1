"""A/B harness for the prompt's attention alone, at a served geometry.

Times what a step program calls for its prompt rows
(`modeling/layers/attention.py::PagedAttention._prefill`): `--rows`
rows of `--queries` new tokens behind `--ctx` cached ones, against
`--keys` keys (the chunk's own, or the padded table gathered from the
pages), of which `ctx + queries` are valid. Two sides: the `jnp`
function such a step takes off the kernel's path
(`ops/attention.py::prefill_attention_blocked` from `--blocked-from`
queries x keys a row on, `prefill_attention` under it), and with
`--kernel` the Pallas flash kernel beside it
(`ops/pallas/prefill_attention.py`, its blocks `choose_blocks`' or
pinned by `--blocks`, several at once to compare). `--cells`
runs the calls of the benchmark's cells (PERF.md §5):

    python benchmarks/prefill_ab.py --cells --check --kernel

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
traces);
`laguna-s-2.1-bf16.agent-4k`: `[1, 2048, 48, 128]` on 8 KV heads over
their own 2,048 keys and over 4,096 gathered behind 2,048 (the two
full layers' two chunks), `[1, 2048, 72, 128]` under window 512 over
their own 2,048 and over 3,072 gathered behind 512 (the three window
layers: the table holds the window, the chunk and a page, 160-161
pages, padded to 192);
`mistral-7b-w4a8.batch`: `[rows, 1024, 32, 128]` on their own 1,024
keys of 8 KV heads at 1, 2 and 4 rows, no window (the plain function
on the `jnp` side);
`sarvam-105b-bf16.doc-8k` (PR 53): `[1, 8192, 64, 256]` queries on
their own 8,192 keys, one KV head a query head, keys 256 lanes a head
of which 192 are live and values 128, all live (multi-head latent
attention's up-projected rows, `modeling/layers/mla.py`), and a chunk
of 2,048 on a gathered table of 9,216 behind 2,048, 4,096 and 6,144
cached tokens; `--only sarvam --v-dim 256` is the same calls as the
tree made them before PR 53, values zero-padded to the keys' width.
`--head-dim` is q's and k's head width and `--v-dim` the values'
(default: the same); a cell that states its own keeps them unless the
flag is given. Beside a side's milliseconds stand the call's LIVE
multiply-adds (the pairs of a query and a key the mask leaves, times
the heads, times the lanes a pair that are no padding: the head width
and the values' unless the cell states fewer, Sarvam's 192 + 128 =
320) and the share of `perf/peaks.json`'s bf16 peak that
twice as many operations in that time are.
`--check` compares each side with `prefill_attention` on the same
inputs first, and `--oracle` both with float64 numpy besides (a head at
a time; a minute at the widest call). A call is timed as
`profile_step.device_bench` times a kernel (a loop on the device, the
slope between two trip counts), and printed beside it are the tiles
the 512-rule visits of the padded rectangle's (`count_prefill_tiles`)
where the tree has one. A side whose values are narrower than its
keys is checked against `prefill_attention` on zero-padded values,
sliced (the `jnp` functions have one head width).

It times the tree it is run in: to compare two commits, copy this file
into a `git archive` of the other and run both in one chip call (a
tree before PR 44 has no kernel and says so; one before PR 39 scans
every key block for all queries and has no rule to count by). It is no
code a benchmark cell runs. On the CPU it checks (the kernel in
interpret mode) and times nothing: `--queries 32 --keys 64 --ctx 16
--block 8 --head-dim 32 --v-dim 16 --check --kernel` is a rehearsal.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks.profile_step import device_bench  # noqa: E402

#: (name, queries, keys, ctx, window, heads, KV heads, scale[, rows[,
#: head width, values' width, live lanes a query-key pair]])
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
    ("laguna full, chunk 1", 2048, 2048, 0, 0, 48, 8, 0.0884),
    ("laguna full, chunk 2", 2048, 4096, 2048, 0, 48, 8, 0.0884),
    ("laguna window 512, chunk 1", 2048, 2048, 0, 512, 72, 8, 0.0884),
    ("laguna window 512, chunk 2", 2048, 3072, 512, 512, 72, 8, 0.0884),
    ("mistral, 1 row", 1024, 1024, 0, 0, 32, 8, 0.0884, 1),
    ("mistral, 2 rows", 1024, 1024, 0, 0, 32, 8, 0.0884, 2),
    ("mistral, 4 rows", 1024, 1024, 0, 0, 32, 8, 0.0884, 4),
    # (scale: 192^-0.5 times the yarn factor's square, 1.3689^2)
    ("sarvam whole prompt", 8192, 8192, 0, 0, 64, 64, 0.13524, 1,
     256, 128, 320),
    ("sarvam chunk 2", 2048, 9216, 2048, 0, 64, 64, 0.13524, 1,
     256, 128, 320),
    ("sarvam chunk 3", 2048, 9216, 4096, 0, 64, 64, 0.13524, 1,
     256, 128, 320),
    ("sarvam chunk 4", 2048, 9216, 6144, 0, 64, 64, 0.13524, 1,
     256, 128, 320),
)


def _oracle(q, k, v, ctx, valid, scale, window):
    """`prefill_attention` in float64 numpy, a head at a time."""
    import numpy as np
    q, k, v = (np.asarray(x.astype("float32"), np.float64)
               for x in (q, k, v))
    b, s, heads, _ = q.shape
    group = heads // k.shape[2]
    q_pos = ctx + np.arange(s)[:, None]
    k_pos = np.arange(k.shape[1])[None, :]
    live = (k_pos <= q_pos) & (k_pos < valid)
    if window:
        live &= k_pos > q_pos - window
    out = np.zeros(q.shape[:3] + v.shape[3:])
    for row in range(b):
        for h in range(heads):
            scores = np.where(
                live, q[row, :, h] @ k[row, :, h // group].T * scale,
                -np.inf)
            top = scores.max(axis=1, keepdims=True)
            p = np.exp(scores - np.where(np.isfinite(top), top, 0.0))
            total = p.sum(axis=1, keepdims=True)
            out[row, :, h] = p @ v[row, :, h // group] / \
                np.where(total == 0.0, 1.0, total)
    return out


def live_pairs(queries, ctx, valid, window) -> int:
    """Pairs of a query and a key that the mask leaves, a row and a
    head: query `i` sits at `ctx + i` and sees the keys up to itself
    that are valid and inside its window."""
    import numpy as np
    pos = ctx + np.arange(queries, dtype=np.int64)
    seen = np.minimum(pos + 1, valid)
    if window:
        seen = seen - np.maximum(pos + 1 - window, 0)
    return int(np.maximum(seen, 0).sum())


def run_one(name, queries, keys, ctx, window, heads, kv_heads, scale,
            rows, head_dim, v_dim, live, args) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from aphrodite_tpu.ops import attention as att
    window = window or None
    key = jax.random.PRNGKey(args.seed)
    dtype = jnp.bfloat16
    head_dim = args.head_dim or head_dim
    v_dim = args.v_dim or v_dim or head_dim
    live = min(live or head_dim + v_dim, head_dim + v_dim)
    if v_dim > head_dim:
        raise SystemExit("the jnp side pads the values to the keys' "
                         f"width: --v-dim {v_dim} > --head-dim {head_dim}")
    q = jax.random.normal(key, (rows, queries, heads, head_dim), dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1),
                          (rows, keys, kv_heads, head_dim), dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2),
                          (rows, keys, kv_heads, v_dim), dtype)
    # (the `jnp` functions have one head width: theirs is the values'
    # zero-padded to the keys', and the result sliced)
    v_wide = jnp.pad(v, ((0, 0),) * 3 + ((0, head_dim - v_dim),))
    valid = min(ctx + queries, keys)
    macs = rows * heads * live * live_pairs(queries, ctx, valid, window)
    block = dict(key_block=args.block) if args.block else {}
    count = getattr(att, "count_prefill_tiles", None)
    tiles = "no rule in this tree" if count is None else \
        "{} of {} tiles".format(*count([ctx], [valid], queries, keys,
                                       window, **block))
    ctx_lens = jnp.full((rows,), ctx, jnp.int32)
    valid_lens = jnp.full((rows,), valid, jnp.int32)
    blocked = queries * keys >= args.blocked_from
    print(f"prefill_attn[{name}] q={rows}x{queries} heads={heads}/"
          f"{kv_heads}x{head_dim} values x{v_dim} keys={keys} ctx={ctx} "
          f"window={window}: {tiles}; {macs / 1e9:.4g} G live "
          f"multiply-adds ({live} lanes a pair)", flush=True)

    # (K and V are arguments of a side, not constants of its program:
    # at Sarvam's sizes a program that holds them compiles for minutes)
    def plain(qq, kk, vv):
        return att.prefill_attention(
            qq, kk, vv, ctx_lens, valid_lens, scale,
            sliding_window=window)[..., :v_dim]

    def walk(qq, kk, vv):
        return att.prefill_attention_blocked(
            qq, kk, vv, ctx_lens, valid_lens, scale,
            sliding_window=window, **block)[..., :v_dim]

    sides = [] if args.kernel == "only" else [
        ("blocked" if blocked else "plain", walk if blocked else plain,
         v_wide)]
    if args.kernel:
        try:
            from aphrodite_tpu.ops.pallas import prefill_attention as pf
        except ImportError:
            print("  kernel: none in this tree", flush=True)
        else:
            for blocks in args.blocks.split(",") if args.blocks else [""]:
                # (`QxK`: a copied block is a sub-block)
                pinned = tuple(map(int, blocks.split("x"))) if blocks \
                    else (args.block,) * 2 if args.block else None
                if pinned is None:
                    pad = -queries % pf.TOKEN_TILE, -keys % pf.TOKEN_TILE
                    print("  kernel blocks (queries, keys, keys copied): "
                          f"{pf.choose_blocks(queries + pad[0], keys + pad[1], heads // kv_heads, window)}",
                          flush=True)
                else:
                    pinned = (pinned + pinned[-1:])[:3]

                def kernel(qq, kk, vv, pinned=pinned):
                    return pf.prefill_flash_attention(
                        qq, kk, vv, ctx_lens, valid_lens, scale, window,
                        interpret=jax.default_backend() != "tpu",
                        blocks=pinned)
                sides.append((f"kernel {blocks}".strip(), kernel, v))

    if args.check:
        want = jax.jit(plain)(q, k, v_wide).astype(jnp.float32)
        exact = _oracle(q, k, v, ctx, valid, scale, window) \
            if args.oracle else None
        if exact is not None:
            print("  oracle: max |plain - float64| = "
                  f"{np.abs(np.asarray(want) - exact).max():.4g}",
                  flush=True)
        for side, attend, values in sides:
            got = jax.jit(attend)(q, k, values).astype(jnp.float32)
            err = float(jnp.max(jnp.abs(got - want)))
            said = "" if exact is None else " |{} - float64| = {:.4g}".format(
                side, np.abs(np.asarray(got) - exact).max())
            print(f"  check: max |{side} - plain| = {err:.4g}{said} "
                  f"(finite: {bool(jnp.isfinite(got).all())})",
                  flush=True)
    if jax.default_backend() != "tpu":
        return
    kind = jax.devices()[0].device_kind
    with open(os.path.join(ROOT, "perf", "peaks.json")) as f:
        peaks = json.load(f)["devices"].get(kind)
    if peaks is None:
        raise SystemExit(f"perf/peaks.json has no peaks of {kind!r}")
    for side, attend, values in sides:
        # (an output is no query for the next call: the dependency
        # alone, through one lane of it where the widths differ)
        s, _ = device_bench(
            lambda c, i, attend=attend:
            (c[0] + attend(*c)[..., :1] * jnp.bfloat16(1e-30),) + c[1:],
            (q, k, values), slow=True)
        print(f"  whole call, {side}: {s * 1e3:.3f} ms, "
              f"{2 * macs / s / peaks['bf16_flops_per_s'] * 100:.1f}% of "
              "the bf16 peak over the live multiply-adds", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cells", action="store_true",
                    help="the calls of the benchmark's cells, in place "
                         "of one geometry")
    ap.add_argument("--only", default="",
                    help="with --cells: the cells whose name holds this")
    ap.add_argument("--rows", type=int, default=1)
    ap.add_argument("--queries", type=int, default=2048)
    ap.add_argument("--keys", type=int, default=2048)
    ap.add_argument("--ctx", type=int, default=0,
                    help="cached tokens before the chunk, counted from "
                         "the first key")
    ap.add_argument("--window", type=int, default=0)
    ap.add_argument("--heads", type=int, default=40)
    ap.add_argument("--kv-heads", type=int, default=10)
    ap.add_argument("--head-dim", type=int, default=0,
                    help="q's and k's head width (default: the cell's, "
                         "128 for one geometry)")
    ap.add_argument("--v-dim", type=int, default=0,
                    help="the values' head width (default: the cell's, "
                         "else the head width)")
    ap.add_argument("--scale", type=float, default=0.125)
    ap.add_argument("--block", type=int, default=0,
                    help="keys a block (default: the function's)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--check", action="store_true",
                    help="compare the output with prefill_attention")
    ap.add_argument("--oracle", action="store_true",
                    help="with --check: and with float64 numpy")
    ap.add_argument("--blocked-from", type=int, default=1 << 21,
                    help="queries x keys a row from which the jnp side "
                         "is the blocked function (Phi's and Laguna's "
                         "threshold; every cell's call falls on its "
                         "model's side of it)")
    ap.add_argument("--kernel", nargs="?", const="beside",
                    choices=("beside", "only"),
                    help="the Pallas flash kernel beside the function, "
                         "or (`only`) in its place")
    ap.add_argument("--blocks", default="",
                    help="pin the kernel's blocks, `QxK[xM]` (queries a "
                         "block, keys a sub-block, keys a copied block), "
                         "several with commas, each a side of its own "
                         "(default: the kernel's choice from the shapes)")
    args = ap.parse_args()
    cells = [c for c in CELLS if args.only in c[0]] if args.cells else [
        ("one geometry", args.queries, args.keys, args.ctx, args.window,
         args.heads, args.kv_heads, args.scale, args.rows)]
    for cell in cells:
        # (rows, head width, values' width, live lanes where stated)
        run_one(*(cell + (1, 128, 0, 0)[len(cell) - 8:]), args)


if __name__ == "__main__":
    main()
