"""A/B harness for the W4A8 matmul kernel alone, at a served geometry.

Times `gptq_matmul_a8` (`ops/pallas/quant_matmul.py`) at the calls of
`mistral-7b-w4a8.batch` (PERF.md §5): the four quantised linear layers
of a Mistral-7B decoder layer as the program fuses them, `(K, N)` of
(4,096, 6,144), (4,096, 4,096), (4,096, 28,672) and (14,336, 4,096),
at the 48 rows of a decode step (the streamed grid, the plain rescale)
and the 1,024 of a prompt step (the compiler's grid, the deferred
rescale):

    python benchmarks/qmm_ab.py --arms --check

A call's time is read as the benchmark's per-layer metric reads it:
from a profiler trace of `--reps` single calls, the device's seconds
of the Pallas kernel alone (the trace's `gptq_matmul_a8 bf16[rows,N]
tpu_custom_call`), beside the call's other operations (the prompt
rows' quantiser, the prologue's copies of x, the zeros and the
scales). Its roofline is `perf/rooflines/gptq_matmul_a8.py`'s count,
for this `K` alone where two layers share an `N`.

`--arms` splits the kernel's VPU work three ways, under each way of
making a group's int8 operand (`unpack="planes"`: eight shifted
nibble planes, a subtract, a narrowing convert, which is the kernel
as it was before `_unpack_bytes`; `unpack="bytes"`): the whole call,
the call with the unpack taken out at no cost (the words' own bytes
read as int8 and stacked: wrong numbers, the same bytes moved, the
same dots, the same rescale) and the call with the rescale taken out
(a group's int32 dot stored as it is). What the no-unpack arm reads is
what a free unpack could reach.

`--check` holds the compiled `bytes` output against the compiled
`planes` output, bit for bit, and prints a sha256 of each call's
result: the same line from a `git archive` of another commit (copy
this file into it; a tree without the `unpack` keyword times its one
kernel and prints its hashes) says whether two trees agree.
`--interpret` rehearses `--check` on the CPU at a small size and
times nothing. It is no code a benchmark cell runs.
"""
from __future__ import annotations

import argparse
import functools
import hashlib
import inspect
import itertools
import json
import os
import sys
import tempfile

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from perf.rooflines import gptq_matmul_a8 as roofline  # noqa: E402
from perf.trace import OPS_LINE, find_xplane, short_name  # noqa: E402

CONFIG = os.path.join(ROOT, "perf", "configs", "mistral-7b-w4a8.json")
BITS, GROUP = 4, 128


def least_seconds(peaks: dict, rows: int, K: int, N: int) -> float:
    """The roofline of ONE call `[rows, K] x [K, N]`:
    `roofline.count`'s bytes and operations for this K alone (it gives
    the mean over the layers that share an N)."""
    groups = K / GROUP
    moved = (K * N * BITS / 8 + groups * N * 2 + groups * N * BITS / 8 +
             rows * K + rows * N * 2)
    return max(moved / peaks["hbm_bytes_per_s"],
               2.0 * rows * K * N / peaks["int8_ops_per_s"])


def kernel_seconds(trace_dir: str, rows: int, N: int):
    """`(kernel, others)`: the device's nanoseconds of the Pallas
    matmul (a custom call whose result is bf16[rows, N]) and of every
    other operation, by short name, summed over the trace."""
    from jax.profiler import ProfileData
    kernel, others = [], {}
    want = f"bf16[{rows},{N}] tpu_custom_call"
    for plane in ProfileData.from_file(find_xplane(trace_dir)).planes:
        if not plane.name.startswith("/device:") or "CPU" in plane.name:
            continue
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for e in line.events:
                name = short_name(e.name)
                if name.endswith(want):
                    kernel.append(float(e.duration_ns))
                else:
                    others[name] = others.get(name, 0.0) + \
                        float(e.duration_ns)
        break                                   # one chip
    return kernel, others


ARM_NAMES = {None: "whole", "unpack": "no unpack", "rescale": "no rescale"}


def check_call(call, unpacks, operands, head: str) -> None:
    """A hash of the compiled result under each operand path, and the
    two paths held against each other bit for bit."""
    import jax.numpy as jnp
    outs = {}
    for unpack in unpacks:
        outs[unpack] = np.asarray(
            call(unpack, None)(*operands).astype(jnp.float32))
        digest = hashlib.sha256(outs[unpack].tobytes()).hexdigest()[:16]
        print(f"check {head} unpack={unpack}: sha256 {digest} finite "
              f"{bool(np.isfinite(outs[unpack]).all())} max |y| "
              f"{np.abs(outs[unpack]).max():.4g}", flush=True)
    if len(outs) == 2:
        a, b = outs.values()
        print(f"check {head} bytes == planes bit for bit: "
              f"{bool(np.array_equal(a, b))}", flush=True)


def time_call(f, operands, reps: int, rows: int, N: int):
    """`(kernel us, other operations us, {operation: us})` a call, from
    a trace of `reps` single calls of the compiled `f`."""
    import jax
    jax.block_until_ready(f(*operands))
    trace_dir = tempfile.mkdtemp(prefix="qmm_ab_")
    with jax.profiler.trace(trace_dir):
        for _ in range(reps):
            out = f(*operands)
        jax.block_until_ready(out)
    kernel, others = kernel_seconds(trace_dir, rows, N)
    if not kernel:
        raise SystemExit(f"the trace of [{rows},{N}] holds no kernel call")
    others = {name: ns / len(kernel) / 1e3 for name, ns in others.items()}
    return float(np.median(kernel)) / 1e3, sum(others.values()), others


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", default="48,1024",
                    help="comma list of row counts (a decode step's "
                         "and a prompt step's)")
    ap.add_argument("--arms", action="store_true",
                    help="also time each call without its unpack and "
                         "without its rescale")
    ap.add_argument("--unpack", default="planes,bytes",
                    help="which operand paths to time (a tree without "
                         "the keyword times its one kernel)")
    ap.add_argument("--check", action="store_true",
                    help="bytes against planes bit for bit, and a "
                         "hash of every call's result")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--interpret", action="store_true",
                    help="rehearse on the CPU at a small size: the "
                         "check and the probe, nothing timed")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from aphrodite_tpu.ops.pallas import quant_matmul as qm

    with open(CONFIG) as f:
        shapes = roofline.linear_layers(json.load(f))
    rows_list = [int(r) for r in args.rows.split(",")]
    if args.interpret:
        shapes, rows_list = [(256, 384), (512, 256)], [48, 65]

    has_arms = "unpack" in inspect.signature(
        qm.gptq_matmul_a8.__wrapped__).parameters
    unpacks = args.unpack.split(",") if has_arms else [None]
    ablates = [None, "unpack", "rescale"] if args.arms and has_arms \
        else [None]
    kind = jax.devices()[0].device_kind
    with open(os.path.join(ROOT, "perf", "peaks.json")) as f:
        peaks = json.load(f)["devices"].get(kind)
    if peaks is None and not args.interpret:
        raise SystemExit(f"perf/peaks.json has no peaks of {kind!r}")

    def keywords(unpack, ablate):
        kw = dict(bits=BITS, group_size=GROUP, interpret=args.interpret)
        if has_arms:
            kw.update(unpack=unpack, ablate=ablate)
        return kw

    def call(unpack, ablate):
        kw = keywords(unpack, ablate)
        return jax.jit(lambda x, qw, qz, sc: qm.gptq_matmul_a8(
            x, qw, qz, sc, **kw))

    def tiles_of(unpack, ablate, operands):
        """(block_m, block_n, block_k) of a call, read where the
        wrapper sizes them, off a trace of the undecorated function (a
        trace the jit has cached would size nothing)."""
        sized = []

        def spy(*a, **kw):
            out = prologue(*a, **kw)
            sized.append(out[3][:3])
            return out
        prologue, qm._gptq_prologue = qm._gptq_prologue, spy
        try:
            jax.eval_shape(functools.partial(
                qm.gptq_matmul_a8.__wrapped__,
                **keywords(unpack, ablate)), *operands)
        finally:
            qm._gptq_prologue = prologue
        return sized[-1]

    key = jax.random.PRNGKey(args.seed)
    for (K, N), rows in itertools.product(shapes, rows_list):
        k1, k2, k3, k4 = jax.random.split(jax.random.fold_in(key, K + N),
                                          4)
        operands = (
            jax.random.normal(k4, (rows, K), dtype=jnp.bfloat16),
            jax.lax.bitcast_convert_type(
                jax.random.bits(k1, (K // 8, N), jnp.uint32), jnp.int32),
            jax.lax.bitcast_convert_type(
                jax.random.bits(k2, (K // GROUP, N // 8), jnp.uint32),
                jnp.int32),
            (jax.random.uniform(k3, (K // GROUP, N)) * 0.02 +
             0.002).astype(jnp.bfloat16))
        head = f"[{rows},{K}]x[{K},{N}]"
        if args.check:
            check_call(call, unpacks, operands, head)
        if args.interpret:
            continue
        least = least_seconds(peaks, rows, K, N)
        served = has_arms and qm._resolve_unpack(
            None, BITS, qm._resolve_stream(None, rows))
        for unpack, ablate in itertools.product(unpacks, ablates):
            bm, bn, bk = tiles_of(unpack, ablate, operands)
            us, rest, others = time_call(call(unpack, ablate), operands,
                                         args.reps, rows, N)
            print(f"time {head} unpack={unpack}"
                  f"{' (as served)' if unpack == served else ''} "
                  f"{ARM_NAMES[ablate]}: kernel {us:.1f} us "
                  f"({least / (us * 1e-6) * 100:.1f}% of its roofline, "
                  f"least {least * 1e6:.1f} us), other operations "
                  f"{rest:.1f} us; tiles block_m={bm} block_n={bn} "
                  f"block_k={bk}", flush=True)
            if ablate is None:
                for name, op_us in sorted(others.items(),
                                          key=lambda kv: -kv[1]):
                    if op_us >= 1.0:
                        print(f"      {name}: {op_us:.1f} us", flush=True)


if __name__ == "__main__":
    main()
