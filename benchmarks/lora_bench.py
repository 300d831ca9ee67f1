"""LoRA combine microbench: per-call cost vs slot capacity.

The gathered (bgmv-style) combine's cost must be FLAT in max_loras —
each token fetches only its own adapter — where the old dense sweep
grew linearly (max_loras x the adapter FLOPs per token). Reference:
`kernels/punica/bgmv_impl.cuh` (per-token gather).

Usage: python benchmarks/lora_bench.py [--batch 256] [--rank 16]
Prints one line per slot capacity.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--rank", type=int, default=16)
    ap.add_argument("--hidden", type=int, default=4096)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from aphrodite_tpu.lora.layers import (LORA_A, LORA_B, LORA_IDX,
                                           LoRALinearMethod)
    from aphrodite_tpu.modeling.layers.linear import LinearMethod

    B, H, R = args.batch, args.hidden, args.rank
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (B, 1, H), dtype=jnp.bfloat16)
    w = jax.random.normal(key, (H, H), dtype=jnp.bfloat16) * 0.02

    results = []
    for slots in (2, 8, 32, 64):
        method = LoRALinearMethod(LinearMethod(), max_loras=slots,
                                  max_rank=R)
        params = {
            "weight": w,
            LORA_A: jax.random.normal(key, (slots, H, R),
                                      dtype=jnp.bfloat16) * 0.02,
            LORA_B: jax.random.normal(key, (slots, R, H),
                                      dtype=jnp.bfloat16) * 0.02,
            LORA_IDX: jnp.asarray(
                np.random.RandomState(0).randint(-1, slots, B),
                jnp.int32),
        }

        n1, n2 = 16, 80

        def loop(n):
            def go(params, x):
                def body(i, xx):
                    o = method.apply(params, xx)
                    return xx + o[:, :, :1] * jnp.bfloat16(1e-30)
                return jax.lax.fori_loop(0, n, body, x)
            return jax.jit(go)

        l1, l2 = loop(n1), loop(n2)

        def run(lp):
            out = lp(params, x)
            np.asarray(out)[:1]
            ts = []
            for _ in range(3):
                t0 = time.perf_counter()
                np.asarray(lp(params, x))[:1]
                ts.append(time.perf_counter() - t0)
            return min(ts)
        t1, t2 = run(l1), run(l2)
        per = max(1e-9, (t2 - t1) / (n2 - n1))
        results.append((slots, per))
        print(f"max_loras={slots:3d}: {per * 1e6:9.1f} us/call",
              flush=True)

    worst = max(p for _, p in results)
    base = results[0][1]
    if base <= 2e-6:
        # The slope method bottomed out (timing noise swamps the
        # combine): the growth ratio is meaningless, so say so instead
        # of declaring flatness.
        print(f"base measurement <= {base * 1e6:.1f} us/call: below "
              "the slope method's resolution — growth ratio "
              "unmeasurable in this run")
        return
    print(f"growth {worst / base:.2f}x across "
          f"{results[0][0]}->{results[-1][0]} slots "
          f"({'FLAT' if worst / base < 1.5 else 'NOT FLAT'})")


if __name__ == "__main__":
    main()
