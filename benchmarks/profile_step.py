"""Per-component time attribution for the steady-state decode step.

Measures each device program of one Mistral-7B-shaped GPTQ decode step on
the real chip and compares their sum against the measured full burst
step, so the residual (fusion boundaries, scan overhead) is visible.
This is the profile artifact the round-2 verdict asked for
(PROFILE_r03.md); methodology mirrors the reference's latency bench
(`tests/benchmarks/latency.py`) but per component.

Timing methodology: each component runs as a jitted `lax.fori_loop`
whose body feeds a tiny output-dependent perturbation back into the
input (so XLA cannot hoist the loop-invariant call), synced by ONE small
data pull; per-iteration time is the slope between two trip counts, so
the fixed dispatch and sync cost cancels. This matches how the engine
actually runs decode (a scan inside one dispatch).

Usage: python benchmarks/profile_step.py [--batch 512] [--ctx 128]
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

HIDDEN, LAYERS, HEADS, KV_HEADS, INTER = 4096, 32, 32, 8, 14336
VOCAB, HEAD_DIM = 32000, 128
GROUP = 128
PAGE = 16
#: one token of the decode kernel's head block (8 KV heads), bf16:
#: what sizes its work item (choose_pages_per_chunk)
LANE_BYTES = KV_HEADS * HEAD_DIM * 2


def stream_roofline_static(m: int, K: int, N: int, gs: int = GROUP):
    """Static per-layer-GEMM roofline estimate for the streamed W4A8
    grid at a bench shape: the aphrocheck estimator runs over the real
    `_stream_call` AST with the ACTUAL tile geometry bound (the same
    sizing calls the wrapper makes), so this is the lint-time bound
    evaluated at concrete numbers — printable next to the measured
    us/layer to make estimate-vs-reality drift visible.

    Returns dict(bytes_cell_lo, bytes_cell_hi, cells, bytes_total_lo,
    bytes_total_hi, flops, floor_us) — flops is the analytic
    2*m*K*N (the kernel body is a *refs kernel the static binder
    cannot see into), floor_us the static byte floor at the v5e
    ~820 GB/s spec. Round 7: the estimator distinguishes the
    slot-indexed column-parity accumulator planes (literal-2 lead)
    from the DMA ring slots, so the bound stays the RING traffic even
    when the binding resolves the ring depth to the same small
    integer."""
    import jax.numpy as jnp
    from aphrodite_tpu.ops.pallas.quant_matmul import (_STREAM_K_CAP,
                                                       _stream_pf,
                                                       _tile_k,
                                                       _tile_mn)
    from tools.aphrocheck import build_context
    from tools.aphrocheck.passes import roofline_pass

    block_m, block_n, padded_m = _tile_mn(m, N, jnp.bfloat16)
    block_k = _tile_k(K, gs, cap=_STREAM_K_CAP)
    n_slots = _stream_pf()
    k_tiles, n_tiles = K // block_k, N // block_n
    bindings = dict(
        block_m=block_m, block_n=block_n, block_k=block_k,
        padded_m=padded_m, n_slots=n_slots, k_tiles=k_tiles,
        n_tiles=n_tiles, gpt=block_k // gs, gs=gs, bits=4,
        qw_rows=block_k // 8, qw_cols=block_n, N=N)
    ctx, _ = build_context(
        rels=["aphrodite_tpu/ops/pallas/quant_matmul.py"])
    est = next(e for e in roofline_pass.kernel_estimates(
        ctx, bindings=bindings) if e.key.endswith("::_stream_call"))
    cells = n_tiles * k_tiles
    lo = est.per_cell_bytes.lo * cells
    hi = est.per_cell_bytes.hi * cells
    return {
        "bytes_cell_lo": int(est.per_cell_bytes.lo),
        "bytes_cell_hi": int(est.per_cell_bytes.hi),
        "cells": cells,
        "bytes_total_lo": int(lo),
        "bytes_total_hi": int(hi),
        "flops": 2 * m * K * N,
        "floor_us": lo / (roofline_pass.HBM_GBPS * 1e9) * 1e6,
    }


def ragged_roofline_static(pages_per_chunk: int, page_size: int,
                           hb: int, head_dim: int, kv_bytes_elt: int,
                           num_items: int, group: int = 4):
    """Static per-work-item estimate for the ragged decode attention
    kernel: the estimator over `_paged_decode_impl` with the chunk
    geometry bound. K+V ring traffic per item is the quantity of
    record (the PROFILE_r05 decode attribution's GB/s column)."""
    from tools.aphrocheck import build_context
    from tools.aphrocheck.passes import roofline_pass

    chunk_tokens = pages_per_chunk * page_size
    bindings = dict(
        chunk_tokens=chunk_tokens, hb=hb, head_dim=head_dim,
        page_size=page_size, pages_per_chunk=pages_per_chunk,
        lane_bytes=hb * head_dim * kv_bytes_elt, nw=num_items,
        group=group, rows=group * hb)
    ctx, _ = build_context(
        rels=["aphrodite_tpu/ops/pallas/paged_attention.py"])
    est = next(e for e in roofline_pass.kernel_estimates(
        ctx, bindings=bindings)
        if e.key.endswith("::_paged_decode_impl"))
    return {
        "bytes_cell_lo": int(est.per_cell_bytes.lo),
        "bytes_cell_hi": int(est.per_cell_bytes.hi),
        "items": num_items,
        "bytes_total_lo": int(est.per_cell_bytes.lo) * num_items,
        "floor_us": int(est.per_cell_bytes.lo) * num_items /
        (roofline_pass.HBM_GBPS * 1e9) * 1e6,
    }


def device_bench(step, init, iters: int = 0, reps: int = 3,
                 slow: bool = False, donate: bool = False):
    """step: (carry, i) -> carry, pure device. Returns (s/iter, rtt)
    — and with donate=True, (s/iter, rtt, final_state).

    Dual-iteration-count measurement: the same loop is compiled at a
    small and a large trip count and per-iteration time is the slope
    (t_big - t_small) / (n_big - n_small) — the sync round-trip and any
    fixed dispatch overhead cancel exactly (subtracting a
    separately-measured sync cost is too noisy for small kernels).

    donate=True threads ONE state through every call with buffer
    donation (in-place loops): required when the carry is bigger than
    half of HBM (e.g. the full KV pool) — without it each call holds
    input + output copies. The caller receives the final state to chain
    further measurements on the same buffers (`init` is consumed)."""
    import jax
    import jax.numpy as jnp

    n1, n2 = (8, 40) if slow else (64, 576)
    dn = (0,) if donate else ()

    def make_loop(n):
        return jax.jit(lambda c: jax.lax.fori_loop(
            0, n, lambda i, cc: step(cc, i), c), donate_argnums=dn)
    loop1, loop2 = make_loop(n1), make_loop(n2)
    pull = jax.jit(
        lambda c: jnp.ravel(jax.tree_util.tree_leaves(c)[0])[:1])

    def run(loop, state):
        state = loop(state)
        np.asarray(pull(state))              # compile
        times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            state = loop(state)
            np.asarray(pull(state))
            times.append(time.perf_counter() - t0)
        return min(times), state
    t1, state = run(loop1, init)
    t2, state = run(loop2, state)
    per_iter = max(1e-9, (t2 - t1) / (n2 - n1))
    if donate:
        return per_iter, t1, state
    return per_iter, t1


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=512)
    ap.add_argument("--ctx", type=int, default=128)
    ap.add_argument("--iters", type=int, default=100)
    ap.add_argument("--only", type=str, default="",
                    help="comma list: mesh,qmm,a8,ab,dense,attn,kv,"
                         "head,prefill,pglue,layer,burst,spec,pstep,"
                         "glue,roofline")
    ap.add_argument("--no-roofline-gate", action="store_true",
                    help="skip the pre-run aphrocheck ROOF/FOLD gate")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None

    if not args.no_roofline_gate:
        # Pre-run static perf gate (~2 s): a roofline regression is
        # cheaper to catch here than after a 30-minute TPU session.
        from bench import _roofline_gate
        _roofline_gate()

    def want(tag):
        return only is None or tag in only

    # --- static placement ledger vs the amortized ICI model
    # (host-only: prints the MESHPLAN.json collective counts/bytes
    # next to an earlier dry run's pricing, so the two framings — the
    # verified 2/layer + 1 fixed attribution and the amortized
    # 1.5/layer from a compiled count — stay reconciled) ---
    if want("mesh"):
        plan_path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), os.pardir,
            "MESHPLAN.json")
        with open(plan_path, encoding="utf-8") as f:
            plan = json.load(f)
        geo = plan["geometry_7b"]
        ref = plan["models"][plan["reference_model"]]["all_reduce"]
        print(f"=== static placement ledger (MESHPLAN.json, "
              f"{plan['reference_model']} @ {geo['n_layers']}L, "
              f"bs={geo['batch']}, tp={geo['tp']}) ===")
        print(f"all-reduce: {ref['per_layer']}/layer (o_proj + "
              f"down_proj) + {ref['fixed']} fixed (embed combine) = "
              f"{geo['all_reduce_count_per_step']}/step, "
              f"{geo['all_reduce_mb_per_step']} MB payload -> "
              f"{geo['all_reduce_ici_mb_per_chip']} MB/chip over ICI, "
              f"{geo['all_reduce_ici_ms']} ms @ "
              f"{geo['ici_gbps']:.0f} GB/s")
        print(f"logits all-gather: consumer-side seam (deferred into "
              f"the fused sampler; 0 in the bare step HLO), "
              f"{geo['logits_all_gather_mb']} MB if materialized "
              f"({geo['logits_all_gather_ici_ms']} ms)")
        # The amortized ICI model (1.5/layer from a compiled count,
        # same ring formula) and
        # the device floors it priced against, for the side-by-side.
        hbm_ms = (13.49 / geo["tp"]) * (1 << 30) / 820e9 * 1e3
        mxu_ms = geo["batch"] * 7.24e9 / (geo["tp"] * 197e12) * 1e3
        print(f"amortized ICI model: 1.5 all-reduces/layer "
              f"amortized -> 101 MB/step, 0.98 ms; floors HBM "
              f"{hbm_ms:.2f} ms, MXU {mxu_ms:.2f} ms")
        floor_ms = hbm_ms + geo["all_reduce_ici_ms"]
        proj = geo["batch"] / floor_ms * 1e3
        print(f"repriced with the ledger count: device floor "
              f"{floor_ms:.2f} ms/step -> {proj:,.0f} tok/s, x0.79 "
              f"engine efficiency {proj * 0.79:,.0f} tok/s")
        # --- disagg arm: the handoff domain priced by the same ledger.
        # A finished prefill's pages cross the prefill->decode group
        # seam as ONE batched point-to-point device_put (no ring), so
        # the price is bytes/ici_gbps — printed next to the r05 model
        # so the per-step all-reduce cost and the per-prompt handoff
        # cost share a frame of reference.
        n_p, n_d = geo["disagg_split"]
        print(f"disagg ({n_p},{n_d}) handoff domain: "
              f"{geo['handoff_page_mb']} MB/page "
              f"({geo['handoff_page_ici_us']} us over ICI); "
              f"{geo['handoff_prompt_tokens']}-token prefill = "
              f"{geo['handoff_prompt_pages']} pages, "
              f"{geo['handoff_prompt_mb']:,.0f} MB -> "
              f"{geo['handoff_prompt_ici_ms']} ms/prompt "
              f"point-to-point @ {geo['ici_gbps']:.0f} GB/s")
        amort = (geo["handoff_prompt_ici_ms"]
                 / (geo["handoff_prompt_tokens"] / geo["batch"]))
        print(f"disagg handoff vs r05 step budget: amortized "
              f"{amort:.3f} ms per decode-step-equivalent at "
              f"bs={geo['batch']} vs {geo['all_reduce_ici_ms']} ms "
              f"all-reduce/step — handoff rides the seam, not the "
              f"decode critical path")
        if only == {"mesh"}:
            return

    import jax
    import jax.numpy as jnp
    from aphrodite_tpu.ops.pallas.quant_matmul import gptq_matmul
    from aphrodite_tpu.ops.pallas.paged_attention import (
        paged_decode_attention)
    from aphrodite_tpu.ops.kv_cache import write_to_kv_cache

    B, ctx = args.batch, args.ctx
    key = jax.random.PRNGKey(0)
    rows = []
    rtts = []

    def row(name, per_call_ms, calls_per_step, note=""):
        rows.append((name, per_call_ms, calls_per_step,
                     per_call_ms * calls_per_step, note))
        # Stream each measurement as it lands (a later section crashing
        # must not lose earlier numbers).
        print(f"[measured] {name}: {per_call_ms * 1e3:.1f} us/call "
              f"x{calls_per_step} = "
              f"{per_call_ms * calls_per_step:.3f} ms/step  {note}",
              file=sys.stderr, flush=True)

    # --- quantized matmuls (the four per-layer GEMMs) ---
    qkv_out = (HEADS + 2 * KV_HEADS) * HEAD_DIM        # 6144
    shapes = [
        ("qkv_proj", HIDDEN, qkv_out),
        ("o_proj", HIDDEN, HIDDEN),
        ("gate_up", HIDDEN, 2 * INTER),
        ("down", INTER, HIDDEN),
    ]
    for name, K, N in (shapes if want("qmm") else []):
        x = jax.random.normal(key, (B, K), dtype=jnp.bfloat16)
        qw = jax.random.randint(key, (K // 8, N), 0, 2**31 - 1,
                                dtype=jnp.int32)
        qz = jax.random.randint(key, (K // GROUP, N // 8), 0, 2**31 - 1,
                                dtype=jnp.int32)
        sc = jnp.ones((K // GROUP, N), dtype=jnp.bfloat16) * 0.01

        def qstep(c, i, qw=qw, qz=qz, sc=sc):
            xx, _ = c
            o = gptq_matmul(xx, qw, qz, sc, bits=4, group_size=GROUP)
            # output-dependent feedback: one broadcast-add pass over x
            return (xx + o[:, :1] * jnp.bfloat16(1e-30), o[0, 0]), None

        def qloop(c, i, f=qstep):
            return f(c, i)[0]
        s, rtt = device_bench(qloop, (x, jnp.bfloat16(0.0)))
        rtts.append(rtt)
        flops = 2 * B * K * N
        row(f"gptq_matmul {name} [{B},{K}]x[{K},{N}]", s * 1e3, LAYERS,
            f"{flops / s / 1e12:.1f} TF/s")

    # --- streamed-vs-classic skinny-m A/B (W4A8, the bench decode
    # path): per-layer us and effective weight-streaming GB/s over the
    # four per-layer GEMMs at m in {1, 16, 64}. The streamed grid
    # flattens (n, k) into a work list and drives an explicit weight
    # DMA ring (quant_matmul._stream_kernel) — since round 7 with the
    # DOUBLE-BUFFERED column-parity accumulator (the ROOF003 closure:
    # the run-final flush no longer serializes with the next run's
    # first ring wait) and the activation quantization folded into
    # the kernel prologue; `stream` pins the variant so both compile
    # at identical shapes. Effective GB/s counts the int4 qweight +
    # packed zeros + scales actually read from HBM per layer, printed
    # against the ~820 GB/s v5e floor. ---
    if want("qmm"):
        from aphrodite_tpu.ops.pallas.quant_matmul import gptq_matmul_a8
        layer_weight_bytes = sum(
            K * N // 2 +                    # int4 qweight
            (K // GROUP) * N // 2 +         # packed qzeros
            (K // GROUP) * N * 2            # bf16 scales
            for _, K, N in shapes)
        stream_rows = []
        for M in (1, 16, 64):
            us = {"classic": 0.0, "streamed": 0.0}
            for name, K, N in shapes:
                x = jax.random.normal(key, (M, K), dtype=jnp.bfloat16)
                qw = jax.random.randint(key, (K // 8, N), 0, 2**31 - 1,
                                        dtype=jnp.int32)
                qz = jax.random.randint(key, (K // GROUP, N // 8), 0,
                                        2**31 - 1, dtype=jnp.int32)
                sc = jnp.ones((K // GROUP, N), dtype=jnp.bfloat16) * 0.01
                for label, use_stream in (("classic", False),
                                          ("streamed", True)):
                    def sstep(c, i, qw=qw, qz=qz, sc=sc, st=use_stream):
                        xx = c
                        o = gptq_matmul_a8(xx, qw, qz, sc, bits=4,
                                           group_size=GROUP, stream=st)
                        return xx + o[:, :1] * jnp.bfloat16(1e-30)
                    s, rtt = device_bench(sstep, x)
                    rtts.append(rtt)
                    us[label] += s * 1e6
                    row(f"QMM A/B {label} {name} m={M}", s * 1e3,
                        LAYERS, "")
            stream_rows.append((M, us["classic"], us["streamed"]))
        from tools.aphrocheck.passes.roofline_pass import HBM_GBPS
        print(f"\n=== streamed(double-buffered)-vs-classic W4A8 "
              f"skinny-m A/B (us/layer over the 4 GEMMs; effective "
              f"weight GB/s vs the {HBM_GBPS:.0f} GB/s floor) ===")
        print(f"{'m':>4s} {'classic':>12s} {'streamed':>12s} "
              f"{'speedup':>8s} {'of-floor':>9s}")
        for M, c_us, s_us in stream_rows:
            c_gbs = layer_weight_bytes / (c_us * 1e-6) / 1e9
            s_gbs = layer_weight_bytes / (s_us * 1e-6) / 1e9
            print(f"{M:4d} {c_us:7.1f}us {c_gbs:4.0f}GB/s "
                  f"{s_us:7.1f}us {s_gbs:4.0f}GB/s "
                  f"{c_us / s_us:7.2f}x "
                  f"{s_gbs / HBM_GBPS * 100:7.0f}%")

    # --- roofline calibration: the aphrocheck static estimates next
    # to measured us/layer + effective GB/s, so estimate-vs-reality
    # drift is visible in ONE table (streamed W4A8 matmul + ragged
    # decode attention — the two kernels the ROOF/FOLD motivating
    # findings live in). Static bytes come from the SAME AST walk the
    # lint gate runs, evaluated at the real tile geometry. ---
    if want("roofline"):
        from aphrodite_tpu.ops.pallas.quant_matmul import gptq_matmul_a8
        cal_rows = []
        for M in (1, 16, 64):
            meas_us = 0.0
            static = {"bytes_total_lo": 0, "bytes_total_hi": 0,
                      "flops": 0, "floor_us": 0.0}
            for name, K, N in shapes:
                st = stream_roofline_static(M, K, N)
                for k in static:
                    static[k] += st[k]
                x = jax.random.normal(key, (M, K), dtype=jnp.bfloat16)
                qw = jax.random.randint(key, (K // 8, N), 0, 2**31 - 1,
                                        dtype=jnp.int32)
                qz = jax.random.randint(key, (K // GROUP, N // 8), 0,
                                        2**31 - 1, dtype=jnp.int32)
                sc = jnp.ones((K // GROUP, N), dtype=jnp.bfloat16) * 0.01

                def rstep(c, i, qw=qw, qz=qz, sc=sc):
                    xx = c
                    o = gptq_matmul_a8(xx, qw, qz, sc, bits=4,
                                       group_size=GROUP, stream=True)
                    return xx + o[:, :1] * jnp.bfloat16(1e-30)
                s, rtt = device_bench(rstep, x)
                rtts.append(rtt)
                meas_us += s * 1e6
            cal_rows.append((M, static, meas_us))
        print(f"\n=== roofline calibration: streamed W4A8 matmul "
              f"(4 GEMMs/layer; static = aphrocheck estimate at the "
              f"real tile geometry) ===")
        print(f"{'m':>4s} {'static MB/layer':>18s} {'floor us':>9s} "
              f"{'meas us':>9s} {'eff GB/s':>9s} {'floor/meas':>10s}")
        for M, st, meas_us in cal_rows:
            eff = st["bytes_total_lo"] / (meas_us * 1e-6) / 1e9
            print(f"{M:4d} {st['bytes_total_lo'] / 1e6:8.1f}"
                  f"..{st['bytes_total_hi'] / 1e6:<8.1f} "
                  f"{st['floor_us']:9.1f} {meas_us:9.1f} {eff:9.0f} "
                  f"{st['floor_us'] / meas_us:10.2f}")

        # ragged decode attention at the bench geometry
        from aphrodite_tpu.ops.pallas.paged_attention import (
            build_decode_work_list, choose_pages_per_chunk, head_block)
        r_pps = -(-max(8, -(-ctx // PAGE)) // 8) * 8
        r_npg = B * r_pps + 1
        rkp = jax.random.normal(
            key, (r_npg, PAGE, KV_HEADS * HEAD_DIM), dtype=jnp.bfloat16)
        rvp = jax.random.normal(
            key, (r_npg, PAGE, KV_HEADS * HEAD_DIM), dtype=jnp.bfloat16)
        rtb = jnp.asarray(
            np.random.randint(0, r_npg, (B, r_pps)), jnp.int32)
        rcl = jnp.full((B,), ctx, dtype=jnp.int32)
        rq = jax.random.normal(key, (B, HEADS, HEAD_DIM),
                               dtype=jnp.bfloat16)
        r_ppc = choose_pages_per_chunk(r_pps, PAGE, LANE_BYTES)
        r_work = build_decode_work_list([-(-ctx // PAGE)] * B, r_ppc)
        hb = head_block(KV_HEADS, HEAD_DIM, jnp.bfloat16)
        n_items = int(r_work[1].shape[0]) * (KV_HEADS // hb)
        ast_static = ragged_roofline_static(
            r_ppc, PAGE, hb, HEAD_DIM, 2, n_items)

        def rastep(c, i):
            qq = c
            o = paged_decode_attention(
                qq, rkp, rvp, rtb, rcl, None, scale=0.0884,
                pages_per_chunk=r_ppc, work_items=r_work)
            return qq + o * jnp.bfloat16(1e-30)
        s, rtt = device_bench(rastep, rq)
        rtts.append(rtt)
        meas_us = s * 1e6
        akv = 2 * B * KV_HEADS * ctx * HEAD_DIM * 2
        print(f"\n=== roofline calibration: ragged decode attention "
              f"(b={B} ctx={ctx}; {n_items} work cells) ===")
        print(f"  static ring bytes/cell "
              f"{ast_static['bytes_cell_lo']:,}.."
              f"{ast_static['bytes_cell_hi']:,}  "
              f"analytic KV bytes {akv:,}")
        print(f"  static floor {ast_static['floor_us']:.1f} us   "
              f"measured {meas_us:.1f} us   "
              f"KV eff {akv / (meas_us * 1e-6) / 1e9:.0f} GB/s")

    # --- W4A8 quantized matmuls (int8 MXU path), same shapes ---
    if want("a8"):
        from aphrodite_tpu.ops.pallas.quant_matmul import gptq_matmul_a8
    for name, K, N in (shapes if want("a8") else []):
        x = jax.random.normal(key, (B, K), dtype=jnp.bfloat16)
        qw = jax.random.randint(key, (K // 8, N), 0, 2**31 - 1,
                                dtype=jnp.int32)
        qz = jax.random.randint(key, (K // GROUP, N // 8), 0, 2**31 - 1,
                                dtype=jnp.int32)
        sc = jnp.ones((K // GROUP, N), dtype=jnp.bfloat16) * 0.01

        def a8step(c, i, qw=qw, qz=qz, sc=sc):
            xx, _ = c
            o = gptq_matmul_a8(xx, qw, qz, sc, bits=4,
                               group_size=GROUP)
            return (xx + o[:, :1] * jnp.bfloat16(1e-30), o[0, 0])

        s, rtt = device_bench(a8step, (x, jnp.bfloat16(0.0)))
        rtts.append(rtt)
        flops = 2 * B * K * N
        row(f"W4A8 gptq_matmul {name} [{B},{K}]x[{K},{N}]", s * 1e3,
            LAYERS, f"{flops / s / 1e12:.1f} TF/s")

    # --- W4A8 kernel A/B: classic (per-group scale-FMA after every
    # int8 dot) vs deferred (int32 group accumulator planes, one
    # batched rescale at k-tile flush) at the three bench geometries:
    # m=64 (small decode), 512 (the bench batch), 8192 (one prefill
    # round). Shape is gate_up, the widest and most time-dominant of
    # the four per-layer GEMMs; the `deferred` static kwarg pins the
    # variant so both compile at identical shapes. ---
    if want("ab"):
        from aphrodite_tpu.ops.pallas.quant_matmul import gptq_matmul_a8
        K, N = HIDDEN, 2 * INTER
        qw = jax.random.randint(key, (K // 8, N), 0, 2**31 - 1,
                                dtype=jnp.int32)
        qz = jax.random.randint(key, (K // GROUP, N // 8), 0, 2**31 - 1,
                                dtype=jnp.int32)
        sc = jnp.ones((K // GROUP, N), dtype=jnp.bfloat16) * 0.01
        ab_rows = []
        for M in (64, 512, 8192):
            x = jax.random.normal(key, (M, K), dtype=jnp.bfloat16)
            tfs = {}
            for label, use_def in (("classic", False),
                                   ("deferred", True)):
                def abstep(c, i, qw=qw, qz=qz, sc=sc, d=use_def):
                    xx = c
                    o = gptq_matmul_a8(xx, qw, qz, sc, bits=4,
                                       group_size=GROUP, deferred=d)
                    return xx + o[:, :1] * jnp.bfloat16(1e-30)
                s, rtt = device_bench(abstep, x, slow=(M >= 4096))
                rtts.append(rtt)
                tfs[label] = 2 * M * K * N / s / 1e12
                row(f"W4A8 A/B {label} gate_up m={M}", s * 1e3, LAYERS,
                    f"{tfs[label]:.1f} TF/s")
            ab_rows.append((M, tfs["classic"], tfs["deferred"]))
        print(f"\n=== W4A8 kernel A/B "
              f"(gate_up [m,{K}]x[{K},{N}], effective TF/s) ===")
        print(f"{'m':>6s} {'classic':>10s} {'deferred':>10s} "
              f"{'speedup':>9s}")
        for M, c, d in ab_rows:
            print(f"{M:6d} {c:10.1f} {d:10.1f} {d / c:8.2f}x")

    # --- bf16 dense matmuls, same shapes (MXU roofline comparison) ---
    for name, K, N in (shapes if want("dense") else []):
        x = jax.random.normal(key, (B, K), dtype=jnp.bfloat16)
        w = jax.random.normal(key, (K, N), dtype=jnp.bfloat16)

        def dstep(c, i, w=w):
            xx = c
            o = jnp.dot(xx, w, preferred_element_type=jnp.float32
                        ).astype(jnp.bfloat16)
            return xx + o[:, :1] * jnp.bfloat16(1e-30)
        s, rtt = device_bench(dstep, x)
        rtts.append(rtt)
        flops = 2 * B * K * N
        row(f"bf16 dense {name}", s * 1e3, LAYERS,
            f"{flops / s / 1e12:.1f} TF/s")

    # --- decode attention (bench geometry: ctx tokens resident) ---
    pages_per_seq = -(-max(8, -(-ctx // PAGE)) // 8) * 8
    num_pages = B * pages_per_seq + 1
    kp = jax.random.normal(
        key, (num_pages, PAGE, KV_HEADS * HEAD_DIM), dtype=jnp.bfloat16)
    vp = jax.random.normal(
        key, (num_pages, PAGE, KV_HEADS * HEAD_DIM), dtype=jnp.bfloat16)
    tables = jnp.asarray(
        np.random.randint(0, num_pages, (B, pages_per_seq)), jnp.int32)
    ctx_lens = jnp.full((B,), ctx, dtype=jnp.int32)
    q3 = jax.random.normal(key, (B, HEADS, HEAD_DIM), dtype=jnp.bfloat16)
    kv_bytes = 2 * B * KV_HEADS * ctx * HEAD_DIM * 2
    if want("attn"):
        from aphrodite_tpu.ops.pallas.paged_attention import (
            build_decode_work_list, choose_pages_per_chunk)
        # Attribution row: the engine default (ragged work-list grid,
        # built exactly as ModelRunner._prepare_decode does).
        attr_ppc = choose_pages_per_chunk(pages_per_seq, PAGE,
                                          LANE_BYTES)
        attr_work = build_decode_work_list(
            [-(-ctx // PAGE)] * B, attr_ppc)

        def astep(c, i):
            qq = c
            o = paged_decode_attention(
                qq, kp, vp, tables, ctx_lens, None, scale=0.0884,
                pages_per_chunk=attr_ppc, work_items=attr_work)
            return qq + o * jnp.bfloat16(1e-30)
        s, rtt = device_bench(astep, q3)
        rtts.append(rtt)
        row(f"decode_attn ragged b={B} ctx={ctx}", s * 1e3, LAYERS,
            f"{kv_bytes / s / 1e9:.0f} GB/s KV")

        # The round-7 AMLA-vs-classic RESCALE A/B at the bench
        # page-32 geometry (mirrors the W4A8 `--only ab` table): ctx
        # 128 is the bench point (one item a row), 512 and 2000 are
        # the multi-item serving shapes. Batch shrinks with ctx so
        # the KV pool stays within HBM. The amla column pins the
        # exponent-bias-add rescale against the classic multiply on
        # the same grid (the kernel's `amla` keyword), with effective
        # KV GB/s against the 820 GB/s floor.
        ab_rows = []
        PAGE32 = 32
        for ab_ctx, ab_b in ((128, 512), (512, 256), (2000, 64)):
            pps = -(-max(4, -(-ab_ctx // PAGE32)) // 4) * 4
            npg = ab_b * pps + 1
            kp32 = jax.random.normal(
                key, (npg, PAGE32, KV_HEADS * HEAD_DIM),
                dtype=jnp.bfloat16)
            vp32 = jax.random.normal(
                key, (npg, PAGE32, KV_HEADS * HEAD_DIM),
                dtype=jnp.bfloat16)
            tb32 = jnp.asarray(
                (np.random.permutation(npg - 1) + 1)[:ab_b * pps]
                .reshape(ab_b, pps), jnp.int32)
            cl32 = jnp.full((ab_b,), ab_ctx, jnp.int32)
            q32 = jax.random.normal(key, (ab_b, HEADS, HEAD_DIM),
                                    dtype=jnp.bfloat16)
            ab_ppc = choose_pages_per_chunk(pps, PAGE32, LANE_BYTES)
            ab_work = build_decode_work_list(
                [-(-ab_ctx // PAGE32)] * ab_b, ab_ppc)
            ab_kv = 2 * ab_b * KV_HEADS * ab_ctx * HEAD_DIM * 2
            us = {}
            for label, wk, use_amla in (
                    ("ragged", ab_work, True),
                    ("ragged-mulrescale", ab_work, False)):
                def abstep(c, i, kpp=kp32, vpp=vp32, tb=tb32,
                           cl=cl32, wk=wk, ppc=ab_ppc, am=use_amla):
                    qq = c
                    o = paged_decode_attention(
                        qq, kpp, vpp, tb, cl, None, scale=0.0884,
                        pages_per_chunk=ppc, work_items=wk, amla=am)
                    return qq + o * jnp.bfloat16(1e-30)
                s, rtt = device_bench(abstep, q32)
                rtts.append(rtt)
                us[label] = s * 1e6
                row(f"ATTN A/B {label} b={ab_b} ctx={ab_ctx} "
                    f"page={PAGE32}", s * 1e3, LAYERS,
                    f"{ab_kv / s / 1e9:.0f} GB/s KV")
            ab_rows.append((ab_b, ab_ctx, ab_kv, us))
        from tools.aphrocheck.passes.roofline_pass import HBM_GBPS
        print(f"\n=== decode attention A/B (page {PAGE32}, us/layer; "
              f"amla = exponent-bias-add rescale vs the classic "
              f"multiply on the SAME ragged grid; KV GB/s vs the "
              f"{HBM_GBPS:.0f} GB/s floor) ===")
        print(f"{'batch':>6s} {'ctx':>6s} "
              f"{'ragged':>10s} {'mul-resc':>10s} {'amla-x':>7s} "
              f"{'KV-GB/s':>8s} {'of-floor':>9s}")
        for ab_b, ab_ctx, ab_kv, us in ab_rows:
            gbs = ab_kv / (us["ragged"] * 1e-6) / 1e9
            print(f"{ab_b:6d} {ab_ctx:6d} "
                  f"{us['ragged']:10.1f} "
                  f"{us['ragged-mulrescale']:10.1f} "
                  f"{us['ragged-mulrescale'] / us['ragged']:6.2f}x "
                  f"{gbs:8.0f} {gbs / HBM_GBPS * 100:7.0f}%")

    # --- KV page write ---
    fk = jax.random.normal(key, (B, KV_HEADS, HEAD_DIM),
                           dtype=jnp.bfloat16)
    # One slot per page (the decode contract: pages sequence-exclusive).
    slots = jnp.asarray(
        np.random.permutation(num_pages)[:B] * PAGE +
        np.random.randint(0, PAGE, B), jnp.int32)

    if want("kv"):
        for variant, distinct in (("decode-pipelined", True),
                                  ("prefill-window", False)):
            def wstep(c, i, distinct=distinct):
                kpp, vpp, f = c
                kpp, vpp = write_to_kv_cache(f, f, kpp, vpp, slots,
                                             distinct_pages=distinct)
                return (kpp, vpp,
                        f + kpp[0, 0, :1] * jnp.bfloat16(1e-30))
            s, rtt = device_bench(wstep, (kp + 0, vp + 0, fk),
                                  slow=True)
            rtts.append(rtt)
            row(f"kv_write {variant} b={B}", s * 1e3, LAYERS, "")

    # --- lm_head ---
    hid = jax.random.normal(key, (B, HIDDEN), dtype=jnp.bfloat16)
    if want("head"):
        w_lm = jax.random.normal(key, (HIDDEN, VOCAB),
                                 dtype=jnp.bfloat16)

        def lstep(c, i):
            hh = c
            o = jnp.dot(hh, w_lm, preferred_element_type=jnp.float32)
            return hh + o[:, :1].astype(jnp.bfloat16) * \
                jnp.bfloat16(1e-30)
        s, rtt = device_bench(lstep, hid)
        rtts.append(rtt)
        row("lm_head matmul", s * 1e3, 1,
            f"{2 * B * HIDDEN * VOCAB / s / 1e12:.1f} TF/s")

    # --- fused sampler (greedy plan, the bench configuration) ---
    if want("head"):
        from aphrodite_tpu.modeling.layers.sampler import (Sampler,
                                                           fused_sample)
        from aphrodite_tpu.modeling.sampling_metadata import (
            SamplingMetadata)
        from aphrodite_tpu.common.sampling_params import SamplingParams
        from aphrodite_tpu.common.sequence import SequenceData
        sp = SamplingParams(temperature=0.0, max_tokens=16,
                            ignore_eos=True)
        sampling = SamplingMetadata(
            seq_groups=[([i], sp) for i in range(B)],
            seq_data={i: SequenceData([1, 2, 3]) for i in range(B)},
            prompt_lens=[])
        sampler = Sampler(VOCAB)
        plan = sampler.plan(sampling, pad_to=B)
        logits = jax.random.normal(key, (B, VOCAB), dtype=jnp.float32)

        def sstep(c, i):
            lg = c
            packed, _ = fused_sample(lg, plan.tensors,
                                     plan.key_parts.at[:, 1].add(i),
                                     max_best_of=plan.max_best_of,
                                     num_topk=plan.num_topk,
                                     need_logprobs=False)
            return lg + packed[:, :1].astype(jnp.float32) * 1e-30
        s, rtt = device_bench(sstep, logits)
        rtts.append(rtt)
        row("fused_sample (greedy)", s * 1e3, 1, "")

    # --- prefill-shape quant matmul (one scheduling round: 4096 toks) ---
    if want("prefill"):
        M = 4096
        for name, K, N in shapes:
            x = jax.random.normal(key, (M, K), dtype=jnp.bfloat16)
            qw = jax.random.randint(key, (K // 8, N), 0, 2**31 - 1,
                                    dtype=jnp.int32)
            qz = jax.random.randint(key, (K // GROUP, N // 8), 0,
                                    2**31 - 1, dtype=jnp.int32)
            sc = jnp.ones((K // GROUP, N), dtype=jnp.bfloat16) * 0.01

            def pstep(c, i, qw=qw, qz=qz, sc=sc):
                xx = c
                o = gptq_matmul(xx, qw, qz, sc, bits=4,
                                group_size=GROUP)
                return xx + o[:, :1] * jnp.bfloat16(1e-30)
            s, rtt = device_bench(pstep, x, slow=True)
            rtts.append(rtt)
            flops = 2 * M * K * N
            row(f"PREFILL gptq_matmul {name} m={M}", s * 1e3, LAYERS,
                f"{flops / s / 1e12:.1f} TF/s")

        # prefill dense attention + KV write at one round's shape
        from aphrodite_tpu.ops.attention import prefill_attention
        pb, ps = 128, 32                     # 128 seqs x 32 tokens
        qp = jax.random.normal(key, (pb, ps, HEADS, HEAD_DIM),
                               dtype=jnp.bfloat16)
        kvp = jax.random.normal(key, (pb, ps, KV_HEADS, HEAD_DIM),
                                dtype=jnp.bfloat16)
        plens = jnp.full((pb,), ps, jnp.int32)

        def prefstep(c, i):
            qq = c
            o = prefill_attention(qq, kvp, kvp,
                                  jnp.zeros((pb,), jnp.int32), plens,
                                  0.0884)
            return qq + o * jnp.bfloat16(1e-30)
        s, rtt = device_bench(prefstep, qp, slow=True)
        rtts.append(rtt)
        row(f"PREFILL attention b={pb} s={ps}", s * 1e3, LAYERS, "")

        fkp = jax.random.normal(key, (pb * ps, KV_HEADS, HEAD_DIM),
                                dtype=jnp.bfloat16)
        pslots = jnp.asarray(np.arange(pb * ps), jnp.int32)

        def pwstep(c, i):
            kpp, vpp, f = c
            kpp, vpp = write_to_kv_cache(f, f, kpp, vpp, pslots,
                                         distinct_pages=False)
            return (kpp, vpp, f + kpp[0, 0, :1] * jnp.bfloat16(1e-30))
        s, rtt = device_bench(pwstep, (kp + 0, vp + 0, fkp), slow=True)
        rtts.append(rtt)
        row(f"PREFILL kv_write {pb * ps} toks", s * 1e3, LAYERS, "")

    # --- prefill GLUE at the bench 8k-round geometry: everything in a
    # prompt step that is neither a quant matmul nor attention. These
    # are the per-layer elementwise terms an earlier profile lumped as
    # one residual; each is measured standalone so the PROFILE
    # artifact can attribute the residual line by line. ---
    if want("pglue"):
        from aphrodite_tpu.modeling.layers.activation import silu_and_mul
        from aphrodite_tpu.modeling.layers.layernorm import (
            fused_add_rms_norm)
        from aphrodite_tpu.modeling.layers.rotary_embedding import get_rope
        from aphrodite_tpu.ops.pallas.quant_matmul import (
            _quantize_activations_int8)
        M8 = 8192
        hid8 = jax.random.normal(key, (M8, HIDDEN), dtype=jnp.bfloat16)
        wnorm = jnp.ones((HIDDEN,), jnp.bfloat16)

        def nstep(c, i):
            h, r = c
            o, r2 = fused_add_rms_norm(h, r, wnorm, 1e-5)
            return (h + o * jnp.bfloat16(1e-30), r2)
        s, rtt = device_bench(nstep, (hid8, jnp.zeros_like(hid8)),
                              slow=True)
        rtts.append(rtt)
        row(f"PGLUE fused_add_rms_norm m={M8}", s * 1e3, 2 * LAYERS, "")

        gup = jax.random.normal(key, (M8, 2 * INTER), dtype=jnp.bfloat16)

        def astep(c, i):
            g = c
            o = silu_and_mul(g)
            return g + jnp.pad(o, ((0, 0), (0, INTER))) * \
                jnp.bfloat16(1e-30)
        s, rtt = device_bench(astep, gup, slow=True)
        rtts.append(rtt)
        row(f"PGLUE silu_and_mul m={M8}", s * 1e3, LAYERS, "")

        rope = get_rope(HEAD_DIM, HEAD_DIM, 4096, 10000.0)
        # Same shape llama.py hands rope: heads split out.
        q8 = jax.random.normal(key, (256, 32, HEADS, HEAD_DIM),
                               dtype=jnp.bfloat16)
        k8 = jax.random.normal(key, (256, 32, KV_HEADS, HEAD_DIM),
                               dtype=jnp.bfloat16)
        pos8 = jnp.tile(jnp.arange(32, dtype=jnp.int32)[None], (256, 1))

        def rstep(c, i):
            qq, kk = c
            q2, k2 = rope(pos8, qq, kk)
            return (qq + q2 * jnp.bfloat16(1e-30),
                    kk + k2 * jnp.bfloat16(1e-30))
        s, rtt = device_bench(rstep, (q8, k8), slow=True)
        rtts.append(rtt)
        row(f"PGLUE rope 256x32", s * 1e3, LAYERS, "")

        def qstep(c, i):
            h = c
            x8, xs = _quantize_activations_int8(h)
            return h + (x8[:, :1] * xs[:, :1]).astype(jnp.bfloat16) * \
                jnp.bfloat16(1e-30)
        s, rtt = device_bench(qstep, hid8, slow=True)
        rtts.append(rtt)
        # 4 matmuls quantize per layer (qkv/o/gate_up/down inputs).
        row(f"PGLUE act int8 quant m={M8}", s * 1e3, 4 * LAYERS, "")

        def permstep(c, i):
            # The same blockwise [R, pack] transpose _gptq_prologue
            # applies to x per 128-group (gs=128 -> R=16, pack=8).
            h = c
            xp = h.reshape(M8, HIDDEN // 128, 16, 8).swapaxes(
                2, 3).reshape(M8, HIDDEN)
            return h + xp * jnp.bfloat16(1e-30)
        s, rtt = device_bench(permstep, hid8, slow=True)
        rtts.append(rtt)
        row(f"PGLUE x plane-permute m={M8}", s * 1e3, 4 * LAYERS, "")

    # --- one full decoder layer (GPTQ), as the engine runs it ---
    if want("layer"):
        from types import SimpleNamespace
        from aphrodite_tpu.modeling.models.llama import LlamaDecoderLayer
        from aphrodite_tpu.modeling.layers.quantization.gptq import (
            GPTQConfig)
        from aphrodite_tpu.modeling.hf_loader import (
            initialize_dummy_params)
        from aphrodite_tpu.modeling.input_metadata import InputMetadata
        cfg = SimpleNamespace(
            hidden_size=HIDDEN, intermediate_size=INTER,
            num_attention_heads=HEADS, num_key_value_heads=KV_HEADS,
            rms_norm_eps=1e-5, rope_theta=10000.0,
            max_position_embeddings=4096, hidden_act="silu",
            sliding_window=None, rope_scaling=None)
        layer = LlamaDecoderLayer(
            cfg, 0, dtype=jnp.bfloat16,
            linear_method=GPTQConfig(4, 128).get_linear_method())

        class _M:                      # initialize_dummy_params surface
            def __init__(self, lyr):
                self._lyr = lyr

            def init_params(self):
                return self._lyr.init()
        lparams = initialize_dummy_params(_M(layer), seed=0)
        hid3 = jax.random.normal(key, (B, 1, HIDDEN),
                                 dtype=jnp.bfloat16)
        pos = jnp.full((B, 1), ctx - 1, dtype=jnp.int32)
        meta = InputMetadata(
            slot_mapping=slots,
            block_tables=tables,
            context_lens=ctx_lens,
            is_prompt=False)

        def lyrstep(c, i):
            h, res, kpp, vpp = c
            out, res, (kpp, vpp) = layer(lparams, pos, h, res,
                                         (kpp, vpp), meta)
            return (hid3 + out * jnp.bfloat16(1e-30), res, kpp, vpp)
        s, rtt = device_bench(
            lyrstep, (hid3, jnp.zeros_like(hid3), kp + 0, vp + 0))
        rtts.append(rtt)
        row(f"FULL decoder layer (gptq) b={B}", s * 1e3, LAYERS, "")

    # --- the REAL burst step, whole-program, with ablations ---
    # Reproduces ModelRunner._burst_step exactly (32-layer model, logits
    # on all rows, fused sample, metadata advance) inside the same
    # fori_loop structure the engine's lax.scan burst compiles to, so
    # the gap between SUM(components) and the engine's measured ms/step
    # is decomposed: (a) model-only vs 32x single-layer = cross-layer
    # glue + scan carry handling; (b) +logits+sample vs model-only =
    # head overhead in situ; (c) full burst vs bench.py's wall
    # ms/step = host-side remainder.
    if want("burst"):
        from types import SimpleNamespace as _NS
        from aphrodite_tpu.modeling.models.llama import LlamaForCausalLM
        from aphrodite_tpu.modeling.layers.quantization.gptq import (
            GPTQConfig)
        from aphrodite_tpu.modeling.hf_loader import (
            initialize_dummy_params)
        from aphrodite_tpu.modeling.input_metadata import InputMetadata
        from aphrodite_tpu.modeling.layers.sampler import (
            Sampler, fused_sample)
        from aphrodite_tpu.modeling.sampling_metadata import (
            SamplingMetadata)
        from aphrodite_tpu.common.sampling_params import SamplingParams
        from aphrodite_tpu.common.sequence import SequenceData

        cfg = _NS(
            architectures=["LlamaForCausalLM"], vocab_size=VOCAB,
            hidden_size=HIDDEN, intermediate_size=INTER,
            num_hidden_layers=LAYERS, num_attention_heads=HEADS,
            num_key_value_heads=KV_HEADS, rms_norm_eps=1e-5,
            rope_theta=10000.0, max_position_embeddings=4096,
            tie_word_embeddings=False, hidden_act="silu")
        model = LlamaForCausalLM(
            cfg, dtype=jnp.bfloat16,
            linear_method=GPTQConfig(4, GROUP).get_linear_method())
        mparams = initialize_dummy_params(model, seed=0)
        pages_per_seq_b = -(-max(8, -(-ctx // PAGE)) // 8) * 8
        npg = B * pages_per_seq_b + 1
        kv_caches = [
            (jnp.zeros((npg, PAGE, KV_HEADS * HEAD_DIM), jnp.bfloat16),
             jnp.zeros((npg, PAGE, KV_HEADS * HEAD_DIM), jnp.bfloat16))
            for _ in range(LAYERS)
        ]
        tbl = jnp.asarray(
            np.arange(B * pages_per_seq_b).reshape(B, pages_per_seq_b),
            jnp.int32)
        meta0 = InputMetadata(
            slot_mapping=jnp.asarray(
                np.arange(B) * pages_per_seq_b * PAGE + (ctx - 1),
                jnp.int32),
            block_tables=tbl,
            context_lens=jnp.full((B,), ctx, jnp.int32),
            is_prompt=False)
        ids0 = jnp.ones((B, 1), jnp.int32)
        pos0 = jnp.full((B, 1), ctx - 1, jnp.int32)

        sp = SamplingParams(temperature=0.0, max_tokens=16,
                            ignore_eos=True)
        sampling = SamplingMetadata(
            seq_groups=[([i], sp) for i in range(B)],
            seq_data={i: SequenceData([1, 2, 3]) for i in range(B)},
            prompt_lens=[])
        splr = Sampler(VOCAB)
        plan = splr.plan(sampling, pad_to=B)
        gmask = jnp.ones((B,), bool)

        def advance(meta, pos):
            # Clamp exactly like ModelRunner._burst_step: positions pin
            # at the last allocated slot so the walk stays in-table.
            pos2 = jnp.minimum(pos + 1,
                               pages_per_seq_b * PAGE - 1)
            p = pos2[:, 0]
            page = jnp.take_along_axis(
                meta.block_tables, (p // PAGE)[:, None], axis=1)[:, 0]
            return meta.replace(
                slot_mapping=page * PAGE + p % PAGE,
                context_lens=p + 1), pos2

        def model_only(c, t):
            ids, pos, meta, kv, prm = c
            hidden, kv = model(prm, ids, pos, kv, meta)
            # Feedback: next ids depend on hidden (keeps the loop live);
            # metadata advances exactly as the real burst does.
            ids = jnp.maximum(
                ids, (hidden[:, :1, 0] * jnp.bfloat16(0)).astype(
                    jnp.int32))
            meta, pos2 = advance(meta, pos)
            return (ids, pos2, meta, kv, prm)

        def full_burst(c, t):
            ids, pos, meta, kv, prm = c
            hidden, kv = model(prm, ids, pos, kv, meta)
            flat = hidden.reshape(-1, hidden.shape[-1])
            logits = model.compute_logits(prm, flat)
            packed, _ = fused_sample(
                logits, plan.tensors, plan.key_parts.at[:, 1].add(t),
                max_best_of=plan.max_best_of, num_topk=plan.num_topk,
                need_logprobs=False)
            next_tok = jnp.where(gmask, packed[:, 0], packed[:, 1])
            ids = next_tok[:, None].astype(jnp.int32)
            meta, pos2 = advance(meta, pos)
            return (ids, pos2, meta, kv, prm)

        # ONE state threaded through both ablations with donation: the
        # KV pool is over half of HBM, so un-donated loops OOM, and jit
        # must not close over the params (they'd be baked into the
        # program as constants).
        state = (ids0, pos0, meta0, kv_caches, mparams)
        for nm, fn in (("model-only(32L)", model_only),
                       ("FULL burst step", full_burst)):
            s, rtt, state = device_bench(fn, state, slow=True,
                                         donate=True)
            rtts.append(rtt)
            row(f"BURST {nm} b={B}", s * 1e3, 1, "")
        del state

    # --- speculative verify A/B: the widened k+1-row verify dispatch
    # vs the classic 1-row decode (same model, same ragged work-list
    # grid; the verify arm rides spec_verify=True, i.e. the slot-wise
    # KV scatter instead of the fused in-kernel write, exactly as
    # ModelRunner.dispatch_steps dispatches it). The headline is
    # the BREAK-EVEN acceptance: cost_verify/cost_classic - 1 drafted
    # tokens must land per step before speculation pays on-device
    # (host-side draft + rejection are noise next to a dispatch). ---
    if want("spec"):
        from types import SimpleNamespace as _NSP
        from aphrodite_tpu.common import flags
        from aphrodite_tpu.modeling.models.llama import LlamaForCausalLM
        from aphrodite_tpu.modeling.layers.quantization.gptq import (
            GPTQConfig)
        from aphrodite_tpu.modeling.hf_loader import (
            initialize_dummy_params)
        from aphrodite_tpu.modeling.input_metadata import InputMetadata
        from aphrodite_tpu.ops.pallas.paged_attention import (
            build_decode_work_list, choose_pages_per_chunk)

        SPEC_K = flags.get_int("APHRODITE_SPEC_K")
        cfg_s = _NSP(
            architectures=["LlamaForCausalLM"], vocab_size=VOCAB,
            hidden_size=HIDDEN, intermediate_size=INTER,
            num_hidden_layers=LAYERS, num_attention_heads=HEADS,
            num_key_value_heads=KV_HEADS, rms_norm_eps=1e-5,
            rope_theta=10000.0, max_position_embeddings=4096,
            tie_word_embeddings=False, hidden_act="silu")
        smodel = LlamaForCausalLM(
            cfg_s, dtype=jnp.bfloat16,
            linear_method=GPTQConfig(4, GROUP).get_linear_method())
        sprm = initialize_dummy_params(smodel, seed=0)
        # Pages must cover position ctx-1+k (the scheduler's spec
        # reservation contract); table width rides the 8-page bucket.
        pps_data = -(-(ctx + SPEC_K) // PAGE)
        width = -(-pps_data // 8) * 8
        npg_s = B * pps_data + 1
        skv = [
            (jnp.zeros((npg_s, PAGE, KV_HEADS * HEAD_DIM),
                       jnp.bfloat16),
             jnp.zeros((npg_s, PAGE, KV_HEADS * HEAD_DIM),
                       jnp.bfloat16))
            for _ in range(LAYERS)
        ]

        def verify_geom(rows_per_seq):
            """(ids, pos, metadata) for B sequences x rows_per_seq
            consecutive verify rows, built as _prepare_spec_verify
            does: row j carries position ctx-1+j, attends with
            ctx_lens = pos+1, and all rows of a sequence share its
            pages."""
            j = np.tile(np.arange(rows_per_seq), B)
            sidx = np.repeat(np.arange(B), rows_per_seq)
            nrows = B * rows_per_seq
            pos = (ctx - 1 + j).astype(np.int32)
            page = sidx * pps_data + pos // PAGE
            slots = (page * PAGE + pos % PAGE).astype(np.int32)
            ctxl = (pos + 1).astype(np.int32)
            tbl = np.zeros((nrows, width), np.int32)
            tbl[:, :pps_data] = (sidx[:, None] * pps_data +
                                 np.arange(pps_data)[None, :])
            counts = (-(-ctxl // PAGE)).tolist()
            ppc = choose_pages_per_chunk(width, PAGE, LANE_BYTES)
            work = build_decode_work_list(counts, ppc)
            meta = InputMetadata(
                slot_mapping=jnp.asarray(slots),
                block_tables=jnp.asarray(tbl),
                context_lens=jnp.asarray(ctxl),
                is_prompt=False,
                decode_work=tuple(jnp.asarray(w) for w in work),
                decode_ppc=ppc,
                spec_verify=rows_per_seq > 1)
            return (jnp.ones((nrows, 1), jnp.int32),
                    jnp.asarray(pos[:, None]), meta)

        spec_ms = {}
        for nm, rps in (("classic 1-row", 1),
                        (f"verify k={SPEC_K}", SPEC_K + 1)):
            sids, spos, smeta = verify_geom(rps)

            def sstep(c, i, spos=spos, smeta=smeta):
                ids, kv, prm = c
                hidden, kv = smodel(prm, ids, spos, kv, smeta)
                flat = hidden.reshape(-1, hidden.shape[-1])
                logits = smodel.compute_logits(prm, flat)
                ids = jnp.maximum(
                    ids, (logits[:, :1] * 0).astype(jnp.int32))
                return (ids, kv, prm)

            # Three chained device_bench calls -> three independent
            # slope samples (bench.py round-5 discipline) on the same
            # donated KV pool.
            state = (sids, skv, sprm)
            samples = []
            for _ in range(3):
                s, rtt, state = device_bench(sstep, state, slow=True,
                                             donate=True)
                rtts.append(rtt)
                samples.append(round(s * 1e3, 3))
            _, skv, sprm = state
            spec_ms[rps] = samples
            med = sorted(samples)[1]
            row(f"SPEC {nm} b={B}", med, 1,
                f"{B * rps} rows" + (", spec_verify" if rps > 1
                                     else ""))
        del skv, state
        classic_s, verify_s = (sorted(spec_ms[1])[1],
                               sorted(spec_ms[SPEC_K + 1])[1])
        break_even = verify_s / classic_s - 1.0
        print(f"\n=== spec verify A/B b={B} ctx={ctx}: classic "
              f"{classic_s:.3f} ms/step, verify(k={SPEC_K}) "
              f"{verify_s:.3f} ms/step -> break-even "
              f"{break_even:.2f} accepted tok/step ===")
        print(json.dumps({
            "metric": "spec_verify_cost_x",
            "value": round(verify_s / classic_s, 3),
            "unit": "x classic dispatch",
            "samples": [round(v / c, 3) for v, c in
                        zip(spec_ms[SPEC_K + 1], spec_ms[1])],
            "n_runs": 3,
            "detail": {"batch": B, "ctx": ctx, "spec_k": SPEC_K,
                       "classic_ms_samples": spec_ms[1],
                       "verify_ms_samples": spec_ms[SPEC_K + 1],
                       "break_even_accepted_tok_per_step":
                       round(break_even, 2)},
        }))

    # --- the REAL whole prompt step (one scheduling round) ---
    if want("pstep"):
        from types import SimpleNamespace as _NS2
        from aphrodite_tpu.modeling.models.llama import LlamaForCausalLM
        from aphrodite_tpu.modeling.layers.quantization.gptq import (
            GPTQConfig)
        from aphrodite_tpu.modeling.hf_loader import (
            initialize_dummy_params)
        from aphrodite_tpu.modeling.input_metadata import InputMetadata

        import aphrodite_tpu.modeling.models.llama as LM

        cfg2 = _NS2(
            architectures=["LlamaForCausalLM"], vocab_size=VOCAB,
            hidden_size=HIDDEN, intermediate_size=INTER,
            num_hidden_layers=LAYERS, num_attention_heads=HEADS,
            num_key_value_heads=KV_HEADS, rms_norm_eps=1e-5,
            rope_theta=10000.0, max_position_embeddings=4096,
            tie_word_embeddings=False, hidden_act="silu")
        # Bench prefill geometry: 256 seqs x 32 tokens (8192 tokens, 2
        # pages/seq), page-aligned -> the whole-page writer engages.
        PB, PS = 256, 32
        ppp = PS // PAGE
        npg2 = PB * ppp + 1
        cells = PB * ppp

        def fresh_meta():
            return InputMetadata(
                slot_mapping=jnp.asarray(np.arange(PB * PS), jnp.int32),
                block_tables=jnp.asarray(
                    np.arange(PB * ppp).reshape(PB, ppp), jnp.int32),
                context_lens=jnp.zeros((PB,), jnp.int32),
                prompt_lens=jnp.full((PB,), PS, jnp.int32),
                prefill_cells=(
                    jnp.asarray(np.arange(cells), jnp.int32),
                    jnp.asarray(np.arange(cells), jnp.int32),
                    jnp.full((cells,), PAGE, jnp.int32)),
                is_prompt=True)

        def measure_pstep(label, patch=None, with_kv=True):
            """One whole prompt step, optionally with a glue op patched
            out of the MODEL (fresh build so rope factories re-run).
            full - ablated = the op's true IN-CONTEXT cost, fusion and
            all — the standalone pglue rows overestimate ops XLA fuses
            into their consumers."""
            saved = {}
            for name, fn in (patch or {}).items():
                saved[name] = getattr(LM, name)
                setattr(LM, name, fn)
            try:
                pmodel = LM.LlamaForCausalLM(
                    cfg2, dtype=jnp.bfloat16,
                    linear_method=GPTQConfig(4, GROUP)
                    .get_linear_method())
                prm = initialize_dummy_params(pmodel, seed=0)
                kv = [
                    (jnp.zeros((npg2, PAGE, KV_HEADS * HEAD_DIM),
                               jnp.bfloat16),
                     jnp.zeros((npg2, PAGE, KV_HEADS * HEAD_DIM),
                               jnp.bfloat16))
                    for _ in range(LAYERS)
                ] if with_kv else None
                pids = jnp.ones((PB, PS), jnp.int32)
                ppos = jnp.tile(
                    jnp.arange(PS, dtype=jnp.int32)[None], (PB, 1))

                def prompt_step(c, t):
                    ids, pos, meta, kvs, prm2 = c
                    hidden, kvs = pmodel(prm2, ids, pos, kvs, meta)
                    flat = hidden.reshape(-1, hidden.shape[-1])
                    sel = jnp.arange(PB, dtype=jnp.int32) * PS + (PS - 1)
                    logits = pmodel.compute_logits(
                        prm2, jnp.take(flat, sel, axis=0))
                    ids = jnp.maximum(
                        ids, (logits[:, :1] * 0).astype(jnp.int32))
                    return (ids, pos, meta, kvs, prm2)

                s, rtt, _ = device_bench(
                    prompt_step, (pids, ppos, fresh_meta(), kv, prm),
                    slow=True, donate=True)
                rtts.append(rtt)
                row(f"PROMPT step {label}", s * 1e3, 1, "")
                return s
            finally:
                for name, fn in saved.items():
                    setattr(LM, name, fn)

        class _IdentityRope:
            def __call__(self, positions, q, k):
                return q, k

        # Each variant costs ~2 min (model build + two trip-count
        # compiles of the full 8k step); APHRODITE_PSTEP variants=
        # comma list selects a subset so runs fit the shell timeout.
        from aphrodite_tpu.common import flags
        wanted = flags.get_str("APHRODITE_PSTEP").split(",")
        if "full" in wanted:
            measure_pstep(f"{PB}x{PS} (8k tok, 32L)")
        if "nokv" in wanted:
            measure_pstep(f"{PB}x{PS} NO-KV-write", with_kv=False)
        if "nosilu" in wanted:
            measure_pstep(
                f"{PB}x{PS} no-silu",
                patch={"silu_and_mul":
                       lambda x: x[..., :x.shape[-1] // 2]})
        if "nonorm" in wanted:
            measure_pstep(
                f"{PB}x{PS} no-norm",
                patch={"fused_add_rms_norm":
                       lambda h, r, w, eps:
                       (h, h if r is None else h + r),
                       "rms_norm": lambda x, w, eps: x})
        if "norope" in wanted:
            measure_pstep(
                f"{PB}x{PS} no-rope",
                patch={"get_rope": lambda *a, **k: _IdentityRope()})
        if "noattn" in wanted:
            class _NoAttention:
                def __init__(self, *a, **k):
                    pass

                def __call__(self, q, k, v, k_pages, v_pages, meta):
                    # Keeps shapes: q is already [b, s, H*d].
                    return q, k_pages, v_pages
            measure_pstep(
                f"{PB}x{PS} no-attention(+write)",
                patch={"PagedAttention": _NoAttention})

    # --- elementwise glue: rmsnorm x2 + silu_and_mul per layer ---
    if want("glue"):
        from aphrodite_tpu.modeling.layers.layernorm import rms_norm
        from aphrodite_tpu.modeling.layers.activation import silu_and_mul
        wn = jnp.ones((HIDDEN,), jnp.bfloat16)
        g = jax.random.normal(key, (B, 2 * INTER), dtype=jnp.bfloat16)

        def gstep(c, i):
            h, gg = c
            a = rms_norm(h, wn, 1e-5)
            b = rms_norm(a, wn, 1e-5)
            act = silu_and_mul(gg)
            return (b + act[:, :1] * jnp.bfloat16(1e-30), gg)
        s, rtt = device_bench(gstep, (hid, g))
        rtts.append(rtt)
        row("norms+act glue (x2 rmsnorm + silu_mul)", s * 1e3, LAYERS,
            "")

    # --- report ---
    total_attr = 0.0
    print(f"\n=== decode-step attribution (batch={B}, ctx={ctx}, "
          f"backend={jax.default_backend()}, "
          f"rtt~{np.median(rtts) * 1e3:.0f}ms) ===")
    print(f"{'component':54s} {'us/call':>9s} {'xN':>4s} "
          f"{'ms/step':>8s}  note")
    # SUM counts each component of one real decode step exactly once:
    # the bf16-dense roofline rows, the prefill-variant writer, and the
    # FULL-layer cross-check (which already contains the components)
    # are reference rows, not addends.
    excluded = ("bf16 dense", "kv_write prefill-window", "FULL decoder",
                "PREFILL", "BURST", "PROMPT", "SPEC", "W4A8",
                "ATTN A/B", "QMM A/B")
    for name, ms_call, n, ms_step, note in rows:
        print(f"{name:54s} {ms_call * 1e3:9.1f} {n:4d} {ms_step:8.3f}  "
              f"{note}")
        if not any(name.startswith(e) for e in excluded):
            total_attr += ms_step
    print(f"{'SUM (attributed, decode step)':54s} {'':9s} {'':4s} "
          f"{total_attr:8.3f}")
    ideal = 2 * 7.24e9 * B / 197e12 * 1e3
    print(f"roofline: {ideal:.1f} ms/step for {B} tok "
          f"(7.24 GFLOP/tok bf16 @ 197 TF/s)")
    print(json.dumps({"attributed_ms_per_step": round(total_attr, 2),
                      "batch": B, "ctx": ctx}))


if __name__ == "__main__":
    main()
