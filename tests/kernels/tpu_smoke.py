"""Compiled-on-TPU kernel smoke: runs the Pallas kernels NON-interpret
on the real chip and checks numerics against the XLA references.

Run from the repo root on a machine with a chip (the pytest suite
forces CPU, where this script refuses to run):
    python tests/kernels/tpu_smoke.py
Exit code 0 = all kernels compiled and matched; non-zero otherwise,
including when there is no chip.
"""
import contextlib
import sys

import numpy as np

sys.path.insert(0, ".")


def kda_checks(check, failures, rs) -> None:
    """`ops/pallas/kda.py` at the served shape (32 heads of 128 x 128,
    12,288 convolution channels, float32 state): a prompt chunk of
    1,024 tokens from zeros and the same tokens as two calls through
    the slot (a 64-token kernel chunk's boundary inside each), against
    the recurrence a token at a time; decays from `exp(-4.5)` a token
    down, so that `exp(-G)` over a chunk would overflow; a decode step
    of 64 rows (60 live on scattered slots, 4 pad rows on the scratch
    one) against the one-step update, NaN in every slot no row holds.
    The arrays hold three layers, the calls name the middle one."""
    import jax
    import jax.numpy as jnp
    from aphrodite_tpu.ops.pallas import kda
    heads, d, slots, t, at = 32, 128, 96, 1024, 1

    def f(*shape):
        return jnp.asarray(rs.randn(*shape), jnp.float32)

    def unit(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    def inputs(*lead):
        return (unit(f(*lead, heads, d)) * d ** -0.5,
                unit(f(*lead, heads, d)), f(*lead, heads, d),
                -jnp.exp(jnp.asarray(rs.uniform(-9.0, 1.5,
                                                lead + (heads, d)),
                                     jnp.float32)),
                jnp.asarray(rs.uniform(0, 1, lead + (heads,)),
                            jnp.float32))
    args = inputs(1, t)
    state = jnp.full((3, slots + 1, heads, d, d), jnp.nan, jnp.float32)
    one, yes = jnp.asarray([7], jnp.int32), jnp.asarray([1], jnp.int32)
    o_ref, s_ref = kda.kda_chunk_ref(*args, state, one, yes, at)
    o_got, s_got = kda.kda_chunk(*args, state, one, yes, at)
    scale = float(jnp.abs(o_ref).max())
    check("kda chunk, 1,024 tokens (of the largest output)",
          np.asarray(o_ref) / scale, np.asarray(o_got) / scale, tol=1e-4)
    check("kda chunk, the slot's state", np.asarray(s_ref)[at, 7],
          np.asarray(s_got)[at, 7], tol=1e-4)
    half = 576          # nine kernel chunks, then seven
    o1, s1 = kda.kda_chunk(*(a[:, :half] for a in args), state, one, yes,
                           at)
    o2, s2 = kda.kda_chunk(*(a[:, half:] for a in args), s1, one, 0 * yes,
                           at)
    check("kda chunk, two calls through the slot",
          np.asarray(o_ref) / scale,
          np.concatenate([o1, o2], axis=1) / scale, tol=1e-4)
    if not (np.isnan(np.asarray(s2)[at, [0, 6, 8, slots]]).all() and
            np.isnan(np.asarray(s2)[[0, 2]]).all()):
        failures.append(("kda chunk touched a slot no row holds", 0))
    rows, live = 64, 60
    owners = rs.permutation(slots)[:live]
    slot = np.full((rows,), slots, np.int32)
    slot[:live] = owners
    held = np.zeros(slots + 1, bool)
    held[owners] = True
    held[slots] = True
    mine = held[None, :] & (np.arange(3) == at)[:, None]
    st = jnp.where(mine[..., None, None, None],
                   f(3, slots + 1, heads, d, d), jnp.nan)
    tl = jnp.where(mine[..., None, None], f(3, slots + 1, 4, 3 * heads * d),
                   jnp.nan).astype(jnp.bfloat16)
    x = f(rows, 3 * heads * d).astype(jnp.bfloat16)
    step = inputs(rows)
    want = kda.kda_update_ref(x, *step, st, tl, jnp.asarray(slot), at)
    got = kda.kda_update(x, *step, st, tl, jnp.asarray(slot), at)
    check("kda update, the rows' outputs", np.asarray(want[0])[:live],
          np.asarray(got[0])[:live], tol=1e-4)
    check("kda update, the live slots' state",
          np.asarray(want[1])[at, owners], np.asarray(got[1])[at, owners],
          tol=1e-4)
    check("kda update, the live slots' tail",
          np.asarray(want[2], np.float32)[at, owners],
          np.asarray(got[2], np.float32)[at, owners], tol=1e-6)
    if not (np.isnan(np.asarray(got[1])[~mine]).all() and
            np.isnan(np.asarray(got[2], np.float32)[~mine]).all()):
        failures.append(("kda update touched a slot no row holds", 0))


def main() -> int:
    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    print(f"platform={dev.platform} device_kind={dev.device_kind!r} "
          f"device_count={len(jax.devices())}")
    if dev.platform != "tpu":
        print(f"FAIL: platform is {dev.platform!r}; the compiled "
              "kernels need a tpu")
        return 1

    from aphrodite_tpu.modeling.layers.quantization.gptq import (
        GPTQConfig, GPTQLinearMethod)
    from aphrodite_tpu.ops.attention import paged_decode_attention_ref
    from aphrodite_tpu.ops.pallas.paged_attention import (
        paged_decode_attention)
    from aphrodite_tpu.ops.pallas.quant_matmul import gptq_matmul

    rs = np.random.RandomState(0)
    failures = []

    @contextlib.contextmanager
    def section(name):
        """A kernel the compiler refuses must not hide the ones after
        it: record the exception as this section's failure and go on."""
        try:
            yield
        except Exception as e:
            msg = f"{type(e).__name__}: {str(e).splitlines()[0][:300]}"
            print(f"{name}: RAISED {msg}")
            failures.append((name, msg))

    # -- decode attention kernels, bf16 + int8 KV, alibi --
    Hq, Hkv, d, page, pps, pages, B = 32, 8, 128, 32, 4, 256, 24
    q = jnp.asarray(rs.randn(B, Hq, d) * 0.1, jnp.bfloat16)
    kp = jnp.asarray(rs.randn(pages, page, Hkv * d) * 0.1, jnp.bfloat16)
    vp = jnp.asarray(rs.randn(pages, page, Hkv * d) * 0.1, jnp.bfloat16)
    bt = jnp.asarray(rs.randint(0, pages, (B, pps)), jnp.int32)
    ctx_np = rs.randint(1, pps * page, (B,)).astype(np.int32)
    ctx_np[0] = 0          # padded row: single-chunk path must still
    ctx = jnp.asarray(ctx_np)  # wait its prefetched DMAs and mask all
    scale = d ** -0.5

    def oracle(*a, **k):
        # The jnp reference NaNs on fully-masked (ctx==0) rows; the
        # kernels output zeros there.
        out = np.asarray(paged_decode_attention_ref(*a, **k), np.float32)
        out[np.asarray(ctx) == 0] = 0.0
        return out

    def check(name, ref_, got_, tol=3e-2):
        err = np.abs(ref_ - got_).max()
        print(f"{name}: max err {err:.2e}")
        if not (err < tol):          # NaN-rejecting
            failures.append((name, err))

    with section("decode attention (classic grid)"):
        ref = oracle(q, kp, vp, bt, ctx, scale)

        for name, ppc in (("tokenmajor", 2),
                          ("tokenmajor single-chunk", 4)):
            got = np.asarray(paged_decode_attention(
                q, kp, vp, bt, ctx, scale=scale,
                pages_per_chunk=ppc), np.float32)
            check(f"{name} bf16", ref, got)

        S = 0.05
        kp8 = jnp.clip(jnp.round(kp.astype(jnp.float32) / S), -127,
                       127).astype(jnp.int8)
        vp8 = jnp.clip(jnp.round(vp.astype(jnp.float32) / S), -127,
                       127).astype(jnp.int8)
        ref8 = oracle(q, kp8.astype(jnp.float32) * S,
                      vp8.astype(jnp.float32) * S, bt, ctx, scale)
        got8 = np.asarray(paged_decode_attention(
            q, kp8, vp8, bt, ctx, scale=scale, kv_scale=S,
            pages_per_chunk=2), np.float32)
        check("tokenmajor int8 KV", ref8, got8)

        slopes = jnp.asarray([2.0 ** -(i / 4 + 1) for i in range(Hq)],
                             jnp.float32)
        refa = oracle(q, kp, vp, bt, ctx, scale, alibi_slopes=slopes)
        gota = np.asarray(paged_decode_attention(
            q, kp, vp, bt, ctx, slopes, scale=scale, pages_per_chunk=2),
            np.float32)
        check("tokenmajor alibi", refa, gota)

    with section("decode attention (ragged grid)"):
        # -- ragged work-list grid (compiled): mixed real chunk counts,
        #    a ctx=0 row's masked item, dead list padding --
        from aphrodite_tpu.ops.pallas.paged_attention import (
            build_decode_work_list)
        pages_i = [max(1, -(-int(c) // page)) for c in ctx_np]
        for ppcr in (2, 4):
            workr = build_decode_work_list(pages_i, ppcr)
            gotr = np.asarray(paged_decode_attention(
                q, kp, vp, bt, ctx, scale=scale, pages_per_chunk=ppcr,
                work_items=workr), np.float32)
            check(f"ragged ppc={ppcr} bf16", ref, gotr)
        got8r = np.asarray(paged_decode_attention(
            q, kp8, vp8, bt, ctx, scale=scale, kv_scale=S,
            pages_per_chunk=2,
            work_items=build_decode_work_list(pages_i, 2)), np.float32)
        check("ragged int8 KV", ref8, got8r)
        # The classic online-softmax multiply: the reference the
        # exponent-bias rescale is held bit-equal to.
        gotm = np.asarray(paged_decode_attention(
            q, kp, vp, bt, ctx, scale=scale, pages_per_chunk=2,
            work_items=build_decode_work_list(pages_i, 2), amla=False),
            np.float32)
        check("ragged bf16, classic rescale multiply", ref, gotm)

    with section("decode attention (512-token items, live pages)"):
        # -- the served item: pages of 16 on a table 88 wide (no
        #    multiple of the policy's 32-page item), a row's last item
        #    with 1, some and all pages live, NaN in every page no row
        #    holds (the pad entries' page among them): a dead page
        #    copied, or a ring slot not clean under p = 0, reads NaN.
        #    One head block (whole-page descriptors), two (lane
        #    slices), and 8-bit pages. --
        from aphrodite_tpu.ops.pallas.paged_attention import (
            build_decode_work_list, choose_pages_per_chunk, lane_bytes_of)
        ctx5 = np.array([1025, 1100, 1408, 513, 0, 40, 1024, 777],
                        np.int32)
        cnt5 = -(-ctx5 // 16)
        tbl5 = np.zeros((len(ctx5), 88), np.int32)
        used = 1
        for i, n in enumerate(cnt5):
            tbl5[i, :n] = np.arange(used, used + n)
            used += n
        mask5 = ctx5 > 0
        for hkv5, dt5 in ((8, jnp.bfloat16), (16, jnp.bfloat16),
                          (8, jnp.int8)):
            raw = rs.randn(used + 4, 16, hkv5 * d) * (
                20 if dt5 == jnp.int8 else 0.1)
            kv5 = [jnp.asarray(np.round(raw) if dt5 == jnp.int8 else raw,
                               dt5),
                   jnp.asarray(np.round(raw[::-1]) if dt5 == jnp.int8
                               else raw[::-1], dt5)]
            q5 = jnp.asarray(rs.randn(len(ctx5), 32, d) * 0.1,
                             jnp.bfloat16)
            s5 = 0.05 if dt5 == jnp.int8 else 1.0
            ref5 = np.asarray(paged_decode_attention_ref(
                q5, kv5[0].astype(jnp.float32) * s5,
                kv5[1].astype(jnp.float32) * s5, jnp.asarray(tbl5),
                jnp.asarray(np.maximum(ctx5, 1)), scale), np.float32)
            if dt5 != jnp.int8:     # every page no row holds: NaN
                dead = np.ones(used + 4, bool)
                dead[1:used] = False
                kv5 = [x.at[jnp.asarray(np.flatnonzero(dead))].set(
                    jnp.nan) for x in kv5]
            ppc5 = choose_pages_per_chunk(
                88, 16, lane_bytes_of(hkv5, d, dt5))
            got5 = np.asarray(paged_decode_attention(
                q5, kv5[0], kv5[1], jnp.asarray(tbl5),
                jnp.asarray(ctx5), scale=scale, kv_scale=s5,
                pages_per_chunk=ppc5,
                work_items=build_decode_work_list(cnt5, ppc5)),
                np.float32)
            tag5 = f"ragged ppc={ppc5} Hkv={hkv5} {jnp.dtype(dt5).name}"
            check(tag5, ref5[mask5], got5[mask5])
            check(tag5 + ", the pad row reads 0", 0.0, got5[~mask5])

    with section("decode attention (SmallThinker: 4 KV heads, page groups)"):
        # -- the decode kernel as `smallthinker-21ba3b-bf16.batch-8k`
        #    calls it, once a layer with its page group's table: 28
        #    query heads over 4 KV heads of 128, pages of 16, 24 rows,
        #    the fused write. A full group's table 576 wide at contexts
        #    of 8,193-8,960 (513-560 pages); a window group's 320 wide
        #    holding 257-258 pages, the window of 4,096 as a mask (the
        #    first page kept is partly passed). NaN in every page no
        #    row holds. --
        from aphrodite_tpu.ops.pallas.paged_attention import (
            build_decode_work_list, choose_pages_per_chunk, lane_bytes_of)
        hq6, hkv6, rows6 = 28, 4, 24
        ppc6 = choose_pages_per_chunk(576, 16,
                                      lane_bytes_of(hkv6, d, jnp.bfloat16))
        for tag6, width6, window6 in (("full", 576, None),
                                      ("window", 320, 4096)):
            whole = rs.randint(8193, 8961, (rows6,)).astype(np.int32)
            whole[:2] = (8193, 8960)
            # a window group's table starts at the page that holds the
            # oldest key of the window; its context counts from there
            let_go = np.maximum(0, whole - 1 - window6 + 1) // 16 * 16 \
                if window6 else np.zeros_like(whole)
            ctx6 = whole - let_go
            cnt6 = -(-ctx6 // 16)
            tbl6 = np.zeros((rows6, width6), np.int32)
            used6 = 1
            for i, n in enumerate(cnt6):
                tbl6[i, :n] = np.arange(used6, used6 + n)
                used6 += n
            raw6 = rs.randn(used6 + 4, 16, hkv6 * d) * 0.1
            kv6 = [jnp.asarray(raw6, jnp.bfloat16),
                   jnp.asarray(raw6[::-1], jnp.bfloat16)]
            q6 = jnp.asarray(rs.randn(rows6, hq6, d) * 0.1, jnp.bfloat16)
            new6 = [jnp.asarray(rs.randn(rows6, hkv6, d) * 0.1,
                                jnp.bfloat16) for _ in range(2)]
            # the reference after the slot-mapped write of the new token
            slots6 = jnp.asarray(
                tbl6[np.arange(rows6), (ctx6 - 1) // 16] * 16 +
                (ctx6 - 1) % 16)
            from aphrodite_tpu.ops.kv_cache import write_to_kv_cache
            wk6, wv6 = write_to_kv_cache(new6[0], new6[1], kv6[0], kv6[1],
                                         slots6)
            ref6 = np.asarray(paged_decode_attention_ref(
                q6, wk6, wv6, jnp.asarray(tbl6), jnp.asarray(ctx6), scale,
                window=window6), np.float32)
            dead6 = np.ones(used6 + 4, bool)
            dead6[1:used6] = False
            kv6 = [x.at[jnp.asarray(np.flatnonzero(dead6))].set(jnp.nan)
                   for x in kv6]
            got6, gk6, gv6 = paged_decode_attention(
                q6, kv6[0], kv6[1], jnp.asarray(tbl6), jnp.asarray(ctx6),
                None, new6[0], new6[1], scale=scale,
                pages_per_chunk=ppc6,
                work_items=build_decode_work_list(cnt6, ppc6),
                window=window6)
            check(f"{tag6} group, table {width6}, ppc={ppc6}", ref6,
                  np.asarray(got6, np.float32))
            live6 = ~dead6
            check(f"{tag6} group, the fused write's pages",
                  np.asarray(wk6, np.float32)[live6],
                  np.asarray(gk6, np.float32)[live6], tol=1e-6)

    with section("EvaByte: 32 KV heads of one query row, summary pages"):
        # -- the kernels as `evabyte-6.5b-bf16.doc-5k` calls them: 32
        #    query heads over 32 KV heads of 128 (ONE query row a KV
        #    head, 4,096 lanes a token row), pages of 16, tables 192
        #    wide holding `[summary pages ; window pages]`: rows just
        #    past an edge (24 summary pages and one or two of the new
        #    window) beside rows about to reach one (16 and 128). The
        #    decode kernel with the fused write and read-only at 1 and
        #    24 rows; both page writers; the prompt's flash kernel over
        #    a chunk's own keys and over a gathered table; the pooling
        #    of a window's 128 pages into 8 against the definition. --
        from aphrodite_tpu.modeling.layers.eva_attention import (
            summarise_pages)
        from aphrodite_tpu.ops.attention import prefill_attention
        from aphrodite_tpu.ops.kv_cache import write_to_kv_cache
        from aphrodite_tpu.ops.pallas.kv_write import (write_kv_pages,
                                                       write_kv_pages_prefill)
        from aphrodite_tpu.ops.pallas.paged_attention import (
            build_decode_work_list, choose_pages_per_chunk, lane_bytes_of)
        from aphrodite_tpu.ops.pallas.prefill_attention import (
            prefill_flash_attention)
        h9, w9 = 32, 192
        ppc9 = choose_pages_per_chunk(w9, 16,
                                      lane_bytes_of(h9, d, jnp.bfloat16))
        for rows9 in (1, 24):
            ctx9 = np.array([(24 * 16 + 5, 16 * 16 + 2040, 24 * 16 + 17,
                              16 * 16 + 1283)[i % 4] for i in range(rows9)],
                            np.int32)
            cnt9 = -(-ctx9 // 16)
            tbl9 = np.zeros((rows9, w9), np.int32)
            used9 = 1
            for i, n in enumerate(cnt9):
                tbl9[i, :n] = np.arange(used9, used9 + n)
                used9 += n
            raw9 = rs.randn(used9 + 4, 16, h9 * d) * 0.1
            kv9 = [jnp.asarray(raw9, jnp.bfloat16),
                   jnp.asarray(raw9[::-1], jnp.bfloat16)]
            q9 = jnp.asarray(rs.randn(rows9, h9, d) * 0.1, jnp.bfloat16)
            new9 = [jnp.asarray(rs.randn(rows9, h9, d) * 0.1, jnp.bfloat16)
                    for _ in range(2)]
            slots9 = jnp.asarray(
                tbl9[np.arange(rows9), (ctx9 - 1) // 16] * 16 +
                (ctx9 - 1) % 16)
            wk9, wv9 = write_to_kv_cache(new9[0], new9[1], kv9[0], kv9[1],
                                         slots9)
            work9 = build_decode_work_list(cnt9, ppc9)
            common9 = (jnp.asarray(tbl9), jnp.asarray(ctx9), None)
            ref9 = np.asarray(paged_decode_attention_ref(
                q9, wk9, wv9, jnp.asarray(tbl9), jnp.asarray(ctx9), scale),
                np.float32)
            got9 = paged_decode_attention(
                q9, wk9, wv9, *common9, None, None, scale=scale,
                pages_per_chunk=ppc9, work_items=work9)
            check(f"{rows9} rows read-only, table {w9}, ppc={ppc9}", ref9,
                  np.asarray(got9, np.float32))
            got9, gk9, _ = paged_decode_attention(
                q9, kv9[0], kv9[1], *common9, new9[0], new9[1], scale=scale,
                pages_per_chunk=ppc9, work_items=work9)
            check(f"{rows9} rows, the fused write", ref9,
                  np.asarray(got9, np.float32))
            check(f"{rows9} rows, the fused write's pages",
                  np.asarray(wk9, np.float32), np.asarray(gk9, np.float32),
                  tol=1e-6)
        # both writers into pages of 4,096 lanes
        pool9 = jnp.zeros((136, 16, h9 * d), jnp.bfloat16)
        chunk9 = jnp.asarray(rs.randn(2048, h9 * d) * 0.1, jnp.bfloat16)
        pid9 = rs.permutation(136)[:128].astype(np.int32)
        pk9, pv9 = write_kv_pages_prefill(
            chunk9, chunk9, pool9, pool9, jnp.asarray(pid9),
            jnp.asarray(np.arange(128, dtype=np.int32)),
            jnp.full((128,), 16, jnp.int32))
        check("prefill page writer, 128 cells of 4,096 lanes",
              np.asarray(chunk9, np.float32),
              np.asarray(pk9, np.float32)[pid9].reshape(2048, h9 * d),
              tol=1e-6)
        tok9 = jnp.asarray(rs.randn(3, h9 * d) * 0.1, jnp.bfloat16)
        slot9 = np.array([5 * 16 + 3, 77 * 16 + 15, 130 * 16], np.int32)
        dk9, _ = write_kv_pages(tok9, tok9, pk9, pv9, jnp.asarray(slot9),
                                distinct_pages=True)
        want9 = np.asarray(pk9, np.float32)
        want9[slot9 // 16, slot9 % 16] = np.asarray(tok9, np.float32)
        check("decode page writer, 4,096 lanes", want9,
              np.asarray(dk9, np.float32), tol=1e-6)
        # the prompt's kernel: a chunk's own keys, and a gathered table
        # of two windows' summaries and a window behind the chunk
        for keys9, ctx_of in ((2048, 0), (3072, 256 + 768)):
            fq9 = jnp.asarray(rs.randn(2, 2048, h9, d), jnp.bfloat16)
            fk9 = jnp.asarray(rs.randn(2, keys9, h9, d), jnp.bfloat16)
            fv9 = jnp.asarray(rs.randn(2, keys9, h9, d), jnp.bfloat16)
            fctx9 = np.array([ctx_of, ctx_of // 2], np.int32)
            fnew9 = np.array([keys9 - ctx_of, 1280], np.int32) \
                if ctx_of else np.array([2048, 1280], np.int32)
            fargs9 = (fq9, fk9, fv9, jnp.asarray(fctx9),
                      jnp.asarray(fctx9 + np.minimum(fnew9, 2048)),
                      d ** -0.5)
            check(f"prompt attention 32/32 heads, {keys9} keys",
                  np.asarray(prefill_attention(*fargs9), np.float32),
                  np.asarray(prefill_flash_attention(*fargs9), np.float32))
        # the pooling: two windows of 128 pages into 8 pages each, and
        # six pad rows whose writes are dropped
        src9 = np.zeros((8, 128), np.int32)
        dst9 = np.full((8, 8), used9 + 4, np.int32)
        src9[0], src9[1] = np.arange(1, 129), np.arange(140, 268)
        dst9[0], dst9[1] = np.arange(300, 308), np.arange(310, 318)
        phi9 = jnp.asarray(rs.rand(h9, d) * 4 - 2, jnp.bfloat16)
        mu9 = jnp.asarray(rs.rand(h9, d) * 8 - 4, jnp.bfloat16)
        sk9, sv9 = jax.jit(lambda k, v, s, t: summarise_pages(
            k, v, s, t, phi9, mu9, scale, h9))(
            kv9[0], kv9[1], jnp.asarray(src9), jnp.asarray(dst9))
        k32 = np.asarray(kv9[0], np.float32).reshape(-1, 16, h9, d)
        v32 = np.asarray(kv9[1], np.float32).reshape(-1, 16, h9, d)
        for r in (0, 1):
            kk, vv = k32[src9[r]], v32[src9[r]]      # [128, 16, H, d]
            sc9 = np.einsum("pjhd,hd->pjh", kk,
                            np.asarray(phi9, np.float32)) * scale
            pw9 = np.exp(sc9 - sc9.max(1, keepdims=True))
            pw9 /= pw9.sum(1, keepdims=True)
            kb9 = np.einsum("pjh,pjhd->phd", pw9, kk) + \
                np.asarray(mu9, np.float32)
            vb9 = np.einsum("pjh,pjhd->phd", pw9, vv)
            for got, want, tag in ((sk9, kb9, "keys"), (sv9, vb9, "values")):
                check(f"summarise window {r}, pooled {tag}",
                      want.reshape(8, 16, h9 * d),
                      np.asarray(got, np.float32)[dst9[r]], tol=3e-2)
        untouched = np.ones(used9 + 4, bool)
        untouched[dst9[:2].ravel()] = False
        check("summarise leaves every other page alone",
              np.asarray(kv9[0], np.float32)[untouched],
              np.asarray(sk9, np.float32)[untouched], tol=1e-6)

    with section("Sarvam: latent pages, one array a layer, 64 query rows"):
        # (its names carry an `l`: the sections after it read `page`,
        # `pages`, `bt`, `ctx`, `scale` and `q` of the first ones)
        # Sarvam-105B's page: [c 512 | k_r 64] padded to 640 lanes, ONE
        # array, the values its first 512 lanes. The kernel with
        # `latent` against the jnp path over the array as K and as V:
        # NaN in every page no row holds, the fused write of the one
        # row, 64 rows at contexts of 8k-9k in a table 576 wide; and
        # the prompt writer over one array.
        from aphrodite_tpu.ops.kv_cache import write_to_latent_cache
        from aphrodite_tpu.ops.pallas.kv_write import write_kv_pages_prefill
        from aphrodite_tpu.ops.pallas.paged_attention import (
            build_decode_work_list, choose_pages_per_chunk, lane_bytes_of,
            padded_work_length)
        lanes, latent, heads, lpage, width, rows = 640, 512, 64, 16, 576, 64
        lscale = 192 ** -0.5 * 1.3689 ** 2
        ppc = choose_pages_per_chunk(
            width, lpage, lane_bytes_of(1, lanes, jnp.bfloat16))
        srng = np.random.default_rng(52)
        lctx = np.array([8193 + (i * 131) % 1024 for i in range(rows)],
                       np.int32)
        lctx[5], lctx[11], lctx[40] = 0, 1, 513
        counts = -(-lctx // lpage)
        pool = 1 + int(counts.sum())
        perm = srng.permutation(pool - 1) + 1
        lbt = np.zeros((rows, width), np.int32)
        at = 0
        for b, n in enumerate(counts):
            lbt[b, :n] = perm[at:at + n]
            at += n
        dead = np.ones(pool + 4, bool)
        dead[perm] = False
        raw = (srng.normal(size=(pool + 4, lpage, lanes)) * 0.3).astype(
            np.float32)
        raw[..., 576:] = 0.0
        raw[dead] = np.nan
        lpages = jnp.asarray(raw, jnp.bfloat16)
        lq = srng.normal(size=(rows, heads, lanes)) * 0.3
        lq[..., 576:] = 0.0
        lq = jnp.asarray(lq, jnp.bfloat16)
        row = srng.normal(size=(rows, lanes)) * 0.3
        row[..., 576:] = 0.0
        row = jnp.asarray(row, jnp.bfloat16)
        slots = np.where(
            lctx > 0,
            lbt[np.arange(rows), np.maximum(lctx - 1, 0) // lpage] * lpage
            + (lctx - 1) % lpage, lpages.shape[0] * lpage)
        want_pages = write_to_latent_cache(row, lpages,
                                           jnp.asarray(slots, jnp.int32))
        clean = jnp.where(jnp.asarray(dead)[:, None, None], 0, want_pages)
        want = np.asarray(paged_decode_attention_ref(
            lq, clean, clean, jnp.asarray(lbt),
            jnp.asarray(np.maximum(lctx, 1)), lscale)[..., :latent],
            np.float32)
        items = int(sum(max(1, -(-n // ppc)) for n in counts))
        work = build_decode_work_list(
            counts, ppc, pad_to=padded_work_length(items, rows, width, ppc))
        got, got_pages = paged_decode_attention(
            lq, lpages, None, jnp.asarray(lbt), jnp.asarray(lctx), None,
            row.reshape(rows, 1, lanes), None, scale=lscale,
            pages_per_chunk=ppc, work_items=work, latent=latent)
        np.testing.assert_array_equal(np.asarray(got_pages, np.float32),
                                      np.asarray(want_pages, np.float32))
        got = np.asarray(got, np.float32)
        assert got.shape == (rows, heads, latent) and np.isfinite(got).all()
        live = lctx > 0
        np.testing.assert_allclose(got[live], want[live], rtol=2e-2,
                                   atol=2e-2)
        np.testing.assert_allclose(got[~live], 0.0, atol=1e-6)
        print("Sarvam latent decode, 64 rows: max err "
              f"{np.abs(got[live] - want[live]).max():.2e}")
        # the prompt writer: three whole pages and a tail of five rows
        chunk = jnp.asarray(srng.normal(size=(8 * lpage, lanes)),
                            jnp.bfloat16)
        ids = np.full((8,), lpages.shape[0], np.int32)
        ids[:4] = perm[:4]
        valid = np.full((8,), lpage, np.int32)
        valid[3] = 5
        wrote = write_kv_pages_prefill(
            chunk, None, clean, None, jnp.asarray(ids),
            jnp.arange(8, dtype=jnp.int32), jnp.asarray(valid))
        slots = np.full((8 * lpage,), lpages.shape[0] * lpage, np.int32)
        for t in range(3 * lpage + 5):
            slots[t] = ids[t // lpage] * lpage + t % lpage
        np.testing.assert_array_equal(
            np.asarray(wrote, np.float32),
            np.asarray(write_to_latent_cache(chunk, clean,
                                             jnp.asarray(slots)),
                       np.float32))

    with section("decode attention (Phi-4-mini-flash: 10 KV heads, "
                 "one head block)"):
        # -- the decode kernel as `phi-4-mini-flash-bf16.reason-2k`
        #    calls it: 40 query heads over 10 KV heads of 128 (a
        #    differential pair of the model's 64-wide heads held as one
        #    head; all ten in one head block, a page one contiguous
        #    copy, 384-token items), the model's own scale 1/8, half of
        #    every query zeros, pages of 16, 64 rows.
        #    The full layer's table 192 wide at contexts of 2,049-3,072
        #    with the fused write; a window group's 40 wide holding
        #    32-33 pages under the window of 512; a cross layer's call,
        #    read-only over the full layer's pages. NaN in every page
        #    no row holds. --
        from aphrodite_tpu.ops.pallas.paged_attention import (
            build_decode_work_list, choose_pages_per_chunk, lane_bytes_of)
        from aphrodite_tpu.ops.kv_cache import write_to_kv_cache
        hq7, hkv7, rows7, scale7 = 40, 10, 64, 0.125
        for tag7, width7, window7, fused7 in (
                ("full", 192, None, True), ("window", 40, 512, True),
                ("cross", 192, None, False)):
            ppc7 = choose_pages_per_chunk(
                width7, 16, lane_bytes_of(hkv7, d, jnp.bfloat16))
            whole = rs.randint(2049, 3073, (rows7,)).astype(np.int32)
            whole[:2] = (2049, 3072)
            let_go = np.maximum(0, whole - window7) // 16 * 16 \
                if window7 else np.zeros_like(whole)
            ctx7 = whole - let_go
            cnt7 = -(-ctx7 // 16)
            tbl7 = np.zeros((rows7, width7), np.int32)
            used7 = 1
            for i, n in enumerate(cnt7):
                tbl7[i, :n] = np.arange(used7, used7 + n)
                used7 += n
            raw7 = rs.randn(used7 + 4, 16, hkv7 * d) * 0.3
            kv7 = [jnp.asarray(raw7, jnp.bfloat16),
                   jnp.asarray(raw7[::-1], jnp.bfloat16)]
            q7 = rs.randn(rows7, hq7, d) * 0.3
            q7[:, 0::2, 64:] = 0.0          # [q1 ; 0]
            q7[:, 1::2, :64] = 0.0          # [0 ; q2]
            q7 = jnp.asarray(q7, jnp.bfloat16)
            new7 = [jnp.asarray(rs.randn(rows7, hkv7, d) * 0.3,
                                jnp.bfloat16) for _ in range(2)]
            wk7, wv7 = kv7
            if fused7:
                slots7 = jnp.asarray(
                    tbl7[np.arange(rows7), (ctx7 - 1) // 16] * 16 +
                    (ctx7 - 1) % 16)
                wk7, wv7 = write_to_kv_cache(new7[0], new7[1], kv7[0],
                                             kv7[1], slots7)
            ref7 = np.asarray(paged_decode_attention_ref(
                q7, wk7, wv7, jnp.asarray(tbl7), jnp.asarray(ctx7), scale7,
                window=window7), np.float32)
            dead7 = np.ones(used7 + 4, bool)
            dead7[1:used7] = False
            kv7 = [x.at[jnp.asarray(np.flatnonzero(dead7))].set(jnp.nan)
                   for x in kv7]
            got7 = paged_decode_attention(
                q7, kv7[0], kv7[1], jnp.asarray(tbl7), jnp.asarray(ctx7),
                None, new7[0] if fused7 else None,
                new7[1] if fused7 else None, scale=scale7,
                pages_per_chunk=ppc7,
                work_items=build_decode_work_list(cnt7, ppc7),
                window=window7)
            if fused7:
                got7, gk7, _ = got7
                check(f"{tag7} layer, the fused write's pages",
                      np.asarray(wk7, np.float32)[~dead7],
                      np.asarray(gk7, np.float32)[~dead7], tol=1e-6)
            check(f"{tag7} layer, table {width7}, ppc={ppc7}", ref7,
                  np.asarray(got7, np.float32))

    with section("selective scan (Phi-4-mini-flash: chunk scan, decode "
                 "update)"):
        # -- `ops/pallas/ssm_scan.py` at the served shape: 5,120
        #    channels, 16 states, float32. A prompt chunk of 2,048
        #    tokens from zeros, and the same tokens as two chunks of
        #    1,024 through the slot, against the jnp scan; a decode
        #    step of 64 rows (60 live on scattered slots, 4 pad rows on
        #    the scratch slot) against the jnp update, NaN in every
        #    slot no row holds. The arrays are the model's, three
        #    layers each, the calls naming the middle one: the other
        #    two are NaN before and after. --
        from aphrodite_tpu.ops.pallas import ssm_scan as ssm
        n8, ch8, slots8, t8 = 16, 5120, 128, 2048
        f8 = lambda *shape: jnp.asarray(rs.randn(*shape), jnp.float32)
        u8, b8, c8 = f8(1, t8, ch8), f8(1, t8, n8), f8(1, t8, n8)
        dl8 = jax.nn.softplus(f8(1, t8, ch8) - 4.0)
        a8 = -jnp.exp(jnp.asarray(rs.uniform(-1.5, 1.5, (n8, ch8)),
                                  jnp.float32))
        d8 = jnp.asarray(rs.uniform(0, 0.5, (ch8,)), jnp.float32)
        at8 = 1
        st8 = jnp.full((3, slots8 + 1, n8, ch8), jnp.nan, jnp.float32)
        one, yes = jnp.asarray([7], jnp.int32), jnp.asarray([1], jnp.int32)
        y_ref, s_ref = ssm.ssm_scan_ref(u8, dl8, b8, c8, a8, d8, st8, one,
                                        yes, at8)
        y_got, s_got = ssm.selective_scan(u8, dl8, b8, c8, a8, d8, st8,
                                          one, yes, at8)
        check("chunk scan, 2,048 tokens", np.asarray(y_ref),
              np.asarray(y_got), tol=1e-4)
        check("chunk scan, the slot's state", np.asarray(s_ref)[at8, 7],
              np.asarray(s_got)[at8, 7], tol=1e-4)
        half = t8 // 2
        y1, s1 = ssm.selective_scan(
            u8[:, :half], dl8[:, :half], b8[:, :half], c8[:, :half], a8, d8,
            st8, one, yes, at8)
        y2, s2 = ssm.selective_scan(
            u8[:, half:], dl8[:, half:], b8[:, half:], c8[:, half:], a8, d8,
            s1, one, 0 * yes, at8)
        check("two chunks through the slot",
              np.asarray(y_ref), np.concatenate([y1, y2], axis=1),
              tol=1e-4)
        if not (np.isnan(np.asarray(s2)[at8, [0, 6, 8, slots8]]).all() and
                np.isnan(np.asarray(s2)[[0, 2]]).all()):
            failures.append(("ssm scan touched a slot no row holds", 0))
        rows8, live8 = 64, 60
        owners = rs.permutation(slots8)[:live8]
        slot8 = np.full((rows8,), slots8, np.int32)
        slot8[:live8] = owners
        held = np.zeros(slots8 + 1, bool)
        held[owners] = True
        held[slots8] = True
        mine = held[None, :, None, None] & \
            (np.arange(3) == at8)[:, None, None, None]
        st9 = jnp.where(mine, f8(3, slots8 + 1, n8, ch8), jnp.nan)
        tl9 = jnp.where(mine, f8(3, slots8 + 1, 4, ch8),
                        jnp.nan).astype(jnp.bfloat16)
        x9 = f8(rows8, ch8).astype(jnp.bfloat16)
        u9, b9, c9 = f8(rows8, ch8), f8(rows8, n8), f8(rows8, n8)
        dl9 = jax.nn.softplus(f8(rows8, ch8) - 4.0)
        want = ssm.ssm_update_ref(x9, u9, dl9, b9, c9, a8, d8, st9, tl9,
                                  jnp.asarray(slot8), at8)
        got = ssm.selective_update(x9, u9, dl9, b9, c9, a8, d8, st9, tl9,
                                   jnp.asarray(slot8), at8)
        check("decode update, the rows' outputs",
              np.asarray(want[0])[:live8], np.asarray(got[0])[:live8],
              tol=1e-4)
        check("decode update, the live slots' state",
              np.asarray(want[1])[at8, owners],
              np.asarray(got[1])[at8, owners], tol=1e-4)
        check("decode update, the live slots' tail",
              np.asarray(want[2], np.float32)[at8, owners],
              np.asarray(got[2], np.float32)[at8, owners], tol=1e-6)
        free = ~mine[..., 0, 0]
        if not (np.isnan(np.asarray(got[1])[free]).all() and
                np.isnan(np.asarray(got[2], np.float32)[free]).all()):
            failures.append(("ssm update touched a slot no row holds", 0))

    with section("delta rule (Kimi Linear: chunk kernel, decode update)"):
        kda_checks(check, failures, rs)

    with section("decode attention (padded heads)"):
        # -- head 64/80: padded-lane decode (pages pad head_dim to 128) --
        for d_true in (64, 80):
            dp = 128
            qs = jnp.asarray(rs.randn(B, Hq, d_true) * 0.1, jnp.bfloat16)
            k4 = rs.randn(pages, page, Hkv, d_true) * 0.1
            v4 = rs.randn(pages, page, Hkv, d_true) * 0.1
            kps = jnp.asarray(k4.reshape(pages, page, -1), jnp.bfloat16)
            vps = jnp.asarray(v4.reshape(pages, page, -1), jnp.bfloat16)
            pad3 = ((0, 0), (0, 0), (0, dp - d_true))
            pad4 = ((0, 0), (0, 0), (0, 0), (0, dp - d_true))
            refs = oracle(qs, kps, vps, bt, ctx, scale)
            kpp = jnp.asarray(np.pad(k4, pad4).reshape(pages, page, -1),
                              jnp.bfloat16)
            vpp = jnp.asarray(np.pad(v4, pad4).reshape(pages, page, -1),
                              jnp.bfloat16)
            got = np.asarray(paged_decode_attention(
                jnp.pad(qs, pad3), kpp, vpp, bt, ctx, scale=scale,
                pages_per_chunk=2), np.float32)[..., :d_true]
            check(f"tokenmajor head{d_true} padded", refs, got)

    with section("decode attention (fused KV write)"):
        # -- fused-write drain protocol: page CONTENTS after multi-batch
        #    fused decode (compiled, non-interpret) must match a host-side
        #    slot write bit-for-bit. The cell-(i-2) writeback drain
        #    (paged_attention.py:185-201,307-339) is the subtle part: a
        #    dropped or mis-slotted writeback corrupts a page silently.
        for Hq2, Hkv2, tag in ((32, 8, "n_hb=1"), (16, 16, "n_hb=2")):
            B2, d2, page2, pps2 = 24, 128, 16, 8
            pages2 = B2 * pps2 + 1
            q2 = jnp.asarray(rs.randn(B2, Hq2, d2) * 0.1, jnp.bfloat16)
            kp2 = jnp.asarray(rs.randn(pages2, page2, Hkv2 * d2) * 0.1,
                              jnp.bfloat16)
            vp2 = jnp.asarray(rs.randn(pages2, page2, Hkv2 * d2) * 0.1,
                              jnp.bfloat16)
            # Sequence-exclusive pages (the engine decode contract), in a
            # shuffled order so page ids don't correlate with batch index.
            perm = rs.permutation(pages2 - 1)
            bt2 = jnp.asarray(perm[:B2 * pps2].reshape(B2, pps2), jnp.int32)
            ctx2_np = rs.randint(1, pps2 * page2, (B2,)).astype(np.int32)
            ctx2_np[5] = 0                     # padded row: no write
            ctx2_np[7] = 1                     # minimum context
            ctx2_np[11] = pps2 * page2         # full table
            ctx2 = jnp.asarray(ctx2_np)
            kn2 = jnp.asarray(rs.randn(B2, Hkv2, d2) * 0.1, jnp.bfloat16)
            vn2 = jnp.asarray(rs.randn(B2, Hkv2, d2) * 0.1, jnp.bfloat16)
            for ppc2, grid in ((2, "classic"), (pps2, "classic"),
                               (2, "ragged"), (pps2, "ragged")):
                # Ragged work lists come from each row's RESERVED pages
                # (the full table width here), the runner's discipline —
                # chunks past ctx are masked, and the write counter ring
                # must stay correct with one writer item per row.
                work2 = build_decode_work_list([pps2] * B2, ppc2) \
                    if grid == "ragged" else None
                outf, kpf, vpf = paged_decode_attention(
                    q2, kp2, vp2, bt2, ctx2, knew=kn2, vnew=vn2,
                    scale=scale, pages_per_chunk=ppc2, work_items=work2)
                ekp = np.asarray(kp2, np.float32).copy()
                evp = np.asarray(vp2, np.float32).copy()
                knf = np.asarray(kn2, np.float32).reshape(B2, Hkv2 * d2)
                vnf = np.asarray(vn2, np.float32).reshape(B2, Hkv2 * d2)
                for i in range(B2):
                    c = int(ctx2_np[i])
                    if c == 0:
                        continue
                    pg = int(np.asarray(bt2)[i, (c - 1) // page2])
                    ekp[pg, (c - 1) % page2] = knf[i]
                    evp[pg, (c - 1) % page2] = vnf[i]
                errk = np.abs(np.asarray(kpf, np.float32) - ekp).max()
                errv = np.abs(np.asarray(vpf, np.float32) - evp).max()
                name = f"fused-write contents {tag} ppc={ppc2} {grid}"
                print(f"{name}: k err {errk:.2e} v err {errv:.2e}")
                if not (errk == 0.0 and errv == 0.0):   # bit-for-bit
                    failures.append((name, max(errk, errv)))
                # attention output must equal the reference computed over
                # the POST-write pages (the injected token participates)
                ref2 = np.asarray(paged_decode_attention_ref(
                    q2, jnp.asarray(ekp, jnp.bfloat16),
                    jnp.asarray(evp, jnp.bfloat16), bt2, ctx2, scale),
                    np.float32)
                ref2[ctx2_np == 0] = 0.0
                erro = np.abs(np.asarray(outf, np.float32) - ref2).max()
                print(f"{name}: out err {erro:.2e}")
                if not (erro < 3e-2):
                    failures.append((name + " out", erro))

    with section("prompt attention (the flash kernel)"):
        # -- rows at different contexts, a pad row, a window, heads of
        # 6 and 7 a KV head, a chunk and keys of odd tiles --
        from aphrodite_tpu.ops.attention import prefill_attention
        from aphrodite_tpu.ops.pallas.prefill_attention import (
            prefill_flash_attention)
        for fq, fkv, fkeys, fwin in ((28, 4, 1152, None), (12, 2, 1152, 200),
                                     (32, 8, 384, None)):
            fq_ = jnp.asarray(rs.randn(3, 384, fq, d), jnp.bfloat16)
            fk_ = jnp.asarray(rs.randn(3, fkeys, fkv, d), jnp.bfloat16)
            fv_ = jnp.asarray(rs.randn(3, fkeys, fkv, d), jnp.bfloat16)
            fnew = np.array([384, 0, 301], np.int32)
            fctx = np.array([fkeys - 384, 0, (fkeys - 384) // 3], np.int32)
            fargs = (fq_, fk_, fv_, jnp.asarray(fctx),
                     jnp.asarray(fctx + fnew), d ** -0.5)
            check(f"prompt attention {fq}/{fkv} heads, {fkeys} keys, "
                  f"window {fwin}",
                  np.asarray(prefill_attention(
                      *fargs, sliding_window=fwin), np.float32),
                  np.asarray(prefill_flash_attention(
                      *fargs, sliding_window=fwin), np.float32))

    with section("prompt attention (values at their own head width)"):
        # -- Sarvam's form (PR 53): one KV head a query head, 256 lanes
        # of keys a head (192 live) and 128 of values; a prompt on its
        # own keys and a chunk behind a prefix in a padded table.
        # Against `prefill_attention` (one head width: the values
        # zero-padded, the result sliced), and EQUAL to the call that
        # hands the kernel those padded values, which is the call a
        # tree before PR 53 made: a column of `p @ V` does not know how
        # many columns ride beside it --
        for vs, vkeys, vctx in ((1024, 1024, 0), (512, 1536, 640)):
            vq_ = jnp.asarray(rs.randn(2, vs, 16, 256), jnp.bfloat16)
            vk_ = jnp.asarray(rs.randn(2, vkeys, 16, 256), jnp.bfloat16)
            vv_ = jnp.asarray(rs.randn(2, vkeys, 16, 128), jnp.bfloat16)
            vwide = jnp.pad(vv_, ((0, 0),) * 3 + ((0, 128),))
            vlens = (jnp.asarray([vctx, vctx // 2], jnp.int32),
                     jnp.asarray([vctx + vs, vctx // 2 + vs - 77], jnp.int32),
                     0.135)
            got_v = prefill_flash_attention(vq_, vk_, vv_, *vlens)
            name = f"prompt attention 256/128 lanes, {vs} on {vkeys} keys"
            if got_v.shape != (2, vs, 16, 128):
                failures.append((name + " shape", got_v.shape))
            check(name,
                  np.asarray(prefill_attention(vq_, vk_, vwide, *vlens),
                             np.float32)[..., :128],
                  np.asarray(got_v, np.float32))
            check(name + ", against the values zero-padded to 256",
                  np.asarray(prefill_flash_attention(
                      vq_, vk_, vwide, *vlens), np.float32)[..., :128],
                  np.asarray(got_v, np.float32), tol=1e-30)

    with section("prefill page writer"):
        # -- prefill page writer (whole-page DMA, partial tail, OOB) --
        from aphrodite_tpu.ops.pallas.kv_write import (write_kv_pages,
                                                       write_kv_pages_prefill)
        wp, wps, whd = 16, 16, 1024
        kpw = jnp.asarray(rs.randn(wp, wps, whd) * 0.1, jnp.bfloat16)
        vpw = jnp.asarray(rs.randn(wp, wps, whd) * 0.1, jnp.bfloat16)
        knw = rs.randn(4 * 32, whd).astype(np.float32) * 0.1
        vnw = rs.randn(4 * 32, whd).astype(np.float32) * 0.1
        pidw = np.array([1, 2, 4, 5, 7, 8, wp, wp], dtype=np.int32)
        sblkw = np.array([0, 1, 2, 3, 4, 5, 0, 0], dtype=np.int32)
        vldw = np.array([16, 16, 16, 5, 16, 9, 0, 0], dtype=np.int32)
        gk, gv = write_kv_pages_prefill(
            jnp.asarray(knw, jnp.bfloat16), jnp.asarray(vnw, jnp.bfloat16),
            kpw, vpw, jnp.asarray(pidw), jnp.asarray(sblkw),
            jnp.asarray(vldw))
        ek = np.asarray(kpw, np.float32)
        for c in range(8):
            if pidw[c] >= wp:
                continue
            rows = np.asarray(jnp.asarray(knw, jnp.bfloat16), np.float32)
            ek[pidw[c], :vldw[c]] = rows[sblkw[c] * wps:
                                         sblkw[c] * wps + vldw[c]]
        errw = np.abs(np.asarray(gk, np.float32) - ek).max()
        print(f"prefill page writer: max err {errw:.2e}")
        if not (errw < 1e-6):
            failures.append(("prefill_writer", errw))

    with section("prefill page writer (SmallThinker chunk)"):
        # -- a chunk of 2,048 tokens into pages of 4 KV heads x 128
        #    lanes: 128 whole-page cells --
        shd, scells = 4 * 128, 2048 // 16
        spool = jnp.zeros((scells + 8, 16, shd), jnp.bfloat16)
        srows = jnp.asarray(rs.randn(2048, shd) * 0.1, jnp.bfloat16)
        spid = rs.permutation(scells + 8)[:scells].astype(np.int32)
        sk, _ = write_kv_pages_prefill(
            srows, srows, spool, spool, jnp.asarray(spid),
            jnp.asarray(np.arange(scells, dtype=np.int32)),
            jnp.full((scells,), 16, jnp.int32))
        errs = np.abs(np.asarray(sk, np.float32)[spid].reshape(2048, shd)
                      - np.asarray(srows, np.float32)).max()
        print(f"prefill page writer, 128 cells of 512 lanes: "
              f"max err {errs:.2e}")
        if not (errs < 1e-6):
            failures.append(("prefill_writer_smallthinker", errs))

    with section("decode page writer"):
        # decode pipelined writer on-chip
        slots_d = jnp.asarray(np.array([3 * wps + 2, 9 * wps + 7,
                                        11 * wps + 1, wp * wps],
                                       dtype=np.int32))
        kd = jnp.asarray(rs.randn(4, whd) * 0.1, jnp.bfloat16)
        gk2, _ = write_kv_pages(kd, kd, gk, gv, slots_d,
                                distinct_pages=True)
        ek2 = np.asarray(gk, np.float32)
        for i, s in enumerate(np.asarray(slots_d)[:3]):
            ek2[s // wps, s % wps] = np.asarray(kd, np.float32)[i]
        errd = np.abs(np.asarray(gk2, np.float32) - ek2).max()
        print(f"decode pipelined writer: max err {errd:.2e}")
        if not (errd < 1e-6):
            failures.append(("decode_writer", errd))

    with section("gptq_matmul"):
        # -- fused GPTQ dequant matmul --
        bits, gs, K, N, m = 4, 128, 4096, 14336, 256
        pack, G = 32 // bits, K // gs
        qw = jnp.asarray(rs.randint(-2**31, 2**31, (K // pack, N),
                                    dtype=np.int32))
        qz = jnp.asarray(rs.randint(-2**31, 2**31, (G, N // pack),
                                    dtype=np.int32))
        sc = jnp.asarray(rs.rand(G, N) * 0.01, jnp.bfloat16)
        x = jnp.asarray(rs.randn(m, K), jnp.bfloat16)
        method = GPTQLinearMethod(GPTQConfig(bits, gs))
        params = {"qweight": qw, "qzeros": qz, "scales": sc,
                  "g_idx": jnp.asarray(np.arange(K) // gs, np.int32)}
        refq = np.asarray(x @ method.dequantize(params, jnp.bfloat16),
                          np.float32)
        gotq = np.asarray(gptq_matmul(x, qw, qz, sc, bits=bits,
                                      group_size=gs), np.float32)
        rel = np.abs(refq - gotq).max() / (np.abs(refq).max() + 1e-9)
        print(f"gptq_matmul int4: rel err {rel:.2e}")
        if rel > 3e-2:
            failures.append(("gptq", rel))

    with section("gptq streamed grid"):
        # -- streamed skinny-m grid, compiled on the real chip: the
        # decode-shaped (m<=64) work-list/DMA-ring path vs the classic
        # grid at identical inputs, W4A16 and W4A8 (deferred on/off) --
        from aphrodite_tpu.ops.pallas.quant_matmul import gptq_matmul_a8
        xs16 = jnp.asarray(rs.randn(16, K), jnp.bfloat16)
        refs16 = np.asarray(xs16 @ method.dequantize(params, jnp.bfloat16),
                            np.float32)
        gots16 = np.asarray(gptq_matmul(xs16, qw, qz, sc, bits=bits,
                                        group_size=gs, stream=True),
                            np.float32)
        rel = np.abs(refs16 - gots16).max() / (np.abs(refs16).max() + 1e-9)
        print(f"gptq_matmul streamed m=16: rel err {rel:.2e}")
        if rel > 3e-2:
            failures.append(("gptq_stream", rel))
        a8c = np.asarray(gptq_matmul_a8(xs16, qw, qz, sc, bits=bits,
                                        group_size=gs, stream=False),
                         np.float32)
        for tag, kwargs in (("stream", dict(stream=True)),
                            ("stream+deferred",
                             dict(stream=True, deferred=True))):
            a8s = np.asarray(gptq_matmul_a8(xs16, qw, qz, sc, bits=bits,
                                            group_size=gs, **kwargs),
                             np.float32)
            rel = np.abs(a8c - a8s).max() / (np.abs(a8c).max() + 1e-9)
            print(f"gptq_matmul_a8 {tag} m=16 vs classic: rel err {rel:.2e}")
            if rel > 1e-3:
                failures.append((f"gptq_a8_{tag}", rel))

    with section("gptq W4A8 at Mistral-7B layer shapes"):
        # Every (K, N) of the 7B decoder layer at the row counts the
        # serving path reaches: decode and speculative verify (m <= 64:
        # streamed grid, activations quantized in the kernel prologue),
        # a full decode batch, and prefill rounds (classic/deferred
        # grids behind the one-pass quantize kernel, whose whole-K row
        # block is the tightest VMEM fit at K=14336).
        for K7, N7 in ((4096, 6144), (4096, 4096), (4096, 28672),
                       (14336, 4096)):
            qw7 = jnp.asarray(rs.randint(-2**31, 2**31, (K7 // 8, N7),
                                         dtype=np.int32))
            qz7 = jnp.asarray(rs.randint(-2**31, 2**31,
                                         (K7 // 128, N7 // 8),
                                         dtype=np.int32))
            sc7 = jnp.asarray(rs.rand(K7 // 128, N7) * 0.01, jnp.bfloat16)
            w7 = method.dequantize(
                {"qweight": qw7, "qzeros": qz7, "scales": sc7,
                 "g_idx": jnp.asarray(np.arange(K7) // 128, np.int32)},
                jnp.bfloat16)
            for m7 in (8, 40, 512, 4096):
                x7 = jnp.asarray(rs.randn(m7, K7), jnp.bfloat16)
                ref7 = np.asarray(x7 @ w7, np.float32)
                got7 = np.asarray(gptq_matmul_a8(
                    x7, qw7, qz7, sc7, bits=4, group_size=128),
                    np.float32)
                rel = np.abs(ref7 - got7).max() / \
                    (np.abs(ref7).max() + 1e-9)
                name = f"gptq_matmul_a8 K={K7} N={N7} m={m7}"
                print(f"{name}: rel err {rel:.2e}")
                if not rel < 3e-2:
                    failures.append((name, rel))
            # The byte unpack against the plane unpack at the rows of
            # `mistral-7b-w4a8.batch` (48 on the streamed grid, which
            # serves it; 1,024 on the compiler's, benchmarks/qmm_ab.py's
            # arm): the same int8 operand, so not a bit differs.
            for m7 in (48, 1024):
                x7 = jnp.asarray(rs.randn(m7, K7), jnp.bfloat16)
                planes7, bytes7 = (np.asarray(gptq_matmul_a8(
                    x7, qw7, qz7, sc7, bits=4, group_size=128,
                    unpack=u), np.float32) for u in ("planes", "bytes"))
                same = bool(np.array_equal(planes7, bytes7))
                name = f"gptq_matmul_a8 K={K7} N={N7} m={m7} bytes"
                print(f"{name} == planes bit for bit: {same}")
                if not same:
                    failures.append((name, "differs"))

    with section("the int32 -> int8 bitcast's row order"):
        # `_unpack_bytes` reads a [r, c] int32 plane as [4r, c] int8 and
        # `plane_permutation(byte_rows=True)` takes byte b of word-row
        # i for row 4i + b, as interpret mode has it: words whose bytes
        # say their own 4i + b, read back as int8 rows.
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu
        word_rows = np.arange(16, dtype=np.int64)[:, None]
        byte = np.arange(4, dtype=np.int64)[None, :]
        words = ((4 * word_rows + byte) << (8 * byte)).sum(1)

        def bitcast_kernel(w_ref, o_ref):
            o_ref[...] = pltpu.bitcast(w_ref[...], jnp.int8)
        rows8 = np.asarray(pl.pallas_call(
            bitcast_kernel,
            out_shape=jax.ShapeDtypeStruct((64, 128), jnp.int8))(
                jnp.asarray(np.broadcast_to(
                    words.astype(np.int32)[:, None], (16, 128)))))
        same = rows8[:, 0].tolist() == list(range(64)) and \
            bool((rows8 == rows8[:, :1]).all())
        print(f"bitcast int32[16,128] -> int8[64,128]: row 4i + b holds "
              f"byte b of word-row i: {same} ({rows8[:8, 0].tolist()}...)")
        if not same:
            failures.append(("bitcast row order", rows8[:, 0].tolist()))

    with section("awq_matmul"):
        # -- fused AWQ dequant matmul --
        from aphrodite_tpu.modeling.layers.quantization.awq import (
            AWQConfig, AWQLinearMethod)
        from aphrodite_tpu.ops.pallas.quant_matmul import (awq_matmul,
                                                           int8_matmul)
        K, N, m = 4096, 6144, 256
        G = K // 128
        qwa = jnp.asarray(rs.randint(-2**31, 2**31, (K, N // 8),
                                     dtype=np.int32))
        qza = jnp.asarray(rs.randint(-2**31, 2**31, (G, N // 8),
                                     dtype=np.int32))
        sca = jnp.asarray(rs.rand(G, N) * 0.01, jnp.bfloat16)
        xa = jnp.asarray(rs.randn(m, K), jnp.bfloat16)
        amethod = AWQLinearMethod(AWQConfig(4, 128))
        aparams = {"qweight": qwa, "qzeros": qza, "scales": sca}
        refa2 = np.asarray(xa @ amethod.dequantize(aparams, jnp.bfloat16),
                           np.float32)
        gota2 = np.asarray(awq_matmul(xa, qwa, qza, sca, group_size=128),
                           np.float32)
        rel = np.abs(refa2 - gota2).max() / (np.abs(refa2).max() + 1e-9)
        print(f"awq_matmul int4: rel err {rel:.2e}")
        if rel > 3e-2:
            failures.append(("awq", rel))

    with section("gguf q4k/q8 matmul"):
        # -- GGUF at-rest matmuls (Q4_K affine, Q8_0 grouped int8) --
        from aphrodite_tpu.modeling.layers.quantization.gguf import (
            GGUFConfig, GGUFLinearMethod, q4k_to_kernel)
        from aphrodite_tpu.ops.pallas.quant_matmul import (gguf_q4k_matmul,
                                                           gguf_q8_matmul)
        Kg, Ng, mg = 4096, 4096, 256
        nblk = Ng * Kg // 256
        blkb = np.zeros((nblk, 144), np.uint8)
        dscale = (rs.rand(nblk).astype(np.float16) * 0.01 + 1e-3)
        blkb[:, 0:2] = dscale.view(np.uint8).reshape(nblk, 2)
        blkb[:, 2:4] = dscale.view(np.uint8).reshape(nblk, 2)
        blkb[:, 4:16] = rs.randint(0, 256, (nblk, 12), dtype=np.uint8)
        blkb[:, 16:144] = rs.randint(0, 256, (nblk, 128), dtype=np.uint8)
        qwg, dlg, mlg = q4k_to_kernel(blkb, Ng, Kg)
        gmethod = GGUFLinearMethod(GGUFConfig())
        wg = gmethod.dequantize(
            {"qweight": jnp.asarray(qwg), "dl": jnp.asarray(dlg),
             "ml": jnp.asarray(mlg)}, jnp.bfloat16)
        xg = jnp.asarray(rs.randn(mg, Kg), jnp.bfloat16)
        refg = np.asarray(xg @ wg, np.float32)
        gotg = np.asarray(gguf_q4k_matmul(
            xg, jnp.asarray(qwg), jnp.asarray(dlg.astype(np.float32)),
            jnp.asarray(mlg.astype(np.float32))), np.float32)
        rel = np.abs(refg - gotg).max() / (np.abs(refg).max() + 1e-9)
        print(f"gguf_q4k_matmul: rel err {rel:.2e}")
        if rel > 3e-2:
            failures.append(("gguf_q4k", rel))

        qs8 = jnp.asarray(rs.randint(-128, 128, (Kg, Ng), dtype=np.int8))
        dg8 = jnp.asarray(rs.rand(Kg // 32, Ng) * 0.01 + 1e-3, jnp.float32)
        ref8m = np.asarray((xg.astype(jnp.float32) @
                            (qs8.astype(jnp.float32) *
                             jnp.repeat(dg8, 32, axis=0))), np.float32)
        got8m = np.asarray(gguf_q8_matmul(xg, qs8, dg8), np.float32)
        rel = np.abs(ref8m - got8m).max() / (np.abs(ref8m).max() + 1e-9)
        print(f"gguf_q8_matmul: rel err {rel:.2e}")
        if rel > 3e-2:
            failures.append(("gguf_q8", rel))

    with section("squeezellm_matmul"):
        # -- SqueezeLLM fused LUT matmul --
        from aphrodite_tpu.modeling.layers.quantization.squeezellm import (
            SqueezeLLMConfig)
        from aphrodite_tpu.ops.pallas.quant_matmul import squeezellm_matmul
        Ks, Ns, ms = 4096, 4096, 256
        luts = jnp.asarray(rs.randn(Ns, 16) * 0.01, jnp.float32)
        qws = jnp.asarray(rs.randint(-2**31, 2**31, (Ks // 8, Ns),
                                     dtype=np.int32))
        xs = jnp.asarray(rs.randn(ms, Ks), jnp.bfloat16)
        smethod = SqueezeLLMConfig().get_linear_method()
        refs2 = np.asarray(xs @ smethod.dequantize(
            {"qweight": qws, "lookup_table": luts}, jnp.bfloat16),
            np.float32)
        gots2 = np.asarray(squeezellm_matmul(xs, qws, luts), np.float32)
        rel = np.abs(refs2 - gots2).max() / (np.abs(refs2).max() + 1e-9)
        print(f"squeezellm_matmul: rel err {rel:.2e}")
        if rel > 3e-2:
            failures.append(("squeezellm", rel))

    with section("gguf i8g matmul"):
        # -- GGUF grouped-int8 (Q6_K-at-rest form) matmul --
        from aphrodite_tpu.ops.pallas.quant_matmul import gguf_i8g_matmul
        qsg = jnp.asarray(rs.randint(-128, 128, (Ks, Ns), dtype=np.int8))
        dg16 = jnp.asarray(rs.rand(Ks // 16, Ns) * 0.01 + 1e-3, jnp.float32)
        xg2 = jnp.asarray(rs.randn(ms, Ks), jnp.bfloat16)
        refg2 = np.asarray(
            (xg2.astype(jnp.float32) @
             (qsg.astype(jnp.float32) * jnp.repeat(dg16, 16, axis=0))),
            np.float32)
        gotg2 = np.asarray(gguf_i8g_matmul(xg2, qsg, dg16), np.float32)
        rel = np.abs(refg2 - gotg2).max() / (np.abs(refg2).max() + 1e-9)
        print(f"gguf_i8g_matmul: rel err {rel:.2e}")
        if rel > 3e-2:
            failures.append(("gguf_i8g", rel))

    with section("int8_matmul"):
        # -- int8 dense matmul --
        w8 = jnp.asarray(rs.randint(-128, 128, (K, N), dtype=np.int8))
        s8 = jnp.asarray(rs.rand(N) * 0.01 + 1e-3, jnp.float32)
        refi = np.asarray((xa.astype(jnp.float32) @ w8.astype(jnp.float32))
                          * s8, np.float32)
        goti = np.asarray(int8_matmul(xa, w8, s8), np.float32)
        rel = np.abs(refi - goti).max() / (np.abs(refi).max() + 1e-9)
        print(f"int8_matmul: rel err {rel:.2e}")
        if rel > 3e-2:
            failures.append(("int8", rel))

    if failures:
        print("FAILURES:", failures)
        return 1
    print("TPU kernel smoke: ALL OK (compiled, non-interpret)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
