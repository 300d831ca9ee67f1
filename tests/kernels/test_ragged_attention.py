"""Ragged work-list decode attention: the flattened (sequence, chunk)
grid vs the numpy oracle across ragged ctx mixes (multi-chunk, GQA head
blocks, int8 KV, fused-write equivalence), plus the routing/config
satellites: call-time APHRODITE_ATTN_PF validation, the dense list of
a call without one, fused-write routing preconditions, and
padded-table (page 0) masking."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aphrodite_tpu.ops.pallas import paged_attention as pa
from aphrodite_tpu.ops.pallas.paged_attention import (
    build_decode_work_list, choose_pages_per_chunk,
    paged_decode_attention)

from test_attention import make_problem, numpy_paged_attention

# A ragged serving-style mix: single-token, padded (ctx 0), multi-chunk
# at several chunk counts, and a full-table row (page_size 8,
# pages_per_seq 8 in make_problem geometry).
RAGGED_CTX = np.array([1, 0, 40, 64, 17], dtype=np.int32)


def ragged_problem(num_q_heads=8, num_kv_heads=2, ppc=2, seed=0):
    q, kp, vp, bt, _ = make_problem(
        batch=len(RAGGED_CTX), num_q_heads=num_q_heads,
        num_kv_heads=num_kv_heads, dim=128, page_size=8,
        pages_per_seq=8, pages=64, seed=seed)
    ctx = RAGGED_CTX.copy()
    pages_i = [-(-int(c) // 8) for c in ctx]
    work = build_decode_work_list(pages_i, ppc)
    return q, kp, vp, bt, ctx, work


@pytest.mark.parametrize("num_q_heads,num_kv_heads,ppc",
                         [(4, 4, 2),      # MHA, hb=4
                          (8, 2, 2),      # GQA group 4
                          (8, 1, 4),      # MQA
                          (12, 12, 2),    # hb=6, n_hb=2 head blocks
                          (8, 2, 8)])     # one chunk spans the table
def test_ragged_matches_oracle_mixed_ctx(num_q_heads, num_kv_heads,
                                         ppc):
    """Ragged ctx mix incl. multi-chunk rows and a ctx=0 pad row (must
    output exact zeros — its single masked work item still writes its
    lane). Tolerance 1e-2: bf16 dot operands vs the f32 oracle, same
    as the tests of test_attention.py."""
    q, kp, vp, bt, ctx, work = ragged_problem(num_q_heads,
                                              num_kv_heads, ppc)
    expected = numpy_paged_attention(q, kp, vp, bt,
                                     np.maximum(ctx, 1), 0.1)
    expected[ctx == 0] = 0.0
    got = paged_decode_attention(
        jnp.array(q), jnp.array(kp), jnp.array(vp), jnp.array(bt),
        jnp.array(ctx), scale=0.1, pages_per_chunk=ppc,
        work_items=work, interpret=True)
    got = np.array(got)
    np.testing.assert_allclose(got[ctx == 0], 0.0, atol=1e-6)
    mask = ctx > 0
    np.testing.assert_allclose(got[mask], expected[mask], rtol=1e-2,
                               atol=1e-2)


def test_ragged_reserved_pages_over_approximation():
    """The model runner builds chunk counts from RESERVED pages (a
    burst reserves pages past the live context), so work items whose
    chunk lies wholly beyond ctx must be inert: fully-masked chunks
    leave the online-softmax state untouched."""
    q, kp, vp, bt, ctx, _ = ragged_problem()
    # Every row claims the full 8-page reservation regardless of ctx.
    work = build_decode_work_list([8] * len(ctx), 2)
    expected = numpy_paged_attention(q, kp, vp, bt,
                                     np.maximum(ctx, 1), 0.1)
    expected[ctx == 0] = 0.0
    got = paged_decode_attention(
        jnp.array(q), jnp.array(kp), jnp.array(vp), jnp.array(bt),
        jnp.array(ctx), scale=0.1, pages_per_chunk=2,
        work_items=work, interpret=True)
    got = np.array(got)
    mask = ctx > 0
    np.testing.assert_allclose(got[mask], expected[mask], rtol=1e-2,
                               atol=1e-2)
    np.testing.assert_allclose(got[~mask], 0.0, atol=1e-6)


def test_ragged_int8_kv():
    """int8 KV pages under the ragged grid: scale folds into score and
    epilogue."""
    q, kp, vp, bt, ctx, work = ragged_problem()
    S = 0.05
    k8 = np.clip(np.round(kp / S), -127, 127).astype(np.int8)
    v8 = np.clip(np.round(vp / S), -127, 127).astype(np.int8)
    expected = numpy_paged_attention(q, k8.astype(np.float32) * S,
                                     v8.astype(np.float32) * S, bt,
                                     np.maximum(ctx, 1), 0.1)
    expected[ctx == 0] = 0.0
    got = paged_decode_attention(
        jnp.array(q), jnp.array(k8), jnp.array(v8), jnp.array(bt),
        jnp.array(ctx), scale=0.1, kv_scale=S, pages_per_chunk=2,
        work_items=work, interpret=True)
    mask = ctx > 0
    np.testing.assert_allclose(np.array(got)[mask], expected[mask],
                               rtol=1e-2, atol=1e-2)


def test_ragged_alibi():
    q, kp, vp, bt, ctx, work = ragged_problem()
    slopes = np.array([2.0 ** -(i + 1) for i in range(8)],
                      dtype=np.float32)
    expected = numpy_paged_attention(q, kp, vp, bt,
                                     np.maximum(ctx, 1), 0.1,
                                     alibi_slopes=slopes)
    expected[ctx == 0] = 0.0
    got = paged_decode_attention(
        jnp.array(q), jnp.array(kp), jnp.array(vp), jnp.array(bt),
        jnp.array(ctx), jnp.array(slopes), scale=0.1,
        pages_per_chunk=2, work_items=work, interpret=True)
    mask = ctx > 0
    np.testing.assert_allclose(np.array(got)[mask], expected[mask],
                               rtol=1e-2, atol=1e-2)


@pytest.mark.parametrize("num_q_heads,num_kv_heads,ppc",
                         [(8, 2, 2), (8, 2, 4),
                          (12, 12, 2)])    # hb=6, n_hb=2: the write
                                           # counter spans two j sweeps
def test_ragged_fused_write_equals_separate_writer(num_q_heads,
                                                   num_kv_heads, ppc):
    """Fused KV injection on the ragged grid must equal
    write-then-attend (the separate slot-mapped writer), both in
    attention output and in the final page contents — the fused-write
    vs separate-writer equivalence check of the acceptance criteria.
    Covers multi-chunk rows (the write lands in chunk c_star only) and
    a ctx=0 pad row (no write, zero output)."""
    from aphrodite_tpu.ops.kv_cache import write_to_kv_cache
    rng = np.random.default_rng(11)
    q, kp, vp, bt, ctx, work = ragged_problem(num_q_heads,
                                              num_kv_heads, ppc)
    B, d = q.shape[0], 128
    # Globally sequence-exclusive pages (the engine's decode contract).
    perm = rng.permutation(kp.shape[0] - 1) + 1
    for b in range(B):
        n_pages = -(-int(max(ctx[b], 1)) // 8)
        bt[b, :n_pages] = perm[b * 8:b * 8 + n_pages]
    knew = rng.normal(size=(B, num_kv_heads, d)).astype(np.float32)
    vnew = rng.normal(size=(B, num_kv_heads, d)).astype(np.float32)
    slots = np.full((B,), kp.shape[0] * 8, dtype=np.int32)
    for b in range(B):
        if ctx[b] > 0:
            pos = ctx[b] - 1
            slots[b] = bt[b][pos // 8] * 8 + pos % 8
    ref_k, ref_v = write_to_kv_cache(
        jnp.asarray(knew), jnp.asarray(vnew), jnp.asarray(kp),
        jnp.asarray(vp), jnp.asarray(slots))
    want = numpy_paged_attention(q, np.asarray(ref_k),
                                 np.asarray(ref_v), bt,
                                 np.maximum(ctx, 1), 0.1)
    want[ctx == 0] = 0.0
    out, got_k, got_v = paged_decode_attention(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(bt), jnp.asarray(ctx), None, jnp.asarray(knew),
        jnp.asarray(vnew), scale=0.1, pages_per_chunk=ppc,
        work_items=work, interpret=True)
    got = np.asarray(out)
    mask = ctx > 0
    np.testing.assert_allclose(got[mask], want[mask], rtol=1e-2,
                               atol=1e-2)
    np.testing.assert_allclose(got[~mask], 0.0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(got_k), np.asarray(ref_k),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(got_v), np.asarray(ref_v),
                               atol=1e-6)


def test_ragged_padded_table_page0_masked():
    """Padded block-table entries (page 0) beyond a row's real pages
    must stay masked at ragged ctx mixes: poison page 0 with huge
    values and check the mix still matches the oracle (which never
    reads past ctx)."""
    q, kp, vp, bt, ctx, work = ragged_problem()
    kp = kp.copy()
    vp = vp.copy()
    kp[0] = 1e4
    vp[0] = 1e4
    # Rows' pad entries already point at page 0 (make_problem zeros).
    expected = numpy_paged_attention(q, kp, vp, bt,
                                     np.maximum(ctx, 1), 0.1)
    expected[ctx == 0] = 0.0
    for variant_work in (work, None):     # ragged AND classic grids
        got = paged_decode_attention(
            jnp.array(q), jnp.array(kp), jnp.array(vp), jnp.array(bt),
            jnp.array(ctx), scale=0.1, pages_per_chunk=2,
            work_items=variant_work, interpret=True)
        got = np.array(got)
        assert np.isfinite(got).all()
        mask = ctx > 0
        np.testing.assert_allclose(got[mask], expected[mask],
                                   rtol=1e-2, atol=1e-2)
        np.testing.assert_allclose(got[~mask], 0.0, atol=1e-6)


# ---- 256-token items: only live pages are copied ----

#: Contexts under 16-page (256-token) items on a table 40 pages wide,
#: which is no multiple of the item. A row's last item has 1 page live
#: (257), some (356: 7; 640: 8, reaching the table's last column) or
#: all 16 (512); a pad row; a row whose first item is partly live.
ITEM_CTX = np.array([257, 356, 512, 640, 0, 5], dtype=np.int32)
ITEM_PAGE, ITEM_PPC, ITEM_WIDTH = 16, 16, 40


def item_problem(reserve=0, seed=3):
    """Rows of ITEM_CTX, each with pages for `reserve` tokens more
    than its context (a burst's reservation). Every page that no row
    holds is NaN, page 0 among them, which the table's pad entries
    point at: a kernel that copied a dead page would carry NaN into
    the PV dot (0 x NaN)."""
    rng = np.random.default_rng(seed)
    B, Hq, Hkv, d = len(ITEM_CTX), 8, 2, 128
    pool = 1 + sum(-(-(int(c) + reserve) // ITEM_PAGE)
                   for c in ITEM_CTX if c)
    q = rng.normal(size=(B, Hq, d)).astype(np.float32)
    kp = np.full((pool + 8, ITEM_PAGE, Hkv * d), np.nan, np.float32)
    vp = kp.copy()
    bt = np.zeros((B, ITEM_WIDTH), dtype=np.int32)
    perm = rng.permutation(pool - 1) + 1
    counts, taken = [], 0
    for b, c in enumerate(ITEM_CTX):
        n = min(-(-(int(c) + reserve) // ITEM_PAGE), ITEM_WIDTH) \
            if c else 0
        bt[b, :n] = perm[taken:taken + n]
        taken += n
        counts.append(n)
        kp[bt[b, :n]] = rng.normal(size=(n, ITEM_PAGE, Hkv * d))
        vp[bt[b, :n]] = rng.normal(size=(n, ITEM_PAGE, Hkv * d))
    return q, kp, vp, bt, counts


def _against_oracle(got, q, kp, vp, bt, ctx):
    got = np.asarray(got)
    assert np.isfinite(got).all()
    want = numpy_paged_attention(q, kp, vp, bt, np.maximum(ctx, 1), 0.1)
    live = ctx > 0
    np.testing.assert_allclose(got[live], want[live], rtol=1e-2,
                               atol=1e-2)
    np.testing.assert_allclose(got[~live], 0.0, atol=1e-6)


def test_items_of_256_tokens_copy_live_pages_only():
    """The oracle at 256-token items on a table that is no multiple of
    the item, with NaN in every page no row holds: the output is
    finite and the oracle's, so a row's last item copied its live
    pages and nothing else, and what the ring held beside them was
    clean."""
    q, kp, vp, bt, counts = item_problem()
    assert ITEM_WIDTH % ITEM_PPC and max(counts) == ITEM_WIDTH
    work = build_decode_work_list(counts, ITEM_PPC)
    got = paged_decode_attention(
        jnp.array(q), jnp.array(kp), jnp.array(vp), jnp.array(bt),
        jnp.array(ITEM_CTX), scale=0.1, pages_per_chunk=ITEM_PPC,
        work_items=work, interpret=True)
    _against_oracle(got, q, kp, vp, bt, ITEM_CTX)


def test_fused_write_lands_in_a_partly_live_item():
    """Position ctx-1 of the 257-token row is the one live page of its
    last item, of the 5-token row a page of a first item that is
    partly live: the page written back is the slot writer's, and the
    NaN pages stay what they were."""
    from aphrodite_tpu.ops.kv_cache import write_to_kv_cache
    rng = np.random.default_rng(5)
    q, kp, vp, bt, counts = item_problem()
    B = len(ITEM_CTX)
    knew = rng.normal(size=(B, 2, 128)).astype(np.float32)
    vnew = rng.normal(size=(B, 2, 128)).astype(np.float32)
    slots = np.full((B,), kp.shape[0] * ITEM_PAGE, dtype=np.int32)
    for b, c in enumerate(ITEM_CTX):
        if c:
            slots[b] = bt[b, (c - 1) // ITEM_PAGE] * ITEM_PAGE + \
                (c - 1) % ITEM_PAGE
    ref_k, ref_v = write_to_kv_cache(
        jnp.asarray(knew), jnp.asarray(vnew), jnp.asarray(kp),
        jnp.asarray(vp), jnp.asarray(slots))
    out, got_k, got_v = paged_decode_attention(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(bt), jnp.asarray(ITEM_CTX), None,
        jnp.asarray(knew), jnp.asarray(vnew), scale=0.1,
        pages_per_chunk=ITEM_PPC,
        work_items=build_decode_work_list(counts, ITEM_PPC),
        interpret=True)
    _against_oracle(out, q, np.asarray(ref_k), np.asarray(ref_v), bt,
                    ITEM_CTX)
    np.testing.assert_array_equal(np.asarray(got_k), np.asarray(ref_k))
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(ref_v))


#: Ten KV heads of 128 in bf16 pages (Phi-4-mini-flash's differential
#: pairs, 40 query heads, scale 1/8): ONE head block, the page's whole
#: lane axis, at the policy's 24-page (384-token) items. Contexts whose
#: last item has 1 page live (385), some (900: 9), all (768) or that
#: fill a first item partly (5, 100); a pad row. Under a window of 512
#: a row's table starts at the window's first page (32-33 pages held:
#: a whole item and one of 8-9 live pages, as a Phi window layer's).
TEN_CTX = np.array([385, 900, 768, 1130, 0, 5, 100], dtype=np.int32)
TEN_PAGE, TEN_HQ, TEN_HKV, TEN_SCALE = 16, 40, 10, 0.125


def ten_head_problem(window, width, seed=7):
    """(q, K pages, V pages, table, contexts, page counts, dead pages):
    bf16 pages, every page no row holds NaN (page 0, which the table's
    pad entries point at, among them), pages in shuffled order."""
    rng = np.random.default_rng(seed)
    ctx = TEN_CTX.copy()
    if window:      # what the block manager's window group lets go of
        ctx -= np.maximum(0, ctx - window) // TEN_PAGE * TEN_PAGE
    counts = -(-ctx // TEN_PAGE)
    pool = 1 + int(counts.sum())
    B, lanes = len(ctx), TEN_HKV * 128
    perm = rng.permutation(pool - 1) + 1
    bt = np.zeros((B, width), dtype=np.int32)
    taken = 0
    for b, n in enumerate(counts):
        bt[b, :n] = perm[taken:taken + n]
        taken += n
    dead = np.ones(pool + 4, bool)
    dead[perm] = False
    pages = []
    for _ in range(2):
        raw = rng.normal(size=(pool + 4, TEN_PAGE, lanes)) * 0.3
        raw[dead] = np.nan
        pages.append(jnp.asarray(raw, jnp.bfloat16))
    q = rng.normal(size=(B, TEN_HQ, 128)) * 0.3
    q[:, 0::2, 64:] = 0.0           # [q1 ; 0]
    q[:, 1::2, :64] = 0.0           # [0 ; q2]
    return (jnp.asarray(q, jnp.bfloat16), pages[0], pages[1], bt, ctx,
            counts, dead)


def _ten_head_write(kp, vp, bt, ctx):
    """A new token's K and V a row, and the pages as the slot writer
    leaves them."""
    from aphrodite_tpu.ops.kv_cache import write_to_kv_cache
    rng = np.random.default_rng(9)
    B = len(ctx)
    new = [jnp.asarray(rng.normal(size=(B, TEN_HKV, 128)) * 0.3,
                       jnp.bfloat16) for _ in range(2)]
    slots = np.where(
        ctx > 0, bt[np.arange(B), np.maximum(ctx - 1, 0) // TEN_PAGE]
        * TEN_PAGE + (ctx - 1) % TEN_PAGE, kp.shape[0] * TEN_PAGE)
    return new, write_to_kv_cache(new[0], new[1], kp, vp,
                                  jnp.asarray(slots, jnp.int32))


@pytest.mark.parametrize("window,width,fused,ragged", [
    (None, 80, True, True), (None, 80, False, True),
    (512, 40, True, True), (512, 40, False, True),
    (None, 80, True, False),
], ids=["full-fused-write", "full-read-only", "window-fused-write",
        "window-read-only", "no-work-list"])
def test_ten_heads_are_one_head_block(window, width, fused, ragged):
    """The three calls of a Phi decode step (the full layer's with the
    fused write, a cross layer's read-only over the same pages, a
    window layer's) and a read-only window call, at ten KV heads in one
    block, against the jnp reference: the output its, the pages
    written the slot writer's exactly, no dead page read (NaN in every
    page no row holds), items that end partly live. A call without a
    work list runs the dense list of the table width, whose items
    beyond a row's context copy nothing: NaN stays in every page no
    row holds there too."""
    from aphrodite_tpu.ops.attention import paged_decode_attention_ref
    assert pa.head_block(TEN_HKV, 128, jnp.bfloat16) == TEN_HKV
    ppc = choose_pages_per_chunk(
        80, TEN_PAGE, pa.lane_bytes_of(TEN_HKV, 128, jnp.bfloat16))
    assert ppc == 24
    q, kp, vp, bt, ctx, counts, dead = ten_head_problem(window, width)
    assert any(0 < n % ppc < ppc for n in counts)   # partly live items
    dead = jnp.asarray(dead)[:, None, None]
    new, (want_k, want_v) = (None, None), (kp, vp)
    if fused:
        new, (want_k, want_v) = _ten_head_write(kp, vp, bt, ctx)
    # the reference gathers every table entry: give it the pages with
    # zeros where no row's context reaches
    want = np.asarray(paged_decode_attention_ref(
        q, jnp.where(dead, 0, want_k), jnp.where(dead, 0, want_v),
        jnp.asarray(bt), jnp.asarray(np.maximum(ctx, 1)), TEN_SCALE,
        window=window), np.float32)
    got = paged_decode_attention(
        q, kp, vp, jnp.asarray(bt), jnp.asarray(ctx), None, *new,
        scale=TEN_SCALE, pages_per_chunk=ppc,
        work_items=build_decode_work_list(counts, ppc) if ragged
        else None, window=window, interpret=True)
    if fused:
        got, got_k, got_v = got
        np.testing.assert_array_equal(np.asarray(got_k, np.float32),
                                      np.asarray(want_k, np.float32))
        np.testing.assert_array_equal(np.asarray(got_v, np.float32),
                                      np.asarray(want_v, np.float32))
    got = np.asarray(got, np.float32)
    assert np.isfinite(got).all()
    live = ctx > 0
    np.testing.assert_allclose(got[live], want[live], rtol=2e-2,
                               atol=2e-2)
    np.testing.assert_allclose(got[~live], 0.0, atol=1e-6)


#: AI21-Jamba2-3B's attention layers: ONE KV head of 128 under 20 query
#: heads (a row's packed query has 20 rows, a page copy is 4 KB of one
#: lane tile, a 512-token item 32 pages). Contexts a row, cycled: pad
#: rows, a first token, a canary's 496, items that end partly live
#: (513 is an item and a page), the cell's longest, 1,536.
MQA_CTX = (700, 0, 1, 496, 513, 17, 1536, 64, 1030, 0, 300, 5)
MQA_PAGE, MQA_HQ, MQA_WIDTH = 16, 20, 96


def mqa_problem(rows, seed=13):
    """(q, K pages, V pages, table, contexts, page counts, dead pages)
    at one KV head: bf16 pages, NaN in every page no row holds (page 0,
    which the table's pad entries point at, among them), pages in
    shuffled order."""
    rng = np.random.default_rng(seed)
    ctx = np.array([MQA_CTX[b % len(MQA_CTX)] for b in range(rows)],
                   dtype=np.int32)
    counts = -(-ctx // MQA_PAGE)
    pool = 1 + int(counts.sum())
    perm = rng.permutation(pool - 1) + 1
    bt = np.zeros((rows, MQA_WIDTH), dtype=np.int32)
    taken = 0
    for b, n in enumerate(counts):
        bt[b, :n] = perm[taken:taken + n]
        taken += n
    dead = np.ones(pool + 4, bool)
    dead[perm] = False
    pages = []
    for _ in range(2):
        raw = rng.normal(size=(pool + 4, MQA_PAGE, 128)) * 0.3
        raw[dead] = np.nan
        pages.append(jnp.asarray(raw, jnp.bfloat16))
    q = jnp.asarray(rng.normal(size=(rows, MQA_HQ, 128)) * 0.3,
                    jnp.bfloat16)
    return q, pages[0], pages[1], bt, ctx, counts, dead


@pytest.mark.parametrize("rows,fused", [
    (1, True), (96, True), (129, True), (96, False)],
    ids=["one-row", "96-rows", "129-rows", "96-rows-read-only"])
def test_one_kv_head_under_twenty_query_heads(rows, fused):
    """The decode kernel and its fused K/V write at Jamba's attention
    shape (`head_block` 1, the lane axis one tile, items of 32 pages),
    at one row, a full bucket of 96 and the 129 past the slots, against
    the jnp path: the output its, the pages written the slot writer's
    exactly, no dead page read (NaN in every page no row holds), pad
    rows zeros."""
    from aphrodite_tpu.ops.attention import paged_decode_attention_ref
    from aphrodite_tpu.ops.kv_cache import write_to_kv_cache
    assert pa.head_block(1, 128, jnp.bfloat16) == 1
    ppc = choose_pages_per_chunk(
        MQA_WIDTH, MQA_PAGE, pa.lane_bytes_of(1, 128, jnp.bfloat16))
    assert ppc == 32
    q, kp, vp, bt, ctx, counts, dead = mqa_problem(rows)
    dead = jnp.asarray(dead)[:, None, None]
    new, (want_k, want_v) = (None, None), (kp, vp)
    if fused:
        rng = np.random.default_rng(9)
        new = [jnp.asarray(rng.normal(size=(rows, 1, 128)) * 0.3,
                           jnp.bfloat16) for _ in range(2)]
        slots = np.where(
            ctx > 0, bt[np.arange(rows), np.maximum(ctx - 1, 0) // MQA_PAGE]
            * MQA_PAGE + (ctx - 1) % MQA_PAGE, kp.shape[0] * MQA_PAGE)
        want_k, want_v = write_to_kv_cache(
            new[0], new[1], kp, vp, jnp.asarray(slots, jnp.int32))
    want = np.asarray(paged_decode_attention_ref(
        q, jnp.where(dead, 0, want_k), jnp.where(dead, 0, want_v),
        jnp.asarray(bt), jnp.asarray(np.maximum(ctx, 1)), 128 ** -0.5),
        np.float32)
    got = paged_decode_attention(
        q, kp, vp, jnp.asarray(bt), jnp.asarray(ctx), None, *new,
        scale=128 ** -0.5, pages_per_chunk=ppc,
        work_items=build_decode_work_list(counts, ppc), interpret=True)
    if fused:
        got, got_k, got_v = got
        np.testing.assert_array_equal(np.asarray(got_k, np.float32),
                                      np.asarray(want_k, np.float32))
        np.testing.assert_array_equal(np.asarray(got_v, np.float32),
                                      np.asarray(want_v, np.float32))
    got = np.asarray(got, np.float32)
    assert np.isfinite(got).all()
    live = ctx > 0
    np.testing.assert_allclose(got[live], want[live], rtol=2e-2,
                               atol=2e-2)
    np.testing.assert_allclose(got[~live], 0.0, atol=1e-6)


def test_a_pinned_head_block_reads_what_the_whole_one_reads():
    """`hb=` (benchmarks/attn_ab.py's arm) divides the ten heads into
    two lane-sliced blocks of five, as the policy did before a block
    was the whole lane axis: the same output and the same pages."""
    q, kp, vp, bt, ctx, counts, _ = ten_head_problem(None, 80)
    new, _ = _ten_head_write(kp, vp, bt, ctx)

    def call(hb):
        return paged_decode_attention(
            q, kp, vp, jnp.asarray(bt), jnp.asarray(ctx), None, *new,
            scale=TEN_SCALE, pages_per_chunk=16, hb=hb,
            work_items=build_decode_work_list(counts, 16),
            interpret=True)
    whole, halves = call(None), call(5)
    np.testing.assert_allclose(np.asarray(halves[0], np.float32),
                               np.asarray(whole[0], np.float32),
                               atol=1e-2)
    for a, b in zip(halves[1:], whole[1:]):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    with pytest.raises(ValueError, match="does not divide"):
        call(4)


@pytest.mark.parametrize("steps_on", [0, 20])
def test_a_bursts_list_serves_the_context_of_the_step(steps_on):
    """A burst builds one list from the pages it reserved and reuses
    it over its steps: which pages an item copies follows the context
    length of the call, not the list. At the first step the reserved
    pages beyond the context are NaN (an item that lies wholly beyond
    it copies nothing); twenty steps on, the 512-token row has grown
    into its third item and the 257-token row into a second live
    page."""
    q, kp, vp, bt, counts = item_problem(reserve=40)
    ctx = np.where(ITEM_CTX > 0, ITEM_CTX + steps_on, 0).astype(np.int32)
    ctx[3] = ITEM_CTX[3]        # already at the table's width
    kp, vp = kp.copy(), vp.copy()
    for b, c in enumerate(ctx):                 # not yet written
        kp[bt[b, -(-int(c) // ITEM_PAGE):counts[b]]] = np.nan
        vp[bt[b, -(-int(c) // ITEM_PAGE):counts[b]]] = np.nan
    work = build_decode_work_list(counts, ITEM_PPC)
    # a third item: the 640-token row, and the 512-token row's
    # reservation (552 tokens), wholly beyond its context at step 0
    assert work[1].tolist().count(2) == 2
    got = paged_decode_attention(
        jnp.array(q), jnp.array(kp), jnp.array(vp), jnp.array(bt),
        jnp.array(ctx), scale=0.1, pages_per_chunk=ITEM_PPC,
        work_items=work, interpret=True)
    _against_oracle(got, q, kp, vp, bt, ctx)


# ---- satellite: call-time APHRODITE_ATTN_PF ----

def test_pf_depth_read_at_call_time(monkeypatch):
    """A bad APHRODITE_ATTN_PF must fail the CALL, not the import (the
    old module-level read killed every import and froze A/B sweeps to
    one value per process)."""
    import importlib
    monkeypatch.setenv("APHRODITE_ATTN_PF", "banana")
    importlib.reload(pa)                 # import survives a bad value
    q, kp, vp, bt, ctx, _ = ragged_problem()
    with pytest.raises(ValueError, match="APHRODITE_ATTN_PF"):
        pa.paged_decode_attention(
            jnp.array(q), jnp.array(kp), jnp.array(vp), jnp.array(bt),
            jnp.array(ctx), scale=0.1, pages_per_chunk=2,
            interpret=True)
    monkeypatch.setenv("APHRODITE_ATTN_PF", "0")
    with pytest.raises(ValueError, match=">= 1"):
        pa.paged_decode_attention(
            jnp.array(q), jnp.array(kp), jnp.array(vp), jnp.array(bt),
            jnp.array(ctx), scale=0.1, pages_per_chunk=2,
            interpret=True)
    # Different depths are selectable in ONE process (no re-import).
    monkeypatch.setenv("APHRODITE_ATTN_PF", "2")
    assert pa._pf_depth() == 2
    monkeypatch.setenv("APHRODITE_ATTN_PF", "7")
    assert pa._pf_depth() == 7
    monkeypatch.delenv("APHRODITE_ATTN_PF")
    importlib.reload(pa)


# ---- satellite: a call without a work list ----

def test_a_width_the_item_does_not_divide_runs_as_given(monkeypatch):
    """pages_per_seq % pages_per_chunk != 0 and no work list: the item
    is used as given (no clamp to a divisor), a row's last item copies
    its live pages only, and the result matches the oracle."""
    q, kp, vp, bt, ctx = make_problem(batch=3, num_q_heads=8,
                                      num_kv_heads=2, dim=128,
                                      page_size=4, pages_per_seq=12,
                                      pages=64)
    seen = {}
    real_impl = pa._paged_decode_impl

    def spy(*a, **kw):
        seen.update(ppc=kw["pages_per_chunk"], items=a[6].tolist())
        return real_impl(*a, **kw)
    monkeypatch.setattr(pa, "_paged_decode_impl", spy)
    expected = numpy_paged_attention(q, kp, vp, bt, ctx, 0.1)
    got = pa.paged_decode_attention(
        jnp.array(q), jnp.array(kp), jnp.array(vp), jnp.array(bt),
        jnp.array(ctx), scale=0.1, pages_per_chunk=8, interpret=True)
    assert seen["ppc"] == 8
    # two items a row (8 pages and the 4 left), padded to the bucket
    assert seen["items"] == [0, 1] * 3 + [-1, -1]
    np.testing.assert_allclose(np.array(got), expected, rtol=1e-2,
                               atol=1e-2)
    with pytest.raises(ValueError, match="pages_per_chunk"):
        pa.paged_decode_attention(
            jnp.array(q), jnp.array(kp), jnp.array(vp), jnp.array(bt),
            jnp.array(ctx), scale=0.1, pages_per_chunk=0,
            interpret=True)


@pytest.mark.parametrize("fused", [False, True],
                         ids=["read-only", "fused-write"])
def test_no_work_list_equals_the_list_of_true_page_counts(fused):
    """A call without a list builds the dense list of its table width
    (three 16-page items a row on a table 40 wide) and equals, bit for
    bit, the call with the list of the rows' true page counts: the
    5-token row's and the 257-token row's later items, and all of the
    pad row's, hold no live page, copy nothing (NaN in every page no
    row holds) and leave the online-softmax state as it was. With the
    fused write, the pages written are the same too."""
    rng = np.random.default_rng(11)
    q, kp, vp, bt, counts = item_problem()
    assert -(-counts[5] // ITEM_PPC) == 1 < -(-ITEM_WIDTH // ITEM_PPC)
    B = len(ITEM_CTX)
    new = (None, None)
    if fused:
        new = tuple(jnp.asarray(rng.normal(size=(B, 2, 128)),
                                jnp.float32) for _ in range(2))

    def call(work):
        return paged_decode_attention(
            jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(bt), jnp.asarray(ITEM_CTX), None, *new,
            scale=0.1, pages_per_chunk=ITEM_PPC, work_items=work,
            interpret=True)
    listed = call(build_decode_work_list(counts, ITEM_PPC))
    dense = call(None)
    for a, b in zip(listed if fused else (listed,),
                    dense if fused else (dense,)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    out = np.asarray(dense[0] if fused else dense)
    assert np.isfinite(out).all() and np.abs(out).max() > 0


def test_the_module_has_one_decode_kernel():
    """One kernel body, and none of what selected between two."""
    kernels = [n for n in vars(pa) if n.startswith("_decode_kernel")]
    assert kernels == ["_decode_kernel_ragged"]
    for gone in ("_decode_kernel_tm", "ragged_enabled", "amla_enabled",
                 "clamp_pages_per_chunk"):
        assert not hasattr(pa, gone), gone


# ---- work-list builder ----

def test_build_work_list_structure():
    ws, wc = build_decode_work_list([1, 0, 5, 8], 2, pad_to=12)
    # rows: 1 page -> 1 chunk; 0 pages -> 1 masked item; 5 -> 3; 8 -> 4
    assert ws.tolist() == [0, 1, 2, 2, 2, 3, 3, 3, 3,  # 9 real items
                           4, 4, 4,                    # dead: dummy row
                           -1]                         # sentinel
    assert wc.tolist() == [0, 0, 0, 1, 2, 0, 1, 2, 3, -1, -1, -1]


def test_build_work_list_bucketing_and_errors():
    ws, wc = build_decode_work_list([1] * 5, 2)
    assert wc.shape[0] == 8 and ws.shape[0] == 9   # bucketed to 8
    assert ws[-1] == -1
    with pytest.raises(ValueError, match="pad_to"):
        build_decode_work_list([4, 4], 2, pad_to=3)


#: one token of a head block of 8 bf16 heads of 128 (Mistral's pages)
MISTRAL_LANE_BYTES = 8 * 128 * 2


@pytest.mark.parametrize("width,page,lane_bytes,want", [
    (4, 32, MISTRAL_LANE_BYTES, 4),     # a table narrower than an item
    (8, 16, MISTRAL_LANE_BYTES, 8),     # is one item
    (64, 32, MISTRAL_LANE_BYTES, 16),   # 512-token items, at any batch
    (64, 16, MISTRAL_LANE_BYTES, 32),
    (72, 16, MISTRAL_LANE_BYTES, 32),   # the benchmark cell's widths: no
    (80, 16, MISTRAL_LANE_BYTES, 32),   # multiple of the item, and not
    (88, 16, MISTRAL_LANE_BYTES, 32),   # shrunk to a divisor (11 pages)
    (88, 16, 8 * 128 * 1, 32),          # 8-bit pages: the cap holds
    (88, 16, 8 * 256 * 2, 16),          # lanes twice as wide: 256 tokens
    (88, 16, 8 * 512 * 4, 8),           # never under 128 tokens
])
def test_choose_pages_per_chunk_policy(width, page, lane_bytes, want):
    """The item is the largest multiple of 128 tokens, up to 512, that
    leaves the read ring four slots inside its budget: a function of
    the shapes a call sees, not of the batch."""
    ppc = choose_pages_per_chunk(width, page, lane_bytes)
    assert ppc == want
    tokens = ppc * page
    assert ppc == width or tokens % 128 == 0
    slots = pa._ring_slots(6, tokens, lane_bytes)
    assert slots >= pa._MIN_RING_SLOTS or tokens == 128
    assert slots * 2 * tokens * lane_bytes <= pa._RING_BUDGET_BYTES or \
        tokens == 128


def _divisor_block(heads):
    return next(hb for hb in (8, 7, 6, 5, 4, 3, 2, 1) if heads % hb == 0)


@pytest.mark.parametrize("dtype,head_dim,whole_up_to", [
    (jnp.bfloat16, 128, 10),        # 2,560 B of lanes a token
    (jnp.int8, 128, 21),            # 2,688 B
    (jnp.float8_e5m2, 128, 21),
    (jnp.float32, 128, 5),
    (jnp.bfloat16, 256, 5),         # padded 192- or 256-wide heads
])
def test_head_block_is_the_whole_lane_axis_while_the_ring_affords_it(
        dtype, head_dim, whole_up_to):
    """All heads in one block while the read ring keeps four slots of
    a 384-token item, by shapes and the page type alone; past that the
    largest divisor <= 8, as before. The item and the ring follow the
    block (`lane_bytes_of`), so runner and kernel agree."""
    itemsize = jnp.dtype(dtype).itemsize
    for heads in range(1, 41):
        hb = pa.head_block(heads, head_dim, dtype)
        want = heads if heads <= whole_up_to else _divisor_block(heads)
        assert hb == want, (heads, hb)
        lane_bytes = pa.lane_bytes_of(heads, head_dim, dtype)
        assert lane_bytes == hb * head_dim * itemsize
        tokens = choose_pages_per_chunk(4096, 16, lane_bytes) * 16
        if hb == heads and heads > 8:
            assert tokens >= pa._MIN_WHOLE_ITEM_TOKENS
            assert pa._ring_slots(6, tokens, lane_bytes) >= \
                pa._MIN_RING_SLOTS


@pytest.mark.parametrize("heads,dtype,hb,item_tokens", [
    (8, jnp.bfloat16, 8, 512),      # mistral-7b-w4a8: as it was
    (4, jnp.bfloat16, 4, 512),      # smallthinker-21ba3b-bf16: as it was
    (1, jnp.bfloat16, 1, 512),      # jamba2-3b-bf16: one head, one lane tile
    (10, jnp.bfloat16, 10, 384),    # phi-4-mini-flash-bf16: was 5, 512
    (9, jnp.bfloat16, 9, 384),      # was 3
    (11, jnp.bfloat16, 1, 512),     # past the threshold: divides
    (12, jnp.bfloat16, 6, 512),
    (16, jnp.bfloat16, 8, 512), (32, jnp.bfloat16, 8, 512),
    (16, jnp.int8, 16, 512),        # was 8
    (21, jnp.int8, 21, 384), (22, jnp.int8, 2, 512),
])
def test_head_block_of_the_served_models(heads, dtype, hb, item_tokens):
    assert pa.head_block(heads, 128, dtype) == hb
    assert choose_pages_per_chunk(
        4096, 16, pa.lane_bytes_of(heads, 128, dtype)) * 16 == item_tokens


def test_padded_work_length_gives_a_bucket_few_lengths():
    """The list length is part of a decode program's key: batch x 2^k,
    clamped to the dense cell count. At the cell's 48 rows and 512-token
    items every table width has one length."""
    assert [pa.padded_work_length(n, 48, 88, 32)
            for n in (48, 49, 96, 97, 144)] == [48, 96, 96, 144, 144]
    assert pa.padded_work_length(48 * 3, 48, 72, 32) == 144
    assert pa.padded_work_length(48 * 11, 48, 88, 8) == 528   # PR 31's
    assert pa.padded_work_length(3, 1, 72, 32) == 3


def test_count_decode_pages_follows_the_kernels_rule():
    """A row copies its live pages as far as its items reach: all of
    them, when the list was built from pages that cover the context."""
    fetched, live = pa.count_decode_pages(
        [257, 356, 512, 640, 0, 5], [2, 2, 2, 3, 1, 1], 16, 16)
    assert (fetched, live) == (17 + 23 + 32 + 40 + 0 + 1,) * 2
    # items that stop short of the context (never built by the runner)
    assert pa.count_decode_pages([640], [2], 16, 16) == (32, 40)


# ---- satellite: fused-write routing preconditions ----

def _routing_layer(sliding_window):
    from aphrodite_tpu.modeling.layers.attention import PagedAttention
    layer = PagedAttention(8, 128, 0.1, num_kv_heads=2,
                           sliding_window=sliding_window)
    # Pretend the kernel path is available (CPU test hosts report
    # backend != tpu); the ROUTING predicate is what's under test.
    layer._pallas_decode_ok = lambda k_pages, metadata: True
    return layer


def test_sliding_window_routes_to_the_fused_write_too():
    """A window layer's table slides (its page group lets whole pages
    go and counts from the first it keeps); it does not wrap. So the
    write position is ctx-1 of the table the layer is given, as for
    any other layer, and the fused in-kernel write serves it."""
    from aphrodite_tpu.modeling.input_metadata import InputMetadata
    meta = InputMetadata(
        slot_mapping=jnp.zeros((2,), jnp.int32),
        block_tables=jnp.zeros((2, 4), jnp.int32),
        context_lens=jnp.ones((2,), jnp.int32),
        is_prompt=False)
    pages = jnp.zeros((4, 8, 2 * 128), jnp.bfloat16)
    assert _routing_layer(None)._fused_decode_ok(pages, meta)
    assert _routing_layer(1024)._fused_decode_ok(pages, meta)
    # Prompt steps and cache-less profiling runs never fuse either.
    assert not _routing_layer(None)._fused_decode_ok(
        pages, meta.replace(is_prompt=True))
    assert not _routing_layer(None)._fused_decode_ok(None, meta)


def test_layer_passes_work_list_to_kernel(monkeypatch):
    """PagedAttention._decode must hand metadata.decode_work and the
    runner's pages_per_chunk through to the kernel (and fall back to
    the shared chunk policy when no list rides the metadata)."""
    from aphrodite_tpu.modeling.input_metadata import InputMetadata
    from aphrodite_tpu.modeling.layers.attention import PagedAttention
    calls = {}

    def fake_kernel(q3, kpp, vpp, tables, cl, slopes, knew=None,
                    vnew=None, **kw):
        calls.update(kw)
        return jnp.zeros_like(q3)
    monkeypatch.setattr(pa, "paged_decode_attention", fake_kernel)
    layer = PagedAttention(8, 128, 0.1, num_kv_heads=2)
    layer._pallas_decode_ok = lambda k_pages, metadata: True
    pages = jnp.zeros((64, 8, 2 * 128), jnp.float32)
    work = build_decode_work_list([2, 1], 2)
    meta = InputMetadata(
        slot_mapping=jnp.zeros((2,), jnp.int32),
        block_tables=jnp.zeros((2, 8), jnp.int32),
        context_lens=jnp.ones((2,), jnp.int32),
        is_prompt=False,
        decode_work=(jnp.asarray(work[0]), jnp.asarray(work[1])),
        decode_ppc=2)
    q = jnp.zeros((2, 1, 8 * 128), jnp.float32)
    layer._decode(q, pages, pages, meta)
    assert calls["pages_per_chunk"] == 2
    assert calls["work_items"] is meta.decode_work
    # Without a runner-built list: shared policy, no work items.
    layer._decode(q, pages, pages, meta.replace(decode_work=None))
    assert calls["work_items"] is None
    # ... sized from the layer's own head block and the pages' type
    assert calls["pages_per_chunk"] == choose_pages_per_chunk(
        8, 8, 2 * 128 * 4) == 8


# ---- model runner: work-list build inside the bucketed burst ----

def test_model_runner_builds_consistent_work_list():
    """_prepare_decode must emit a decode_work list consistent with
    its padded tables: chunk counts from each row's REAL reserved
    pages, the shared pages_per_chunk policy (sized from the lanes of
    the runner's pages), padded rows one masked item, dead padding to
    the runner's length; and it counts the pages the step's attention
    copies and those that are live."""
    from types import SimpleNamespace
    from aphrodite_tpu.common.sampling_params import SamplingParams
    from aphrodite_tpu.common.sequence import (SequenceData,
                                               SequenceGroupMetadata)
    from aphrodite_tpu.common.config import PageGroups
    from aphrodite_tpu.common.tracing import Tracer
    from aphrodite_tpu.executor.model_runner import ModelRunner

    runner = ModelRunner.__new__(ModelRunner)
    runner.page_size = 16
    runner.num_slots = 16 * 1024
    runner.kv_scale = 1.0
    runner.pages_bucket = 8
    runner.attn_lane_bytes = MISTRAL_LANE_BYTES
    runner.tracer = Tracer()
    runner._input_sharding = None      # single-device placement plan
    runner._results_committed = False  # weights made by a program
    runner._tp = 1
    runner._decode_work = {}
    runner.page_groups = PageGroups.of([False], None)
    runner.step_counters = ()

    sp = SamplingParams(temperature=0.0, max_tokens=4, ignore_eos=True)
    mds = []
    # Ragged mix: 3, 40, and 600 tokens -> 1, 3, and 38 reserved pages
    # (two 512-token items on a table 40 pages wide).
    for i, n_tok in enumerate((3, 40, 600)):
        data = SequenceData(list(range(n_tok)))
        n_pages = -(-n_tok // 16)
        mds.append(SequenceGroupMetadata(
            request_id=str(i), is_prompt=False,
            seq_data={i: data}, sampling_params=sp,
            block_tables={i: list(range(100 * i, 100 * i + n_pages))},
            persistent_data={i: {}}))
    inputs, _ = ModelRunner._prepare_decode(runner, mds)
    meta = inputs["metadata"]
    assert meta.decode_work is not None and meta.decode_ppc > 0
    ws, wc = (np.asarray(meta.decode_work[0]),
              np.asarray(meta.decode_work[1]))
    padded_batch = inputs["padded_batch"]          # bucketed to 4
    ppc = meta.decode_ppc
    # The batch rides as one array in the place of its block tables;
    # the program slices it.
    _, _, meta = ModelRunner._unpacked(runner, None, None, meta)
    assert meta.block_tables.shape == (4, 40)
    assert np.asarray(meta.context_lens).tolist() == [3, 40, 600, 0]
    assert np.asarray(meta.slot_mapping).tolist() == [
        2, 102 * 16 + 7, 237 * 16 + 7, runner.num_slots]
    assert ppc == choose_pages_per_chunk(
        meta.block_tables.shape[1], 16, MISTRAL_LANE_BYTES) == 32
    # The step's pages, counted where the list is built: each row's
    # pages below its context length, all of them copied.
    assert runner.tracer.counts["attn.pages_live"] == 1 + 3 + 38
    assert runner.tracer.counts["attn.pages_fetched"] == 1 + 3 + 38
    # Every padded row appears, chunks contiguous and chunk-ordered.
    expected_chunks = [max(1, -(-p // ppc)) for p in (1, 3, 38)] + \
        [1] * (padded_batch - 3)
    seqs, chunks = [], []
    for i, n in enumerate(expected_chunks):
        seqs.extend([i] * n)
        chunks.extend(range(n))
    nw_real = len(seqs)
    assert ws[:nw_real].tolist() == seqs
    assert wc[:nw_real].tolist() == chunks
    # Padding is dead items targeting the dummy row; sentinel closes.
    assert (wc[nw_real:] == -1).all()
    assert (ws[nw_real:-1] == padded_batch).all()
    assert ws[-1] == -1
    # The padded length follows the padded_batch * 2^k discipline.
    assert wc.shape[0] == pa.padded_work_length(
        nw_real, padded_batch, 40, ppc) == 8
    # A row's last item may reach past the table's width (40 is no
    # multiple of 32); its live pages never do.
    max_chunk = wc[:nw_real].max()
    assert (max_chunk + 1) * ppc > meta.block_tables.shape[1]
    assert -(-600 // 16) <= meta.block_tables.shape[1]
    # The device copy of the list is kept while no row's chunk count
    # changes (a token more on a row's last page), and rebuilt when one
    # does.
    mds[0].seq_data[0].append_token_id(7, 0.0)
    again, _ = ModelRunner._prepare_decode(runner, mds)
    assert again["metadata"].decode_work[0] is inputs[
        "metadata"].decode_work[0]
    # (a step counts its pages whether the list was rebuilt or not)
    assert runner.tracer.counts["attn.pages_live"] == 2 * (1 + 3 + 38)
    mds[1].block_tables[1] = list(range(100, 100 + ppc + 1))
    grown, _ = ModelRunner._prepare_decode(runner, mds)
    assert grown["metadata"].decode_work[0] is not inputs[
        "metadata"].decode_work[0]
