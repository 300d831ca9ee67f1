"""Pallas TPU flash-decoding kernel over the paged KV cache.

TPU-native replacement for the reference's PagedAttention V1/V2 CUDA
kernels (`kernels/attention/attention_kernels.cu:717,907`, 951 lines of
FasterTransformer-derived CUDA). Design (round-3 "token-major" layout,
round-6 "ragged work-list" grid):

- KV pages are TOKEN-MAJOR with heads collapsed into lanes:
      k_pages, v_pages: [num_pages, page_size, H * d]
  One token's K (all heads) is contiguous; one page is a contiguous
  [page_size, H*d] slab. A grid cell DMAs a whole page (or an aligned
  hb*d lane slice of it) in ONE descriptor — 32 KB-class transfers
  instead of 4 KB — and the layout has no Mosaic tile padding for ANY
  head count (lanes = H*d >= 128 always), so it survives tp-sharding
  down to one local head.
- RAGGED WORK-LIST GRID (the one grid; "Ragged Paged Attention",
  arxiv 2604.15464): the caller flattens (sequence, chunk) pairs into
  a 1-D list of REAL work items — one item per pages_per_chunk pages a
  sequence actually reserved, not per batch-max-context cell — and
  scalar-prefetches it as two int32 arrays (wi_seq, wi_chunk). Grid is
  (n_hb, num_work_items); short sequences contribute few items, long
  ones many, and list padding is DEAD items (chunk -1) that skip DMA,
  compute, and output entirely. An item is as large as the read ring
  affords (choose_pages_per_chunk: 512 tokens of bf16 Mistral pages),
  because a grid cell costs what it costs whatever it holds, and it
  copies only its pages below the row's context length: a row's last
  item stops at its last live page, so the table width need be no
  multiple of the item and no dead page is read (the ring's V slots
  start clean, so what a partly live item leaves uncopied is finite
  under p = 0). What is a row's and not an item's (the packed query)
  is built at the row's first item. ONE unified prefetch ring runs across
  all items: cell i issues cell i+pf_depth's K+V page copies
  back-to-back before waiting its own, so page-DMA latency overlaps
  several cells' compute regardless of how many chunks any sequence
  has. Work items of one sequence are grid-adjacent, so cross-chunk
  online-softmax state lives in persistent VMEM scratch (reset at
  chunk 0, finalized at the sequence's last item) — no inter-cell HBM
  combine pass. A call without work_items runs the same kernel over
  the dense list of its table width (paged_decode_attention).
- Head blocks: hb = Hkv, the page's whole lane axis (one contiguous
  descriptor a page, a page visited once), while the read ring
  affords four slots of a 384-token item (`head_block`: up to ten bf16
  heads of 128, up to 21 of 8-bit pages); past that the largest
  divisor of Hkv that is <= 8, each block a lane slice. The cell's hb
  kv-heads ride as a LANE block: scores come from one MXU dot
  [group*hb, hb*d] x [hb*d, chunk] where q is packed block-diagonally
  (row r holds q in its own head's d lanes, zeros elsewhere) —
  cross-head products are exactly zero, so no masked score tile and no
  H-times VPU exp waste (the round-2 allheads kernel's documented
  flaw).
- p@V lands as [rows, hb*d]; each row's own head block is extracted
  with hb static lane-slices (masked adds) — no in-register reshape.
- FUSED KV WRITE (decode steps): pass knew/vnew [batch, n_hb, hb*d]
  and the kernel injects the current token's K/V into the loaded chunk
  in VMEM (position ctx-1) and writes that ONE page back to HBM
  (lane-sliced per head block, pages aliased in place) — replacing the
  separate page-writer kernel pass entirely: the page was being DMA'd
  in for attention anyway, so the write costs two extra page-sized
  DMAs instead of a whole second kernel's round trips. Only ONE
  work item per (sequence, head block) issues a write (the chunk
  holding position ctx-1), so the writeback ring is keyed by an SMEM
  write counter — the n-th write waits the (n-_WB_SLOTS)-th — instead
  of by grid cell, which would leave gaps whenever a cell doesn't
  write.
  PRECONDITIONS (the engine's decode contract): pages are
  sequence-exclusive; position ctx-1 lies within the sequence's
  RESERVED block-table entries (burst reservation guarantees this —
  the caller passes pad-clamped tables, so a violation would silently
  write a valid-but-wrong page rather than fault); sliding-window
  models must NOT use this (their write slot rotates modulo the
  window; the layer routes them to the slot-mapped writer).

Padded block-table entries must point at any valid page (use 0); padded
positions are masked to -inf before the online-softmax update, and the
cache is zero-initialized, so garbage pages never produce NaNs.

int8/fp8 KV pages dequant in-kernel: the scale folds into the score
scale (q·k·S == (q·S)·k) and the output epilogue; fused writes
quantize the injected token into stored units first.

AMLA rescale (round 7, arxiv 2509.25224 — the FOLD002 closure):
scores ride the base-2 domain (log2(e) folds into the static q scale)
and the online-softmax running max quantizes UP to an integer, so the
per-chunk correction 2^(m_prev - m_new) is an exact power of two. The
default path applies it to the l and [rows, d] accumulator planes as
an exponent-bias ADD (`_mul_pow2`: bitcast, integer add, bitcast) —
the per-chunk VPU multiplies FOLD002 flagged are gone. The classic
multiply survives only behind the `amla=False` keyword, as the
reference tests/kernels/test_amla_attention.py holds the add
bit-equal to away from underflow (the correction is an exact power of
two either way); no served call passes it.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from aphrodite_tpu.common import flags

_NEG_INF = -2.0**30  # large-but-finite: avoids inf-inf NaNs in corrections

#: log2(e): scores ride the BASE-2 domain (folded into the static q
#: scale), so the online-softmax weights are exp2 and the running max
#: quantizes to an integer — the AMLA precondition (arxiv 2509.25224).
_LOG2E = 1.4426950408889634


def _mul_pow2(x, delta):
    """x * 2^delta as an exponent-bias ADD — AMLA's mul-by-add rescale
    (arxiv 2509.25224): bitcast f32 -> int32, add delta << 23, bitcast
    back. `delta` is integer-valued f32 <= 0 (the online-softmax
    running max never decreases). Entries whose biased exponent would
    underflow — including x == 0 and denormals — map to exactly 0.0,
    which is what the multiply rounds to on TPU (denormals flush);
    finite normals cannot overflow since delta <= 0. Assumes finite x
    (the accumulators are bounded by construction: p <= 1 and chunks
    are <= 512 tokens)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    d = jnp.maximum(delta, -254.0).astype(jnp.int32)
    shifted = jax.lax.bitcast_convert_type(bits + (d << 23),
                                           jnp.float32)
    exp_field = jax.lax.shift_right_logical(bits, 23) & 0xFF
    return jnp.where(exp_field + d > 0, shifted, 0.0)

#: What a device trace calls this kernel over LATENT pages
#: (`_paged_decode_impl`'s `latent`), by the start of the operation's
#: name: the call is NAMED so (`pallas_call`'s `name=`), apart from the
#: K/V-pair calls, which appear under the jitted function's name
#: (`_paged_decode_impl`). The benchmark's reader finds the latent
#: calls' seconds by it (`perf/layers/decode_attn_latent_roofline_pct.py`
#: reads this constant from this file).
LATENT_DEVICE_OP_PREFIXES = ("paged-decode-latent",)

# Fused-write writeback ring depth: write n reuses slot n % _WB_SLOTS and
# waits write n-_WB_SLOTS's DMA, so deeper rings hide more write latency.
_WB_SLOTS = 8

# Combined K+V read-ring VMEM budget: it sizes the work item
# (choose_pages_per_chunk), and the prefetch depth is trimmed to it
# where a caller asks for larger chunks, so the ring never crowds out
# the rest of the ~16 MB VMEM.
_RING_BUDGET_BYTES = 8 * 1024 * 1024

# The largest work item the policy gives, in tokens (the f32 score
# tile [rows, tokens] and the dequantised K and V of 8-bit pages are
# temporaries beside the ring), and the slots it leaves the ring: the
# item in use, two in flight and the one a landing load must not
# alias.
_MAX_ITEM_TOKENS = 512
_MIN_RING_SLOTS = 4

# The shortest item whose copies still take longer than the cell's
# instruction stream (its fixed cost, its descriptors' issue time and
# its arithmetic; PERF.md §5): a head block is the page's whole lane
# axis as long as the ring affords such an item (head_block).
_MIN_WHOLE_ITEM_TOKENS = 384

# Ragged work-list length buckets (each distinct padded length is one
# compiled program — same power-of-two-and-a-half spacing rationale
# as the decode batch buckets in executor/model_runner.py).
_WORK_BUCKETS = [8, 16, 24, 32, 48, 64, 96, 128, 192, 256, 384, 512,
                 768, 1024, 1536, 2048, 3072, 4096]


def _pf_depth() -> int:
    """Cross-cell read-pipeline depth (cell i starts cell i+depth's
    chunk loads). Read from APHRODITE_ATTN_PF at CALL time — reading
    and validating at import killed every import on a bad env var and
    forced a re-import per A/B sweep point. The registry's strict
    validation raises FlagError (a ValueError naming the flag) on a
    malformed or < 1 value."""
    return flags.get_int("APHRODITE_ATTN_PF")


def _item_tokens(lane_bytes: int) -> int:
    """Tokens of the largest work item that leaves the read ring
    `_MIN_RING_SLOTS` slots of `lane_bytes` a token (K and V) inside
    its budget: a multiple of 128, so the score tile keeps whole
    lanes, up to `_MAX_ITEM_TOKENS`."""
    tokens = _RING_BUDGET_BYTES // (_MIN_RING_SLOTS * 2 * lane_bytes)
    return min(max(tokens // 128 * 128, 128), _MAX_ITEM_TOKENS)


def head_block(num_kv_heads: int, head_dim: int, dtype) -> int:
    """The KV heads a grid cell holds. All of them, so that the block
    is the page's whole lane axis (every copy one contiguous page, a
    page visited once, a row's packed query built once), whenever the
    ring still affords `_MIN_RING_SLOTS` slots of an item of
    `_MIN_WHOLE_ITEM_TOKENS`: up to ten bf16 heads of 128 lanes, up to
    21 of 8-bit pages. Past that the largest divisor of H that is
    <= 8, each block a lane slice of the page (16 and 32 bf16 heads:
    blocks of 8). `head_dim` is the pages' (padded) head size. Scores
    cost hb x the minimal FLOPs, on an otherwise idle MXU."""
    lanes = num_kv_heads * head_dim * jnp.dtype(dtype).itemsize
    if _item_tokens(lanes) >= _MIN_WHOLE_ITEM_TOKENS:
        return num_kv_heads
    for hb in (8, 7, 6, 5, 4, 3, 2):
        if num_kv_heads % hb == 0:
            return hb
    return 1


def lane_bytes_of(num_kv_heads: int, head_dim: int, dtype) -> int:
    """What one token of one head block holds in K (or in V): the lane
    width of a ring slot and of a page copy, in bytes. `head_dim` is
    the pages' (padded) head size. It sizes the ring (`_ring_slots`)
    and the work item (`choose_pages_per_chunk`)."""
    hb = head_block(num_kv_heads, head_dim, dtype)
    return hb * head_dim * jnp.dtype(dtype).itemsize


def choose_pages_per_chunk(pages_per_seq: int, page_size: int,
                           lane_bytes: int) -> int:
    """The shared work-item policy (layer + model runner must agree —
    the runner builds the ragged work list with it, the layer passes
    the same value to the kernel): the largest item, in pages, that
    leaves the read ring `_MIN_RING_SLOTS` slots inside its budget, a
    multiple of 128 tokens so the score tile keeps whole lanes, up to
    `_MAX_ITEM_TOKENS`, at every batch size. `lane_bytes` is
    `lane_bytes_of` the pages: bf16 Mistral pages (eight heads of 128)
    and narrower ones give 512-token items, a whole block of nine or
    ten such heads 384, blocks of 4 KB a token 256 (`_item_tokens`).
    A cell costs some 0.5 us whatever it holds and a page's two
    descriptors 40 ns to issue, so an item under 384 tokens of such
    pages takes longer than its copies (PERF.md §5), which is where
    `head_block` stops widening the block; what the ring loses in
    depth costs nothing down to two items ahead. A table narrower
    than an item is one item; a width that is no multiple of the item
    needs no divisor, because a row's last item copies only its live
    pages."""
    return max(1, min(_item_tokens(lane_bytes) // page_size,
                      pages_per_seq))


def padded_work_length(num_items: int, batch: int, pages_per_seq: int,
                       pages_per_chunk: int) -> int:
    """The length the model runner pads a decode work list to: batch x
    2^k items, clamped to the dense cell count. Each (batch, table
    width) bucket then exposes only a few list lengths (the length is
    part of a decode program's key), so a fluctuating serving mix
    reuses compiles; padding is dead items the kernel skips."""
    mix = 1
    while batch * mix < num_items:
        mix *= 2
    return batch * min(mix, -(-pages_per_seq // pages_per_chunk))


def count_decode_pages(context_lens, chunk_counts, pages_per_chunk: int,
                       page_size: int):
    """(fetched, live) pages of one decode call, by the kernel's own
    rule: an item copies its pages below the row's context length and
    no others, so a row fetches its live pages as far as its items
    reach. Host arithmetic for the engine's counters
    (`aphrodite:decode_attn_pages_*_total`); nothing on the device."""
    live = -(-np.asarray(context_lens, dtype=np.int64) // page_size)
    reach = np.asarray(chunk_counts, dtype=np.int64) * pages_per_chunk
    return int(np.minimum(live, reach).sum()), int(live.sum())


def _bucket_work(n: int) -> int:
    for b in _WORK_BUCKETS:
        if n <= b:
            return b
    return -(-n // 1024) * 1024


def build_decode_work_list(page_counts, pages_per_chunk: int,
                           pad_to: int = None):
    """Flatten ragged per-sequence page work into the 1-D work list the
    ragged kernel scalar-prefetches.

    page_counts: per batch row (INCLUDING padded rows), the number of
    real block-table entries the row reserved; rows with 0 pages (pad
    lanes) still get one fully-masked item so their output lane is
    written (zeros): the ctx==0 contract.

    Returns (wi_seq [NW+1] int32, wi_chunk [NW] int32) numpy arrays:
    wi_seq[w] is the batch row of item w, wi_chunk[w] its chunk index.
    Items of one row are contiguous and chunk-ordered (the kernel's
    persistent-accumulator contract). List padding beyond the real
    items is DEAD (chunk -1, seq = len(page_counts) — the kernel's
    dummy output row); wi_seq carries one trailing -1 sentinel so the
    last real item detects it is its row's final chunk."""
    seqs, chunks = [], []
    for i, npg in enumerate(page_counts):
        n = max(1, -(-int(npg) // pages_per_chunk))
        seqs.extend([i] * n)
        chunks.extend(range(n))
    nw = len(seqs)
    padded = _bucket_work(nw) if pad_to is None else pad_to
    if padded < nw:
        raise ValueError(f"pad_to={padded} < {nw} real work items")
    dummy = len(page_counts)
    wi_seq = np.full((padded + 1,), -1, dtype=np.int32)
    wi_seq[:nw] = seqs
    wi_seq[nw:padded] = dummy
    wi_chunk = np.full((padded,), -1, dtype=np.int32)
    wi_chunk[:nw] = chunks
    return wi_seq, wi_chunk


def _quantize_row(row, dtype, kv_scale):
    # The single KV number-format contract lives in ops/kv_quant.py
    # (pure jnp — legal inside the kernel body).
    from aphrodite_tpu.ops.kv_quant import quantize_kv
    return quantize_kv(row, dtype, kv_scale)


def _decode_kernel_ragged(
    # scalar prefetch
    block_tables_ref,   # [batch+1, pages_per_seq] int32 (SMEM)
    context_lens_ref,   # [batch+1] int32 (SMEM; row batch is the dummy)
    wi_seq_ref,         # [NW+1] int32: batch row of item w; [NW] = -1
    wi_chunk_ref,       # [NW] int32: chunk of item w; -1 = dead padding
    # inputs (slopes_ref only with has_alibi; knew/vnew only with
    # fused_write), then outputs, then scratch
    *refs,
    hb: int,
    group: int,
    head_dim: int,
    pages_per_chunk: int,
    page_size: int,
    scale: float,
    kv_scale: float,
    pf_depth: int,
    chunk_slots: int,
    whole_lanes: bool,
    has_alibi: bool = False,
    fused_write: bool = False,
    amla: bool = True,
    ablate: str = None,
    window: int = None,
    latent: int = None,
):
    refs = list(refs)
    if latent is not None:
        # A LATENT page (one array, one ring, one copy a page): every
        # `v_*` name below is None and the values are the first
        # `latent` lanes of the keys.
        q_ref, k_hbm = refs[:2]
        v_hbm = v_buf = vnew_ref = vwb = None
        if fused_write:
            knew_ref, out_ref, kp_out = refs[2:5]
            (k_buf, sems, acc_scr, m_scr, l_scr, qp_scr,
             kwb, wbsem, wb_meta) = refs[5:]
            k_hbm = kp_out
        else:
            knew_ref = None
            out_ref = refs[2]
            (k_buf, sems, acc_scr, m_scr, l_scr, qp_scr) = refs[3:]
            kwb = wbsem = wb_meta = None
        slopes_ref = None
    else:
        q_ref, k_hbm, v_hbm = refs[:3]
        refs = refs[3:]
        slopes_ref = refs.pop(0) if has_alibi else None
        if fused_write:
            knew_ref, vnew_ref = refs[:2]
            out_ref, kp_out, vp_out = refs[2:5]
            scratch = refs[5:]
            (k_buf, v_buf, sems, acc_scr, m_scr, l_scr, qp_scr,
             kwb, vwb, wbsem, wb_meta) = scratch
            # reads and writes go through the aliased OUTPUT refs so
            # in-place semantics hold
            k_hbm, v_hbm = kp_out, vp_out
        else:
            knew_ref = vnew_ref = None
            out_ref = refs[0]
            (k_buf, v_buf, sems, acc_scr, m_scr, l_scr, qp_scr) = refs[1:]
            kwb = vwb = wbsem = wb_meta = None

    j = pl.program_id(0)
    w = pl.program_id(1)
    n_hb = pl.num_programs(0)
    nw = pl.num_programs(1)
    cell = j * nw + w
    total_cells = n_hb * nw
    d = head_dim
    rows = group * hb
    chunk_tokens = pages_per_chunk * page_size

    s_idx = wi_seq_ref[w]          # dead items carry the dummy row
    c = wi_chunk_ref[w]
    item_live = c >= 0
    ctx = context_lens_ref[s_idx]

    def page_of(hbm, page_idx, j2):
        # One head block (n_hb == 1) is the page's whole lane axis:
        # indexing the page alone makes every copy one contiguous
        # descriptor; several head blocks each take their lane slice.
        if whole_lanes:
            return hbm.at[page_idx]
        return hbm.at[page_idx, :, pl.ds(j2 * hb * d, hb * d)]

    def live_pages(seq2, c2):
        # Pages of item (seq2, c2) that lie below the row's context
        # length: the only ones copied. A row's last item stops at its
        # last live page (the page the fused write lands in holds
        # position ctx-1 and is live by this rule); an item a burst
        # reserved beyond the context copies nothing.
        pages = (context_lens_ref[seq2] + page_size - 1) // page_size
        return jnp.clip(pages - c2 * pages_per_chunk, 0, pages_per_chunk)

    def over_live_pages(n_live, visit, whole=None):
        # A whole item keeps the static unroll (or does `whole` once);
        # a partly live one guards each page (its last page cannot be
        # live).
        @pl.when(n_live == pages_per_chunk)
        def _():
            if whole is not None:
                return whole()
            for p in range(pages_per_chunk):
                visit(p)

        @pl.when(n_live < pages_per_chunk)
        def _():
            for p in range(pages_per_chunk - 1):
                @pl.when(p < n_live)
                def _(p=p):
                    visit(p)

    def start_cell(cell2, j2, w2):
        # Dead targets get no DMAs (and later skip the wait), so list
        # padding costs no bandwidth — only a skipped grid cell.
        @pl.when(wi_chunk_ref[w2] >= 0)
        def _():
            seq2, c2 = wi_seq_ref[w2], wi_chunk_ref[w2]
            slot2 = jax.lax.rem(cell2, chunk_slots)

            def start_page(p):
                # K and V of a page issued back-to-back: its two
                # copies land adjacently in the DMA queue, so the
                # engine overlaps them instead of draining all K
                # before any V.
                page_idx = block_tables_ref[seq2,
                                            c2 * pages_per_chunk + p]
                dst = pl.ds(p * page_size, page_size)
                pltpu.make_async_copy(
                    page_of(k_hbm, page_idx, j2),
                    k_buf.at[slot2, dst, :], sems.at[slot2, 0]).start()
                if v_hbm is not None:
                    pltpu.make_async_copy(
                        page_of(v_hbm, page_idx, j2),
                        v_buf.at[slot2, dst, :],
                        sems.at[slot2, 1]).start()
            over_live_pages(live_pages(seq2, c2), start_page)

    def wait_cell(slot, n_live):
        # A wait reads its semaphore and the size of its destination
        # only, so it names no page: a whole item waits once for K and
        # once for V, a partly live one page by page.
        def wait_on(dst):
            pltpu.make_async_copy(k_buf.at[slot, dst, :],
                                  k_buf.at[slot, dst, :],
                                  sems.at[slot, 0]).wait()
            if v_buf is not None:
                pltpu.make_async_copy(v_buf.at[slot, dst, :],
                                      v_buf.at[slot, dst, :],
                                      sems.at[slot, 1]).wait()

        over_live_pages(
            n_live, lambda p: wait_on(pl.ds(p * page_size, page_size)),
            whole=lambda: wait_on(pl.ds(0, chunk_tokens)))

    # ---- unified cross-cell prefetch ring over ALL work items ----
    @pl.when(cell == 0)
    def _():
        if fused_write:
            wb_meta[0] = 0          # writeback counter
        # What a slot holds where nothing was copied (a partly live
        # item's dead pages) meets p = 0 in the PV dot, and 0 x NaN is
        # NaN: the V ring starts clean, and from then on holds only
        # zeros and copies of live pages. (Stale K only makes scores
        # that the context mask replaces; a latent page's ring is its
        # values' too.)
        val_buf = k_buf if v_buf is None else v_buf

        def clean(slot2, _):
            val_buf[slot2] = jnp.zeros(val_buf.shape[1:], val_buf.dtype)
        jax.lax.fori_loop(0, chunk_slots, clean, None)
        # Cells 1..pf_depth have no predecessor pf_depth back; cell 0
        # seeds their loads.
        if ablate != "copies":
            def seed(cell2, _):
                start_cell(cell2, cell2 // nw, jax.lax.rem(cell2, nw))
            jax.lax.fori_loop(0, min(pf_depth + 1, total_cells), seed,
                              None)

    if ablate != "copies":
        @pl.when((cell >= 1) & (cell + pf_depth < total_cells))
        def _():
            nc = cell + pf_depth
            start_cell(nc, nc // nw, jax.lax.rem(nc, nw))

    # Cross-chunk state persists in VMEM scratch across grid cells; a
    # sequence's items are grid-adjacent, so what is a row's and not an
    # item's is made at its chunk 0 and read by its later items, and
    # finalizing at its last item needs no inter-cell HBM combine
    # pass: the online-softmax state, and the row's packed query.
    @pl.when(item_live & (c == 0))
    def _():
        acc_scr[...] = jnp.zeros_like(acc_scr)
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        # Block-diagonal q packing: row r (serving q head
        # j*hb*group + r, kv head hh = r // group of this cell's
        # block) carries q in lanes [hh*d, (hh+1)*d) and zeros
        # elsewhere, so the single [rows, hb*d] x [hb*d, chunk] dot
        # yields exact per-head scores. log2(e) folds into the static
        # scale: scores land in the BASE-2 domain the AMLA rescale
        # needs. The operand rounds to bf16 (accumulation stays f32):
        # f32 matmuls run the MXU in multi-pass mode at ~1/6 the bf16
        # rate, and at a cell's tiny FLOP count the f32 dots were its
        # binding cost.
        q = q_ref[0, 0].astype(jnp.float32) * \
            (scale * kv_scale * _LOG2E)                  # [rows, d]
        q_rep = jax.lax.concatenate([q] * hb, 1)         # [rows, hb*d]
        lane_head = jax.lax.broadcasted_iota(
            jnp.int32, (rows, hb * d), 1) // d
        row_head = jax.lax.broadcasted_iota(
            jnp.int32, (rows, hb * d), 0) // group
        qp_scr[...] = jnp.where(lane_head == row_head, q_rep,
                                0.0).astype(jnp.bfloat16)

    if fused_write:
        pos_new = jnp.maximum(ctx - 1, 0)
        c_star = pos_new // chunk_tokens
        r_star = jax.lax.rem(pos_new, chunk_tokens)
        p_star = r_star // page_size
        g_star = block_tables_ref[s_idx, pos_new // page_size]
        is_writer = item_live & (ctx > 0) & (c == c_star)

    @pl.when(item_live)
    def _():
        slot = jax.lax.rem(cell, chunk_slots)
        if ablate != "copies":
            wait_cell(slot, live_pages(s_idx, c))

        if fused_write:
            # Only ONE item per (sequence, head block) writes — the
            # chunk holding position ctx-1 — so the writeback ring is
            # keyed by an SMEM write counter, not the grid cell: the
            # n-th write waits the (n-_WB_SLOTS)-th write's DMA before
            # reusing its buffer slot. wb_meta layout: [0] counter,
            # [1..WB] page of the slot's outstanding write,
            # [1+WB..1+2*WB] its head block.
            @pl.when(is_writer)
            def _():
                n = wb_meta[0]
                s_wb = jax.lax.rem(n, _WB_SLOTS)

                @pl.when(n >= _WB_SLOTS)
                def _():
                    pgs = wb_meta[1 + s_wb]
                    pj = wb_meta[1 + _WB_SLOTS + s_wb]
                    pltpu.make_async_copy(
                        kwb.at[s_wb], page_of(k_hbm, pgs, pj),
                        wbsem.at[s_wb, 0]).wait()
                    if vwb is not None:
                        pltpu.make_async_copy(
                            vwb.at[s_wb], page_of(v_hbm, pgs, pj),
                            wbsem.at[s_wb, 1]).wait()

                pg = pl.ds(p_star * page_size, page_size)
                rows_p = jax.lax.broadcasted_iota(
                    jnp.int32, (page_size, k_buf.shape[2]), 0)
                r_in_page = jax.lax.rem(r_star, page_size)
                kq = _quantize_row(knew_ref[0, 0], k_buf.dtype,
                                   kv_scale)
                if vwb is not None:
                    vq = _quantize_row(vnew_ref[0, 0], v_buf.dtype,
                                       kv_scale)
                kpage = jnp.where(rows_p == r_in_page, kq,
                                  k_buf[slot, pg, :])
                if vwb is not None:
                    vpage = jnp.where(rows_p == r_in_page, vq,
                                      v_buf[slot, pg, :])
                k_buf[slot, pg, :] = kpage
                if vwb is not None:
                    v_buf[slot, pg, :] = vpage
                kwb[s_wb] = kpage
                if vwb is not None:
                    vwb[s_wb] = vpage
                pltpu.make_async_copy(
                    kwb.at[s_wb], page_of(k_hbm, g_star, j),
                    wbsem.at[s_wb, 0]).start()
                if vwb is not None:
                    pltpu.make_async_copy(
                        vwb.at[s_wb], page_of(v_hbm, g_star, j),
                        wbsem.at[s_wb, 1]).start()
                wb_meta[1 + s_wb] = g_star
                wb_meta[1 + _WB_SLOTS + s_wb] = j
                wb_meta[0] = n + 1

        if ablate == "compute":
            return

        k = k_buf[slot]                              # [chunk, hb*d]
        if k.dtype != jnp.bfloat16:                  # int8/fp8 KV dequant
            k = k.astype(jnp.bfloat16)
        s = jax.lax.dot_general(
            qp_scr[...], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)      # [rows, chunk]
        pos = c * chunk_tokens + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        if slopes_ref is not None:
            s = s + (slopes_ref[0, :, :1] * _LOG2E) * \
                pos.astype(jnp.float32)
        live = pos < ctx
        if window is not None:
            # a causal window: the newest `window` keys, the row's own
            # among them
            live = live & (pos >= ctx - window)
        s = jnp.where(live, s, _NEG_INF)

        m_prev = m_scr[:, :1]                        # [rows, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        # The running max quantizes UP to an integer, so the chunk
        # correction 2^(m_prev - m_new) is an exact power of two:
        # applied as an exponent-bias ADD (amla) or as the classic
        # VPU multiply (the tests' reference) — bit-equal away from
        # underflow.
        m_new = jnp.maximum(m_prev, jnp.ceil(m_cur))
        delta = m_prev - m_new                       # integer, <= 0
        p_exp = jnp.where(live, jnp.exp2(s - m_new), 0.0)
        l_prev = l_scr[:, :1]
        if amla:
            l_new = _mul_pow2(l_prev, delta) + \
                jnp.sum(p_exp, axis=1, keepdims=True)
        else:
            corr = jnp.exp2(delta)
            l_new = l_prev * corr + jnp.sum(p_exp, axis=1,
                                            keepdims=True)

        if v_buf is None:
            v = k[:, :latent]                        # [chunk, latent]
        else:
            v = v_buf[slot]                          # [chunk, hb*d]
        if v.dtype != jnp.bfloat16:                  # int8/fp8 KV dequant
            v = v.astype(jnp.bfloat16)
        pv = jax.lax.dot_general(
            p_exp.astype(jnp.bfloat16), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)      # [rows, hb*d]
        if latent is not None:
            pv_sel = pv             # one "head": every row's own
        else:
            rh = jax.lax.broadcasted_iota(jnp.int32, (rows, d),
                                          0) // group
            pv_sel = jnp.zeros((rows, d), jnp.float32)
            for h in range(hb):
                pv_sel = pv_sel + jnp.where(
                    rh == h, pv[:, h * d:(h + 1) * d], 0.0)
        if amla:
            acc_scr[...] = _mul_pow2(acc_scr[...], delta) + pv_sel
        else:
            acc_scr[...] = acc_scr[...] * corr + pv_sel
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

        # This row's final chunk (the next item belongs to another row
        # — or is the -1 sentinel / dead padding): normalize and write
        # the output block. Intermediate items leave out_ref alone; the
        # out index map revisits the same block for adjacent same-row
        # items, so the last write is the one that lands.
        @pl.when(wi_seq_ref[w + 1] != s_idx)
        def _():
            l_final = l_scr[:, :1]
            l_safe = jnp.where(l_final == 0.0, 1.0, l_final)
            out_ref[0, 0] = (acc_scr[...] * (kv_scale / l_safe)).astype(
                out_ref.dtype)

    if fused_write:
        # Drain: at most one outstanding writeback per ring slot (the
        # n-th write waited the (n-WB)-th); the final cell waits each
        # slot that was ever used.
        @pl.when(cell == total_cells - 1)
        def _():
            n_end = wb_meta[0]
            for kslot in range(_WB_SLOTS):
                @pl.when(kslot < n_end)
                def _(kslot=kslot):
                    pgs = wb_meta[1 + kslot]
                    pj = wb_meta[1 + _WB_SLOTS + kslot]
                    pltpu.make_async_copy(
                        kwb.at[kslot], page_of(k_hbm, pgs, pj),
                        wbsem.at[kslot, 0]).wait()
                    if vwb is not None:
                        pltpu.make_async_copy(
                            vwb.at[kslot], page_of(v_hbm, pgs, pj),
                            wbsem.at[kslot, 1]).wait()


def _ring_slots(pf_depth: int, chunk_tokens: int, lane_bytes: int) -> int:
    """Read-ring depth: pf_depth+2 slots (a landing load must never
    alias a live slot), trimmed to the VMEM budget when chunks are
    large. Floor 3 keeps at least one chunk of cross-cell prefetch."""
    n_slots = pf_depth + 2
    per_slot = 2 * chunk_tokens * lane_bytes        # K + V
    while n_slots > 3 and n_slots * per_slot > _RING_BUDGET_BYTES:
        n_slots -= 1
    return n_slots


@functools.partial(
    jax.jit,
    static_argnames=("scale", "kv_scale", "pages_per_chunk", "pf_depth",
                     "amla", "interpret", "ablate", "window", "hb",
                     "latent"))
def _paged_decode_impl(
    q, k_pages, v_pages, block_tables, context_lens, wi_seq, wi_chunk,
    alibi_slopes, knew, vnew, *, scale, kv_scale, pages_per_chunk,
    pf_depth, amla, interpret, ablate=None, window=None, hb=None,
    latent=None,
):
    """`latent` (static; the value lanes of a LATENT page,
    `common/config.py::PageGroups.latent`): `k_pages` is the one array
    a layer has, `[num_pages, page_size, head_dim]` with one "head" a
    token; `v_pages` and `vnew` are None, a row's values are the first
    `latent` lanes of its keys, and the result is `[batch, heads,
    latent]`. One ring, one copy a page, one written page."""
    batch, num_q_heads, head_dim = q.shape
    num_pages, page_size, hd = k_pages.shape
    num_kv_heads = hd // head_dim
    pages_per_seq = block_tables.shape[1]
    group = num_q_heads // num_kv_heads
    if hb is None:
        hb = head_block(num_kv_heads, head_dim, k_pages.dtype)
    n_hb = num_kv_heads // hb
    rows = group * hb
    chunk_tokens = pages_per_chunk * page_size
    fused_write = knew is not None
    lane_bytes = hb * head_dim * k_pages.dtype.itemsize
    #: the lanes of a value and of an output row, and the page arrays
    #: (with each its ring, its new row and its written page)
    out_dim = head_dim if latent is None else latent
    sides = 2 if latent is None else 1

    # q rows are kv-head-major, so the rows for head block j are the
    # contiguous slice [j*rows, (j+1)*rows).
    q_blocked = q.reshape(batch, n_hb, rows, head_dim)

    # The dummy row (index batch): dead padding items and the last
    # cell's out block land here; ctx 0 / page 0 keep its DMAs and
    # masking inert, and the row is sliced off below.
    q_blocked = jnp.concatenate(
        [q_blocked, jnp.zeros((1,) + q_blocked.shape[1:],
                              q_blocked.dtype)])
    block_tables = jnp.concatenate(
        [block_tables, jnp.zeros((1, pages_per_seq), jnp.int32)])
    context_lens = jnp.concatenate(
        [context_lens, jnp.zeros((1,), jnp.int32)])
    nw = wi_chunk.shape[0]
    n_slots = _ring_slots(pf_depth, chunk_tokens, lane_bytes)
    kernel = functools.partial(
        _decode_kernel_ragged,
        hb=hb, group=group, head_dim=head_dim,
        pages_per_chunk=pages_per_chunk, page_size=page_size,
        scale=scale, kv_scale=kv_scale,
        pf_depth=min(pf_depth, n_slots - 2), chunk_slots=n_slots,
        whole_lanes=n_hb == 1,
        has_alibi=alibi_slopes is not None, fused_write=fused_write,
        amla=amla, ablate=ablate, window=window, latent=latent)

    def qmap(j, w, tbl, cl, ws, wc):
        return (ws[w], j, 0, 0)

    def smap(j, w, *_):
        return (j, 0, 0)
    prefetch = [block_tables, context_lens, wi_seq, wi_chunk]

    in_specs = [
        pl.BlockSpec((1, 1, rows, head_dim), qmap),
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    inputs = prefetch + [q_blocked, k_pages, v_pages]
    if latent is not None:
        del in_specs[-1], inputs[-1]
    kp_input_idx = len(prefetch) + 1
    if alibi_slopes is not None:
        in_specs.append(pl.BlockSpec((1, rows, 128), smap))
        inputs.append(jnp.broadcast_to(
            alibi_slopes.astype(jnp.float32).reshape(n_hb, rows, 1),
            (n_hb, rows, 128)))
    if fused_write:
        # The singleton axis keeps the block's last two dims equal to
        # the array's ((1, hb*d)) — a (1, 1, hb*d) block over
        # [batch, n_hb>1, hb*d] is not a legal Mosaic tiling.
        def with_dummy_row(new):
            new = new.reshape(batch, n_hb, 1, hb * head_dim)
            return jnp.concatenate(
                [new, jnp.zeros((1,) + new.shape[1:], new.dtype)])

        def nmap(*a):
            return qmap(*a)[:2] + (0, 0)
        spec_new = pl.BlockSpec((1, 1, 1, hb * head_dim), nmap)
        for new in (knew, vnew)[:sides]:
            in_specs.append(spec_new)
            inputs.append(with_dummy_row(new))

    # (written out entry by entry: `tools/aphrocheck`'s roofline pass
    # reads the rings and their depth off this list)
    scratch = [
        pltpu.VMEM((n_slots, chunk_tokens, hb * head_dim),
                   k_pages.dtype),
        pltpu.VMEM((n_slots, chunk_tokens, hb * head_dim),
                   k_pages.dtype),
        pltpu.SemaphoreType.DMA((n_slots, 2)),
        pltpu.VMEM((rows, head_dim), jnp.float32),
        pltpu.VMEM((rows, 128), jnp.float32),
        pltpu.VMEM((rows, 128), jnp.float32),
        # a row's packed query, built at its first item
        pltpu.VMEM((rows, hb * head_dim), jnp.bfloat16),
    ]
    if latent is not None:
        # one ring (the values are the keys' first lanes), and an
        # accumulator as wide as a value
        del scratch[1]
        scratch[2] = pltpu.VMEM((rows, latent), jnp.float32)
    out_shape = [jax.ShapeDtypeStruct((batch + 1, n_hb, rows, out_dim),
                                      q.dtype)]
    out_specs = [pl.BlockSpec((1, 1, rows, out_dim), qmap)]
    io_aliases = {}
    if fused_write:
        scratch.extend([
            pltpu.VMEM((_WB_SLOTS, page_size, hb * head_dim),
                       k_pages.dtype),
            pltpu.VMEM((_WB_SLOTS, page_size, hb * head_dim),
                       k_pages.dtype),
            pltpu.SemaphoreType.DMA((_WB_SLOTS, 2)),
            # SMEM write-counter + per-slot (page, head block) of the
            # outstanding writeback (see _decode_kernel_ragged).
            pltpu.SMEM((1 + 2 * _WB_SLOTS,), jnp.int32),
        ])
        if latent is not None:
            del scratch[-3]     # one written page a row
        out_shape.extend([jax.ShapeDtypeStruct(k_pages.shape,
                                               k_pages.dtype)] * sides)
        out_specs.extend([pl.BlockSpec(memory_space=pl.ANY)] * sides)
        # Flattened input indices of k_pages/v_pages alias kernel
        # outputs 1/2 (after the four scalar-prefetch inputs and q).
        io_aliases = {kp_input_idx + side: 1 + side
                      for side in range(sides)}

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(n_hb, nw),
        in_specs=in_specs,
        out_specs=out_specs if fused_write else out_specs[0],
        scratch_shapes=scratch,
    )
    result = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape if fused_write else out_shape[0],
        input_output_aliases=io_aliases,
        interpret=interpret,
        **({} if latent is None else {"name": LATENT_DEVICE_OP_PREFIXES[0]}),
    )(*inputs)
    if fused_write:
        out, *pages = result
        return (out[:batch].reshape(batch, num_q_heads, out_dim), *pages)
    return result[:batch].reshape(batch, num_q_heads, out_dim)


def paged_decode_attention(
    q: jax.Array,             # [batch, num_q_heads, head_dim]
    k_pages: jax.Array,       # [num_pages, page_size, H * head_dim]
    v_pages: jax.Array,
    block_tables: jax.Array,  # [batch, pages_per_seq] int32, 0-padded
    context_lens: jax.Array,  # [batch] int32
    alibi_slopes: jax.Array = None,   # [num_q_heads] f32, optional
    knew: jax.Array = None,   # [batch, Hkv, head_dim]: fused KV write
    vnew: jax.Array = None,
    *,
    scale: float,
    kv_scale: float = 1.0,
    pages_per_chunk: int = 8,
    work_items=None,          # (wi_seq [NW+1], wi_chunk [NW]) int32
    amla: bool = True,        # False: the tests' reference rescale
    ablate: str = None,       # benchmarks/attn_ab.py: time a part alone
    hb: int = None,           # benchmarks/attn_ab.py: pin the head block
    interpret: bool = False,
    window: int = None,       # attend over the newest `window` keys only
    latent: int = None,       # LATENT pages: the value lanes (below)
):
    """Token-major flash-decoding attention (see module docstring).

    Without knew/vnew: returns attn_out [batch, Hq, d] over the given
    pages (read-only). With knew/vnew: ALSO writes the current token
    (position ctx-1 per sequence) into its page in place and returns
    (attn_out, k_pages, v_pages) — the aliased, updated page arrays.

    work_items is the grid's work list: arrays from
    build_decode_work_list, which MUST have been built with this
    call's pages_per_chunk (choose_pages_per_chunk is the policy both
    sides share). The table width need be no multiple of it: an item
    copies only its pages below the context length, and those lie
    inside the table. Without work_items the call builds the dense
    list of its static shapes, every row the items of its whole table:
    the same over-approximation as a list of reserved pages, since an
    item past a row's context copies and computes nothing.

    `ablate` is the measurement hook of benchmarks/attn_ab.py (the
    output is then meaningless): "compute" skips a live item's
    arithmetic and leaves its copies and waits, "copies" skips the
    page copies and computes on whatever the ring holds.

    `hb` is that harness's too: it pins the KV heads a grid cell holds
    (a divisor of the head count; `pages_per_chunk` and the work list
    are then the caller's to size for it) where `head_block` decides.

    `amla` is the online-softmax rescale: True = AMLA exponent-bias
    adds, what every served call runs; False = the classic per-chunk
    multiply, kept as the reference that
    tests/kernels/test_amla_attention.py holds the adds bit-equal to.

    `latent`: the pages are LATENT (`PageGroups.latent`): `k_pages`
    `[num_pages, page_size, head_dim]` is the one array a layer has,
    one "head" a token under every query row; `v_pages` and `vnew` are
    None, a token's value is the first `latent` lanes of its key, and
    the result is `[batch, Hq, latent]` (with `knew`, `(attn_out,
    k_pages)`). A page is copied once.

    `window`: a row attends over positions `ctx - window` to `ctx - 1`
    of its table only (a causal window of `window` keys, its own
    among them). The caller's table starts at the page that holds the
    oldest of them, or before it: a row's first work item must have a
    live key."""
    batch, num_q_heads, head_dim = q.shape
    num_pages, page_size, hd = k_pages.shape
    if hd % head_dim != 0:
        raise ValueError(f"{hd=} not a multiple of {head_dim=}")
    num_kv_heads = hd // head_dim
    if num_q_heads % num_kv_heads != 0:
        raise ValueError(f"{num_q_heads=} % {num_kv_heads=}")
    if hb is not None and (hb < 1 or num_kv_heads % hb != 0):
        raise ValueError(f"{hb=} does not divide {num_kv_heads=}")
    if latent is not None and (
            num_kv_heads != 1 or v_pages is not None or vnew is not None
            or alibi_slopes is not None or not 0 < latent <= head_dim
            or latent % 128):
        raise ValueError(
            "latent pages are one array of one head a token, their "
            f"values whole lane tiles of it: {latent=}, {head_dim=}, "
            f"{num_kv_heads=}")
    pages_per_seq = block_tables.shape[1]
    pf_depth = _pf_depth()      # call-time env read + validation
    if pages_per_chunk < 1:
        raise ValueError(
            f"pages_per_chunk must be >= 1, got {pages_per_chunk}")
    if work_items is None:
        work_items = build_decode_work_list(
            [pages_per_seq] * batch, pages_per_chunk)
    wi_seq, wi_chunk = work_items
    wi_seq = jnp.asarray(wi_seq, jnp.int32)
    wi_chunk = jnp.asarray(wi_chunk, jnp.int32)
    if wi_seq.shape[0] != wi_chunk.shape[0] + 1:
        raise ValueError(
            f"wi_seq must carry one trailing sentinel: "
            f"{wi_seq.shape[0]=} != {wi_chunk.shape[0]=} + 1")
    return _paged_decode_impl(
        q, k_pages, v_pages, block_tables, context_lens, wi_seq,
        wi_chunk, alibi_slopes, knew, vnew, scale=scale,
        kv_scale=kv_scale, pages_per_chunk=pages_per_chunk,
        pf_depth=pf_depth, amla=bool(amla), interpret=interpret,
        ablate=ablate, window=window, hb=hb, latent=latent)
