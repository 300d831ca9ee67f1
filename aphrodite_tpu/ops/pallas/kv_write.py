"""Pallas TPU kernel: in-place KV-cache page writes (token-major).

TPU-native equivalent of the reference's `reshape_and_cache` CUDA kernel
(`kernels/cache_kernels.cu:221`). The XLA scatter version
(`ops/kv_cache.py:write_to_kv_cache`) is semantically identical, but XLA
materializes full-cache layout-conversion copies around the scatter when
the scattered values arrive late in the program (the transformer layer
chain) — measured at tens of ms per step for multi-GB caches. This
kernel updates the HBM page arrays directly via async DMAs and declares
`input_output_aliases`, so the update is guaranteed in place regardless
of program structure.

Layout: pages are [num_pages, page_size, H * d] (token-major, heads in
lanes — see ops/kv_cache.py). One token's K or V is one full lane row,
but a single row is a sub-tile write (the (8, 128) VMEM tile spans 8
page slots), so the kernel read-modify-writes the token's aligned 8-row
window: DMA window in, insert the row with a vector select, DMA window
back. Grid cells run sequentially on the TPU core, so same-window
tokens in one batch serialize correctly. K and V windows pipeline
against each other (both reads start before either wait).

Slot convention matches the scatter path: slot = page * page_size +
offset; out-of-range slots (>= num_pages * page_size) are skipped — the
padding no-op.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_WIN = 8     # sublane tile: aligned row-window granularity for f32/bf16


def _write_kernel(
    # scalar prefetch
    slots_ref,      # [num_tokens] int32 (SMEM)
    # inputs
    knew_ref,       # [1, 1, H*d] VMEM (token i's k, heads in lanes;
    vnew_ref,       #  rank-3 so the block's last two dims are legal)
    k_in,           # [P, S, H*d] ANY/HBM (aliased with k_out)
    v_in,
    # outputs (aliased)
    k_out,
    v_out,
    # scratch
    kwin,           # [_WIN, H*d] VMEM
    vwin,
    sems,
    *,
    page_size: int,
    num_slots: int,
):
    del k_in, v_in
    i = pl.program_id(0)
    slot = slots_ref[i]

    @pl.when(slot < num_slots)
    def _():
        page = slot // page_size
        off = slot % page_size
        j = jax.lax.rem(off, _WIN)
        mask = jax.lax.broadcasted_iota(
            jnp.int32, (_WIN, 1), 0) == j

        for wi in range(page_size // _WIN):   # static unroll per window
            @pl.when(off // _WIN == wi)
            def _():
                dst_k = k_out.at[page, pl.ds(wi * _WIN, _WIN), :]
                dst_v = v_out.at[page, pl.ds(wi * _WIN, _WIN), :]
                ck = pltpu.make_async_copy(dst_k, kwin, sems.at[0])
                cv = pltpu.make_async_copy(dst_v, vwin, sems.at[1])
                ck.start()
                cv.start()
                ck.wait()
                cv.wait()
                kwin[...] = jnp.where(mask, knew_ref[0], kwin[...])
                vwin[...] = jnp.where(mask, vnew_ref[0], vwin[...])
                wk = pltpu.make_async_copy(kwin, dst_k, sems.at[0])
                wv = pltpu.make_async_copy(vwin, dst_v, sems.at[1])
                wk.start()
                wv.start()
                wk.wait()
                wv.wait()


def _decode_write_kernel(
    # scalar prefetch
    slots_ref,      # [num_tokens] int32 (SMEM)
    # inputs
    knew_ref,       # [num_tokens, 1, H*d] VMEM (all tokens' k rows)
    vnew_ref,
    k_in,           # [P, S, H*d] ANY/HBM (aliased with k_out)
    v_in,
    # outputs (aliased)
    k_out,
    v_out,
    # scratch
    kbuf,           # [2, page_size, H*d] VMEM
    vbuf,
    rsem,           # [2, 2] read semaphores (slot, k/v)
    wsem,           # [2, 2] writeback semaphores
    *,
    page_size: int,
    num_slots: int,
):
    """Pipelined decode-path writer: whole-page read-modify-write with a
    2-slot double buffer ACROSS grid cells — cell i waits cell i-1's
    writeback of the shared slot, starts cell i+1's page read, then
    modifies its own already-resident page. Requires every token to
    target a DISTINCT page (true for decode batches: one token per
    sequence, pages are sequence-exclusive after CoW), so in-flight
    writebacks never alias a pending read."""
    del k_in, v_in
    i = pl.program_id(0)
    n = pl.num_programs(0)

    def ok(j):
        return slots_ref[j] < num_slots

    def page_of(j):
        return slots_ref[j] // page_size

    def copies(j, slot, to_hbm):
        pg = page_of(j)
        if to_hbm:
            return (pltpu.make_async_copy(kbuf.at[slot], k_out.at[pg],
                                          wsem.at[slot, 0]),
                    pltpu.make_async_copy(vbuf.at[slot], v_out.at[pg],
                                          wsem.at[slot, 1]))
        return (pltpu.make_async_copy(k_out.at[pg], kbuf.at[slot],
                                      rsem.at[slot, 0]),
                pltpu.make_async_copy(v_out.at[pg], vbuf.at[slot],
                                      rsem.at[slot, 1]))

    s = jax.lax.rem(i, 2)
    sn = jax.lax.rem(i + 1, 2)

    @pl.when((i == 0) & ok(0))
    def _():
        for c in copies(0, 0, False):
            c.start()

    # Free the next slot: cell i-1's writeback used it.
    @pl.when((i >= 1) & ok(i - 1))
    def _():
        for c in copies(i - 1, sn, True):
            c.wait()

    # Prefetch cell i+1's page while this cell computes.
    @pl.when((i + 1 < n) & ok(i + 1))
    def _():
        for c in copies(i + 1, sn, False):
            c.start()

    @pl.when(ok(i))
    def _():
        for c in copies(i, s, False):
            c.wait()
        off = jax.lax.rem(slots_ref[i], page_size)
        mask = jax.lax.broadcasted_iota(
            jnp.int32, (page_size, 1), 0) == off
        kbuf[s] = jnp.where(mask, knew_ref[0], kbuf[s])
        vbuf[s] = jnp.where(mask, vnew_ref[0], vbuf[s])
        for c in copies(i, s, True):
            c.start()

    # Last cell drains its own writeback (everyone else's is waited by
    # the following cell).
    @pl.when((i == n - 1) & ok(i))
    def _():
        for c in copies(i, s, True):
            c.wait()


def _prefill_write_kernel(
    # scalar prefetch
    page_ids_ref,   # [cells] int32; >= num_pages skips the cell
    valids_ref,     # [cells] int32 tokens covered (1..page_size)
    # For each of the `sides` page arrays (K and V; a latent page's
    # one), side by side: inputs `blk` [C * page_size, H*d] VMEM (C
    # cells' rows) and `pages_in` [P, S, H*d] ANY/HBM (aliased), then
    # outputs `pages_out` (aliased), then scratch `buf` [2, page_size,
    # H*d] VMEM tail staging; after them `rsem` and `wsem` [C, 2].
    *refs,
    page_size: int,
    num_pages: int,
    pages_per_cell: int,
    sides: int = 2,
):
    """Prefill page writer: each grid cell writes `pages_per_cell`
    WHOLE pages with DMAs issued STRAIGHT from the (auto-pipelined)
    input block to their HBM pages — no staging copy, and the per-cell
    fixed cost (grid step + block handoff, ~10 us measured round 4)
    amortizes over C pages instead of one. Partial tail pages
    read-modify-write through a small staging buffer. All writebacks
    are waited before the cell ends: the input buffer is recycled two
    cells later by the pipeline, so in-flight reads from it must not
    outlive the cell."""
    blks = refs[:sides]
    outs = refs[2 * sides:3 * sides]
    bufs = refs[3 * sides:3 * sides + 2]    # (always two: the caller's)
    rsem, wsem = refs[3 * sides + 2:]
    i = pl.program_id(0)
    C = pages_per_cell

    for c in range(C):                        # static unroll
        cell = i * C + c
        pg = page_ids_ref[cell]
        valid = valids_ref[cell]
        rows = pl.ds(c * page_size, page_size)

        @pl.when((pg < num_pages) & (valid >= page_size))
        def _full():
            for side in range(sides):
                pltpu.make_async_copy(blks[side].at[rows, :],
                                      outs[side].at[pg],
                                      wsem.at[c, side]).start()

        @pl.when((pg < num_pages) & (valid < page_size))
        def _partial():
            # Tail page: merge valid rows over the existing page.
            # Safe because each tail RMW is fully synchronous (start +
            # wait before the next statement) — a cell may hold many
            # tails (pages_per_cell up to 16), but at most one is ever
            # in flight; the alternating slot is incidental.
            s = c % 2
            reads = [pltpu.make_async_copy(outs[side].at[pg],
                                           bufs[side].at[s],
                                           rsem.at[s, side])
                     for side in range(sides)]
            for copy in reads:
                copy.start()
            for copy in reads:
                copy.wait()
            riota = jax.lax.broadcasted_iota(
                jnp.int32, (page_size, 1), 0)
            for side in range(sides):
                bufs[side][s] = jnp.where(
                    riota < valid, blks[side][rows, :], bufs[side][s])
            writes = [pltpu.make_async_copy(bufs[side].at[s],
                                            outs[side].at[pg],
                                            wsem.at[c, side])
                      for side in range(sides)]
            for copy in writes:
                copy.start()
            for copy in writes:
                copy.wait()

    # Drain the full-page writebacks issued above (tail pages waited
    # inline). Re-constructed copies wait the matching semaphores.
    for c in range(C):
        cell = i * C + c
        pg = page_ids_ref[cell]
        rows = pl.ds(c * page_size, page_size)

        @pl.when((pg < num_pages) & (valids_ref[cell] >= page_size))
        def _():
            for side in range(sides):
                pltpu.make_async_copy(blks[side].at[rows, :],
                                      outs[side].at[pg],
                                      wsem.at[c, side]).wait()


def write_kv_pages_prefill(
    knew: jax.Array,      # [B * padded_len, H*d]
    vnew: Optional[jax.Array],
    k_pages: jax.Array,   # [num_pages, page_size, H*d]
    v_pages: Optional[jax.Array],
    page_ids: jax.Array,  # [cells] int32; >= num_pages skips
    src_blocks: jax.Array,  # [cells] int32; MUST equal arange(cells)
    valids: jax.Array,    # [cells] int32 valid rows (1..page_size)
    *,
    interpret: bool = False,
):
    """Whole-page prefill writer (see _prefill_write_kernel).

    Contract: cell c's source rows are knew[c*page_size:(c+1)*page_size]
    — i.e. `src_blocks` is the identity. _prepare_prompt's page-aligned
    cell layout guarantees this (cell i*ppp+p reads block i*ppp+p, with
    a host-side assert there); the check below only fires for EAGER
    callers (tests) — under jit the args are tracers and the caller's
    assert is the real guard.

    `vnew` and `v_pages` None: the pages are LATENT (one array a
    layer, `common/config.py::PageGroups.latent`); `knew` holds the
    tokens' rows and the result is the one updated array."""
    tokens, hd = knew.shape
    num_pages, page_size, _ = k_pages.shape
    cells = page_ids.shape[0]
    dtype = k_pages.dtype
    news = [knew] if vnew is None else [knew, vnew]
    pages = [k_pages] if v_pages is None else [k_pages, v_pages]
    sides = len(pages)
    import numpy as _np
    try:                      # tracers (jit callers) raise here and skip
        src_np = _np.asarray(src_blocks)
        ids_np = _np.asarray(page_ids)
    except (jax.errors.TracerArrayConversionError,
            jax.errors.ConcretizationTypeError):
        src_np = None
    if src_np is not None:
        live = ids_np < num_pages
        if not (src_np[live] == _np.arange(cells)[live]).all():
            raise ValueError(
                "write_kv_pages_prefill requires identity src_blocks "
                "(cell c reads knew rows [c*page_size, (c+1)*page_size))")
    # Pages per grid cell: the largest power of two <= 16 dividing the
    # cell count (cells = padded_batch * pages_per_prompt, so real
    # workloads have deep power-of-two factors).
    C = max(c for c in (16, 8, 4, 2, 1) if cells % c == 0)

    # (specs and scratch written out entry by entry for
    # `tools/aphrocheck`'s roofline pass; a latent page's one array
    # drops its second entries and leaves the second buffer idle)
    in_specs = [
        pl.BlockSpec((C * page_size, hd), lambda i, pids, vld: (i, 0)),
        pl.BlockSpec((C * page_size, hd), lambda i, pids, vld: (i, 0)),
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    if sides == 1:
        del in_specs[1], in_specs[-1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(cells // C,),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * sides,
        scratch_shapes=[
            pltpu.VMEM((2, page_size, hd), dtype),
            pltpu.VMEM((2, page_size, hd), dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SemaphoreType.DMA((C, 2)),
        ],
    )
    kernel = functools.partial(
        _prefill_write_kernel,
        page_size=page_size,
        num_pages=num_pages,
        pages_per_cell=C,
        sides=sides,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(k_pages.shape, dtype)] * sides,
        # inputs: 0=page_ids, 1=valids, then the new rows (knew, vnew),
        # then the pages (k_pages, v_pages), each aliased to its output
        input_output_aliases={2 + sides + side: side
                              for side in range(sides)},
        interpret=interpret,
    )(page_ids, valids, *(new.astype(dtype) for new in news), *pages)
    return out[0] if sides == 1 else out


def can_use_pallas_writer(dtype, page_size: int, hd: int) -> bool:
    """f32/bf16 pages, 8-aligned page_size, lane-aligned H*d rows
    (int8/fp8 tile at 32 sublanes — those fall back to the XLA
    scatter)."""
    return (dtype in (jnp.bfloat16, jnp.float32)
            and page_size % _WIN == 0 and hd % 128 == 0)


def write_kv_pages(
    knew: jax.Array,      # [num_tokens, H*d] (heads collapsed in lanes)
    vnew: jax.Array,
    k_pages: jax.Array,   # [num_pages, page_size, H*d]
    v_pages: jax.Array,
    slots: jax.Array,     # [num_tokens] int32; >= num_slots skips
    *,
    distinct_pages: bool = False,
    interpret: bool = False,
):
    """In-place paged KV write; returns the (aliased) updated pages.

    distinct_pages=True (decode batches: one token per sequence, pages
    sequence-exclusive) selects the cross-cell pipelined whole-page
    writer; the default serialized window writer handles same-page
    tokens (prefill)."""
    num_tokens, hd = knew.shape
    num_pages, page_size, _ = k_pages.shape
    num_slots = num_pages * page_size
    dtype = k_pages.dtype

    if distinct_pages:
        kernel = functools.partial(
            _decode_write_kernel,
            page_size=page_size,
            num_slots=num_slots,
        )
        scratch = [
            pltpu.VMEM((2, page_size, hd), dtype),
            pltpu.VMEM((2, page_size, hd), dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SemaphoreType.DMA((2, 2)),
        ]
    else:
        kernel = functools.partial(
            _write_kernel,
            page_size=page_size,
            num_slots=num_slots,
        )
        scratch = [
            pltpu.VMEM((_WIN, hd), dtype),
            pltpu.VMEM((_WIN, hd), dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(num_tokens,),
        in_specs=[
            pl.BlockSpec((1, 1, hd), lambda i, *_: (i, 0, 0)),
            pl.BlockSpec((1, 1, hd), lambda i, *_: (i, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        scratch_shapes=scratch,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(k_pages.shape, dtype),
            jax.ShapeDtypeStruct(v_pages.shape, dtype),
        ],
        # inputs (flattened, incl. scalar prefetch):
        # 0=slots, 1=knew, 2=vnew, 3=k_pages, 4=v_pages
        input_output_aliases={3: 0, 4: 1},
        interpret=interpret,
    )(slots, knew.astype(dtype).reshape(num_tokens, 1, hd),
      vnew.astype(dtype).reshape(num_tokens, 1, hd), k_pages, v_pages)
