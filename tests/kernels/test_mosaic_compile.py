"""Does Mosaic take the serving path's kernels at Mistral-7B widths?

Asked of the real compiler, without a chip: libtpu compiles for a
DESCRIBED topology (`jax.experimental.topologies`) with no device
attached, so a kernel the compiler refuses fails here, on the CPU,
before chip time is spent finding out. This checks acceptance only;
numerics on the chip are `tests/kernels/tpu_smoke.py`.

The interpret-mode tests cannot see these failures: both kernels
repaired in PR 21 (the streamed W4A8 grid's bf16 scale ring and the
one-pass activation quantizer's VMEM fit at K=14336) passed every
interpret test while Mosaic refused them.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest

# libtpu logs to /tmp/tpu_logs unless told not to.
os.environ.setdefault("TPU_LOG_DIR", "disabled")

BF16, I32 = jnp.bfloat16, jnp.int32
#: (K, N) of the four GPTQ linears in one Mistral-7B decoder layer.
LAYER_SHAPES = [(4096, 6144), (4096, 4096), (4096, 28672), (14336, 4096)]


@pytest.fixture(scope="module")
def on_v5e():
    """ShapeDtypeStruct factory placing operands on one v5e device of a
    described (not attached) 2x2 topology."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e topology: {e}")
    sharding = SingleDeviceSharding(topo.devices[0])
    # The suite's persistent compilation cache holds CPU executables;
    # TPU ones can be written there but not read back without a chip.
    # JAX latches its use-the-cache decision, hence the resets.
    from jax.experimental.compilation_cache import compilation_cache
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=sharding)
    jax.config.update("jax_enable_compilation_cache", cache_was_on)
    compilation_cache.reset_cache()


def _gptq_operands(sds, m, K, N):
    return (sds((m, K), BF16), sds((K // 8, N), I32),
            sds((K // 128, N // 8), I32), sds((K // 128, N), BF16))


@pytest.mark.parametrize(
    "m,K,N", [(8, K, N) for K, N in LAYER_SHAPES] + [(40, 14336, 4096)])
def test_streamed_w4a8_compiles(on_v5e, m, K, N):
    """Decode (m=8) and speculative verify (m=40) rows: the streamed
    work-list grid with the in-kernel activation quantize prologue."""
    from aphrodite_tpu.ops.pallas.quant_matmul import gptq_matmul_a8
    gptq_matmul_a8.lower(*_gptq_operands(on_v5e, m, K, N), bits=4,
                         group_size=128, stream=True).compile()


@pytest.mark.parametrize("K,N", [(4096, 28672), (14336, 4096)])
def test_prefill_w4a8_compiles(on_v5e, K, N):
    """Prefill rows: the one-pass activation quantizer (whole-K row
    blocks: K=14336 is the tightest scoped-VMEM fit) and the deferred
    W4A8 grid behind it."""
    from aphrodite_tpu.ops.pallas.quant_matmul import (_quant8_call,
                                                       gptq_matmul_a8)
    m = 4096
    jax.jit(functools.partial(_quant8_call, interpret=False)).lower(
        on_v5e((m, K), BF16)).compile()
    _, qw, qz, sc = _gptq_operands(on_v5e, m, K, N)
    gptq_matmul_a8.lower(on_v5e((m, K), jnp.int8), qw, qz, sc, bits=4,
                         group_size=128, stream=False).compile()


@pytest.mark.parametrize("m", [48, 1024])
@pytest.mark.parametrize("K,N", LAYER_SHAPES)
def test_w4a8_byte_unpack_compiles_at_the_cells_shapes(on_v5e, m, K, N):
    """`mistral-7b-w4a8.batch`'s eight calls with the operand from
    `_unpack_bytes` (an int32 -> int8 `pltpu.bitcast` of the biased
    words, two int8 planes stacked on a 32-row tile seam): the 48
    decode rows on the streamed grid, which take it by themselves, and
    the 1,024 prompt rows on the compiler's grid with the deferred
    rescale, which take it from `benchmarks/qmm_ab.py` alone."""
    from aphrodite_tpu.ops.pallas import quant_matmul as qm
    assert qm._resolve_unpack(None, 4, streamed=m <= 64) == (
        "bytes" if m <= 64 else "planes")
    qm.gptq_matmul_a8.lower(*_gptq_operands(on_v5e, m, K, N), bits=4,
                            group_size=128, unpack="bytes").compile()


@pytest.mark.parametrize("m,bits,unpack", [
    (48, 8, None), (1024, 8, None), (48, 4, "planes")])
def test_w4a8_plane_unpack_still_compiles(on_v5e, m, bits, unpack):
    """The calls that keep `_unpack_planes`: 8-bit words on either
    grid, and the 4-bit arm `benchmarks/qmm_ab.py` holds the byte
    unpack against on the streamed grid (4-bit prompt rows take the
    planes by themselves: `test_prefill_w4a8_compiles`)."""
    from aphrodite_tpu.ops.pallas import quant_matmul as qm
    K, N = LAYER_SHAPES[0]
    pack = 32 // bits
    assert qm._resolve_unpack(unpack, bits, streamed=m <= 64) == "planes"
    qm.gptq_matmul_a8.lower(
        on_v5e((m, K), BF16), on_v5e((K // pack, N), I32),
        on_v5e((K // 128, N // pack), I32), on_v5e((K // 128, N), BF16),
        bits=bits, group_size=128, unpack=unpack).compile()


#: (rows, query heads, KV heads, table width in pages, page dtype,
#: fused KV write). The first two are a small batch; the next six the
#: benchmark cell's decode programs (48 rows, pages of 16, the three
#: table widths its contexts of 1,024-1,408 reach, decode steps and the
#: read-only verify rounds): 512-token items, so a ring that outgrew
#: the 16 MiB scoped VMEM at the served shape is met here and not on
#: the chip; then 8-bit pages (512-token items too, the ring twice as
#: deep) and two head blocks (n_hb > 1: the lane-sliced copies); last
#: the far end of `head_block`'s rule, 8-bit pages of 16 and of 21 KV
#: heads in ONE block (2,048 and 2,688 lanes; the dequantised K and V
#: of an item are temporaries beside the ring).
DECODE_CASES = [
    (8, 32, 8, 24, BF16, True), (8, 32, 8, 24, BF16, False),
    (48, 32, 8, 72, BF16, True), (48, 32, 8, 72, BF16, False),
    (48, 32, 8, 80, BF16, True), (48, 32, 8, 80, BF16, False),
    (48, 32, 8, 88, BF16, True), (48, 32, 8, 88, BF16, False),
    (48, 32, 8, 88, jnp.int8, True),
    (48, 32, 16, 88, BF16, True),
    (48, 64, 16, 88, jnp.int8, True), (48, 84, 21, 88, jnp.int8, True),
    (48, 84, 21, 88, jnp.float8_e5m2, True),
]


@pytest.mark.parametrize("B,Hq,Hkv,pps,dtype,fused", DECODE_CASES)
def test_decode_attention_compiles(on_v5e, B, Hq, Hkv, pps, dtype,
                                   fused):
    """The ragged decode kernel with the AMLA rescale, fused KV write
    (decode steps) and read-only (speculative verify rounds), its work
    list sized by the shared policy and padded as the runner pads
    it."""
    from aphrodite_tpu.ops.pallas.paged_attention import (
        build_decode_work_list, choose_pages_per_chunk, lane_bytes_of,
        padded_work_length, paged_decode_attention)
    d, page = 128, 16
    ppc = choose_pages_per_chunk(pps, page,
                                 lane_bytes_of(Hkv, d, dtype))
    # rows spread over the last bucket of the table, as contexts are
    counts = [pps - i % 8 for i in range(B)]
    items = sum(-(-n // ppc) for n in counts)
    work = build_decode_work_list(
        counts, ppc, pad_to=padded_work_length(items, B, pps, ppc))
    pages = on_v5e((5077, page, Hkv * d), dtype)
    new = on_v5e((B, Hkv, d), BF16)

    def attend(q, kp, vp, tables, ctx, kn, vn):
        return paged_decode_attention(
            q, kp, vp, tables, ctx, None, kn if fused else None,
            vn if fused else None, scale=d ** -0.5,
            pages_per_chunk=ppc, work_items=work, amla=True)

    jax.jit(attend, donate_argnums=(1, 2) if fused else ()).lower(
        on_v5e((B, Hq, d), BF16), pages, pages, on_v5e((B, pps), I32),
        on_v5e((B,), I32), new, new).compile()


#: SmallThinker's decode programs (`smallthinker-21ba3b-bf16.batch-8k`):
#: 28 query heads over 4 KV heads of 128 (seven queries a KV head, one
#: head block of 512 lanes), pages of 16, 24 rows. (table width, window,
#: fused write): the full group's table at 8,192-8,960 tokens (576 wide,
#: 513-560 pages held), the canary's one row at 512, and a window
#: group's at 320 (257-258 pages held under a window of 4,096), with
#: the window's mask in the kernel; the widths a window group's table
#: has while a prompt is written (385 pages at most) are prefill's.
GROUP_CASES = [
    (24, 576, None, True), (1, 512, None, True), (24, 320, 4096, True),
    (24, 320, 4096, False), (24, 448, 4096, True),
]


@pytest.mark.parametrize("B,pps,window,fused", GROUP_CASES)
def test_decode_attention_compiles_at_smallthinker_shapes(
        on_v5e, B, pps, window, fused):
    """The same kernel as above, called once a layer with its page
    group's table: 4 KV heads, tables five times as wide, and for a
    window layer the mask over the newest 4,096 keys."""
    from aphrodite_tpu.ops.pallas.paged_attention import (
        build_decode_work_list, choose_pages_per_chunk, lane_bytes_of,
        padded_work_length, paged_decode_attention)
    Hq, Hkv, d, page = 28, 4, 128, 16
    ppc = choose_pages_per_chunk(pps, page, lane_bytes_of(Hkv, d, BF16))
    assert ppc == 32
    held = 258 if window else pps - 16
    counts = [held - i % 2 for i in range(B)]
    items = sum(-(-n // ppc) for n in counts)
    work = build_decode_work_list(
        counts, ppc, pad_to=padded_work_length(items, B, pps, ppc))
    # a pool of 4.5 GB in three pairs of page arrays
    pages = on_v5e((46000, page, Hkv * d), BF16)
    new = on_v5e((B, Hkv, d), BF16)

    def attend(q, kp, vp, tables, ctx, kn, vn):
        return paged_decode_attention(
            q, kp, vp, tables, ctx, None, kn if fused else None,
            vn if fused else None, scale=d ** -0.5,
            pages_per_chunk=ppc, work_items=work, amla=True,
            window=window)

    jax.jit(attend, donate_argnums=(1, 2) if fused else ()).lower(
        on_v5e((B, Hq, d), BF16), pages, pages, on_v5e((B, pps), I32),
        on_v5e((B,), I32), new, new).compile()


def test_kv_writer_compiles_at_smallthinker_shapes(on_v5e):
    """The prefill page writer for a chunk of 2,048 tokens into pages
    of 4 KV heads x 128 lanes (128 cells), once a layer with its
    group's cells."""
    from aphrodite_tpu.ops.pallas.kv_write import (can_use_pallas_writer,
                                                   write_kv_pages_prefill)
    page, hd = 16, 4 * 128
    assert can_use_pallas_writer(BF16, page, hd)
    pages = on_v5e((46000, page, hd), BF16)
    cells = 2048 // page
    chunk = on_v5e((cells * page, hd), BF16)
    ids = on_v5e((cells,), I32)
    jax.jit(write_kv_pages_prefill, donate_argnums=(2, 3)).lower(
        chunk, chunk, pages, pages, ids, ids, ids).compile()


def test_kv_writers_compile(on_v5e):
    from aphrodite_tpu.ops.pallas.kv_write import (write_kv_pages,
                                                   write_kv_pages_prefill)
    page, hd, tokens = 16, 1024, 40
    pages = on_v5e((2048, page, hd), BF16)
    rows = on_v5e((tokens, hd), BF16)
    for distinct in (True, False):
        jax.jit(functools.partial(write_kv_pages,
                                  distinct_pages=distinct),
                donate_argnums=(2, 3)).lower(
            rows, rows, pages, pages, on_v5e((tokens,), I32)).compile()
    cells = 8 * 512 // page
    chunk = on_v5e((cells * page, hd), BF16)
    ids = on_v5e((cells,), I32)
    jax.jit(write_kv_pages_prefill, donate_argnums=(2, 3)).lower(
        chunk, chunk, pages, pages, ids, ids, ids).compile()


#: Phi-4-mini-flash's decode programs (`phi-4-mini-flash-bf16.reason-2k`):
#: 40 query heads over 10 KV heads of 128, a differential pair of the
#: model's 64-wide heads held as one head: ONE head block of ten (1,280
#: lanes, a page one contiguous 40 KB descriptor; PR 37: two blocks of
#: 5 before), a head count no other cell has; the model's own scale
#: 1/8; 384-token items, four ring slots. (query heads, KV heads, rows,
#: table width, window, fused write): at the cell's 48 rows and at 64,
#: the full layer's table at 2,049-3,072 tokens (192 wide) with the
#: fused write, a cross layer's read-only call over the same pages, a
#: window group's table (40 wide, 32-33 pages held under the window of
#: 512); the canary's one row under and over 128 pages. Then the head
#: counts just past the rule's threshold, which still divide into lane
#: slices: 12 heads in two blocks of 6, 11 in eleven of 1.
FLASH_CASES = [
    (40, 10, rows, pps, window, fused)
    for rows in (48, 64)
    for pps, window, fused in ((192, None, True), (192, None, False),
                               (40, 512, True))
] + [
    (40, 10, 1, 128, None, True), (40, 10, 1, 192, None, False),
    (40, 10, 1, 40, 512, True),
    (48, 12, 48, 192, None, True), (44, 11, 48, 192, None, True),
]


@pytest.mark.parametrize("Hq,Hkv,B,pps,window,fused", FLASH_CASES)
def test_decode_attention_compiles_at_phi4flash_shapes(
        on_v5e, Hq, Hkv, B, pps, window, fused):
    from aphrodite_tpu.ops.pallas.paged_attention import (
        build_decode_work_list, choose_pages_per_chunk, head_block,
        lane_bytes_of, padded_work_length, paged_decode_attention)
    d, page = 128, 16
    assert head_block(Hkv, d, BF16) == {10: 10, 12: 6, 11: 1}[Hkv]
    ppc = choose_pages_per_chunk(pps, page, lane_bytes_of(Hkv, d, BF16))
    assert ppc == (24 if Hkv == 10 else 32)
    held = 33 if window else pps - 8
    counts = [held - i % 2 for i in range(B)]
    items = sum(-(-n // ppc) for n in counts)
    work = build_decode_work_list(
        counts, ppc, pad_to=padded_work_length(items, B, pps, ppc))
    # a pool of 6 GB in one pair of page arrays
    pages = on_v5e((75000, page, Hkv * d), BF16)
    new = on_v5e((B, Hkv, d), BF16)

    def attend(q, kp, vp, tables, ctx, kn, vn):
        return paged_decode_attention(
            q, kp, vp, tables, ctx, None, kn if fused else None,
            vn if fused else None, scale=0.125, pages_per_chunk=ppc,
            work_items=work, amla=True, window=window)

    jax.jit(attend, donate_argnums=(1, 2) if fused else ()).lower(
        on_v5e((B, Hq, d), BF16), pages, pages, on_v5e((B, pps), I32),
        on_v5e((B,), I32), new, new).compile()


#: Laguna-S-2.1's attention layers: 8 KV heads of 128 under 48 query
#: heads (a full layer) and 72 (a window layer, `window=512`): 6 and 9
#: query rows a KV head, where 4, 7 and 20 ran before. (query heads,
#: rows, table width, window): the cell's decode buckets with contexts
#: of 4,097-4,736 tokens (tables 320 wide; a window group holds 33-34
#: pages of them), the canary's one row at 256 pages, and 65 rows.
LAGUNA_CASES = [
    (heads, rows, 320, window)
    for heads, window in ((48, None), (72, 512))
    for rows in (4, 16, 48, 64, 65)
] + [(48, 1, 256, None), (72, 1, 256, 512)]


@pytest.mark.parametrize("Hq,B,pps,window", LAGUNA_CASES)
def test_decode_attention_compiles_at_laguna_shapes(on_v5e, Hq, B, pps,
                                                    window):
    from aphrodite_tpu.ops.pallas.paged_attention import (
        build_decode_work_list, choose_pages_per_chunk, head_block,
        lane_bytes_of, padded_work_length, paged_decode_attention)
    Hkv, d, page = 8, 128, 16
    assert head_block(Hkv, d, BF16) == 8 and Hq // Hkv in (6, 9)
    ppc = choose_pages_per_chunk(pps, page, lane_bytes_of(Hkv, d, BF16))
    held = 34 if window else pps - 24
    counts = [held - i % 2 for i in range(B)]
    items = sum(-(-n // ppc) for n in counts)
    work = build_decode_work_list(
        counts, ppc, pad_to=padded_work_length(items, B, pps, ppc))
    # a pool of 3.7 GB in the one pair of page arrays: 32 KB a page a side
    pages = on_v5e((57000, page, Hkv * d), BF16)
    new = on_v5e((B, Hkv, d), BF16)

    def attend(q, kp, vp, tables, ctx, kn, vn):
        return paged_decode_attention(
            q, kp, vp, tables, ctx, None, kn, vn, scale=128 ** -0.5,
            pages_per_chunk=ppc, work_items=work, amla=True, window=window)

    jax.jit(attend, donate_argnums=(1, 2)).lower(
        on_v5e((B, Hq, d), BF16), pages, pages, on_v5e((B, pps), I32),
        on_v5e((B,), I32), new, new).compile()


#: EvaByte's attention layers: 32 KV heads of 128 under 32 query heads,
#: ONE query row a KV head and 4,096 lanes a token row (no cell has
#: more than 20 KV heads or fewer than 4 query rows a head). A row's
#: table is `[summary pages ; window pages]`, 24-152 pages, 192 wide.
#: (rows, fused write): the cell's 24 rows and the canary's one.
EVABYTE_CASES = [(rows, fused) for rows in (1, 24)
                 for fused in (True, False)]


@pytest.mark.parametrize("B,fused", EVABYTE_CASES)
def test_decode_attention_compiles_at_evabyte_shapes(on_v5e, B, fused):
    from aphrodite_tpu.ops.pallas.paged_attention import (
        build_decode_work_list, choose_pages_per_chunk, lane_bytes_of,
        padded_work_length, paged_decode_attention)
    H, d, page, pps = 32, 128, 16, 192
    ppc = choose_pages_per_chunk(pps, page, lane_bytes_of(H, d, BF16))
    # rows just past an edge (24 summary pages and a page) beside rows
    # about to reach one (16 summary pages and 128)
    counts = [(25, 144)[i % 2] for i in range(B)]
    items = sum(-(-n // ppc) for n in counts)
    work = build_decode_work_list(
        counts, ppc, pad_to=padded_work_length(items, B, pps, ppc))
    # a pool of 10 GB in 8 pairs of page arrays: 128 KB a page a side
    pages = on_v5e((4780, page, H * d), BF16)
    new = on_v5e((B, H, d), BF16) if fused else None

    def attend(q, kp, vp, tables, ctx, kn, vn):
        return paged_decode_attention(
            q, kp, vp, tables, ctx, None, kn, vn, scale=d ** -0.5,
            pages_per_chunk=ppc, work_items=work, amla=True)

    jax.jit(attend, donate_argnums=(1, 2) if fused else ()).lower(
        on_v5e((B, H, d), BF16), pages, pages, on_v5e((B, pps), I32),
        on_v5e((B,), I32), new, new).compile()


def test_kv_writers_compile_at_evabyte_shapes(on_v5e):
    """Both page writers into pages of 32 KV heads x 128 lanes: the
    prompt's whole-page writer for four chunks of 2,048 bytes (512
    cells) and for one, and the token writer (a prompt chunk that
    starts inside a page)."""
    from aphrodite_tpu.ops.pallas.kv_write import (can_use_pallas_writer,
                                                   write_kv_pages,
                                                   write_kv_pages_prefill)
    page, hd = 16, 32 * 128
    assert can_use_pallas_writer(BF16, page, hd)
    pages = on_v5e((4780, page, hd), BF16)
    for cells in (4 * 2048 // page, 2048 // page):
        chunk = on_v5e((cells * page, hd), BF16)
        ids = on_v5e((cells,), I32)
        jax.jit(write_kv_pages_prefill, donate_argnums=(2, 3)).lower(
            chunk, chunk, pages, pages, ids, ids, ids).compile()
    rows = on_v5e((40, hd), BF16)
    for distinct in (True, False):
        jax.jit(functools.partial(write_kv_pages,
                                  distinct_pages=distinct),
                donate_argnums=(2, 3)).lower(
            rows, rows, pages, pages, on_v5e((40,), I32)).compile()


def test_the_summarise_program_compiles_at_evabyte_shapes(on_v5e):
    """`summarise_pages` for eight closed windows of 128 pages of 32
    heads x 128 lanes into 8 summary pages each, in a pool of the
    cell's size; the pool is updated in place (donated) and the
    program's temporaries stay under a quarter of a gigabyte
    (a window at a time)."""
    from aphrodite_tpu.modeling.layers.eva_attention import summarise_pages
    page, heads, d = 16, 32, 128
    pages = on_v5e((4780, page, heads * d), BF16)
    vec = on_v5e((heads, d), BF16)

    def pool(kp, vp, src, dst, phi, mu):
        return summarise_pages(kp, vp, src, dst, phi, mu, d ** -0.5, heads)

    compiled = jax.jit(pool, donate_argnums=(0, 1)).lower(
        pages, pages, on_v5e((8, 128), I32), on_v5e((8, 8), I32), vec,
        vec).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 28


#: AI21-Jamba2-3B's attention layers: ONE KV head of 128 under 20 query
#: heads, so the page's lane axis is one lane tile, a page copy is 4 KB
#: and a row's packed query has 20 rows; 512-token items. (rows, table
#: width, fused write): the cell's decode buckets with contexts of
#: 513-1,536 tokens (a table 96 wide), the canary's one row, and 129
#: rows (the decode bucket past the slots).
MQA_CASES = [(rows, 96, True) for rows in (32, 64, 96, 128, 129)] + [
    (1, 32, True), (128, 96, False), (128, 256, True)] + [
    # a batch of `_WIDE_ROWS` rows or more: tables 128 wide
    (rows, 128, True) for rows in (64, 96, 128)]


@pytest.mark.parametrize("B,pps,fused", MQA_CASES)
def test_decode_attention_compiles_at_jamba_shapes(on_v5e, B, pps, fused):
    from aphrodite_tpu.ops.pallas.paged_attention import (
        build_decode_work_list, choose_pages_per_chunk, head_block,
        lane_bytes_of, padded_work_length, paged_decode_attention)
    Hq, Hkv, d, page = 20, 1, 128, 16
    assert head_block(Hkv, d, BF16) == 1
    assert lane_bytes_of(Hkv, d, BF16) == 256
    ppc = choose_pages_per_chunk(pps, page, 256)
    assert ppc == 32
    counts = [pps - 8 - i % 2 for i in range(B)]
    items = sum(-(-n // ppc) for n in counts)
    work = build_decode_work_list(
        counts, ppc, pad_to=padded_work_length(items, B, pps, ppc))
    # 6.4 GB of pool in the two pairs of page arrays: 4 KB a page a side
    pages = on_v5e((400000, page, Hkv * d), BF16)
    new = on_v5e((B, Hkv, d), BF16)

    def attend(q, kp, vp, tables, ctx, kn, vn):
        return paged_decode_attention(
            q, kp, vp, tables, ctx, None, kn if fused else None,
            vn if fused else None, scale=128 ** -0.5, pages_per_chunk=ppc,
            work_items=work, amla=True)

    jax.jit(attend, donate_argnums=(1, 2) if fused else ()).lower(
        on_v5e((B, Hq, d), BF16), pages, pages, on_v5e((B, pps), I32),
        on_v5e((B,), I32), new, new).compile()


@pytest.mark.parametrize("tokens", [512, 4096])
def test_kv_writer_compiles_at_jamba_shapes(on_v5e, tokens):
    """The prefill page writer into pages of one KV head x 128 lanes:
    one 512-token prompt, and a step of eight of them."""
    from aphrodite_tpu.ops.pallas.kv_write import (can_use_pallas_writer,
                                                   write_kv_pages_prefill)
    page, hd = 16, 128
    assert can_use_pallas_writer(BF16, page, hd)
    pages = on_v5e((400000, page, hd), BF16)
    cells = tokens // page
    chunk = on_v5e((cells * page, hd), BF16)
    ids = on_v5e((cells,), I32)
    jax.jit(write_kv_pages_prefill, donate_argnums=(2, 3)).lower(
        chunk, chunk, pages, pages, ids, ids, ids).compile()


def test_kv_writer_compiles_at_phi4flash_shapes(on_v5e):
    """The prefill page writer for a chunk of 2,048 tokens into pages
    of 10 KV heads x 128 lanes, once a page-holding layer."""
    from aphrodite_tpu.ops.pallas.kv_write import (can_use_pallas_writer,
                                                   write_kv_pages_prefill)
    page, hd = 16, 10 * 128
    assert can_use_pallas_writer(BF16, page, hd)
    pages = on_v5e((75000, page, hd), BF16)
    cells = 2048 // page
    chunk = on_v5e((cells * page, hd), BF16)
    ids = on_v5e((cells,), I32)
    jax.jit(write_kv_pages_prefill, donate_argnums=(2, 3)).lower(
        chunk, chunk, pages, pages, ids, ids, ids).compile()


#: the selective-scan kernels at Phi-4-mini-flash's widths: 5,120
#: channels, 16 states, 128 state slots and the scratch one, nine state
#: layers in the arrays.
_SSM = dict(n=16, ch=5120, slots=129, layers=9)


@pytest.mark.parametrize("rows,tokens", [(1, 2048), (1, 1024), (2, 512),
                                         (1, 128), (8, 512), (1, 512)])
def test_ssm_chunk_scan_compiles(on_v5e, rows, tokens):
    """A prompt chunk's scan: channels in blocks of 512, time in blocks
    of 256 with the state in VMEM, the slot's state aliased in place.
    (A chunk under 128 tokens is padded to 128 by the dispatcher.)"""
    from aphrodite_tpu.ops.pallas.ssm_scan import _ssm_scan_impl
    n, ch, slots = _SSM["n"], _SSM["ch"], _SSM["slots"]
    f32 = jnp.float32
    seq, coeff = on_v5e((rows, tokens, ch), f32), \
        on_v5e((rows, n, tokens), f32)
    _ssm_scan_impl.lower(
        seq, seq, coeff, coeff, on_v5e((n, ch), f32), on_v5e((1, ch), f32),
        on_v5e((_SSM["layers"], slots, n, ch), f32), on_v5e((1,), I32),
        on_v5e((rows,), I32), on_v5e((rows,), I32)).compile()


@pytest.mark.parametrize("rows", [64, 8, 1, 96, 128, 12, 4, 48])
def test_ssm_decode_update_compiles(on_v5e, rows):
    """A decode step's update: a row's state and convolution tail by
    its layer and slot id, read, moved on and written in place; the
    tail four rows a slot (`StateSpec.allocated`)."""
    from aphrodite_tpu.ops.pallas.ssm_scan import _ssm_update_impl
    n, ch, slots, layers = (_SSM[k] for k in ("n", "ch", "slots", "layers"))
    f32 = jnp.float32
    from aphrodite_tpu.ops.pallas.ssm_scan import _row_blocks
    row = on_v5e(jax.eval_shape(
        _row_blocks, jax.ShapeDtypeStruct((rows, ch), f32)).shape, f32)
    _ssm_update_impl.lower(
        row, row, row, on_v5e((n, rows), f32),
        on_v5e((n, rows), f32), on_v5e((n, ch), f32), on_v5e((1, ch), f32),
        on_v5e((layers, slots, n, ch), f32),
        on_v5e((layers, slots, 4, ch), BF16), on_v5e((1,), I32),
        on_v5e((rows,), I32)).compile()


# ---- the state arrays through a step program's Mamba layers ----
#
# A kernel that compiles says nothing of what the compiler puts AROUND
# it. Until PR 42 the convolution's tail was an array a layer,
# `[slots + 1, 3, 5120]`: with 3 rows on its second-minor axis the
# device's default layout has the rows outermost, a Pallas operand is
# row-major, and an operand of 4 MB is small enough to be staged whole
# in the compiler's alternate memory. So every layer of every decode
# step sliced the WHOLE array in tap by tap, re-laid it out, and copied
# it back out after the kernel: 7.6% of the device's time in Jamba's
# cell, counted by no roofline. The optimised HLO shows all of it
# without a chip, which is what these cases read.

#: operations that move an operand whole (`ConcatBitcast` is the custom
#: call that joins the slices of a staged operand)
_WHOLE_ARRAY_MOVES = ("slice", "slice-start", "copy", "copy-start",
                      "ConcatBitcast")


def _instructions(hlo: str):
    """(opcode, the shapes of its result and of its operands) of every
    instruction of every computation of an HLO module's text. A custom
    call goes by its target."""
    import re
    shapes, out = {}, []
    pattern = re.compile(
        r"^\s*(?:ROOT )?%?([\w.\-]+) = (\(.*?\)|\S+) ([\w\-]+)\((.*)$")
    for line in hlo.splitlines():
        if line.rstrip().endswith("{"):         # a computation begins
            shapes = {}
        found = pattern.match(line)
        if not found:
            continue
        name, result, opcode, rest = found.groups()
        shapes[name] = result
        target = re.search(r'custom_call_target="([^"]+)"', rest)
        operands = [shapes.get(n, "") for n in
                    re.findall(r"%([\w.\-]+)", rest.split("), ")[0])]
        out.append((target.group(1) if target else opcode,
                    [result] + operands))
    return out


def _whole_array_moves(hlo: str, array: str):
    """The opcodes, in order, of the operations that read or write an
    array of shape `array` (`bf16[26,129,4,5120]`) whole."""
    return [opcode for opcode, shapes in _instructions(hlo)
            if opcode in _WHOLE_ARRAY_MOVES and
            any(array + "{" in s or s == array for s in shapes)]


def _mixer_program(monkeypatch, sds, *, rows, run, tail, state, norms,
                   tokens=1):
    """The optimised HLO of `run`'s Mamba layers of a step, the real
    `MambaMixer` at the served widths (hidden 2,560, 5,120 channels, 16
    states, four taps, bfloat16) over state arrays of shapes `tail` and
    `state` (a list of each: an array a layer, or the model's one),
    donated as the step programs donate them; a decode step of `rows`
    rows at `tokens` 1, else a prompt chunk."""
    import types
    from aphrodite_tpu.modeling.input_metadata import InputMetadata
    from aphrodite_tpu.modeling.layers.mamba import MambaMixer
    # (the dispatchers ask the backend, which is the CPU here)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    config = types.SimpleNamespace(
        hidden_size=2560, mamba_d_inner=5120, mamba_d_state=16,
        mamba_d_conv=4, mamba_dt_rank=160)
    mixer = MambaMixer(config, "m", BF16, None, inner_norms=norms)
    params = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype), jax.eval_shape(mixer.init))

    def step(params, h, positions, slots, tails, states):
        meta = InputMetadata(
            slot_mapping=slots, block_tables=slots[:, None],
            context_lens=slots, state_slots=slots, is_prompt=tokens > 1,
            prompt_lens=slots if tokens > 1 else None)
        # (the parent's arrays had no layer axis: the control's get one
        # here, a bitcast, and give it back)
        tails, states = ([a.reshape((1,) * (4 - a.ndim) + a.shape)
                          for a in arrays] for arrays in (tails, states))
        for at, layer in run:
            out, _, (tails[at], states[at]) = mixer(
                params, h, positions, (tails[at], states[at]), meta, layer)
            h = h + out
        return h, [a.reshape(s) for a, s in zip(tails, tail)], \
            [a.reshape(s) for a, s in zip(states, state)]

    return jax.jit(step, donate_argnums=(4, 5)).lower(
        params, sds((rows, tokens, 2560), BF16), sds((rows, tokens), I32),
        sds((rows,), I32), [sds(s, BF16) for s in tail],
        [sds(s, jnp.float32) for s in state]).compile().as_text()


#: (rows of a decode step, state layers in the arrays, the layers run,
#: inner norms): Jamba's cell runs 26 layers at 128 rows, three of them
#: here (the first, one in the middle, the last); Phi's all nine at 48
_STATE_CASES = {
    "jamba": (128, 26, (0, 12, 25), True),
    "phi": (48, 9, tuple(range(9)), False),
}


def _state_arrays(layers):
    return f"bf16[{layers},129,4,5120]", f"f32[{layers},129,16,5120]"


@pytest.mark.parametrize("model", list(_STATE_CASES))
def test_a_decode_step_moves_no_state_array_whole(on_v5e, monkeypatch,
                                                  model):
    """The model's one tail array and its one state array go into the
    first layer's kernel call and out of the last one's: nothing
    between slices, copies, stages or re-lays-out either of them.
    Found: at Jamba's geometry nothing at all (137 MB and 1.1 GB fit no
    alternate memory); at Phi's the 48 MB tail array is staged ONCE a
    program, one `copy-start` in before the first call and one out
    after the last, since the kernel states its cost (`_update_cost`:
    the compiler then counts its operands worth holding close); never
    once a layer, and never re-laid-out."""
    rows, layers, run, norms = _STATE_CASES[model]
    hlo = _mixer_program(
        monkeypatch, on_v5e, rows=rows, run=[(0, l) for l in run],
        tail=[(layers, 129, 4, 5120)], state=[(layers, 129, 16, 5120)],
        norms=norms)
    calls = [shapes for op, shapes in _instructions(hlo)
             if op == "tpu_custom_call"]
    assert len(calls) == len(run)
    tail, state = _state_arrays(layers)
    # each call takes both arrays whole and gives both back
    for shapes in calls:
        assert sum(tail + "{" in s for s in shapes[1:]) == 1 and \
            sum(state + "{" in s for s in shapes[1:]) == 1
        assert tail + "{" in shapes[0] and state + "{" in shapes[0]
    staged_once = ["copy-start"] * 2 if model == "phi" else []
    assert _whole_array_moves(hlo, tail) in ([], staged_once)
    assert _whole_array_moves(hlo, state) == []


@pytest.mark.parametrize("model,rows,tokens", [("jamba", 8, 512),
                                               ("phi", 1, 2048)])
def test_a_prompt_step_moves_no_state_array_whole(on_v5e, monkeypatch,
                                                  model, rows, tokens):
    """A prompt chunk reads its rows' tails (`tail[layer, slots]`) and
    writes them back (`.at[layer, slots].set`) in place, and the chunk
    scan takes the state array as the update does."""
    _, layers, run, norms = _STATE_CASES[model]
    hlo = _mixer_program(
        monkeypatch, on_v5e, rows=rows, tokens=tokens,
        run=[(0, l) for l in run], tail=[(layers, 129, 4, 5120)],
        state=[(layers, 129, 16, 5120)], norms=norms)
    tail, state = _state_arrays(layers)
    assert sum(op == "tpu_custom_call"
               for op, _ in _instructions(hlo)) == len(run)
    assert _whole_array_moves(hlo, tail) == []
    assert _whole_array_moves(hlo, state) == []


def test_the_check_sees_the_parents_layout_sliced_and_copied(on_v5e,
                                                             monkeypatch):
    """The control: the same mixer over the layout this replaced, a
    `[129, 3, 5120]` tail and a `[129, 16, 5120]` state a layer. Every
    layer's tail is re-laid-out whole before its kernel call and back
    after it, and where the compiler also stages it in its alternate
    memory it is sliced in tap by tap and joined (what the chip's trace
    showed as `slice-done bf16[129,1,5120]` and `copy
    bf16[129,3,5120]`; which layers are staged is the scheduler's
    choice); the state array, row-major by default and too large to
    stage, passes untouched, as both do now."""
    hlo = _mixer_program(
        monkeypatch, on_v5e, rows=128, run=[(l, 0) for l in range(3)],
        tail=[(129, 3, 5120)] * 3, state=[(129, 16, 5120)] * 3, norms=True)
    moves = _whole_array_moves(hlo, "bf16[129,3,5120]")
    assert moves.count("copy") == 2 * 3
    staged = moves.count("ConcatBitcast")
    assert staged >= 1 and moves.count("slice-start") == 3 * staged
    assert len(moves) == 6 + 4 * staged
    # the parameter's layout is the cause: the three rows outermost
    assert "bf16[129,3,5120]{2,0,1:T(8,128)(2,1)} parameter" in hlo
    assert _whole_array_moves(hlo, "f32[129,16,5120]") == []


# ---- the prompt's attention as the flash kernel ----

#: (rows, queries, query heads, KV heads, keys, window[, lanes a head
#: of q and k, of v: 128 both unless stated]): the calls of the cells'
#: prompt steps (`benchmarks/prefill_ab.py::CELLS`, and Jamba's
#: 512-token prompts on one KV head)
PREFILL_CASES = {
    "mistral-1-row": (1, 1024, 32, 8, 1024, None),
    "mistral-2-rows": (2, 1024, 32, 8, 1024, None),
    "mistral-4-rows": (4, 1024, 32, 8, 1024, None),
    "smallthinker-chunk-1": (1, 2048, 28, 4, 2048, None),
    "smallthinker-full-table": (1, 2048, 28, 4, 8192, None),
    "smallthinker-window-table": (1, 2048, 28, 4, 7168, 4096),
    "smallthinker-window-table-chunk-4": (1, 2048, 28, 4, 6144, 4096),
    "laguna-full-chunk-1": (4, 2048, 48, 8, 2048, None),
    "laguna-full-table": (1, 2048, 48, 8, 4096, None),
    "laguna-window-chunk-1": (1, 2048, 72, 8, 2048, 512),
    "laguna-window-table": (2, 2048, 72, 8, 3072, 512),
    "phi-full": (1, 2048, 40, 10, 2048, None),
    "phi-window": (1, 2048, 40, 10, 2048, 512),
    "jamba": (8, 512, 20, 1, 512, None),
    # 32 KV heads of ONE query row: a chunk's own keys (chunk 1) and a
    # table 192 pages wide gathered (chunks 2 and 3), at 1 and 4 rows
    "evabyte-chunk-1": (4, 2048, 32, 32, 2048, None),
    "evabyte-table": (4, 2048, 32, 32, 3072, None),
    "evabyte-table-1-row": (1, 2048, 32, 32, 3072, None),
    # multi-head latent attention's up-projected rows, one KV head a
    # query head: 192 + 64 pad lanes of keys, 128 of values (PR 53); a
    # prompt whole on its own keys, and a chunk on a gathered table
    "sarvam-whole-prompt": (1, 8192, 64, 64, 8192, None, 256, 128),
    "sarvam-chunk-table": (1, 2048, 64, 64, 9216, None, 256, 128),
}


@pytest.mark.parametrize("case", PREFILL_CASES.values(),
                         ids=list(PREFILL_CASES))
def test_prefill_flash_attention_compiles(on_v5e, case):
    """The prompt's flash kernel at each cell's prompt shapes, called
    as `PagedAttention` (and `LatentAttention`) calls it: q, k, v as
    the projections leave them (`[rows, tokens, heads x lanes]`), seen
    as `[rows, tokens, heads, lanes]`, and the output, as wide a head
    as the values, back as `o_proj` reads it. Mosaic takes it at the
    blocks `choose_blocks` gives, under the scoped VMEM the kernel
    states; and around the call the compiler puts nothing: the three
    operands reach it and the output leaves it without a copy, a slice
    or a staged move. (Of Sarvam's cases that says that the kernel's
    own view of operands given token-major costs nothing; its step's
    up-projection hands K and V over tokens-minor and the compiler
    copies them, `PERF.md` section 7.)"""
    import re
    from aphrodite_tpu.ops.pallas import prefill_attention as flash
    rows, s, Hq, Hkv, kv, window, d, dv = (case + (128, 128))[:8]

    def attend(q, k, v, ctx, valid):
        return flash.prefill_flash_attention(
            q.reshape(rows, s, Hq, d), k.reshape(rows, kv, Hkv, d),
            v.reshape(rows, kv, Hkv, dv), ctx, valid, d ** -0.5,
            window).reshape(rows, s, Hq * dv)

    hlo = jax.jit(attend).lower(
        on_v5e((rows, s, Hq * d), BF16), on_v5e((rows, kv, Hkv * d), BF16),
        on_v5e((rows, kv, Hkv * dv), BF16), on_v5e((rows,), I32),
        on_v5e((rows,), I32)).compile().as_text()
    (call,) = [line for line in hlo.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert "_prefill_flash_impl" in call.split(" = ")[0]
    # the output a head as wide as the values, whatever the keys' width
    assert call.split(" = ")[1].startswith(f"bf16[{rows},{s},{Hq * dv}]{{")
    (stated,) = re.findall(
        r'"scoped_memory_configs":\[\{"memory_space":"1","offset":"\d+",'
        r'"size":"(\d+)"\}\]', call)
    assert int(stated) == flash.VMEM_LIMIT <= 48 << 20
    for array in (f"bf16[{rows},{s},{Hq * d}]",
                  f"bf16[{rows},{kv},{Hkv * d}]",
                  f"bf16[{rows},{kv},{Hkv * dv}]",
                  f"bf16[{rows},{s},{Hq * dv}]"):
        assert _whole_array_moves(hlo, array) == []
    query_block, key_block, major = flash.choose_blocks(
        s, kv, Hq // Hkv, window)
    assert s % query_block == 0 and kv % major == 0 and \
        major % key_block == 0 and major <= flash.KEY_MAJOR
    assert Hq // Hkv * query_block * key_block * 4 <= flash.SCORE_BYTES \
        or key_block == flash.TOKEN_TILE


#: (tokens, top_k, held experts, routed experts, hidden, width): the
#: decode step and the prompt chunk of SmallThinker's and Laguna's
#: cells, and Mixtral's widths, whose matrices go in blocks of columns
EXPERT_CALLS = [(24, 6, 64, 64, 2560, 768), (2048, 6, 64, 64, 2560, 768),
                (64, 10, 128, 256, 3072, 1024),
                (2048, 10, 128, 256, 3072, 1024),
                (16, 2, 8, 8, 4096, 14336), (2048, 2, 8, 8, 4096, 14336)]


@pytest.mark.parametrize(
    "case,dtype", [(case, BF16) for case in EXPERT_CALLS] + [
        (EXPERT_CALLS[0], jnp.float32), (EXPERT_CALLS[3], jnp.float32)],
    ids=lambda c: c.__name__ if hasattr(c, "__name__") else
    "x".join(map(str, c)))
def test_grouped_ffn_compiles(on_v5e, case, dtype):
    """The expert layer's two kernels at the served shapes, with the
    row tile and the aligned row count `FusedMoE` gives them: Mosaic
    takes both under the scoped VMEM they state, and both bear a name
    that starts as the benchmark's readers expect of the layer."""
    from aphrodite_tpu.ops.pallas import grouped_matmul as gm
    tokens, top_k, experts, routed, hidden, width = case
    assert gm.takes_shapes(hidden, width, dtype)
    pairs = tokens * top_k
    tile = gm.row_tile(pairs * experts // routed, experts)
    tiles = gm.num_row_tiles(pairs, experts, tile)
    hlo = gm.grouped_ffn.lower(
        on_v5e((tiles * tile, hidden), dtype),
        on_v5e((experts, hidden, width), dtype),
        on_v5e((experts, hidden, width), dtype),
        on_v5e((experts, width, hidden), dtype), on_v5e((tiles,), I32),
        on_v5e((), I32), tile=tile, act=jax.nn.silu).compile().as_text()
    calls = [line for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    names = [call.split(" = ")[0].split()[-1].lstrip("%")
             for call in calls]
    assert [name.rsplit(".", 1)[0] for name in names] == [
        "ragged-dot-aligned-gate-up", "ragged-dot-aligned-down"]
    assert all(name.startswith(gm.DEVICE_OP_PREFIXES) for name in names)


#: Sarvam-105B's LATENT pages (`PageGroups.latent`): ONE array a
#: layer, a token's row `[c 512 | k_r 64]` padded to 640 lanes under 64
#: query rows, the values its first 512 lanes; 512-token items, tables
#: 576 pages wide (contexts of 8,193-9,216 tokens). (rows, fused
#: write): the cell's decode buckets, the canary's one row.
LATENT_CASES = [(rows, True) for rows in (1, 4, 16, 48, 64)] + [
    (64, False)]


@pytest.mark.parametrize("B,fused", LATENT_CASES)
def test_decode_attention_compiles_at_sarvam_shapes(on_v5e, B, fused):
    """The decode kernel with `latent`: Mosaic takes it, the call
    bears the name the benchmark's reader finds it by, and it has ONE
    page operand (no second array of the pool's shape goes in)."""
    import re
    from aphrodite_tpu.ops.pallas import paged_attention as pa
    Hq, lanes, latent, page, pps = 64, 640, 512, 16, 576
    assert pa.head_block(1, lanes, BF16) == 1
    ppc = pa.choose_pages_per_chunk(pps, page,
                                    pa.lane_bytes_of(1, lanes, BF16))
    assert ppc == 32
    counts = [pps - 8 - i % 2 for i in range(B)]
    items = sum(-(-n // ppc) for n in counts)
    work = pa.build_decode_work_list(
        counts, ppc, pad_to=pa.padded_work_length(items, B, pps, ppc))
    # 1.6 GB a layer: 80,000 pages of 20 KB
    pages = on_v5e((80000, page, lanes), BF16)

    def attend(q, latent_pages, tables, ctx, row):
        return pa.paged_decode_attention(
            q, latent_pages, None, tables, ctx, None,
            row if fused else None, None, scale=0.135,
            pages_per_chunk=ppc, work_items=work, latent=latent)

    hlo = jax.jit(attend, donate_argnums=(1,) if fused else ()).lower(
        on_v5e((B, Hq, lanes), BF16), pages, on_v5e((B, pps), I32),
        on_v5e((B,), I32), on_v5e((B, 1, lanes), BF16)).compile().as_text()
    (call,) = [line for line in hlo.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert call.split(" = ")[0].split()[-1].lstrip("%").startswith(
        pa.LATENT_DEVICE_OP_PREFIXES)
    # the four lists, the queries, the ONE array of pages (and the
    # new rows)
    operands = re.sub(r"/\*.*?\*/", "", call.split("custom-call(")[1]
                      .split(")")[0]).split(", ")
    assert len(operands) == 6 + fused
    assert sum("latent_pages" in name for name in operands) == 1
    assert _whole_array_moves(hlo, "bf16[80000,16,640]") == []


@pytest.mark.parametrize("tokens", [2048, 8192])
def test_kv_writer_compiles_at_sarvam_shapes(on_v5e, tokens):
    """The prefill page writer into ONE array of 640-lane rows: a
    chunk of 2,048 tokens, four of them, a whole prompt of 8,192."""
    from aphrodite_tpu.ops.pallas.kv_write import (can_use_pallas_writer,
                                                   write_kv_pages_prefill)
    page, lanes = 16, 640
    assert can_use_pallas_writer(BF16, page, lanes)
    pages = on_v5e((80000, page, lanes), BF16)
    cells = tokens // page
    ids = on_v5e((cells,), I32)

    def write(rows, latent_pages, ids, src, valid):
        return write_kv_pages_prefill(rows, None, latent_pages, None, ids,
                                      src, valid)
    hlo = jax.jit(write, donate_argnums=(1,)).lower(
        on_v5e((cells * page, lanes), BF16), pages, ids, ids,
        ids).compile().as_text()
    assert _whole_array_moves(hlo, "bf16[80000,16,640]") == []


@pytest.mark.parametrize("rows,s,kv", [(1, 8192, 8192), (4, 2048, 2048),
                                       (1, 2048, 9216), (4, 2048, 9216)],
                         ids=["whole-prompt", "chunk-1-4-rows",
                              "table-1-row", "table-4-rows"])
def test_prefill_flash_attention_compiles_at_sarvam_shapes(on_v5e, rows, s,
                                                          kv):
    """The prompt's flash kernel at 64 heads of 192 + 64 pad lanes
    over up-projected latent rows (`modeling/layers/mla.py`): one KV
    head a query head, K `[rows, keys, 64, 256]` and V at its own 128
    lanes a head (`[rows, keys, 64, 128]`, PR 53), which are the
    output's."""
    from aphrodite_tpu.ops.pallas import prefill_attention as flash
    H, d, dv = 64, 256, 128

    def attend(q, k, v, ctx, valid):
        return flash.prefill_flash_attention(q, k, v, ctx, valid, 0.135)
    compiled = jax.jit(attend).lower(
        on_v5e((rows, s, H, d), BF16), on_v5e((rows, kv, H, d), BF16),
        on_v5e((rows, kv, H, dv), BF16), on_v5e((rows,), I32),
        on_v5e((rows,), I32)).compile()
    (out,) = jax.tree_util.tree_leaves(compiled.out_info)
    assert out.shape == (rows, s, H, dv)


# ---- the delta-rule (KDA) kernels at Kimi Linear's widths ----
#: 32 heads of 128 x 128, 12,288 convolution channels, 192 state slots
#: and the scratch one, six KDA layers in the arrays
_KDA = dict(heads=32, d=128, slots=193, layers=6)


@pytest.mark.parametrize("rows,tokens", [(1, 1024), (4, 1024), (1, 64),
                                         (8, 1024), (1, 2048)])
def test_kda_chunk_compiles(on_v5e, rows, tokens):
    """A prompt chunk: a grid cell a (row, head, 64 tokens), the head's
    state in VMEM across a row's chunks, the slot's state aliased in
    place; float32 products at `Precision.HIGHEST`, the transposed
    ones among them."""
    from aphrodite_tpu.ops.pallas.kda import _kda_chunk_impl
    heads, d, slots, layers = (_KDA[k] for k in
                               ("heads", "d", "slots", "layers"))
    f32 = jnp.float32
    seq = on_v5e((rows, tokens, heads * d), f32)
    _kda_chunk_impl.lower(
        seq, seq, seq, seq, on_v5e((rows, tokens, heads), f32),
        on_v5e((layers, slots, heads, d, d), f32), on_v5e((1,), I32),
        on_v5e((rows,), I32), on_v5e((rows,), I32)).compile()


@pytest.mark.parametrize("rows", [192, 128, 64, 24, 8, 1, 12])
def test_kda_decode_update_compiles(on_v5e, rows):
    """A decode step's update: a row's 2 MiB of matrices and its
    convolution tail by layer and slot id, read, moved on and written
    in place (8 MiB of VMEM for the state's blocks, over the 16 MiB a
    kernel has by default: the call states its limit); a head's
    `exp(g)`, `k` and `q` taken as columns of the `[128, 96]` block
    the caller hands in transposed."""
    from aphrodite_tpu.ops.pallas.kda import _kda_update_impl, _row_blocks
    heads, d, slots, layers = (_KDA[k] for k in
                               ("heads", "d", "slots", "layers"))
    f32 = jnp.float32

    def blocks(width):
        return on_v5e(jax.eval_shape(
            _row_blocks, jax.ShapeDtypeStruct((rows, width), f32)).shape,
            f32)
    _kda_update_impl.lower(
        blocks(3 * heads * d), on_v5e((rows, d, 3 * heads), f32),
        blocks(heads * d), blocks(heads * d),
        on_v5e((layers, slots, heads, d, d), f32),
        on_v5e((layers, slots, 4, 3 * heads * d), BF16),
        on_v5e((1,), I32), on_v5e((rows,), I32)).compile()
