"""Fused weight-dequant matmul Pallas kernels (GPTQ + AWQ layouts).

Reference equivalents: `kernels/quantization/gptq/q_gemm.cu` (exllama
reconstruct+gemm) — the CUDA side fuses int4 dequant into the GEMM so
the full-precision weight matrix never exists in global memory. The XLA
fallback (`quantization/gptq.py` dequantize-then-dot) materializes the
dequantized [in, out] bf16 matrix in HBM every step, which turns a
3.6 GB int4 weight read into ~32 GB of HBM traffic at 7B scale. This
kernel reads the PACKED weights once per tile, unpacks and scales them
in VMEM registers, and feeds the MXU directly.

Layout (AutoGPTQ v1, matching `quantization/gptq.py`):
  qweight [K//pack, N] int32 — pack = 32//bits values along K (rows)
  qzeros  [G, N//pack] int32 — packed along N (cols), stores z-1
  scales  [G, N]       f16/bf16/f32
with G = K // group_size. Dequant: w = (q - (z+1)) * s.

Grid: (m_tiles, n_tiles, k_tiles), k innermost accumulating into a VMEM
f32 scratch; block_k == group_size so each k-step sees exactly one
quantization group (z and s are single rows — a broadcast, no gather).
desc_act (g_idx shuffles) stays on the XLA path.

AWQ (`awq_matmul` below) is the lane-dual: its int32 words pack 8
output columns (interleaved nibble order, `dequantize.cuh:40-53`)
rather than 8 input rows, so the kernel unpacks nibble PLANES along
lanes — tile-local plane-major column order — and the wrapper permutes
zeros/scales into that order in the XLA prologue and un-permutes the
output columns once at the end (reshape/transpose pairs XLA lowers
natively; an in-kernel natural-order unpack would be the same
per-element shuffle disaster the GPTQ docstring describes).
Reference: `kernels/quantization/awq/gemm_kernels.cu:1-667` fuses
dequant into a grouped GEMM the same way.

W4A8 deferred rescale (PROFILE_r05 item 1): the classic W4A8 kernels
interleave each group's depth-`gs` int8 MXU dot with a
[block_m, block_n] f32 scale-FMA on the VPU, which gates the MXU at
~45% of its int8 microbench peak. The `*_a8` wrappers therefore carry
a second kernel variant that lands every group's int32 dot in its OWN
VMEM accumulator plane and applies all the scale rows ONCE, batched,
at k-tile flush. The choice is `m`'s (`_resolve_deferred`: deferred
for m > 64, classic for small-m decode where 2048-deep k-tiles matter
more), with an automatic fallback to the classic path when the extra
int32 planes don't fit the VMEM budget (`_DEFERRED_VMEM_BYTES`). The
`deferred=` keyword holds one variant against the other: the tests',
and the profile harness's `--only ab` mode at the bench geometries.

Streamed skinny-m grid: at m <= 64 the classic (m, n, k) grid is
WEIGHT-STREAMING bound — every grid cell re-pays a fixed
compiler-managed-BlockSpec cost to fetch its
qweight/zeros/scales blocks, and at tiny m that fixed cost dwarfs the
dot (the whole 3.5 GiB int4 matrix moves at ~430 GB/s effective
against an ~820 GB/s HBM floor). The `_stream_kernel` path therefore
flattens the (n, k) tile grid into ONE work-list dimension, keeps the
padded activation block resident in VMEM for the entire call, and
streams the weight tiles through an explicit double-buffered
(`APHRODITE_QMM_STREAM_PF`-deep) cross-cell `make_async_copy` ring
with per-slot DMA semaphores — cell w starts cell w+depth-1's
HBM->VMEM tile copies before waiting on its own, so the next tile's
DMA overlaps the current tile's dequant+dot across ALL cells (the
PR-2 ragged-attention prefetch-ring design applied to the weight
stream). Ring slots replace the per-cell double-buffered BlockSpec
blocks in the VMEM budget, so deeper k-tiles fit (up to 4096 vs the
classic 2048). Taken at m <= 64 (`_resolve_stream`; the `stream=`
keyword is the tests' and the profile harness's way to hold one grid
against the other). Composes with deferred rescale: the int32
group accumulators ride as kernel scratch and the scale rows still
apply once at k-flush.

What a group's int8 operand is made of (W4A8, GPTQ; PERF.md §5, PR
51): the kernels feed the MXU `code - zero` as int8, a quantization
group (`gs` rows of K) at a time. `_unpack_planes` makes it one code
to a 32-bit lane: eight shifted and masked nibble planes, a subtract,
a narrowing convert, four or more VPU operations a code, which at
decode rows outlasted the weight tile's DMA (a streamed call read
73-78% of its bytes' roofline, and 91% with the unpack taken out).
`_unpack_bytes` does the same integer arithmetic four codes to an
operation: two masks leave a word's even and odd nibbles in its four
bytes, one add of 128 - zero in every byte (`_bias_zeros`, made in the
XLA prologue in the place of the plain zeros: the same [G, 1, N] int32
array) and one flip of the bytes' top bits subtract the zero with no
borrow between bytes, and `pltpu.bitcast` reads the words as int8 rows
with no narrowing. The values are bit-equal; the ROW ORDER inside a
group is another (row 4i + b of a plane is byte b of word-row i: the
even columns 8i + 2b of the group, then the odd ones), and x's columns
are laid out to match in the prologue (`_permute_columns`,
`plane_permutation(byte_rows=True)`). 4-bit GPTQ words on the streamed
grid alone take it (`_resolve_unpack`): other widths do not split into
bytes this way, the W4A16 kernel's operand is bfloat16 (an int8 plane
would be widened again), AWQ packs along lanes, and on the compiler's
grid (prompt rows) the dots and the rescale bind, not the unpack,
while the byte order's split of odd from even columns of the INT8
rows costs XLA more than the kernel gains.

Round-7 closures of the two machine-flagged residuals (ROADMAP item
1): (1) the streamed grid's f32 accumulator is now TWO column-parity
planes — the run-final flush epilogue writes the parity plane while
the next column block initializes and accumulates the other, so the
k-run-boundary bubble ROOF003 flagged (flush + output write
serializing with the next run's first ring wait) is covered by the
ring like every other cell; (2) the FOLD001 activation-quantization
chain is folded out of the launchers — streamed a8 calls take the
RAW activation block and quantize it in the kernel prologue (x is
VMEM-resident for the whole call, so HBM never sees an int8 copy),
and the classic grids quantize through the fused one-pass
`_quant8_kernel` instead of the two-pass XLA reduce+elementwise
chain.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from aphrodite_tpu.common import flags
from aphrodite_tpu.common.logger import init_logger
from aphrodite_tpu.common.utils import note_kernel_path

logger = init_logger(__name__)


def _unpack_planes(q: jax.Array, bits: int) -> jax.Array:
    """[r, c] int32, pack along rows -> [r*pack, c] int32, PLANE order.

    Row j of the result is original (unpacked) row (j % r) * pack + j // r
    — i.e. planes of equal bit-shift stacked along sublanes. A sublane
    concatenation is layout-friendly on TPU; the natural-order reshape
    ([r, pack, c] -> [r*pack, c]) interleaves across sublanes and Mosaic
    lowers it to per-element shuffles (~100x slower, measured). The
    matmul wrapper compensates by permuting x's columns once in XLA.
    """
    pack = 32 // bits
    mask = (1 << bits) - 1
    planes = [
        jax.lax.bitwise_and(
            jax.lax.shift_right_logical(q, p * bits), mask)
        for p in range(pack)
    ]
    return jax.lax.concatenate(planes, 0)


# Byte-lane constants of `_unpack_bytes`, as the int32 they are held in.
_NIBBLE_OF_BYTES = np.int32(0x0F0F0F0F)
_SIGN_OF_BYTES = np.int32(0x80808080 - (1 << 32))


def _bias_zeros(z: jax.Array) -> jax.Array:
    """A 4-bit zero point z (1..16) as `_unpack_bytes` takes it: 128 - z
    in each of the word's four bytes (112..127, so no byte borrows)."""
    return _SIGN_OF_BYTES - z * 0x01010101


def _unpack_bytes(words: jax.Array, zbias: jax.Array) -> jax.Array:
    """[r, c] int32 GPTQ 4-bit words (8 codes of consecutive K rows a
    word) and the group's zeros as `_bias_zeros` made them, [1, c] ->
    [r*8, c] int8 `code - zero`: the MXU's operand, four codes to a
    32-bit lane operation.

    Two masks leave the even and the odd nibbles of a word in its four
    BYTES. Adding 128 - zero to every byte at once carries nothing from
    byte to byte (a code is 0..15, the sum 112..142), flipping each
    byte's top bit takes the 128 off again in two's complement, and
    `pltpu.bitcast` reads the [r, c] words as [4r, c] int8 with no
    narrowing: seven operations for a word's eight codes, where
    `_unpack_planes`, a subtract and a convert to int8 spend four or
    more a code, each code alone in a 32-bit lane. The values are the
    same, `(_unpack_planes(words, 4) - zero).astype(int8)`; the ROW
    ORDER differs and `plane_permutation(..., byte_rows=True)` states
    it: byte b of word-row i is row 4i + b of its plane (Mosaic's order
    and interpret mode's: four int8 rows share a sublane's word), the
    even nibbles' plane lies over the odd nibbles'. r a multiple of 8
    keeps the seam on an int8 tile (32 rows)."""
    shifted = jax.lax.shift_right_logical(words, 4)
    planes = [
        pltpu.bitcast(
            ((half & _NIBBLE_OF_BYTES) + zbias) ^ _SIGN_OF_BYTES,
            jnp.int8)
        for half in (words, shifted)
    ]
    return jax.lax.concatenate(planes, 0)


def plane_permutation(K: int, block_k: int, bits: int,
                      byte_rows: bool = False) -> np.ndarray:
    """Column permutation of x matching `_unpack_planes` row order:
    within each block_k-span, position j holds original column
    (j % r) * pack + j // r with r = block_k // pack.

    `byte_rows` gives `_unpack_bytes`' order instead (4-bit, block_k a
    quantisation group): position j < block_k / 2 holds the EVEN
    nibble 2b of word i, j = 4i + b, original column 8i + 2b; the
    second half the odd nibbles, 8i + 2b + 1."""
    pack = 32 // bits
    r = block_k // pack
    j = np.arange(block_k)
    if byte_rows:
        assert bits == 4
        half = j % (block_k // 2)
        within = half // 4 * 8 + half % 4 * 2 + j // (block_k // 2)
    else:
        within = (j % r) * pack + j // r
    blocks = np.arange(0, K, block_k)[:, None]
    return (blocks + within[None, :]).reshape(-1)


def _permute_columns(x: jax.Array, gs: int, pack: int,
                     byte_rows: bool) -> jax.Array:
    """x's columns in the row order of a group's unpacked codes
    (`plane_permutation` per group of `gs`, since the kernels unpack
    each group chunk separately). Both orders are blockwise transposes,
    which XLA lowers natively (an explicit index gather is ~100x slower
    here)."""
    m, K = x.shape
    if byte_rows:
        # [word, byte, even | odd] -> [even | odd, word, byte]. The
        # plain [gs / 2, 2] -> [2, gs / 2] is the same permutation and
        # costs a decode call's bfloat16 rows 4-14 us more on the chip
        # (PERF.md §6, PR 51: XLA lays a two-wide axis out again).
        return x.reshape(m, K // gs, gs // 8, 4, 2).transpose(
            0, 1, 4, 2, 3).reshape(m, K)
    return x.reshape(m, K // gs, gs // pack, pack).swapaxes(
        2, 3).reshape(m, K)


def _resolve_unpack(unpack, bits: int, streamed: bool) -> str:
    """How a W4A8 GPTQ call makes a group's int8 operand: "bytes"
    (`_unpack_bytes`) for 4-bit words on the streamed grid, "planes"
    (`_unpack_planes`, a subtract and a narrowing convert) for every
    other width and on the compiler's grid (the module docstring says
    why). Read off the call at trace time: its `bits`, and the grid its
    `m` takes. An explicit `unpack` is the tests' and
    `benchmarks/qmm_ab.py`'s way to hold one against the other on
    either grid."""
    if unpack is None:
        return "bytes" if bits == 4 and streamed else "planes"
    if unpack not in ("bytes", "planes") or \
            (unpack == "bytes" and bits != 4):
        raise ValueError(f"{unpack=} at {bits=}")
    return unpack


def _a8_operand(words: jax.Array, z: jax.Array, bits: int, unpack: str,
                ablate) -> jax.Array:
    """One group's int8 MXU operand, `code - zero` [gs, c], from its
    packed words [gs // pack, c] and its zero row z [1, c] (plain for
    "planes", `_bias_zeros` for "bytes"). `ablate == "unpack"` is
    `benchmarks/qmm_ab.py`'s arm: the words' own bytes read as int8 and
    stacked to the operand's height, wrong numbers at no VPU work."""
    if ablate == "unpack":
        raw = pltpu.bitcast(words, jnp.int8)
        return jax.lax.concatenate([raw] * (8 // bits), 0)
    if unpack == "bytes":
        return _unpack_bytes(words, z)
    return (_unpack_planes(words, bits) - z).astype(jnp.int8)


def _tile_mn(m: int, N: int, dtype, min_bn: int = 128,
             acc_planes: int = 1):
    """Shared M/N tile sizing for the dequant-matmul kernels:
    (block_m, block_n, padded_m), honoring the APHRODITE_QMM_BLOCK_M/N
    env knobs (A/B-tuned in round 2). min_bn is the kernel's smallest
    legal lane tile (AWQ's plane unpack needs 1024).

    Tiny m (decode at low batch) is grid-overhead bound — the kernel
    dequantizes the whole weight tile per grid cell regardless of m,
    and the ~5 us/cell fixed cost dominates (LATENCY_r03's 12.7 tok/s
    at bs=1 was mostly this); the remedy is DEEPER k tiles (_tile_k
    caps block_k at 1024 for every m — matmuls 77 -> 12 ms/step at
    m=16, round 4) while block_n stays capped at 2048.

    `acc_planes > 1` is the deferred-rescale W4A8 budget: the kernel
    holds that many EXTRA int32 accumulator planes in VMEM, so the
    default m/n caps halve (256 x 1024) to pay for them."""
    sublane = 16 if dtype == jnp.bfloat16 else 8
    bm_default = 512 if acc_planes <= 1 else 256
    bm_cap = flags.get_int("APHRODITE_QMM_BLOCK_M", default=bm_default)
    bm_cap = max(sublane, bm_cap // sublane * sublane)
    block_m = min(bm_cap, -(-m // sublane) * sublane)
    # Full-width lane tiles at every m: the round-2 A/B that capped
    # large-batch tiles at 1024 predates the W4A8 kernels (int8 tiles
    # take half the VMEM); re-measured round 4 at 2048 = +2% bench.
    bn_default = 2048 if acc_planes <= 1 else 1024
    bn_cap = flags.get_int("APHRODITE_QMM_BLOCK_N") or bn_default
    block_n = max((bn for bn in (2048, 1024, 512, 256, 128)
                   if N % bn == 0), default=0)
    if block_n < min_bn:
        raise ValueError(f"{N=} must be a multiple of {min_bn}")
    while block_n > min_bn and (block_n > bn_cap or N % block_n != 0):
        block_n //= 2           # keep N % block_n == 0 under any cap
    padded_m = -(-m // block_m) * block_m
    return block_m, block_n, padded_m


def _tile_k(K: int, gs: int, cap: int = 0) -> int:
    """K tile: block_k spans several quant groups, capped at 1024 (512
    for the affine/LUT kernels) at EVERY m — the round-4 A/B showed the
    deep tile wins at batch 512 too, not just small m (commit f34a566),
    so there is no m-dependent branch here."""
    if not cap:
        # 1024 at every m (round-4 A/B: +2% bench over 512 at batch
        # 512 — fewer grid cells beats the extra VMEM).
        cap = 1024
    cap = flags.get_int("APHRODITE_QMM_BLOCK_K") or cap
    block_k = gs
    while block_k < cap and K % (block_k * 2) == 0:
        block_k *= 2
    return block_k


# Deferred-rescale W4A8 selection (see the module docstring). The k
# tile caps at 512 so the int32 plane count stays at <= 4 for gs=128.
_DEFERRED_K_CAP = 512


def _resolve_deferred(deferred, m: int) -> bool:
    """Selector for the deferred-rescale W4A8 kernels. An explicit
    `deferred` (the tests' and the profile harness's way to hold one
    kernel against the other) wins; otherwise `m` decides: deferred at
    batch/prefill geometries (m > 64) where the per-group scale FMAs
    gate the MXU, classic at small-m decode where the 2048-deep
    k-tiles' grid-cell savings dominate."""
    if deferred is not None:
        return bool(deferred)
    return m > 64


def _deferred_fits(block_m: int, block_n: int, gpt: int) -> bool:
    """Whether the deferred path's accumulators (gpt int32 planes plus
    the f32 plane) fit the scoped-VMEM budget next to the streamed
    x/weight/zero/scale blocks; outside it the wrappers silently fall
    back to the classic kernel."""
    return (gpt * 4 + 4) * block_m * block_n <= _DEFERRED_VMEM_BYTES


# ------------------------------------------ streamed skinny-m path --
# Selection + tile policy for the work-list/DMA-ring grid (see the
# module docstring). _STREAM_K_CAP is deeper than the classic 2048:
# ring slots replace the compiler's per-cell double-buffered weight
# blocks, so the k-tile VMEM budget roughly doubles.
_STREAM_M_MAX = 64
_STREAM_K_CAP = 4096
_STREAM_DEF_K_CAP = 1024     # deferred: int32 planes bound the k depth

# Whole-kernel scoped-VMEM budget for the _clamp_k_vmem pre-check
# (mirrors _deferred_fits, but covers the full tile set: a
# block_k=4096 sweep point once failed to COMPILE instead of clamping).
_QMM_VMEM_BYTES = 16 << 20

# What the deferred-rescale accumulator planes may take of it
# (_deferred_fits).
_DEFERRED_VMEM_BYTES = 8 << 20


def _resolve_stream(stream, m: int) -> bool:
    """Selector for the streamed skinny-m grid: an explicit `stream`
    (profile harness / tests) wins; otherwise `m` decides: the
    streamed grid at m <= 64 (decode and bs=1 bursts), the compiler's
    above."""
    if stream is not None:
        return bool(stream)
    return m <= _STREAM_M_MAX


def _stream_pf() -> int:
    """Weight-DMA ring depth (VMEM tile slots), read from
    APHRODITE_QMM_STREAM_PF at CALL time. The flag is registered
    non-strict with minimum 2, so a malformed or too-small value warns
    and falls back to the default double buffer instead of killing the
    call (let alone the import)."""
    return max(2, flags.get_int("APHRODITE_QMM_STREAM_PF"))


def _cell_bytes(block_k: int, *, layout: str, block_m: int,
                block_n: int, gs: int, pack: int, x_bytes: int,
                s_bytes: int, K: int, stream_slots: int,
                deferred: bool, a16: bool) -> int:
    """Approximate per-cell VMEM footprint of one quant-matmul kernel
    at a candidate block_k — the _clamp_k_vmem cost model. Classic
    grid: compiler-managed input blocks count twice (double
    buffering); streamed grid: the explicit ring replaces the weight
    blocks, x is resident whole, the f32 accumulator is TWO
    column-parity planes (the double-buffered flush), and a8 calls
    additionally hold the in-kernel-quantized int8 copy of x plus the
    row-scale plane."""
    gpt = block_k // gs
    if layout == "awq":
        qw = block_k * (block_n // 8) * 4
        temp = block_k * block_n * 4          # w_pm int32 plane tile
    else:
        qw = (block_k // pack) * block_n * 4
        # a16 materializes the dequantized weight tile; a8 only a
        # per-group unpack transient.
        temp = block_k * block_n * x_bytes if a16 \
            else gs * block_n * 4
    zs = gpt * block_n * (4 + (4 if stream_slots else s_bytes))
    planes = gpt * block_m * block_n * 4 if deferred else 0
    if stream_slots:
        acc = 2 * block_m * block_n * 4       # parity planes
        quant = (block_m * K + block_m * 128 * 4) if not a16 else 0
        return (stream_slots * (qw + zs) + 2 * block_m * K * x_bytes +
                acc + planes + temp + quant)
    acc = block_m * block_n * 4
    return 2 * (block_m * block_k * x_bytes + qw + zs) + acc + \
        planes + temp


def _clamp_k_vmem(block_k: int, gs: int, cell_bytes, tag: str) -> int:
    """Step block_k down (halving — stays a multiple of gs and a
    divisor of K, since _tile_k built it by doubling from gs) until
    the tile set fits the scoped-VMEM budget. The runtime mirror of
    aphrocheck's VMEM001: an oversized APHRODITE_QMM_BLOCK_K now
    clamps with a debug log instead of failing the Mosaic compile."""
    clamped = block_k
    while clamped > gs and cell_bytes(clamped) > _QMM_VMEM_BYTES:
        clamped //= 2
    if clamped != block_k:
        logger.debug(
            "quant_matmul %s: block_k=%d tile set exceeds the "
            "%d MiB VMEM budget; clamped to %d", tag, block_k,
            _QMM_VMEM_BYTES >> 20, clamped)
    return clamped


def _stream_kernel(*refs, layout: str, bits: int, k_tiles: int,
                   n_tiles: int, group_size: int, n_slots: int,
                   a8: bool, deferred: bool, unpack: str = "planes",
                   ablate=None):
    """One work item w = n * k_tiles + k of the streamed skinny-m
    grid: wait on this item's weight-tile DMAs (started n_slots-1
    cells ago by the ring), start the item n_slots-1 ahead, then
    dequant+dot against the RESIDENT activation block. k is the inner
    run: the f32 accumulator persists in scratch across a column
    block's k items, slot-indexed by COLUMN PARITY — the plane for
    column n+1 is initialized and accumulated while column n's flush
    epilogue + output write are still draining, so the run-boundary
    flush no longer serializes with the next run's first ring wait
    (the ROOF003 k-run bubble; parity needed ~620 GB/s effective vs
    the single-plane ~560). Output is written at the last k — the out
    index map revisits the same block for the whole run.

    a8 calls take the RAW activation block and quantize it in the
    w == 0 prologue (per-row absmax over the full resident K — the
    row scale is permutation-invariant, so quantizing the permuted
    block equals permuting the quantized block): x8 and the row
    scales live in scratch for the whole call and HBM never sees an
    int8 activation copy (the FOLD001 fold — Zen-Attention applied
    to the quantization chain).

    The ring protocol is the ragged-attention cross-cell prefetch
    applied to weights: cell 0 seeds the first n_slots items' copies;
    every later cell starts item w + n_slots - 1 (landing in the slot
    cell w - 1 just vacated, the deepest safe prefetch for the slot
    count); every started copy is waited by its consuming cell, so
    nothing stays in flight past the kernel."""
    refs = list(refs)
    x_ref = refs.pop(0)         # [k_tiles, block_m, block_k] resident
    qw_hbm, z_hbm, s_hbm, o_ref = refs[:4]
    qw_ring, z_ring, s_ring, sems, acc_ref = refs[4:9]
    refs = refs[9:]
    x8_scr = refs.pop(0) if a8 else None   # [k_tiles, block_m, block_k]
    xs_scr = refs.pop(0) if a8 else None   # [block_m, 128] row scales
    g32_ref = refs.pop(0) if deferred else None

    w = pl.program_id(0)
    total = n_tiles * k_tiles
    k = jax.lax.rem(w, k_tiles)
    # Column parity selects this run's accumulator plane (the flushed
    # plane of column n stays untouched while column n+1 accumulates).
    par = jax.lax.rem(w // k_tiles, 2)

    gs = group_size
    pack = 32 // bits
    rpg = gs // pack                  # packed rows per group (gptq)
    gpt = z_ring.shape[1]             # quant groups per k-tile
    block_n = o_ref.shape[1]
    qw_rows, qw_cols = qw_ring.shape[1], qw_ring.shape[2]

    def item_dmas(n2, k2, slot2):
        # One work item's three tile copies, issued back-to-back so
        # the DMA engine overlaps them (K+V-style, PR 2).
        return [
            pltpu.make_async_copy(
                qw_hbm.at[pl.ds(k2 * qw_rows, qw_rows),
                          pl.ds(n2 * qw_cols, qw_cols)],
                qw_ring.at[slot2], sems.at[slot2, 0]),
            pltpu.make_async_copy(
                z_hbm.at[pl.ds(k2 * gpt, gpt), :,
                         pl.ds(n2 * block_n, block_n)],
                z_ring.at[slot2], sems.at[slot2, 1]),
            pltpu.make_async_copy(
                s_hbm.at[pl.ds(k2 * gpt, gpt), :,
                         pl.ds(n2 * block_n, block_n)],
                s_ring.at[slot2], sems.at[slot2, 2]),
        ]

    def start_item(n2, k2, slot2):
        for dma in item_dmas(n2, k2, slot2):
            dma.start()

    @pl.when(w == 0)
    def _seed():
        # Cells 1..n_slots-1 have no predecessor far enough back to
        # start their loads; cell 0 seeds them (static unroll).
        for s0 in range(min(n_slots, total)):
            start_item(s0 // k_tiles, s0 % k_tiles, s0 % n_slots)

    @pl.when((w >= 1) & (w + (n_slots - 1) < total))
    def _prefetch():
        nxt = w + (n_slots - 1)
        start_item(nxt // k_tiles, jax.lax.rem(nxt, k_tiles),
                   jax.lax.rem(nxt, n_slots))

    if a8:
        @pl.when(w == 0)
        def _quantize():
            # Folded activation quantization: per-row absmax over the
            # whole resident block, then div/round/clip/cast into the
            # int8 scratch — all VPU time under the first item's
            # weight-DMA wait. Scratch persists across every cell.
            absmax = jnp.max(jnp.abs(x_ref[0].astype(jnp.float32)),
                             axis=1, keepdims=True)
            for kt in range(1, k_tiles):
                absmax = jnp.maximum(
                    absmax,
                    jnp.max(jnp.abs(x_ref[kt].astype(jnp.float32)),
                            axis=1, keepdims=True))
            xs = jnp.maximum(absmax, 1e-8) / 127.0       # [block_m, 1]
            xs_scr[...] = jnp.broadcast_to(xs, xs_scr.shape)
            for kt in range(k_tiles):
                x8_scr[kt] = jnp.clip(
                    jnp.round(x_ref[kt].astype(jnp.float32) / xs),
                    -127, 127).astype(jnp.int8)

    slot = jax.lax.rem(w, n_slots)
    for dma in item_dmas(w // k_tiles, k, slot):
        dma.wait()

    @pl.when(k == 0)
    def _init():
        acc_ref[par] = jnp.zeros(acc_ref.shape[1:], acc_ref.dtype)

    qw_t = qw_ring[slot]              # [qw_rows, qw_cols] int32
    if layout == "awq":
        planes = [
            jax.lax.bitwise_and(
                jax.lax.shift_right_logical(qw_t, 4 * p), 0xF)
            for p in range(8)
        ]
        w_pm = jax.lax.concatenate(planes, 1)     # [block_k, block_n]

    def w_codes(g):
        """Group g's unpacked integer codes [gs, block_n] (plane-major
        rows for gptq, plane-major lanes for awq — the same layouts
        the classic kernels produce)."""
        if layout == "awq":
            return w_pm[g * gs:(g + 1) * gs]
        return _unpack_planes(qw_t[g * rpg:(g + 1) * rpg], bits)

    def w_int8(g):
        """Group g's int8 operand, `code - zero` (a8)."""
        if layout == "awq":
            return (w_codes(g) - z_ring[slot, g]).astype(jnp.int8)
        return _a8_operand(qw_t[g * rpg:(g + 1) * rpg],
                           z_ring[slot, g], bits, unpack, ablate)

    x_tile = x8_scr[k] if a8 else x_ref[k]    # [block_m, block_k]
    if a8 and deferred:
        for g in range(gpt):
            g32_ref[g] = jax.lax.dot_general(
                x_tile[:, g * gs:(g + 1) * gs], w_int8(g),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)
        if ablate == "rescale":
            acc_ref[par] = pltpu.bitcast(g32_ref[0], jnp.float32)
        else:
            acc_ref[par] += jnp.sum(
                g32_ref[...].astype(jnp.float32) *
                s_ring[slot].astype(jnp.float32), axis=0)
    elif a8:
        for g in range(gpt):
            d = jax.lax.dot_general(
                x_tile[:, g * gs:(g + 1) * gs], w_int8(g),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)
            if ablate == "rescale":
                acc_ref[par] = pltpu.bitcast(d, jnp.float32)
            else:
                acc_ref[par] += d.astype(jnp.float32) * \
                    s_ring[slot, g].astype(jnp.float32)
    else:
        chunks = []
        for g in range(gpt):
            z = z_ring[slot, g]                   # [1, block_n] int32
            s = s_ring[slot, g].astype(jnp.float32)
            chunks.append(
                ((w_codes(g) - z).astype(jnp.float32) *
                 s).astype(x_tile.dtype))
        wt = chunks[0] if gpt == 1 else jax.lax.concatenate(chunks, 0)
        acc_ref[par] += jnp.dot(x_tile, wt,
                                preferred_element_type=jnp.float32)

    @pl.when(k == k_tiles - 1)
    def _flush():
        # Run-boundary epilogue off the PARITY plane: the next column
        # block initializes and accumulates the other plane while this
        # write drains, so no ring wait serializes behind it.
        if a8:
            o_ref[...] = (acc_ref[par] *
                          xs_scr[:, :1]).astype(o_ref.dtype)
        else:
            o_ref[...] = acc_ref[par].astype(o_ref.dtype)


def _stream_call(x, qweight, z3, s3, *, layout: str, bits: int,
                 gs: int, block_m: int, block_n: int, block_k: int,
                 padded_m: int, N: int, n_slots: int, a8: bool,
                 deferred: bool, out_dtype, interpret: bool,
                 unpack: str = "planes", ablate=None):
    """Launch _stream_kernel: x [padded_m, K] (already permuted and
    padded; RAW model dtype even for a8 — the kernel quantizes it in
    its prologue) goes resident as [k_tiles, block_m, block_k];
    qweight and the [G, 1, N] zero/scale rows stay in HBM
    (memory_space=HBM) and stream through the ring. The f32
    accumulator is two column-parity planes (the ROOF003
    double-buffered flush). Returns [padded_m, N] (plane-major
    columns for awq — callers un-permute as usual)."""
    if padded_m != block_m:
        raise ValueError(
            f"streamed quant-matmul needs a single m tile: padded m "
            f"{padded_m} != block_m {block_m} (use the classic grid)")
    K = x.shape[1]
    k_tiles = K // block_k
    n_tiles = N // block_n
    gpt = block_k // gs
    if layout == "awq":
        qw_rows, qw_cols = block_k, block_n // 8
    else:
        qw_rows, qw_cols = block_k // (32 // bits), block_n

    x_t = x.reshape(block_m, k_tiles, block_k).swapaxes(0, 1)
    # Scale rows ride the ring as f32: a bf16 [G, 1, N] array is tiled
    # (2, 128) in HBM with its unit dim padded to 2, and Mosaic refuses
    # a 1-deep DMA slice of that. The padded bf16 rows would move the
    # same bytes, so f32 costs the ring nothing.
    s3 = s3.astype(jnp.float32)
    in_specs = [
        pl.BlockSpec((k_tiles, block_m, block_k),
                     lambda w: (0, 0, 0)),
        pl.BlockSpec(memory_space=pltpu.HBM),
        pl.BlockSpec(memory_space=pltpu.HBM),
        pl.BlockSpec(memory_space=pltpu.HBM),
    ]
    inputs = [x_t, qweight, z3, s3]

    scratch = [
        pltpu.VMEM((n_slots, qw_rows, qw_cols), jnp.int32),
        pltpu.VMEM((n_slots, gpt, 1, block_n), jnp.int32),
        pltpu.VMEM((n_slots, gpt, 1, block_n), s3.dtype),
        pltpu.SemaphoreType.DMA((n_slots, 3)),
        pltpu.VMEM((2, block_m, block_n), jnp.float32),
    ]
    if a8:
        scratch.extend([
            pltpu.VMEM((k_tiles, block_m, block_k), jnp.int8),
            pltpu.VMEM((block_m, 128), jnp.float32),
        ])
    if deferred:
        scratch.append(
            pltpu.VMEM((gpt, block_m, block_n), jnp.int32))

    return pl.pallas_call(
        functools.partial(
            _stream_kernel, layout=layout, bits=bits,
            k_tiles=k_tiles, n_tiles=n_tiles, group_size=gs,
            n_slots=n_slots, a8=a8, deferred=deferred, unpack=unpack,
            ablate=ablate),
        grid=(n_tiles * k_tiles,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_m, block_n),
                               lambda w: (0, w // k_tiles)),
        out_shape=jax.ShapeDtypeStruct((padded_m, N), out_dtype),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*inputs)


def _kernel(x_ref, qw_ref, z_ref, s_ref, o_ref, acc_ref, *,
            bits: int, k_tiles: int, group_size: int):
    """One (m, n, k) grid step: dequant a [block_k, block_n] weight tile
    from packed int words and accumulate x-tile @ w-tile.

    block_k may span several quantization groups; each group's 128-row
    (= group_size-row) chunk is unpacked plane-wise and scaled with its
    own (z, s) row, then the chunks concatenate along sublanes into the
    full tile — all layout-friendly ops (no cross-sublane reshapes)."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pack = 32 // bits
    rows_per_group = group_size // pack
    n_groups = z_ref.shape[0]
    chunks = []
    for g in range(n_groups):
        q = _unpack_planes(
            qw_ref[g * rows_per_group:(g + 1) * rows_per_group], bits)
        z = z_ref[g]                                   # [1, bn] int32
        s = s_ref[g].astype(jnp.float32)               # [1, bn]
        chunks.append(
            ((q - z).astype(jnp.float32) * s).astype(x_ref.dtype))
    w = chunks[0] if n_groups == 1 else jax.lax.concatenate(chunks, 0)
    acc_ref[...] += jnp.dot(x_ref[...], w,
                            preferred_element_type=jnp.float32)

    @pl.when(k == k_tiles - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def gptq_supported(in_features: int, out_features: int, bits: int,
                   group_size: int, desc_act: bool) -> bool:
    """Shapes this kernel handles; everything else uses the XLA path."""
    if desc_act or bits not in (4, 8):
        return False
    gs = group_size if group_size != -1 else in_features
    pack = 32 // bits
    return (in_features % gs == 0 and gs % pack == 0 and gs >= 128 and
            gs <= 1024 and out_features % 128 == 0)


def _gptq_prologue(x, qzeros, scales, N: int, bits: int, gs: int,
                   tile_dtype, k_cap: int = 0, acc_planes: int = 1,
                   stream_slots: int = 0, deferred: bool = False,
                   a8: bool = False, byte_rows: bool = False):
    """Shared GPTQ wrapper prologue (one copy of the layout logic for
    the W4A16 and W4A8 kernels): plane-permute and pad x, unpack the
    zero points (+1, AutoGPTQ convention), lift scales to the [G, 1, N]
    block shape, and size the tiles. Returns
    (x, z_all, scales3, tiles) with tiles = (block_m, block_n, block_k,
    padded_m, grid, groups_per_tile, k_tiles). stream_slots > 0 sizes
    for the streamed work-list grid (ring slots instead of per-cell
    weight blocks); deferred adds the int32 accumulator planes to the
    VMEM pre-check. `byte_rows` (the W4A8 wrappers at 4 bits) lays x's
    columns and the zeros out for `_unpack_bytes`: the same arrays of
    the same shapes in another order and another encoding."""
    m, K = x.shape
    pack = 32 // bits
    # Tile sizes: per-grid-step overhead (~5us) dominates when tiles
    # are small, so spend VMEM on big tiles — block_k spans several
    # quant groups (the kernels dequant each group chunk separately).
    block_k = _tile_k(K, gs, cap=k_cap)
    block_m, block_n, padded_m = _tile_mn(m, N, tile_dtype,
                                          acc_planes=acc_planes)
    block_k = _clamp_k_vmem(
        block_k, gs,
        functools.partial(
            _cell_bytes, layout="gptq", block_m=block_m,
            block_n=block_n, gs=gs, pack=pack,
            x_bytes=x.dtype.itemsize, s_bytes=scales.dtype.itemsize,
            K=K, stream_slots=stream_slots, deferred=deferred,
            a16=x.dtype != jnp.int8 and not a8),
        tag="gptq")
    x = _permute_columns(x, gs, pack, byte_rows)
    if padded_m != m:
        x = jnp.pad(x, ((0, padded_m - m), (0, 0)))
    k_tiles = K // block_k
    groups_per_tile = block_k // gs
    grid = (padded_m // block_m, N // block_n, k_tiles)
    # Zeros are unpacked once in the XLA prologue ([G, N] is
    # ~weights/gs — trivial traffic) so the kernel's z block is a plain
    # lane slice; the [G, 1, N] shape keeps the per-group row block
    # legal (a block dim of 1 must equal the array dim).
    shifts = (jnp.arange(pack, dtype=jnp.int32) * bits)[None, None, :]
    z_all = jax.lax.bitwise_and(
        jax.lax.shift_right_logical(qzeros[:, :, None], shifts),
        (1 << bits) - 1).reshape(qzeros.shape[0], 1, N) + 1
    if byte_rows:
        z_all = _bias_zeros(z_all)
    scales3 = scales[:, None, :]
    tiles = (block_m, block_n, block_k, padded_m, grid,
             groups_per_tile, k_tiles)
    return x, z_all, scales3, tiles


@functools.partial(jax.jit,
                   static_argnames=("bits", "group_size", "interpret",
                                    "stream"))
def gptq_matmul(x: jax.Array, qweight: jax.Array, qzeros: jax.Array,
                scales: jax.Array, *, bits: int, group_size: int,
                interpret: bool = False, stream=None) -> jax.Array:
    """y[m, N] = dequant(qweight, qzeros, scales) matmul for 2-D x[m, K].

    block_k == group_size; m is padded to the dtype sublane multiple and
    tiled at <=512 rows; N tiled at 512 lanes (or N if smaller).

    `stream` pins the skinny-m work-list/DMA-ring grid (None =
    taken at m <= 64 — see _resolve_stream)."""
    m, K = x.shape
    N = qweight.shape[1]
    gs = group_size if group_size != -1 else K
    pack = 32 // bits
    use_stream = _resolve_stream(stream, m)
    n_slots = _stream_pf() if use_stream else 0
    x, z_all, scales3, tiles = _gptq_prologue(
        x, qzeros, scales, N, bits, gs, x.dtype,
        k_cap=_STREAM_K_CAP if use_stream else 0,
        stream_slots=n_slots)
    (block_m, block_n, block_k, padded_m, grid,
     groups_per_tile, k_tiles) = tiles

    if use_stream:
        out = _stream_call(
            x, qweight, z_all, scales3, layout="gptq",
            bits=bits, gs=gs, block_m=block_m, block_n=block_n,
            block_k=block_k, padded_m=padded_m, N=N,
            n_slots=n_slots, a8=False, deferred=False,
            out_dtype=x.dtype, interpret=interpret)
        return out[:m] if padded_m != m else out

    out = pl.pallas_call(
        functools.partial(_kernel, bits=bits, k_tiles=k_tiles,
                          group_size=gs),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, n, k: (i, k)),
            pl.BlockSpec((block_k // pack, block_n),
                         lambda i, n, k: (k, n)),
            pl.BlockSpec((groups_per_tile, 1, block_n),
                         lambda i, n, k: (k, 0, n)),
            pl.BlockSpec((groups_per_tile, 1, block_n),
                         lambda i, n, k: (k, 0, n)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n),
                               lambda i, n, k: (i, n)),
        out_shape=jax.ShapeDtypeStruct((padded_m, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, qweight, z_all, scales3)
    return out[:m] if padded_m != m else out


# --------------------------------------------------------------- AWQ --

def _awq_kernel(x_ref, qw_ref, z_ref, s_ref, o_ref, acc_ref, *,
                k_tiles: int, group_size: int):
    """One (m, n, k) grid step for the AWQ layout: qw packs 8 output
    columns per int32 word; nibble planes unpack along LANES into
    tile-local plane-major column order (plane p occupies lanes
    [p*bn/8, (p+1)*bn/8)). z/s arrive pre-arranged in the same order,
    so dequant is elementwise; the wrapper un-permutes the output."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    gs = group_size
    n_groups = z_ref.shape[0]
    qw = qw_ref[...]                                  # [bk, bn/8] int32
    planes = [
        jax.lax.bitwise_and(jax.lax.shift_right_logical(qw, 4 * p), 0xF)
        for p in range(8)
    ]
    w_pm = jax.lax.concatenate(planes, 1)             # [bk, bn] int32
    chunks = []
    for g in range(n_groups):
        q_g = w_pm[g * gs:(g + 1) * gs]
        z = z_ref[g]                                  # [1, bn] int32
        s = s_ref[g].astype(jnp.float32)              # [1, bn]
        chunks.append(
            ((q_g - z).astype(jnp.float32) * s).astype(x_ref.dtype))
    w = chunks[0] if n_groups == 1 else jax.lax.concatenate(chunks, 0)
    acc_ref[...] += jnp.dot(x_ref[...], w,
                            preferred_element_type=jnp.float32)

    @pl.when(k == k_tiles - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def awq_supported(in_features: int, out_features: int,
                  group_size: int) -> bool:
    """Shapes the fused AWQ kernel handles; others use the XLA path."""
    return (in_features % group_size == 0 and
            128 <= group_size <= 1024 and
            out_features % 1024 == 0)    # block_n >= 1024 keeps the
                                         # plane width lane-aligned


def _quantize_activations_int8(x):
    """Per-row symmetric int8 activation quantization — the jnp
    REFERENCE path (non-TPU fallback and the parity oracle for the
    fused forms). Returns (x8 [m, K] int8, xs [m, 1] f32). Hot paths
    never run this chain: the streamed kernels quantize their
    resident block in the kernel prologue and the classic grids go
    through the fused one-pass `_quant8_call` kernel below (the
    FOLD001 fold — the XLA chain paid a second full-width activation
    read between the absmax reduce and the elementwise pass)."""
    absmax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=1,
                     keepdims=True)
    xs = jnp.maximum(absmax, 1e-8) / 127.0
    x8 = jnp.clip(jnp.round(x.astype(jnp.float32) / xs), -127,
                  127).astype(jnp.int8)
    return x8, xs


def _quant8_kernel(x_ref, x8_ref, xs_ref):
    """Fused one-pass activation quantization tile: absmax-reduce and
    div/round/clip/cast entirely in VMEM — HBM reads the raw rows
    once and writes only the int8 copy plus the [rows, 1] scales."""
    xf = x_ref[...].astype(jnp.float32)
    absmax = jnp.max(jnp.abs(xf), axis=1, keepdims=True)
    xs = jnp.maximum(absmax, 1e-8) / 127.0
    x8_ref[...] = jnp.clip(jnp.round(xf / xs), -127,
                           127).astype(jnp.int8)
    xs_ref[...] = xs


def _quant8_call(x, interpret: bool):
    """Launch _quant8_kernel over row blocks (whole-K rows per cell —
    the per-row reduce needs the full contraction width resident)."""
    m, K = x.shape
    sublane = 32                # the int8 output's minimum row tile
    block_m = min(256, -(-m // sublane) * sublane)
    # Scoped VMEM per row: the input block and the int8 output block
    # are both double-buffered, beside one f32 working copy.
    row_bytes = K * (2 * x.dtype.itemsize + 2 + 4)
    while block_m > sublane and block_m * row_bytes > _QMM_VMEM_BYTES:
        block_m = max(sublane, block_m // 2 // sublane * sublane)
    padded_m = -(-m // block_m) * block_m
    if padded_m != m:
        x = jnp.pad(x, ((0, padded_m - m), (0, 0)))
    x8, xs = pl.pallas_call(
        _quant8_kernel,
        grid=(padded_m // block_m,),
        in_specs=[pl.BlockSpec((block_m, K), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((block_m, K), lambda i: (i, 0)),
                   pl.BlockSpec((block_m, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((padded_m, K), jnp.int8),
                   jax.ShapeDtypeStruct((padded_m, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x)
    return (x8[:m], xs[:m]) if padded_m != m else (x8, xs)


def quantize_activations_int8(x, *, interpret: bool = False):
    """Per-row symmetric int8 activation quantization for the classic
    quant-matmul grids: the fused one-pass kernel on TPU (and under
    interpret), the jnp reference chain elsewhere."""
    if interpret or jax.default_backend() == "tpu":
        return _quant8_call(x, interpret)
    return _quantize_activations_int8(x)


def _awq_zs_plane_major(qzeros, scales, N, n_tiles, block_n, G):
    """Arrange z and s into the kernels' tile-local plane-major column
    order (ONE copy of the permutation convention for the W4A16 and
    W4A8 AWQ wrappers): natural column c = t*bn + 8j + e sits at
    t*bn + AWQ_ORDER[e]*(bn/8) + j, built with reshape/transpose
    (XLA-native). Returns (z_pm [G,1,N], s_pm [G,1,N], order)."""
    from aphrodite_tpu.modeling.layers.quantization.awq import (
        AWQ_ORDER, _unpack_awq)
    inv = np.argsort(np.asarray(AWQ_ORDER))

    def to_plane_major(a):
        t = a.reshape(*a.shape[:-1], n_tiles, block_n // 8, 8)
        t = jnp.moveaxis(t[..., inv], -1, -2)     # [.., 8, bn/8]
        return t.reshape(*a.shape[:-1], N)

    z_nat = _unpack_awq(qzeros)                   # [G, N] natural
    z_pm = to_plane_major(z_nat).reshape(G, 1, N)
    s_pm = to_plane_major(scales).reshape(G, 1, N)
    return z_pm, s_pm, np.asarray(AWQ_ORDER)


def _awq_unpermute(y, padded_m, N, n_tiles, block_n, order):
    """Inverse of the kernels' plane-major output column order."""
    y = y.reshape(padded_m, n_tiles, 8, block_n // 8)
    y = jnp.moveaxis(y, -2, -1)[..., order]       # [m, t, bn/8, 8]
    return y.reshape(padded_m, N)


@functools.partial(jax.jit,
                   static_argnames=("group_size", "interpret",
                                    "stream"))
def awq_matmul(x: jax.Array, qweight: jax.Array, qzeros: jax.Array,
               scales: jax.Array, *, group_size: int,
               interpret: bool = False, stream=None) -> jax.Array:
    """y[m, N] = x[m, K] @ dequant(qweight, qzeros, scales) for the AWQ
    int4 layout (qweight [K, N/8] int32, 8 interleaved nibbles along N;
    qzeros [G, N/8] same packing; scales [G, N]; w = (q - z) * s).

    `stream` pins the skinny-m work-list/DMA-ring grid (same contract
    as gptq_matmul)."""
    m, K = x.shape
    N = qweight.shape[1] * 8
    gs = group_size
    G = K // gs

    use_stream = _resolve_stream(stream, m)
    n_slots = _stream_pf() if use_stream else 0
    block_k = _tile_k(K, gs,
                      cap=_STREAM_K_CAP if use_stream else 0)
    # NOTE: pre-refactor AWQ defaulted block_n to 2048 at every m; the
    # shared sizing caps it at 1024 for block_m >= 512. The 0.93x
    # vs-baseline bench row (BENCH notes) was measured WITH the shared
    # sizing, so this is the tuned configuration of record;
    # APHRODITE_QMM_BLOCK_N=2048 restores the old tiling for A/B runs.
    block_m, block_n, padded_m = _tile_mn(m, N, x.dtype, min_bn=1024)
    block_k = _clamp_k_vmem(
        block_k, gs,
        functools.partial(
            _cell_bytes, layout="awq", block_m=block_m,
            block_n=block_n, gs=gs, pack=8,
            x_bytes=x.dtype.itemsize, s_bytes=scales.dtype.itemsize,
            K=K, stream_slots=n_slots, deferred=False, a16=True),
        tag="awq")
    if padded_m != m:
        x = jnp.pad(x, ((0, padded_m - m), (0, 0)))

    k_tiles = K // block_k
    groups_per_tile = block_k // gs
    n_tiles = N // block_n
    grid = (padded_m // block_m, n_tiles, k_tiles)
    z_pm, s_pm, order = _awq_zs_plane_major(qzeros, scales, N,
                                            n_tiles, block_n, G)

    if use_stream:
        out_pm = _stream_call(
            x, qweight, z_pm, s_pm, layout="awq", bits=4,
            gs=gs, block_m=block_m, block_n=block_n, block_k=block_k,
            padded_m=padded_m, N=N, n_slots=n_slots, a8=False,
            deferred=False, out_dtype=x.dtype, interpret=interpret)
        y = _awq_unpermute(out_pm, padded_m, N, n_tiles, block_n,
                           order)
        return y[:m] if padded_m != m else y

    out_pm = pl.pallas_call(
        functools.partial(_awq_kernel, k_tiles=k_tiles, group_size=gs),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, n, k: (i, k)),
            pl.BlockSpec((block_k, block_n // 8),
                         lambda i, n, k: (k, n)),
            pl.BlockSpec((groups_per_tile, 1, block_n),
                         lambda i, n, k: (k, 0, n)),
            pl.BlockSpec((groups_per_tile, 1, block_n),
                         lambda i, n, k: (k, 0, n)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n),
                               lambda i, n, k: (i, n)),
        out_shape=jax.ShapeDtypeStruct((padded_m, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, qweight, z_pm, s_pm)

    y = _awq_unpermute(out_pm, padded_m, N, n_tiles, block_n, order)
    return y[:m] if padded_m != m else y


def _awq_a8_kernel(x_ref, xs_ref, qw_ref, z_ref, s_ref, o_ref,
                   acc_ref, *, k_tiles: int, group_size: int):
    """W4A8 variant of _awq_kernel: int8 activations into the MXU int8
    mode; the zero-point subtraction stays in integers (exact), the
    int32 group partials rescale into the f32 accumulator (same scheme
    as _gptq_a8_kernel)."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    gs = group_size
    n_groups = z_ref.shape[0]
    qw = qw_ref[...]                                  # [bk, bn/8] int32
    planes = [
        jax.lax.bitwise_and(jax.lax.shift_right_logical(qw, 4 * p), 0xF)
        for p in range(8)
    ]
    w_pm = jax.lax.concatenate(planes, 1)             # [bk, bn] int32
    for g in range(n_groups):
        w8 = (w_pm[g * gs:(g + 1) * gs] - z_ref[g]).astype(jnp.int8)
        x8 = x_ref[:, g * gs:(g + 1) * gs]
        d = jax.lax.dot_general(x8, w8, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.int32)
        acc_ref[...] += d.astype(jnp.float32) * \
            s_ref[g].astype(jnp.float32)

    @pl.when(k == k_tiles - 1)
    def _flush():
        o_ref[...] = (acc_ref[...] *
                      xs_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def _awq_a8_deferred_kernel(x_ref, xs_ref, qw_ref, z_ref, s_ref, o_ref,
                            acc_ref, g32_ref, *, k_tiles: int,
                            group_size: int):
    """Deferred-rescale W4A8 AWQ tile: the lane-plane unpack of
    `_awq_a8_kernel` with the `_gptq_a8_deferred_kernel` accumulation
    scheme — per-group int32 planes, all scale rows applied once at
    k-tile flush."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    gs = group_size
    n_groups = z_ref.shape[0]
    qw = qw_ref[...]                                  # [bk, bn/8] int32
    planes = [
        jax.lax.bitwise_and(jax.lax.shift_right_logical(qw, 4 * p), 0xF)
        for p in range(8)
    ]
    w_pm = jax.lax.concatenate(planes, 1)             # [bk, bn] int32
    for g in range(n_groups):
        w8 = (w_pm[g * gs:(g + 1) * gs] - z_ref[g]).astype(jnp.int8)
        x8 = x_ref[:, g * gs:(g + 1) * gs]
        g32_ref[g] = jax.lax.dot_general(
            x8, w8, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
    acc_ref[...] += jnp.sum(
        g32_ref[...].astype(jnp.float32) *
        s_ref[...].astype(jnp.float32), axis=0)

    @pl.when(k == k_tiles - 1)
    def _flush():
        o_ref[...] = (acc_ref[...] *
                      xs_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("group_size", "interpret",
                                    "deferred", "stream"))
def awq_matmul_a8(x: jax.Array, qweight: jax.Array, qzeros: jax.Array,
                  scales: jax.Array, *, group_size: int,
                  interpret: bool = False,
                  deferred=None, stream=None) -> jax.Array:
    """W4A8 AWQ: per-row int8 activation quantization feeding integer
    dots (see awq_matmul for the layout story; only the dequant->dot
    arithmetic differs). `deferred` selects the rescale-at-flush
    kernel and `stream` the skinny-m work-list grid — same contracts
    as gptq_matmul_a8."""
    m, K = x.shape
    N = qweight.shape[1] * 8
    gs = group_size
    G = K // gs

    use_stream = _resolve_stream(stream, m)
    n_slots = _stream_pf() if use_stream else 0
    use_def = _resolve_deferred(deferred, m)
    if use_stream:
        k_cap = _STREAM_DEF_K_CAP if use_def else _STREAM_K_CAP
    else:
        k_cap = _DEFERRED_K_CAP if use_def else 0
    block_k = _tile_k(K, gs, cap=k_cap)
    groups_per_tile = block_k // gs
    block_m, block_n, padded_m = _tile_mn(
        m, N, jnp.bfloat16, min_bn=1024,
        acc_planes=groups_per_tile if use_def else 1)
    if use_def and not _deferred_fits(block_m, block_n,
                                      groups_per_tile):
        use_def = False
        block_k = _tile_k(K, gs,
                          cap=_STREAM_K_CAP if use_stream else 0)
        groups_per_tile = block_k // gs
        block_m, block_n, padded_m = _tile_mn(m, N, jnp.bfloat16,
                                              min_bn=1024)
    block_k = _clamp_k_vmem(
        block_k, gs,
        functools.partial(
            _cell_bytes, layout="awq", block_m=block_m,
            block_n=block_n, gs=gs, pack=8,
            x_bytes=x.dtype.itemsize if use_stream else 1,
            s_bytes=scales.dtype.itemsize, K=K,
            stream_slots=n_slots, deferred=use_def, a16=False),
        tag="awq_a8")
    groups_per_tile = block_k // gs

    k_tiles = K // block_k
    n_tiles = N // block_n
    grid = (padded_m // block_m, n_tiles, k_tiles)
    z_pm, s_pm, order = _awq_zs_plane_major(qzeros, scales, N,
                                            n_tiles, block_n, G)

    if use_stream:
        # Raw activations go resident; the kernel prologue quantizes
        # them in VMEM (the folded FOLD001 chain).
        xr = jnp.pad(x, ((0, padded_m - m), (0, 0))) \
            if padded_m != m else x
        out_pm = _stream_call(
            xr, qweight, z_pm, s_pm, layout="awq", bits=4,
            gs=gs, block_m=block_m, block_n=block_n, block_k=block_k,
            padded_m=padded_m, N=N, n_slots=n_slots, a8=True,
            deferred=use_def, out_dtype=x.dtype, interpret=interpret)
        y = _awq_unpermute(out_pm, padded_m, N, n_tiles, block_n,
                           order)
        return y[:m] if padded_m != m else y

    x8, xs = quantize_activations_int8(x, interpret=interpret)
    if padded_m != m:
        x8 = jnp.pad(x8, ((0, padded_m - m), (0, 0)))
        xs = jnp.pad(xs, ((0, padded_m - m), (0, 0)))

    kernel = functools.partial(
        _awq_a8_deferred_kernel if use_def else _awq_a8_kernel,
        k_tiles=k_tiles, group_size=gs)
    scratch = [pltpu.VMEM((block_m, block_n), jnp.float32)]
    if use_def:
        scratch.append(
            pltpu.VMEM((groups_per_tile, block_m, block_n), jnp.int32))

    out_pm = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, n, k: (i, k)),
            pl.BlockSpec((block_m, 1), lambda i, n, k: (i, 0)),
            pl.BlockSpec((block_k, block_n // 8),
                         lambda i, n, k: (k, n)),
            pl.BlockSpec((groups_per_tile, 1, block_n),
                         lambda i, n, k: (k, 0, n)),
            pl.BlockSpec((groups_per_tile, 1, block_n),
                         lambda i, n, k: (k, 0, n)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n),
                               lambda i, n, k: (i, n)),
        out_shape=jax.ShapeDtypeStruct((padded_m, N), x.dtype),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x8, xs, qweight, z_pm, s_pm)

    y = _awq_unpermute(out_pm, padded_m, N, n_tiles, block_n, order)
    return y[:m] if padded_m != m else y


# -------------------------------------------------- GGUF at-rest ----

def _gguf_q4k_kernel(x_ref, qw_ref, dl_ref, ml_ref, o_ref, acc_ref, *,
                     k_tiles: int):
    """Q4_K-at-rest tile: codes packed GPTQ-style (8 nibbles along K),
    dequant w = q * dl - ml with AFFINE rows per 32-row ggml group.
    Unpacking runs per 128-row super-chunk (16 int32 rows — the aligned
    sublane slice); the four 32-row groups inside land interleaved in
    plane order, so their (dl, ml) rows are gathered with iota masks."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    block_k = qw_ref.shape[0] * 8
    n_sg = dl_ref.shape[0]                   # ggml groups in this tile
    chunks = []
    for c in range(block_k // 128):          # 128-row super-chunks
        q = _unpack_planes(qw_ref[c * 16:(c + 1) * 16], 4)  # [128, bn]
        # plane-order row j holds original row (j % 16) * 8 + j // 16;
        # its ggml group is orig // 32 in {0..3} within this chunk.
        j = jax.lax.broadcasted_iota(jnp.int32, q.shape, 0)
        sel = ((j % 16) * 8 + j // 16) // 32
        dl = jnp.zeros(q.shape, jnp.float32)
        ml = jnp.zeros(q.shape, jnp.float32)
        for sg in range(4):
            g = c * 4 + sg
            if g >= n_sg:
                break
            dl = jnp.where(sel == sg,
                           dl_ref[g].astype(jnp.float32), dl)
            ml = jnp.where(sel == sg,
                           ml_ref[g].astype(jnp.float32), ml)
        chunks.append(
            (q.astype(jnp.float32) * dl - ml).astype(x_ref.dtype))
    w = chunks[0] if len(chunks) == 1 else \
        jax.lax.concatenate(chunks, 0)
    acc_ref[...] += jnp.dot(x_ref[...], w,
                            preferred_element_type=jnp.float32)

    @pl.when(k == k_tiles - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def gguf_q4k_supported(in_features: int, out_features: int) -> bool:
    return (in_features % 256 == 0 and out_features % 128 == 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gguf_q4k_matmul(x: jax.Array, qweight: jax.Array, dl: jax.Array,
                    ml: jax.Array, *,
                    interpret: bool = False) -> jax.Array:
    """y[m, N] = x[m, K] @ (q * dl - ml) with Q4_K codes at rest:
    qweight [K//8, N] int32 (GPTQ plane packing along K), dl/ml
    [K//32, N] (d*subscale, dmin*submin per ggml 32-row group). The
    packed blocks never materialize as a dense matrix in HBM — the
    reference's gguf_kernel.cu fuses dequant the same way."""
    m, K = x.shape
    N = qweight.shape[1]
    G = K // 32
    block_k = _tile_k(K, 128, cap=512) if K % 128 == 0 else K
    block_m, block_n, padded_m = _tile_mn(m, N, x.dtype)
    # Plane-order unpack per 128-row span -> same x column permutation
    # as GPTQ at group_size 128.
    R = 16
    x = x.reshape(m, K // 128, R, 8).swapaxes(2, 3).reshape(m, K)
    if padded_m != m:
        x = jnp.pad(x, ((0, padded_m - m), (0, 0)))
    k_tiles = K // block_k
    grid = (padded_m // block_m, N // block_n, k_tiles)
    gpt = block_k // 32                      # ggml groups per k-tile

    out = pl.pallas_call(
        functools.partial(_gguf_q4k_kernel, k_tiles=k_tiles),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, n, k: (i, k)),
            pl.BlockSpec((block_k // 8, block_n),
                         lambda i, n, k: (k, n)),
            pl.BlockSpec((gpt, 1, block_n), lambda i, n, k: (k, 0, n)),
            pl.BlockSpec((gpt, 1, block_n), lambda i, n, k: (k, 0, n)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n),
                               lambda i, n, k: (i, n)),
        out_shape=jax.ShapeDtypeStruct((padded_m, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, qweight, dl.reshape(G, 1, N), ml.reshape(G, 1, N))
    return out[:m] if padded_m != m else out


def _gguf_q8_kernel(x_ref, qs_ref, d_ref, o_ref, acc_ref, *,
                    k_tiles: int):
    """Q8_0-at-rest tile: int8 rows, scale per 32-row ggml group
    (32-row sublane slices are exactly the int8 tile — aligned)."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    n_groups = d_ref.shape[0]
    chunks = []
    for g in range(n_groups):
        q = qs_ref[g * 32:(g + 1) * 32].astype(jnp.float32)
        chunks.append(
            (q * d_ref[g].astype(jnp.float32)).astype(x_ref.dtype))
    w = chunks[0] if n_groups == 1 else jax.lax.concatenate(chunks, 0)
    acc_ref[...] += jnp.dot(x_ref[...], w,
                            preferred_element_type=jnp.float32)

    @pl.when(k == k_tiles - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def gguf_q8_supported(in_features: int, out_features: int) -> bool:
    return in_features % 256 == 0 and out_features % 128 == 0


@functools.partial(jax.jit, static_argnames=("interpret",))
def gguf_q8_matmul(x: jax.Array, qs: jax.Array, d: jax.Array, *,
                   interpret: bool = False) -> jax.Array:
    """y[m, N] = x[m, K] @ (int8 qs[K, N] * d[K//32, N]) with Q8_0
    blocks at rest (per-32-row scales; HBM only reads int8 + scales)."""
    m, K = x.shape
    N = qs.shape[1]
    G = K // 32
    block_k = _tile_k(K, 256, cap=512) if K % 256 == 0 else K
    block_m, block_n, padded_m = _tile_mn(m, N, x.dtype)
    if padded_m != m:
        x = jnp.pad(x, ((0, padded_m - m), (0, 0)))
    k_tiles = K // block_k
    grid = (padded_m // block_m, N // block_n, k_tiles)
    gpt = block_k // 32

    out = pl.pallas_call(
        functools.partial(_gguf_q8_kernel, k_tiles=k_tiles),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, n, k: (i, k)),
            pl.BlockSpec((block_k, block_n), lambda i, n, k: (k, n)),
            pl.BlockSpec((gpt, 1, block_n), lambda i, n, k: (k, 0, n)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n),
                               lambda i, n, k: (i, n)),
        out_shape=jax.ShapeDtypeStruct((padded_m, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, qs, d.reshape(G, 1, N))
    return out[:m] if padded_m != m else out


def _gptq_a8_kernel(x_ref, xs_ref, qw_ref, z_ref, s_ref, o_ref,
                    acc_ref, *, bits: int, k_tiles: int,
                    group_size: int, unpack: str, ablate):
    """W4A8 tile: int8 activations into the MXU's int8 mode. Per
    quantization group: the codes minus their zero point as int8
    (`_a8_operand`: `unpack` says how, plane-wise one code a lane or
    byte-wise four, and x's columns arrive in that order; IN INTEGERS
    either way, codes land exactly on the int8 grid — no
    requantization), one int8 x int8 -> int32 dot per group, then scale
    the int32 partials by the group's fp scale row into the f32
    accumulator. The MXU's int8 mode has 2x the bf16 throughput, which
    is the lever the W4A16 kernel can't reach (its matmuls already run
    within ~6% of the bf16 dense roofline)."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pack = 32 // bits
    gs = group_size
    rows_per_group = gs // pack
    n_groups = z_ref.shape[0]
    for g in range(n_groups):
        w8 = _a8_operand(                             # exact: |w|<=2^bits
            qw_ref[g * rows_per_group:(g + 1) * rows_per_group],
            z_ref[g], bits, unpack, ablate)
        x8 = x_ref[:, g * gs:(g + 1) * gs]
        d = jax.lax.dot_general(x8, w8, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.int32)
        if ablate == "rescale":
            acc_ref[...] = pltpu.bitcast(d, jnp.float32)
        else:
            acc_ref[...] += d.astype(jnp.float32) * \
                s_ref[g].astype(jnp.float32)

    @pl.when(k == k_tiles - 1)
    def _flush():
        o_ref[...] = (acc_ref[...] *
                      xs_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def _gptq_a8_deferred_kernel(x_ref, xs_ref, qw_ref, z_ref, s_ref, o_ref,
                             acc_ref, g32_ref, *, bits: int,
                             k_tiles: int, group_size: int,
                             unpack: str, ablate):
    """Deferred-rescale W4A8 tile (PROFILE_r05 item 1): each group's
    int8 x int8 dot lands in its OWN int32 VMEM accumulator plane, and
    the per-group scale rows multiply the int32 partials ONCE, batched,
    at k-tile flush — the MXU issues its depth-`gs` dots back-to-back
    instead of waiting on a [block_m, block_n] f32 scale-FMA between
    every dot (the VPU stall that held the classic `_gptq_a8_kernel`
    at ~45% of the int8 MXU microbench peak). Costs `groups_per_tile`
    extra int32 planes of VMEM (~4x accumulator footprint at block_k
    512), which `_tile_mn(acc_planes=...)` pays for with smaller m/n
    tiles; same integer arithmetic, same results up to f32 summation
    order."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pack = 32 // bits
    gs = group_size
    rows_per_group = gs // pack
    n_groups = z_ref.shape[0]
    # Phase 1 — MXU: unpack + exact integer dots only; nothing touches
    # the f32 accumulator between groups.
    for g in range(n_groups):
        w8 = _a8_operand(                             # exact: |w|<=2^bits
            qw_ref[g * rows_per_group:(g + 1) * rows_per_group],
            z_ref[g], bits, unpack, ablate)
        x8 = x_ref[:, g * gs:(g + 1) * gs]
        g32_ref[g] = jax.lax.dot_general(
            x8, w8, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
    # Phase 2 — one batched rescale at tile flush: [gpt, bm, bn] int32
    # planes times the [gpt, 1, bn] scale rows, summed over the group
    # axis into the f32 accumulator.
    if ablate == "rescale":
        acc_ref[...] = pltpu.bitcast(g32_ref[0], jnp.float32)
    else:
        acc_ref[...] += jnp.sum(
            g32_ref[...].astype(jnp.float32) *
            s_ref[...].astype(jnp.float32), axis=0)

    @pl.when(k == k_tiles - 1)
    def _flush():
        o_ref[...] = (acc_ref[...] *
                      xs_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("bits", "group_size", "interpret",
                                    "deferred", "stream", "unpack",
                                    "ablate"))
def gptq_matmul_a8(x: jax.Array, qweight: jax.Array, qzeros: jax.Array,
                   scales: jax.Array, *, bits: int, group_size: int,
                   interpret: bool = False,
                   deferred=None, stream=None, unpack=None,
                   ablate=None) -> jax.Array:
    """W4A8 variant of gptq_matmul: activations quantize to int8 with a
    per-row scale (absmax) in the XLA prologue, weights stay int4 at
    rest, and the kernel runs integer dots per quantization group. The
    only approximation vs the W4A16 kernel is the activation rounding
    (~0.4% per element, averaging out over the K contraction) —
    opt-in via APHRODITE_W4A8 (see GPTQLinearMethod.apply).

    `deferred` selects the int32-group-accumulator rescale-at-flush
    kernel (None = by `m`, see `_resolve_deferred`); both variants
    compute the same integer dots and differ only in f32 summation
    order. `stream` pins the skinny-m work-list/DMA-ring grid (None =
    taken at m <= 64); the two knobs compose —
    a streamed deferred call keeps its int32 planes in ring scratch.

    A group's int8 operand is made by `_unpack_bytes` for 4-bit words
    on the streamed grid and by `_unpack_planes` otherwise
    (`_resolve_unpack`; `unpack` pins one: the results are bit-equal).
    `ablate` ("unpack", "rescale") is `benchmarks/qmm_ab.py`'s: a call
    with that stretch of the kernel's VPU work taken out, and wrong
    numbers."""
    m, K = x.shape
    N = qweight.shape[1]
    gs = group_size if group_size != -1 else K
    pack = 32 // bits

    use_stream = _resolve_stream(stream, m)
    unpack = _resolve_unpack(unpack, bits, use_stream)
    byte_rows = unpack == "bytes"
    note_kernel_path("w4a8_unpack", unpack,
                     "gptq_matmul_a8, the streamed grid" if use_stream
                     else "gptq_matmul_a8, the compiler's grid")
    n_slots = _stream_pf() if use_stream else 0
    use_def = _resolve_deferred(deferred, m)
    if use_def:
        # Pre-size the deferred tiles so the VMEM-fit fallback is
        # decided before the (single) prologue call.
        bk = _tile_k(K, gs, cap=_STREAM_DEF_K_CAP if use_stream
                     else _DEFERRED_K_CAP)
        gpt = bk // gs
        bm, bn, _ = _tile_mn(m, N, jnp.bfloat16, acc_planes=gpt)
        if not _deferred_fits(bm, bn, gpt):
            use_def = False

    # Classic path: small-m decode is grid-cell-count bound (the whole
    # weight streams once per step regardless of m): 2048-deep k-tiles
    # halve the cell count and measured bs=1 96.9 -> 100.8 tok/s
    # end-to-end. The a8 kernel never materializes the full bf16 tile,
    # so (unlike the W4A16 kernel, whose 2048-deep tile exceeds the
    # 16 MB scoped VMEM limit) the deep tile is legal; batch shapes
    # keep 1024 (round-4 A/B winner there). Deferred path: 512-deep
    # tiles keep the int32 plane count at groups_per_tile <= 4.
    # Streamed path: ring slots replace the per-cell weight blocks in
    # the VMEM budget, so the cap deepens to 4096 (1024 deferred) and
    # _clamp_k_vmem steps it down to fit.
    if use_stream:
        k_cap = _STREAM_DEF_K_CAP if use_def else _STREAM_K_CAP
    elif use_def:
        k_cap = _DEFERRED_K_CAP
    else:
        k_cap = 2048 if m <= 64 else 0

    if use_stream:
        # RAW activations through the shared prologue (permute+pad):
        # the kernel prologue quantizes the resident block in VMEM.
        # Row scales are permutation-invariant, so quantizing the
        # permuted block equals permuting the quantized block.
        xq, z_all, scales3, tiles = _gptq_prologue(
            x, qzeros, scales, N, bits, gs, jnp.bfloat16, k_cap=k_cap,
            acc_planes=(bk // gs) if use_def else 1,
            stream_slots=n_slots, deferred=use_def, a8=True,
            byte_rows=byte_rows)
        (block_m, block_n, block_k, padded_m, grid,
         groups_per_tile, k_tiles) = tiles
        out = _stream_call(
            xq, qweight, z_all, scales3, layout="gptq",
            bits=bits, gs=gs, block_m=block_m, block_n=block_n,
            block_k=block_k, padded_m=padded_m, N=N,
            n_slots=n_slots, a8=True, deferred=use_def,
            out_dtype=x.dtype, interpret=interpret, unpack=unpack,
            ablate=ablate)
        return out[:m] if padded_m != m else out

    x8, xs = quantize_activations_int8(x, interpret=interpret)
    x8, z_all, scales3, tiles = _gptq_prologue(
        x8, qzeros, scales, N, bits, gs, jnp.bfloat16, k_cap=k_cap,
        acc_planes=(bk // gs) if use_def else 1,
        stream_slots=0, deferred=use_def, byte_rows=byte_rows)
    (block_m, block_n, block_k, padded_m, grid,
     groups_per_tile, k_tiles) = tiles
    if padded_m != m:
        xs = jnp.pad(xs, ((0, padded_m - m), (0, 0)))

    kernel = functools.partial(
        _gptq_a8_deferred_kernel if use_def else _gptq_a8_kernel,
        bits=bits, k_tiles=k_tiles, group_size=gs, unpack=unpack,
        ablate=ablate)
    scratch = [pltpu.VMEM((block_m, block_n), jnp.float32)]
    if use_def:
        scratch.append(
            pltpu.VMEM((groups_per_tile, block_m, block_n), jnp.int32))

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, n, k: (i, k)),
            pl.BlockSpec((block_m, 1), lambda i, n, k: (i, 0)),
            pl.BlockSpec((block_k // pack, block_n),
                         lambda i, n, k: (k, n)),
            pl.BlockSpec((groups_per_tile, 1, block_n),
                         lambda i, n, k: (k, 0, n)),
            pl.BlockSpec((groups_per_tile, 1, block_n),
                         lambda i, n, k: (k, 0, n)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n),
                               lambda i, n, k: (i, n)),
        out_shape=jax.ShapeDtypeStruct((padded_m, N), x.dtype),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x8, xs, qweight, z_all, scales3)
    return out[:m] if padded_m != m else out


def _gguf_i8g_kernel(x_ref, qs_ref, d_ref, o_ref, acc_ref, *,
                     k_tiles: int):
    """Grouped-int8 tile: int8 rows with a scale per 16-row group
    (Q6_K's native granularity). 16-row sublane slices of int8 are
    unaligned (the int8 tile is 32 rows), so each aligned 32-row slice
    selects between its two scale rows with a row-iota mask."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    n32 = qs_ref.shape[0] // 32
    chunks = []
    for c in range(n32):
        q = qs_ref[c * 32:(c + 1) * 32].astype(jnp.float32)
        j = jax.lax.broadcasted_iota(jnp.int32, q.shape, 0)
        dlo = d_ref[2 * c].astype(jnp.float32)        # [1, bn]
        dhi = d_ref[2 * c + 1].astype(jnp.float32)
        chunks.append(
            (q * jnp.where(j < 16, dlo, dhi)).astype(x_ref.dtype))
    w = chunks[0] if n32 == 1 else jax.lax.concatenate(chunks, 0)
    acc_ref[...] += jnp.dot(x_ref[...], w,
                            preferred_element_type=jnp.float32)

    @pl.when(k == k_tiles - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def gguf_i8g_supported(in_features: int, out_features: int) -> bool:
    return in_features % 256 == 0 and out_features % 128 == 0


@functools.partial(jax.jit, static_argnames=("interpret",))
def gguf_i8g_matmul(x: jax.Array, qs: jax.Array, d16: jax.Array, *,
                    interpret: bool = False) -> jax.Array:
    """y[m, N] = x[m, K] @ (int8 qs[K, N] * d16[K//16, N]) — the
    grouped-int8 at-rest form: Q6_K repacks into it exactly
    (codes - 32, d*subscale rows), Q8_0 by repeating its per-32 scale
    rows, and other ggml block types requantize into it at load so
    MIXED sibling groups (llama.cpp Q4_K_M puts Q6_K in attn_v/ffn_down
    next to Q4_K) still execute packed instead of falling back to a
    dense bf16 copy. Reference: the per-type mat-vec dispatch in
    `kernels/quantization/gguf/gguf_kernel.cu`."""
    m, K = x.shape
    N = qs.shape[1]
    G = K // 16
    block_k = _tile_k(K, 256, cap=512) if K % 256 == 0 else K
    block_m, block_n, padded_m = _tile_mn(m, N, x.dtype)
    if padded_m != m:
        x = jnp.pad(x, ((0, padded_m - m), (0, 0)))
    k_tiles = K // block_k
    grid = (padded_m // block_m, N // block_n, k_tiles)
    gpt = block_k // 16

    out = pl.pallas_call(
        functools.partial(_gguf_i8g_kernel, k_tiles=k_tiles),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, n, k: (i, k)),
            pl.BlockSpec((block_k, block_n), lambda i, n, k: (k, n)),
            pl.BlockSpec((gpt, 1, block_n), lambda i, n, k: (k, 0, n)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n),
                               lambda i, n, k: (i, n)),
        out_shape=jax.ShapeDtypeStruct((padded_m, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, qs, d16.reshape(G, 1, N))
    return out[:m] if padded_m != m else out


def _gguf_w8a8_kernel(x_ref, xs_ref, qs_ref, s_ref, o_ref, acc_ref, *,
                      k_tiles: int):
    """W8A8 tile: int8 weight rows with a symmetric scale per
    128-row group, int8 activations. Per group: one int8 x int8 ->
    int32 MXU dot at full 128 depth, then the group's fp scale row
    multiplies the int32 partials into the f32 accumulator
    (scale-after-accumulate). No unpack, no zero point, no x column
    permutation — the cheapest kernel in this file. This is the GGUF
    fast path: every ggml block format requantizes into this form at
    load (see quantization/gguf.py), replacing the per-32-row
    dequant-to-bf16 kernels whose VPU work and 4-bit affine handling
    held the GGUF bench row back."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    gs = 128
    n_groups = s_ref.shape[0]
    for g in range(n_groups):
        w8 = qs_ref[g * gs:(g + 1) * gs]
        x8 = x_ref[:, g * gs:(g + 1) * gs]
        d = jax.lax.dot_general(x8, w8, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.int32)
        acc_ref[...] += d.astype(jnp.float32) * \
            s_ref[g].astype(jnp.float32)

    @pl.when(k == k_tiles - 1)
    def _flush():
        o_ref[...] = (acc_ref[...] *
                      xs_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def gguf_w8a8_supported(in_features: int, out_features: int) -> bool:
    return in_features % 128 == 0 and out_features % 128 == 0


@functools.partial(jax.jit, static_argnames=("interpret",))
def gguf_w8a8_matmul(x: jax.Array, qs: jax.Array, s128: jax.Array, *,
                     interpret: bool = False) -> jax.Array:
    """y[m, N] = x[m, K] @ (int8 qs[K, N] * s128[K//128, N]) with int8
    activations (per-row absmax scales, the same approximation as the
    GPTQ/AWQ W4A8 bench path)."""
    m, K = x.shape
    N = qs.shape[1]
    G = K // 128
    block_k = _tile_k(K, 128)
    block_m, block_n, padded_m = _tile_mn(m, N, jnp.bfloat16)
    x8, xs = quantize_activations_int8(x, interpret=interpret)
    if padded_m != m:
        x8 = jnp.pad(x8, ((0, padded_m - m), (0, 0)))
        xs = jnp.pad(xs, ((0, padded_m - m), (0, 0)))
    k_tiles = K // block_k
    grid = (padded_m // block_m, N // block_n, k_tiles)
    gpt = block_k // 128

    out = pl.pallas_call(
        functools.partial(_gguf_w8a8_kernel, k_tiles=k_tiles),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, n, k: (i, k)),
            pl.BlockSpec((block_m, 1), lambda i, n, k: (i, 0)),
            pl.BlockSpec((block_k, block_n), lambda i, n, k: (k, n)),
            pl.BlockSpec((gpt, 1, block_n), lambda i, n, k: (k, 0, n)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n),
                               lambda i, n, k: (i, n)),
        out_shape=jax.ShapeDtypeStruct((padded_m, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x8, xs, qs, s128.reshape(G, 1, N))
    return out[:m] if padded_m != m else out


# ---------------------------------------------- SqueezeLLM 4-bit LUT --

def _sqllm_kernel(x_ref, qw_ref, lut_ref, o_ref, acc_ref, *,
                  k_tiles: int):
    """Non-uniform 4-bit LUT tile: unpack codes plane-wise, materialize
    the weight tile with a 16-way select against the per-column codebook
    rows, accumulate on the MXU. This is the TPU-native form of the CUDA
    shared-memory LUT gather
    (`kernels/quantization/squeezellm/quant_cuda_kernel.cu`): TPUs have
    no per-lane scatter/gather, but a 16-way masked select is pure VPU
    work the codes stream through once per tile — the packed codes are
    the only HBM traffic."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = _unpack_planes(qw_ref[...], 4)       # [block_k, bn] plane order
    w = jnp.zeros(q.shape, jnp.float32)
    for v in range(16):
        w = jnp.where(q == v, lut_ref[v:v + 1, :].astype(jnp.float32),
                      w)
    acc_ref[...] += jnp.dot(x_ref[...], w.astype(x_ref.dtype),
                            preferred_element_type=jnp.float32)

    @pl.when(k == k_tiles - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def squeezellm_supported(in_features: int, out_features: int) -> bool:
    return in_features % 256 == 0 and out_features % 128 == 0


@functools.partial(jax.jit, static_argnames=("interpret",))
def squeezellm_matmul(x: jax.Array, qweight: jax.Array,
                      lookup_table: jax.Array, *,
                      interpret: bool = False) -> jax.Array:
    """y[m, N] = x[m, K] @ w with w[i, j] = lookup_table[j, q[i, j]]:
    qweight [K//8, N] int32 (8 nibbles along K, SqueezeLLM layout),
    lookup_table [N, 16] per-output-channel codebook. Codes stay packed
    in HBM; the dense weight matrix never materializes."""
    m, K = x.shape
    N = qweight.shape[1]
    block_k = _tile_k(K, 256, cap=512) if K % 256 == 0 else K
    block_m, block_n, padded_m = _tile_mn(m, N, x.dtype)
    # Whole-block plane unpack -> x column permutation over each
    # block_k span (same blockwise transpose trick as gptq_matmul).
    r = block_k // 8
    x = x.reshape(m, K // block_k, r, 8).swapaxes(2, 3).reshape(m, K)
    if padded_m != m:
        x = jnp.pad(x, ((0, padded_m - m), (0, 0)))
    k_tiles = K // block_k
    grid = (padded_m // block_m, N // block_n, k_tiles)

    out = pl.pallas_call(
        functools.partial(_sqllm_kernel, k_tiles=k_tiles),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, n, k: (i, k)),
            pl.BlockSpec((block_k // 8, block_n),
                         lambda i, n, k: (k, n)),
            pl.BlockSpec((16, block_n), lambda i, n, k: (0, n)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n),
                               lambda i, n, k: (i, n)),
        out_shape=jax.ShapeDtypeStruct((padded_m, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, qweight, lookup_table.T)
    return out[:m] if padded_m != m else out


# -------------------------------------------------------- int8 dense --

def _int8_kernel(x_ref, w_ref, s_ref, o_ref, acc_ref, *, k_tiles: int):
    """Per-channel int8 weight tile: upcast in VMEM registers (HBM only
    ever sees int8 bytes), accumulate, scale columns at flush."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = w_ref[...].astype(x_ref.dtype)
    acc_ref[...] += jnp.dot(x_ref[...], w,
                            preferred_element_type=jnp.float32)

    @pl.when(k == k_tiles - 1)
    def _flush():
        o_ref[...] = (acc_ref[...] *
                      s_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def int8_supported(in_features: int, out_features: int) -> bool:
    return in_features % 256 == 0 and out_features % 128 == 0


@functools.partial(jax.jit, static_argnames=("interpret",))
def int8_matmul(x: jax.Array, weight: jax.Array, scales: jax.Array, *,
                interpret: bool = False) -> jax.Array:
    """y[m, N] = (x[m, K] @ int8 weight[K, N]) * scales[N] with the
    weight read from HBM at int8 width (the XLA fallback's explicit
    astype may materialize a bf16 copy)."""
    m, K = x.shape
    N = weight.shape[1]
    block_k = 256
    while block_k < 512 and K % (block_k * 2) == 0:
        block_k *= 2
    block_m, block_n, padded_m = _tile_mn(m, N, x.dtype)
    if padded_m != m:
        x = jnp.pad(x, ((0, padded_m - m), (0, 0)))
    k_tiles = K // block_k
    grid = (padded_m // block_m, N // block_n, k_tiles)

    out = pl.pallas_call(
        functools.partial(_int8_kernel, k_tiles=k_tiles),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, n, k: (i, k)),
            pl.BlockSpec((block_k, block_n), lambda i, n, k: (k, n)),
            pl.BlockSpec((1, block_n), lambda i, n, k: (0, n)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n),
                               lambda i, n, k: (i, n)),
        out_shape=jax.ShapeDtypeStruct((padded_m, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, weight, scales.reshape(1, N))
    return out[:m] if padded_m != m else out
