"""Paged KV-cache device ops.

TPU-native equivalents of the reference CUDA cache kernels
(`kernels/cache_kernels.cu:14,88,221` — swap_blocks/copy_blocks/
reshape_and_cache). Layout choice (round-3 "token-major"): per layer
the cache is a pair of page arrays

    k_pages, v_pages: [num_pages, page_size, num_kv_heads * head_dim]

i.e. heads COLLAPSED INTO LANES. Rationale, from the PROFILE_r03
attribution: the previous head-major [H, pages, page, d] layout forced
the decode kernel into pages_per_chunk x H x 2 separate 4 KB DMAs per
sequence (210 GB/s effective KV bandwidth) and the page writer into
per-head read-modify-writes. Token-major makes one page a contiguous
[page_size, H*d] slab (one DMA descriptor, 32 KB-class), keeps any
aligned head sub-block an aligned LANE slice (hb*d is always a
multiple of 128), has no Mosaic tile padding for any head count (even
one local head under tp=heads sharding, where a 4-D [P, page, 1, d]
array would pad its sublane dim 8x), and shards over TP as a plain
lane-dimension partition (contiguous head blocks).
(The reference's [blocks, heads, head/x, block, x] layout is a CUDA
coalescing trick with no TPU analog.)

All ops are functional (return new arrays); under jit the engine donates
the page buffers so XLA performs the scatter in place — no copies of the
multi-GB cache per step (SURVEY.md §7 "in-place KV updates under jit").

Padding convention: invalid slots/indices are encoded as OUT-OF-RANGE
values (>= num_slots); every scatter/gather uses mode='drop' (scatter) or
'fill' (gather) so padded lanes are no-ops. Negative sentinels are NOT
used — JAX wraps negative indices.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from aphrodite_tpu.common.utils import note_kernel_path


def padded_head_size(head_size: int) -> int:
    """Cache pages store head_dim padded to the 128-lane tile: Mosaic
    DMAs slice whole lane tiles, so a 64/80/96-wide head would exclude
    the Pallas decode/write kernels entirely (round-1/2 gate at
    `layers/attention.py:141`). Zero pad lanes are inert — q pads with
    zeros so scores are unchanged, and the output's pad lanes are
    sliced off (the reference's head-size list `attention.py:17` is the
    CUDA analog of this constraint). Cost: up to 2x KV bytes for
    head 64 models — the standard TPU trade."""
    return -(-head_size // 128) * 128


def write_to_kv_cache(
    key: jax.Array,        # [num_tokens, num_kv_heads, head_dim]
    value: jax.Array,      # [num_tokens, num_kv_heads, head_dim]
    k_pages: jax.Array,    # [num_pages, page_size, H * head_dim]
    v_pages: jax.Array,
    slot_mapping: jax.Array,  # [num_tokens] int32; pad with num_slots (OOB)
    kv_scale: float = 1.0,    # int8 quantization scale (trace-time const)
    distinct_pages: bool = False,  # decode batches: 1 token/page
    tp: int = 1,              # mesh tp degree (trace-time const)
) -> Tuple[jax.Array, jax.Array]:
    """Scatter freshly computed K/V for each token into its cache slot.

    Equivalent of `reshape_and_cache` (`kernels/cache_kernels.cu:221`).
    slot = page_index * page_size + page_offset; padded entries must be
    >= num_pages*page_size so mode='drop' discards them.
    """
    num_pages, page_size, hd = k_pages.shape
    num_tokens = key.shape[0]
    key = key.reshape(num_tokens, hd)       # heads -> lanes
    value = value.reshape(num_tokens, hd)

    # TPU: Pallas kernel with input_output_aliases — guaranteed in-place
    # HBM update. The XLA scatter below is semantically identical but XLA
    # wraps it in full-cache layout-conversion copies when the scattered
    # values arrive late in the program (the transformer chain), costing
    # tens of ms/step on multi-GB caches. Single-device meshes only
    # (MESH003): under tp-sharded pages the per-chip custom call would
    # force GSPMD to replicate the cache around it.
    if tp == 1 and jax.default_backend() == "tpu":
        from aphrodite_tpu.ops.pallas.kv_write import (
            can_use_pallas_writer, write_kv_pages)
        if can_use_pallas_writer(k_pages.dtype, page_size, hd):
            note_kernel_path(
                "kv_write", "pallas",
                "pipelined page writer" if distinct_pages
                else "slot-window writer")
            return write_kv_pages(key, value, k_pages, v_pages,
                                  slot_mapping,
                                  distinct_pages=distinct_pages)

    note_kernel_path(
        "kv_write", "reference",
        f"XLA scatter: backend={jax.default_backend()}, tp={tp}, "
        f"pages={k_pages.dtype}")
    k_flat = k_pages.reshape(num_pages * page_size, hd)
    v_flat = v_pages.reshape(num_pages * page_size, hd)

    from aphrodite_tpu.ops.kv_quant import quantize_kv
    key_q = quantize_kv(key, k_pages.dtype, kv_scale)
    value_q = quantize_kv(value, v_pages.dtype, kv_scale)

    k_flat = k_flat.at[slot_mapping, :].set(key_q, mode="drop")
    v_flat = v_flat.at[slot_mapping, :].set(value_q, mode="drop")
    return (k_flat.reshape(k_pages.shape), v_flat.reshape(v_pages.shape))


def write_to_latent_cache(
    rows: jax.Array,          # [num_tokens, lanes]
    pages: jax.Array,         # [num_pages, page_size, lanes]
    slot_mapping: jax.Array,  # [num_tokens] int32; pad with num_slots (OOB)
) -> jax.Array:
    """`write_to_kv_cache` for a LATENT page (`PageGroups.latent`):
    each token's one row into its slot of the one array. The `jnp`
    side of the latent writers: a prompt step on the chip takes the
    whole-page Pallas writer, a decode step the decode kernel's fused
    write (`modeling/layers/mla.py`)."""
    num_pages, page_size, lanes = pages.shape
    note_kernel_path(
        "kv_write", "reference",
        f"XLA scatter of latent rows: backend={jax.default_backend()}")
    flat = pages.reshape(num_pages * page_size, lanes)
    flat = flat.at[slot_mapping, :].set(rows.astype(pages.dtype),
                                        mode="drop")
    return flat.reshape(pages.shape)


def copy_pages(pages: jax.Array, src_indices: jax.Array,
               dst_indices: jax.Array) -> jax.Array:
    """`copy_blocks` for one array of pages (one side of a K/V pair;
    a latent page's one): a gather and a scatter, pad pairs
    out of range on both sides."""
    src = jnp.take(pages, src_indices, axis=0, mode="fill", fill_value=0)
    return pages.at[dst_indices].set(src, mode="drop")


def copy_blocks(
    k_pages: jax.Array,
    v_pages: jax.Array,
    src_indices: jax.Array,   # [num_copies] int32; pad with num_pages (OOB)
    dst_indices: jax.Array,   # [num_copies] int32; pad with num_pages (OOB)
) -> Tuple[jax.Array, jax.Array]:
    """Batched on-device page copies for copy-on-write forks.

    Equivalent of `copy_blocks` (`kernels/cache_kernels.cu:88`), executed
    as one gather + one scatter per cache side instead of a kernel launch
    per pair.
    """
    return (copy_pages(k_pages, src_indices, dst_indices),
            copy_pages(v_pages, src_indices, dst_indices))


def gather_pages(
    pages: jax.Array,         # [num_pages, page_size, H * head_dim]
    page_indices: jax.Array,  # [num_seqs, pages_per_seq]; pad with OOB
    num_kv_heads: int,
) -> jax.Array:
    """Gather each sequence's pages: -> [num_seqs, num_kv_heads,
    pages_per_seq * page_size, head_dim]. Used by the jnp reference
    attention path and by host-side swap staging."""
    _, page_size, hd = pages.shape
    head_dim = hd // num_kv_heads
    num_seqs, pages_per_seq = page_indices.shape
    gathered = jnp.take(pages, page_indices.reshape(-1), axis=0,
                        mode="fill", fill_value=0)
    gathered = gathered.reshape(num_seqs, pages_per_seq * page_size,
                                num_kv_heads, head_dim)
    return gathered.transpose(0, 2, 1, 3)
