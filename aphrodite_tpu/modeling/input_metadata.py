"""The fixed-shape batch descriptor handed to the jitted step functions.

This is the TPU-native replacement for the reference's `InputMetadata`
(`aphrodite/modeling/metadata.py`) + the padded tensor building in
`task_handler/model_runner.py:102-371`: a pytree of device arrays with
static shapes per (phase, bucket), so each bucket compiles exactly once
(SURVEY.md §7 "fixed-shape discipline" / "batch-descriptor ABI").

`is_prompt` and `use_prefix` are static (meta) fields — they select which
jitted program runs, exactly like the reference's prompt/decode split
(`processing/scheduler.py:260-271`).
"""
from __future__ import annotations

from typing import Optional

import jax
from flax import struct


@struct.dataclass
class GroupView:
    """What one page group of a step sees in the place of the batch's
    own `slot_mapping`, `block_tables`, `context_lens`,
    `prefill_cells` and `decode_work`: its table holds the pages the
    group still has, from the first one on, and positions count from
    that page's first token (a full group's from the sequence's)."""
    slot_mapping: jax.Array
    block_tables: jax.Array
    context_lens: jax.Array
    prefill_cells: Optional[tuple] = None
    decode_work: Optional[tuple] = None
    decode_ppc: int = struct.field(pytree_node=False, default=0)


@struct.dataclass
class InputMetadata:
    # [num_tokens] flat slot index per new token; padded entries hold an
    # out-of-range slot (>= num_pages*page_size) so the cache scatter drops
    # them (see ops/kv_cache.py padding convention).
    slot_mapping: jax.Array
    # [batch, pages_per_seq] physical page ids per sequence; padded entries
    # hold an out-of-range page id.
    block_tables: jax.Array
    # [batch] number of valid tokens in cache AFTER this step's writes
    # (decode) or before this chunk (prefill prefix length).
    context_lens: jax.Array
    # [batch] number of valid (non-pad) new tokens per sequence.
    prompt_lens: Optional[jax.Array] = None
    # Prefill page-writer cell descriptors (page_ids, src_blocks,
    # valids), one cell per (sequence, page) — present when the prompt
    # layout is page-aligned so whole pages can be written without
    # read-modify-write (ops/pallas/kv_write.write_kv_pages_prefill).
    prefill_cells: Optional[tuple] = None
    # Ragged decode work list (wi_seq [NW+1], wi_chunk [NW] int32):
    # (sequence, chunk) pairs flattened over each row's REAL reserved
    # pages, built by ModelRunner._prepare_decode with
    # ops/pallas/paged_attention.build_decode_work_list. Rides the
    # burst-scan carry unchanged (chunk counts come from reserved
    # pages, a safe over-approximation of any in-burst context).
    decode_work: Optional[tuple] = None

    # One `GroupView` a page group, for a model whose layers are not
    # one plain group (`common/config.py::PageGroups`); None where
    # they are, and the fields above are the step's. A layer takes its
    # group's view with `for_group`.
    groups: Optional[tuple] = None
    # A decode batch of page groups as it is packed into
    # `block_tables` (`ModelRunner._send_decode_batch`): each group's
    # (columns of its table, whether a column with the tokens its
    # table has let go of precedes them).
    group_layout: tuple = struct.field(pytree_node=False, default=())
    # [batch] each row's state slot, for a model that keeps recurrent
    # state beside its pages (`common/config.py::StateSpec`); pad rows
    # hold the scratch slot, the arrays' last. None for every other
    # model. A packed decode batch carries it as its last column.
    state_slots: Optional[jax.Array] = None

    is_prompt: bool = struct.field(pytree_node=False, default=False)
    # Speculative verify batch: rows are (sequence, position) work
    # items — a sequence may own SEVERAL rows at consecutive
    # positions, all mapping into the SAME KV pages. Static because
    # it routes around two one-token-per-page-per-step assumptions:
    # the fused in-kernel KV write and the pipelined distinct-pages
    # writer (both assume each page is touched by at most one row).
    # The verify batch takes the XLA scatter write (distinct SLOTS,
    # shared pages) + read-only attention instead.
    spec_verify: bool = struct.field(pytree_node=False, default=False)
    # Tensor-parallel degree of the mesh the step runs on (1 = single
    # device). Static: it routes kernel selection — the Pallas paged
    # attention / KV-writer kernels are single-device programs, so a
    # tp-sharded KV cache must take the GSPMD-partitionable jnp paths
    # until they are shard_map-wrapped (the TPLA prefill/decode split
    # seam). Constant per engine, so it adds no compiles.
    tp: int = struct.field(pytree_node=False, default=1)
    # Prefill against a non-empty cached prefix (prefix caching / chunked
    # prefill); selects the gather-from-pages prefill path.
    use_prefix: bool = struct.field(pytree_node=False, default=False)
    # int8 KV dequant scale (value = int8 * kv_scale); 1.0 for non-int8
    # caches. Static so every jit / Pallas compile cache keys on it —
    # the scale is a trace-time constant folded into kernel epilogues.
    kv_scale: float = struct.field(pytree_node=False, default=1.0)
    # pages_per_chunk the decode_work list was built with (0 = no work
    # list). Static: the kernel's chunk geometry is a trace-time
    # constant, and the value is a function of the (batch, pages)
    # bucket, so it adds no compiles of its own.
    decode_ppc: int = struct.field(pytree_node=False, default=0)
    # Sequence-parallel prefill routing: (Mesh, threshold_tokens) when
    # the engine runs with --sequence-parallel-size > 1, else None.
    # Static (Mesh is hashable): prompts at/above the threshold shard
    # their prefill attention over the mesh's "sp" axis via ring
    # attention (ops/ring_attention.py).
    sp: object = struct.field(pytree_node=False, default=None)

    def for_group(self, group: int) -> "InputMetadata":
        """The step as page group `group` sees it."""
        if self.groups is None:
            return self
        view = self.groups[group]
        return self.replace(
            slot_mapping=view.slot_mapping, block_tables=view.block_tables,
            context_lens=view.context_lens,
            prefill_cells=view.prefill_cells, decode_work=view.decode_work,
            decode_ppc=view.decode_ppc, groups=None)
