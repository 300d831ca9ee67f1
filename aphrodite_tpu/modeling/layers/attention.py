"""PagedAttention layer: KV-cache write + prefill/decode dispatch.

Reference: `aphrodite/modeling/layers/attention.py` (cache write `:95`,
xformers prompt path `:104-161`, prefix path `:163-178`, decode dispatch
`:230-302`). TPU-native mapping:

- cache write  -> functional scatter `ops.kv_cache.write_to_kv_cache`
  (buffers donated by the engine, so XLA updates in place);
- prompt path  -> on one TPU the Pallas flash kernel
  (`ops/pallas/prefill_attention.py`; `takes_prefill_kernel` is the
  rule); elsewhere, and under ALiBi or over quantised pages, dense
  causal attention in jnp (`ops.attention.prefill_attention`, in tiles
  from `blocked_from` queries x keys a row on);
- prefix path  -> same prefill math over [gathered prefix ; chunk];
- decode path  -> Pallas flash-decoding kernel over HBM pages
  (`ops/pallas/paged_attention.py`), with the jnp gather path as the
  interpret/CPU fallback.

GQA/MQA, ALiBi, and sliding window are handled in all paths. A layer
with a window sees, like any other, its page group's table
(`InputMetadata.for_group`): the pages the group still holds, from the
first on, positions counted from that page's first token; the window
itself is a mask, in the prefill and in the decode kernel alike, so a
window layer's decode step is the fused kernel's too. A layer that
`writes_kv` nothing attends over the pages another layer of its group
has written (a cross-attention layer over an earlier layer's K and V):
its step writes no page, and its decode step is the read-only kernel.
Head sizes
are unrestricted (the reference's {64..256} list, `attention.py:17`, is a
CUDA register-tiling constraint with no TPU analog).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from aphrodite_tpu.common.utils import note_kernel_path
from aphrodite_tpu.modeling.input_metadata import InputMetadata
from aphrodite_tpu.ops.attention import (BLOCKED_FROM,
                                         paged_decode_attention_ref,
                                         prefill_attention,
                                         prefill_attention_blocked)
from aphrodite_tpu.ops.kv_cache import gather_pages, write_to_kv_cache


def takes_blocked_prefill(seq_len: int, kv_len: int,
                          blocked_from: int) -> bool:
    """Whether a prompt step of `seq_len` queries against `kv_len` keys
    a row takes `prefill_attention_blocked` where it takes a `jnp`
    function (the layer below chooses by it, and the runner counts the
    step's tiles by it, whichever path the step takes)."""
    return seq_len * kv_len >= blocked_from


def takes_prefill_kernel(kv_dtype, tp: int, sp, alibi: bool) -> bool:
    """Whether a prompt step's attention is the Pallas flash kernel
    (`ops/pallas/prefill_attention.py`): on one TPU (the kernel is a
    single-device program: no `tp`, no `sp` ring), K and V in bfloat16
    or float32 as they arrive (quantised pages come with a
    dequantising scale), no ALiBi bias. Everything else keeps the
    `jnp` functions. The layer below chooses by it and the runner
    counts the step by it (`attn.prefill_kernel_steps`)."""
    return (jax.default_backend() == "tpu" and tp == 1 and sp is None
            and not alibi and
            jnp.dtype(kv_dtype) in (jnp.bfloat16, jnp.float32))


class PagedAttention:
    """Stateless attention dispatcher (all state is in the KV pages)."""

    def __init__(
        self,
        num_heads: int,
        head_size: int,
        scale: float,
        num_kv_heads: Optional[int] = None,
        alibi_slopes: Optional[np.ndarray] = None,
        sliding_window: Optional[int] = None,
        use_pallas: bool = True,
        page_group: int = 0,
        writes_kv: bool = True,
        blocked_from: int = BLOCKED_FROM,
    ) -> None:
        self.num_heads = num_heads
        self.head_size = head_size
        self.scale = float(scale)
        self.num_kv_heads = num_kv_heads if num_kv_heads is not None \
            else num_heads
        self.alibi_slopes = None if alibi_slopes is None else \
            jnp.asarray(alibi_slopes, dtype=jnp.float32)
        self.sliding_window = sliding_window
        self.use_pallas = use_pallas
        # which of the step's page groups this layer's cache is in
        self.page_group = page_group
        # False: the pages are another layer's to write; this one reads
        self.writes_kv = writes_kv
        # queries x keys a row from which the prefill takes the keys
        # in blocks (a model of many heads sets it lower)
        self.blocked_from = blocked_from
        from aphrodite_tpu.ops.kv_cache import padded_head_size
        # Cache pages pad head_dim to the 128-lane tile; q/k/v pad with
        # zeros on the way in (inert in scores) and outputs slice the
        # pad lanes off. See ops/kv_cache.padded_head_size.
        self.padded_head = padded_head_size(head_size)

    def __call__(
        self,
        q: jax.Array,              # [batch, seq, num_heads * head_size]
        k: Optional[jax.Array],    # [batch, seq, num_kv_heads * head_size]
        v: Optional[jax.Array],    # (None: a decode step that writes none)
        k_pages: Optional[jax.Array],
        v_pages: Optional[jax.Array],
        metadata: InputMetadata,
    ) -> Tuple[jax.Array, Optional[jax.Array], Optional[jax.Array]]:
        """Returns (attn_out [batch, seq, num_heads*head_size], new
        k_pages, new v_pages). k_pages=None runs cache-less prefill (memory
        profiling, reference `model_runner.profile_run:571`)."""
        metadata = metadata.for_group(self.page_group)
        batch, seq_len, _ = q.shape
        q = q.reshape(batch, seq_len, self.num_heads, self.head_size)
        if k is not None:
            k = k.reshape(batch, seq_len, self.num_kv_heads,
                          self.head_size)
            v = v.reshape(batch, seq_len, self.num_kv_heads,
                          self.head_size)

        fused_decode = self.writes_kv and \
            self._fused_decode_ok(k_pages, metadata)
        if self.writes_kv and k_pages is not None and not fused_decode:
            flat_k = k.reshape(-1, self.num_kv_heads, self.head_size)
            flat_v = v.reshape(-1, self.num_kv_heads, self.head_size)
            if self.padded_head != self.head_size:
                pad = ((0, 0), (0, 0),
                       (0, self.padded_head - self.head_size))
                flat_k = jnp.pad(flat_k, pad)
                flat_v = jnp.pad(flat_v, pad)
            from aphrodite_tpu.ops.pallas.kv_write import (
                can_use_pallas_writer, write_kv_pages_prefill)
            hd = k_pages.shape[2]
            # Single-device meshes only: the Pallas writer is a
            # per-chip program — under tp-sharded pages it would force
            # GSPMD to replicate the cache around the custom call.
            pallas_write = (jax.default_backend() == "tpu" and
                            metadata.tp == 1 and
                            can_use_pallas_writer(k_pages.dtype,
                                                  k_pages.shape[1], hd))
            if (pallas_write and metadata.is_prompt and
                    metadata.prefill_cells is not None):
                # Page-aligned prompt chunks: whole-page writes, no
                # per-token read-modify-write.
                note_kernel_path("kv_write", "pallas",
                                 "prefill whole-page writer")
                pid, sblk, vld = metadata.prefill_cells
                k_pages, v_pages = write_kv_pages_prefill(
                    flat_k.reshape(-1, hd), flat_v.reshape(-1, hd),
                    k_pages, v_pages, pid, sblk, vld)
            else:
                k_pages, v_pages = write_to_kv_cache(
                    flat_k, flat_v, k_pages, v_pages,
                    metadata.slot_mapping,
                    kv_scale=metadata.kv_scale,
                    tp=metadata.tp,
                    # Decode: one token per sequence, pages are
                    # sequence-exclusive -> the pipelined page writer
                    # is safe. Speculative verify rows share pages
                    # (k+1 consecutive positions per sequence), so
                    # they must keep the slot-wise scatter.
                    distinct_pages=(not metadata.is_prompt and
                                    not metadata.spec_verify))
            if not pallas_write:
                # XLA-scatter path only: keep the scatter un-fused from
                # its readers — fusing the in-place page update into the
                # attention gather forces XLA to materialize a full temp
                # copy of the cache (multi-GB/step). The Pallas writer
                # needs no barrier: input_output_aliases pins its
                # in-place semantics regardless of fusion decisions.
                k_pages, v_pages = jax.lax.optimization_barrier(
                    (k_pages, v_pages))

        if metadata.is_prompt:
            out = self._prefill(q, k, v, k_pages, v_pages, metadata)
        elif fused_decode:
            # The decode kernel injects the current token's K/V into
            # its page in place and attends over it — no separate
            # page-writer pass (the page was being DMA'd in anyway).
            note_kernel_path("kv_write", "pallas",
                             "fused into the decode attention kernel")
            out, k_pages, v_pages = self._decode(
                q, k_pages, v_pages, metadata,
                knew=k.reshape(batch, self.num_kv_heads,
                               self.head_size),
                vnew=v.reshape(batch, self.num_kv_heads,
                               self.head_size))
        else:
            out = self._decode(q, k_pages, v_pages, metadata)
        return (out.reshape(batch, seq_len,
                            self.num_heads * self.head_size),
                k_pages, v_pages)

    def _fused_decode_ok(self, k_pages, metadata) -> bool:
        """Routing precondition for the fused in-kernel KV write. The
        kernel derives the write position as ctx-1 of the table it is
        given, which holds for a window layer too: its group's table
        slides (it lets whole pages go and counts from the first it
        keeps), it does not wrap. Speculative verify batches carry
        several rows per sequence into the same page; the fused
        write's one-row-per-page assumption does not hold, so they
        scatter first and attend read-only."""
        return (k_pages is not None and
                not metadata.is_prompt and
                not metadata.spec_verify and
                self._pallas_decode_ok(k_pages, metadata))

    def _pallas_decode_ok(self, k_pages, metadata) -> bool:
        quant_ok = k_pages.dtype in (jnp.bfloat16, jnp.float32) or (
            k_pages.dtype in (jnp.int8, jnp.float8_e5m2) and
            k_pages.shape[1] % 32 == 0)     # 8-bit sublane tile
        # metadata.tp > 1: KV pages are lane-sharded over the mesh and
        # the Pallas kernel is a single-device program; take the
        # GSPMD-partitionable jnp reference path instead (the
        # shard_map wrap is the disaggregated-prefill follow-on seam).
        return (self.use_pallas and jax.default_backend() == "tpu"
                and metadata.tp == 1 and quant_ok)

    def _prefill(self, q, k, v, k_pages, v_pages,
                 metadata: InputMetadata) -> jax.Array:
        batch, seq_len = q.shape[:2]
        prompt_lens = metadata.prompt_lens
        if prompt_lens is None:
            prompt_lens = jnp.full((batch,), seq_len, dtype=jnp.int32)

        # (static, like every choice below: a function of the step
        # program's shapes and types)
        flash = self.use_pallas and takes_prefill_kernel(
            k_pages.dtype if metadata.use_prefix else k.dtype,
            metadata.tp, metadata.sp, self.alibi_slopes is not None)
        if metadata.use_prefix:
            # Attend over [cached prefix ; this chunk] gathered from pages
            # (reference prefix path, triton context_attention_fwd).
            from aphrodite_tpu.ops.kv_quant import dequant_scale
            kv_s = dequant_scale(k_pages.dtype, metadata.kv_scale)
            kv_k = gather_pages(k_pages, metadata.block_tables,
                                self.num_kv_heads)
            kv_v = gather_pages(v_pages, metadata.block_tables,
                                self.num_kv_heads)
            if self.padded_head != self.head_size and not flash:
                kv_k = kv_k[..., :self.head_size]
                kv_v = kv_v[..., :self.head_size]
            if kv_s != 1.0:
                kv_k = kv_k.astype(jnp.float32) * kv_s
                kv_v = kv_v.astype(jnp.float32) * kv_s
            # [b, Hkv, ctx, d] -> [b, ctx, Hkv, d]
            kv_k = kv_k.swapaxes(1, 2)
            kv_v = kv_v.swapaxes(1, 2)
            context_lens = metadata.context_lens
            kv_valid = context_lens + prompt_lens
        else:
            kv_k, kv_v = k, v
            context_lens = jnp.zeros((batch,), dtype=jnp.int32)
            kv_valid = prompt_lens
            if self._ring_eligible(metadata, seq_len):
                return self._ring_prefill(q, k, v, metadata)

        if flash:
            # One kernel for both of the `jnp` branches below: its
            # transient is a tile in VMEM whatever the context, which
            # is all `blocked_from` was for.
            from aphrodite_tpu.ops.pallas.prefill_attention import (
                prefill_flash_attention)
            note_kernel_path(
                "prefill_attention", "pallas",
                "prefill_flash_attention, "
                f"{'gathered prefix' if metadata.use_prefix else 'own keys'}")

            def lanes(x):
                # A head's lanes up to the tile, as the pages hold
                # them and as `_decode` pads: zero lanes add nothing
                # to a score, and the output's slice off below.
                short = self.padded_head - x.shape[-1]
                return jnp.pad(x, ((0, 0),) * 3 + ((0, short),)) \
                    if short else x

            return prefill_flash_attention(
                lanes(q), lanes(kv_k), lanes(kv_v), context_lens,
                kv_valid, self.scale,
                sliding_window=self.sliding_window)[..., :self.head_size]
        note_kernel_path(
            "prefill_attention", "reference",
            f"jnp functions: backend={jax.default_backend()}, "
            f"tp={metadata.tp}, K/V={kv_k.dtype}, "
            f"alibi={self.alibi_slopes is not None}")
        attend = prefill_attention_blocked \
            if takes_blocked_prefill(seq_len, kv_k.shape[1],
                                     self.blocked_from) \
            else prefill_attention
        return attend(
            q, kv_k, kv_v, context_lens, kv_valid, self.scale,
            sliding_window=self.sliding_window,
            alibi_slopes=self.alibi_slopes)

    def _ring_eligible(self, metadata: InputMetadata,
                       seq_len: int) -> bool:
        """Static (trace-time) routing decision for sequence-parallel
        prefill: plain causal prefill at/above the threshold, padded
        length divisible by the sp axis. ALiBi and windows narrower
        than the prompt keep the dense path (the ring kernel implements
        plain causality only)."""
        if metadata.sp is None or self.alibi_slopes is not None:
            return False
        mesh, threshold = metadata.sp
        sp_size = mesh.shape.get("sp", 1)
        if sp_size <= 1 or seq_len < threshold or seq_len % sp_size:
            return False
        if self.sliding_window is not None and \
                seq_len > self.sliding_window:
            return False
        return True

    def _ring_prefill(self, q, k, v, metadata: InputMetadata):
        """Prefill attention sharded over the sp mesh axis: K/V shards
        rotate via ppermute while each device accumulates its queries'
        online softmax (ops/ring_attention.py). Right-pad tokens only
        pollute pad q rows (causal mask), which downstream never reads
        — same contract as the dense path. GQA K/V rotate at Hkv heads
        (the group broadcast happens inside the score einsum)."""
        from aphrodite_tpu.ops.ring_attention import make_ring_fn
        mesh, _ = metadata.sp
        return make_ring_fn(mesh, self.scale)(q, k, v)

    def _decode(self, q, k_pages, v_pages, metadata: InputMetadata,
                knew=None, vnew=None):
        q3 = q.reshape(q.shape[0], self.num_heads, self.head_size)
        if self.padded_head != self.head_size:
            # Pages pad head_dim to the lane tile; zero q lanes leave
            # scores untouched and the output pad lanes slice off below.
            hpad = ((0, 0), (0, 0),
                    (0, self.padded_head - self.head_size))
            q3 = jnp.pad(q3, hpad)
            if knew is not None:
                knew = jnp.pad(knew, hpad)
                vnew = jnp.pad(vnew, hpad)
        # Sliding window: the table is the window group's own and the
        # kernels mask what lies before the newest `window` positions.
        # Quantized pages (int8/fp8) run in-kernel: the int8 scale folds
        # into the score scale and output epilogue (see ops/kv_quant.py).
        from aphrodite_tpu.ops.kv_quant import dequant_scale
        if self._pallas_decode_ok(k_pages, metadata):
            from aphrodite_tpu.ops.pallas.paged_attention import (
                paged_decode_attention)
            note_kernel_path(
                "decode_attention", "pallas",
                "paged_decode_attention, "
                f"{'fused KV write' if knew is not None else 'read-only'}")
            slopes = None if self.alibi_slopes is None else \
                jnp.asarray(self.alibi_slopes, dtype=jnp.float32)
            # Padded table entries hold an out-of-range page id (the XLA
            # gather's fill convention); the kernel DMAs pages raw, so
            # clamp pads to a valid page — masked off by context_lens.
            tables = jnp.minimum(metadata.block_tables,
                                 k_pages.shape[0] - 1)
            # Chunk geometry: when the model runner built a ragged
            # work list it also fixed pages_per_chunk (the list and the
            # kernel's chunk walk must agree); otherwise fall back to
            # the shared policy over the padded table width.
            from aphrodite_tpu.ops.pallas.paged_attention import (
                choose_pages_per_chunk, lane_bytes_of)
            work = metadata.decode_work
            if work is not None and metadata.decode_ppc:
                ppc = metadata.decode_ppc
            else:
                work = None
                ppc = choose_pages_per_chunk(
                    tables.shape[1], k_pages.shape[1],
                    lane_bytes_of(self.num_kv_heads, self.padded_head,
                                  k_pages.dtype))
            result = paged_decode_attention(
                q3, k_pages, v_pages, tables,
                metadata.context_lens, slopes, knew, vnew,
                scale=self.scale,
                kv_scale=dequant_scale(k_pages.dtype,
                                       metadata.kv_scale),
                pages_per_chunk=ppc, work_items=work,
                window=self.sliding_window)
            if knew is not None:
                out, k_pages, v_pages = result
                if self.padded_head != self.head_size:
                    out = out[..., :self.head_size]
                return out[:, None], k_pages, v_pages
            out = result
        else:
            note_kernel_path(
                "decode_attention", "reference",
                f"jnp gather path: backend={jax.default_backend()}, "
                f"tp={metadata.tp}, pages={k_pages.dtype}")
            out = paged_decode_attention_ref(
                q3, k_pages, v_pages, metadata.block_tables,
                metadata.context_lens, self.scale,
                alibi_slopes=self.alibi_slopes,
                kv_scale=metadata.kv_scale, window=self.sliding_window)
        if self.padded_head != self.head_size:
            out = out[..., :self.head_size]
        return out[:, None]  # [batch, 1, H, d]
