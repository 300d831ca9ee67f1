"""Multi-head latent attention over LATENT pages (DeepSeek-V2's MLA).

What a token leaves in the cache of a layer is ONE row `[c | k_r]`:
its normed latent `c` (`latent` lanes) and its one rotary key `k_r`
(`rope` lanes), shared by all heads, zero-padded to the lane tile
(`ops/kv_cache.py::padded_head_size`). The layer's pages are one array
`[pages, page, lanes]` (`common/config.py::PageGroups.latent`), no K/V
pair and no head axis. With `W_kvb` split a head into `W_UK_h`
`[latent, nope]` and `W_UV_h` `[latent, v]`:

- **a prompt step** writes the chunk's rows (the whole-page Pallas
  writer on one TPU, a scatter elsewhere) and attends NOT absorbed:
  its own rows and, past a sequence's first chunk, the prefix's rows
  gathered from the pages (`gather_pages`) are up-projected inside the
  step to `K_h = [c W_UK_h | k_r]` and `V_h = c W_UV_h` (a matmul each
  from the rows as they lie, `_up_weights`), and the prompt attention
  the tree has runs over them (the Pallas flash kernel on one TPU, q
  and K zero-padded to whole lane tiles a head, 192 -> 256 at Sarvam's
  widths, and V at its OWN padded width, 128, which is the output's
  and `o_proj`'s; the `jnp` functions elsewhere, which have one head
  width, with V zero-padded to the keys' and the output sliced);
- **a decode step** attends ABSORBED: `q~_h = [q_nope_h W_UK_h^T |
  q_rope_h]`, scores `q~_h . [c | k_r]` over the rows as the pages
  hold them, `o~_h = sum_t p_t c_t`, `o_h = o~_h W_UV_h`: one KV
  "head" of `lanes` under every query row whose values are the first
  `latent` lanes of its keys, which is the decode kernel's `latent`
  (`ops/pallas/paged_attention.py`: one ring, a page copied once, the
  new row written by the same call), or `paged_decode_attention_ref`
  over the one array as both K and V elsewhere.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from aphrodite_tpu.common.utils import note_kernel_path
from aphrodite_tpu.modeling.input_metadata import InputMetadata
from aphrodite_tpu.modeling.layers.attention import (
    takes_blocked_prefill, takes_prefill_kernel)
from aphrodite_tpu.ops.attention import (BLOCKED_FROM,
                                         paged_decode_attention_ref,
                                         prefill_attention,
                                         prefill_attention_blocked)
from aphrodite_tpu.ops.kv_cache import (gather_pages, padded_head_size,
                                        write_to_latent_cache)


class LatentAttention:
    """Stateless dispatcher, as `PagedAttention` is for K/V pairs."""

    def __init__(self, num_heads: int, nope: int, rope: int, v_dim: int,
                 latent: int, scale: float, page_group: int = 0,
                 blocked_from: int = BLOCKED_FROM) -> None:
        self.num_heads = num_heads
        self.nope, self.rope, self.v_dim = nope, rope, v_dim
        self.latent = latent
        self.scale = float(scale)
        self.page_group = page_group
        self.blocked_from = blocked_from
        #: a row of the pages: `[c | k_r]` up to the lane tile
        self.lanes = padded_head_size(latent + rope)

    def _rows(self, c: jax.Array, k_r: jax.Array) -> jax.Array:
        """`[c | k_r | 0]`: `[..., lanes]` as the pages hold a token's
        row (and as the absorbed query `[q W_UK^T | q_rope | 0]` meets
        it)."""
        pad = self.lanes - self.latent - self.rope
        return jnp.concatenate(
            [c, k_r] + ([jnp.zeros(c.shape[:-1] + (pad,), c.dtype)]
                        if pad else []), axis=-1)

    def __call__(
        self,
        q_nope: jax.Array,      # [batch, seq, heads, nope]
        q_rope: jax.Array,      # [batch, seq, heads, rope], rotated
        c: jax.Array,           # [batch, seq, latent], normed
        k_r: jax.Array,         # [batch, seq, rope], rotated
        w_uk: jax.Array,        # [latent, heads, nope]
        w_uv: jax.Array,        # [latent, heads, v_dim]
        pages: Optional[jax.Array],
        metadata: InputMetadata,
    ) -> Tuple[jax.Array, Optional[jax.Array], jax.Array]:
        """Returns `(out [batch, seq, heads * v_dim], the updated
        pages, prefix tokens this step up-projected from the pages:
        an int32 scalar)`. `pages` None: a prompt step without a
        cache (memory profiling)."""
        metadata = metadata.for_group(self.page_group)
        batch, seq = q_nope.shape[:2]
        if not metadata.is_prompt:
            out, pages = self._decode(q_nope[:, 0], q_rope[:, 0],
                                      self._rows(c[:, 0], k_r[:, 0]),
                                      w_uk, w_uv, pages, metadata)
            return out.reshape(batch, 1, -1), pages, jnp.int32(0)
        if pages is not None:
            pages = self._write_prompt(
                self._rows(c, k_r).reshape(-1, self.lanes), pages,
                metadata)
        out, expanded = self._prefill(q_nope, q_rope, c, k_r, w_uk, w_uv,
                                      pages, metadata)
        return out.reshape(batch, seq, -1), pages, expanded

    def _pallas_ok(self, pages, metadata) -> bool:
        return (jax.default_backend() == "tpu" and metadata.tp == 1
                and pages.dtype in (jnp.bfloat16, jnp.float32))

    def _write_prompt(self, rows, pages, metadata):
        from aphrodite_tpu.ops.pallas.kv_write import (
            can_use_pallas_writer, write_kv_pages_prefill)
        if (self._pallas_ok(pages, metadata) and
                metadata.prefill_cells is not None and
                can_use_pallas_writer(pages.dtype, pages.shape[1],
                                      self.lanes)):
            note_kernel_path("kv_write", "pallas",
                             "prefill whole-page writer, latent rows")
            pid, sblk, vld = metadata.prefill_cells
            return write_kv_pages_prefill(rows, None, pages, None, pid,
                                          sblk, vld)
        # keep the scatter un-fused from its readers (the gather of
        # the prefix below), as `PagedAttention` does
        return jax.lax.optimization_barrier(
            write_to_latent_cache(rows, pages, metadata.slot_mapping))

    def _up_weights(self, w_uk, w_uv, width: int, v_width: int):
        """`(W_K [lanes, heads, width], W_V [lanes, heads, v_width])`:
        a page's row `[c | k_r | 0]` times `W_K` is the row's key of
        every head, `[c W_UK_h | k_r | 0]` (`k_r` through an identity,
        which is exact), times `W_V` its value `[c W_UV_h | 0]`, at
        the head widths the prompt attention runs at (`v_width` =
        `v_dim`: no zero column): one matmul each from the rows as
        the pages hold them, and no slice, concatenation or pad of a
        `[keys, heads, width]` array."""
        heads = self.num_heads
        eye = jnp.eye(self.rope, width, k=self.nope, dtype=w_uk.dtype)
        tail = self.lanes - self.latent - self.rope
        w_k = jnp.concatenate([
            jnp.pad(w_uk, ((0, 0), (0, 0), (0, width - self.nope))),
            jnp.broadcast_to(eye[:, None, :], (self.rope, heads, width)),
            jnp.zeros((tail, heads, width), w_uk.dtype)])
        w_v = jnp.pad(w_uv, ((0, self.lanes - self.latent), (0, 0),
                             (0, v_width - self.v_dim)))
        return w_k, w_v

    def _prefill(self, q_nope, q_rope, c, k_r, w_uk, w_uv, pages,
                 metadata):
        batch, seq = q_nope.shape[:2]
        prompt_lens = metadata.prompt_lens
        if prompt_lens is None:
            prompt_lens = jnp.full((batch,), seq, dtype=jnp.int32)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        if metadata.use_prefix:
            # [cached prefix ; this chunk], read back from the pages
            # (the chunk's own rows were written above) and
            # up-projected here
            rows = gather_pages(pages, metadata.block_tables, 1)[:, 0]
            context_lens = metadata.context_lens
            kv_valid = context_lens + prompt_lens
            expanded = jnp.sum(context_lens, dtype=jnp.int32)
        else:
            rows = self._rows(c, k_r)
            context_lens = jnp.zeros((batch,), dtype=jnp.int32)
            kv_valid = prompt_lens
            expanded = jnp.int32(0)
        flash = takes_prefill_kernel(rows.dtype, metadata.tp, metadata.sp,
                                     False)
        # (the kernel's head widths are whole lane tiles, the values'
        # their own; the `jnp` functions have one width)
        width, v_width = (padded_head_size(q.shape[-1]),
                          padded_head_size(self.v_dim)) \
            if flash else (q.shape[-1],) * 2
        w_k, w_v = self._up_weights(w_uk, w_uv, width, v_width)
        k = jnp.einsum("btl,lhd->bthd", rows, w_k)
        v = jnp.einsum("btl,lhd->bthd", rows, w_v)
        if flash:
            from aphrodite_tpu.ops.pallas.prefill_attention import (
                prefill_flash_attention)
            note_kernel_path(
                "prefill_attention", "pallas",
                "prefill_flash_attention over up-projected latent rows, "
                f"{'gathered prefix' if metadata.use_prefix else 'own keys'}"
                f", keys {width} lanes a head, values {v_width}")
            q = jnp.pad(q, ((0, 0),) * 3 + ((0, width - q.shape[-1]),))
            out = prefill_flash_attention(q, k, v, context_lens, kv_valid,
                                          self.scale)
        else:
            note_kernel_path(
                "prefill_attention", "reference",
                "jnp functions over up-projected latent rows: "
                f"backend={jax.default_backend()}, tp={metadata.tp}")
            attend = prefill_attention_blocked \
                if takes_blocked_prefill(seq, k.shape[1],
                                         self.blocked_from) \
                else prefill_attention
            out = attend(q, k, v, context_lens, kv_valid, self.scale)
        return out[..., :self.v_dim], expanded

    def _decode(self, q_nope, q_rope, row, w_uk, w_uv, pages, metadata):
        """`q_nope` `[b, heads, nope]`, `q_rope` `[b, heads, rope]`,
        `row` `[b, lanes]` the new token's; absorbed on both sides."""
        batch = q_nope.shape[0]
        q = self._rows(jnp.einsum("bhd,chd->bhc", q_nope, w_uk),
                       q_rope)                      # [b, heads, lanes]
        if self._pallas_ok(pages, metadata):
            from aphrodite_tpu.ops.pallas.paged_attention import (
                choose_pages_per_chunk, lane_bytes_of,
                paged_decode_attention)
            note_kernel_path("decode_attention", "pallas",
                             "paged_decode_attention over latent pages, "
                             "fused write of the one row")
            note_kernel_path("kv_write", "pallas",
                             "fused into the decode attention kernel")
            tables = jnp.minimum(metadata.block_tables, pages.shape[0] - 1)
            work = metadata.decode_work
            if work is not None and metadata.decode_ppc:
                ppc = metadata.decode_ppc
            else:
                work = None
                ppc = choose_pages_per_chunk(
                    tables.shape[1], pages.shape[1],
                    lane_bytes_of(1, self.lanes, pages.dtype))
            out, pages = paged_decode_attention(
                q, pages, None, tables, metadata.context_lens, None,
                row.reshape(batch, 1, self.lanes), None,
                scale=self.scale, pages_per_chunk=ppc, work_items=work,
                latent=self.latent)
        else:
            note_kernel_path(
                "decode_attention", "reference",
                "jnp gather path over latent pages: "
                f"backend={jax.default_backend()}, tp={metadata.tp}")
            pages = jax.lax.optimization_barrier(write_to_latent_cache(
                row, pages, metadata.slot_mapping))
            out = paged_decode_attention_ref(
                q, pages, pages, metadata.block_tables,
                metadata.context_lens, self.scale)[..., :self.latent]
        return jnp.einsum("bhc,chd->bhd", out, w_uv), pages
