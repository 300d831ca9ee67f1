"""Builds fixed-shape batches and runs the jitted step functions.

Reference: `aphrodite/task_handler/model_runner.py` (_prepare_prompt
`:102`, _prepare_decode `:245`, _prepare_sample `:372`, CUDA-graph capture
`:654`). TPU-native mapping:

- The reference's CUDA-graph batch-size buckets (`model_runner.py:31`)
  become jit compile-cache buckets: every (phase, batch-bucket,
  seq/page-bucket) shape compiles once and is replayed from XLA's
  compilation cache — same amortization, no graph API needed.
- Ragged host lists are padded into the fixed-shape InputMetadata ABI;
  padded lanes use out-of-range indices so cache scatters drop them
  (see ops/kv_cache.py).
- KV page buffers are DONATED to the step function, so the cache update
  is in-place in HBM (reference updates in place by pointer).
- Sampling runs on the real (unpadded) logit rows.
"""
from __future__ import annotations

import bisect
import contextlib
import functools
from typing import Dict, List, NamedTuple, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from aphrodite_tpu.common import flags, tracing
from aphrodite_tpu.common.config import (ModelConfig, ParallelConfig,
                                         SchedulerConfig)
from aphrodite_tpu.common.logger import init_logger
from aphrodite_tpu.common.sampling_params import SamplingType
from aphrodite_tpu.common.sequence import (SamplerOutput,
                                           SequenceGroupMetadata)
from aphrodite_tpu.executor.program_store import StoredProgram
from aphrodite_tpu.modeling.input_metadata import GroupView, InputMetadata
from aphrodite_tpu.modeling.layers.attention import (takes_blocked_prefill,
                                                     takes_prefill_kernel)
from aphrodite_tpu.modeling.layers.rejection import delta_rejection_length
from aphrodite_tpu.modeling.layers.sampler import (Sampler, fused_sample,
                                                   _fused_sample_jit)
from aphrodite_tpu.modeling.sampling_metadata import (OutputMetadata,
                                                      PersistentMetadata,
                                                      SamplingMetadata)
from aphrodite_tpu.ops.attention import BLOCKED_FROM, count_prefill_tiles
from aphrodite_tpu.ops.kv_cache import copy_pages as _copy_pages_op
from aphrodite_tpu.ops.kv_cache import padded_head_size
from aphrodite_tpu.ops.pallas.paged_attention import (
    build_decode_work_list, choose_pages_per_chunk, count_decode_pages,
    lane_bytes_of, padded_work_length)

logger = init_logger(__name__)

# Decode batch buckets (reference capture sizes, model_runner.py:31).
# Power-of-two-and-a-half spacing: every (batch-bucket, pages-bucket,
# burst-length) triple is its own compiled program, and a 32-layer
# step program takes tens of seconds to compile, so a fluctuating
# serving batch must hit FEW buckets (35 multiples-of-8 buckets made
# cold serving spend more time compiling than decoding); <=33% padding
# waste per step.
_DECODE_BATCH_BUCKETS = [1, 2, 4, 8, 12, 16, 24, 32, 48, 64, 96, 128,
                         192, 256, 384, 512]

_PREFILL_BATCH_BUCKETS = [1, 2, 4, 8, 16, 32]
_PAGES_BUCKET = 8          # block-table width granularity (Pallas chunk)


def _bucket(value: int, buckets: List[int]) -> int:
    idx = bisect.bisect_left(buckets, value)
    if idx == len(buckets):
        return buckets[-1] if value <= buckets[-1] else value
    return buckets[idx]


#: Block tables wider than this many pages are padded to a multiple of
#: `_WIDE_PAGES_BUCKET`: a table width is part of a step program's key,
#: the decode kernel copies live pages only whatever the width, and a
#: batch of 8k contexts that grows by 700 tokens would else walk
#: through six programs a batch bucket. Their decode work lists, once
#: within a factor two of a cell for every chunk of every row, are
#: padded to that (`ModelRunner._work_length`).
_WIDE_TABLE = 128
_WIDE_PAGES_BUCKET = 64
#: A decode batch of this many rows or more takes its tables as wide
#: ones, and no narrower than `_WIDE_TABLE`: its widest row decides
#: the width, many rows that joined together grow together, and in
#: 8-page steps 128 rows growing from 512 tokens to 1,536 walked
#: through eight programs of the largest bucket, each a stall of 7 s
#: (17 s on an empty cache) with every row waiting. The tables' bytes
#: are nothing beside that (`[128, 128]` int32 a step) and a dead item
#: of the work list costs the kernel half a microsecond.
_WIDE_ROWS = 64
#: windows a call of the summarise program pools (`summarise_windows`):
#: one shape, so one program; more windows in a round take more calls
#: (the callers of a group that joined together reach an edge together,
#: four in the benchmark's cell; a pad row costs what a window costs)
_SUMMARISE_ROWS = 4


def _pow2_bucket(value: int, lo: int = 16) -> int:
    b = lo
    while b < value:
        b *= 2
    return b


class SpecVerifyResult(NamedTuple):
    """Per-group outcome of one speculative verify dispatch.

    `samples` is the ACCEPTED run in emission order (1..k+1
    SequenceOutputs): the matched draft prefix plus the first-mismatch
    target sample, or the bonus sample on full acceptance. `accepted`
    counts matched drafts (the drafter's EWMA signal, independent of
    any stop condition the engine applies afterwards)."""
    samples: list
    accepted: int
    proposed: int


class StepHandle:
    """One step of a round, enqueued: its packed result is still on
    the device, until `ModelRunner.pull` brings it over and
    `finalize_step` turns it into `outputs`. Lets a combined round
    enqueue prefill + decode burst back-to-back and sync once, and the
    engine dispatch a round before it pulls the one before. A step
    that ran through the raw-logits route, synced by nature, has its
    `outputs` from the start and no `packed`.

    `num_steps`: the device iterations its program ran (a burst's
    scan; `packed` is then stacked [num_steps, rows, w]). `verify`: of
    a speculative verify step, each group's drafted tokens."""

    __slots__ = ("packed", "sampling", "plan", "num_steps", "verify",
                 "outputs", "counts", "is_prompt")

    def __init__(self, packed, sampling, plan, num_steps: int = 1,
                 verify: Optional[List[List[int]]] = None,
                 outputs: Optional[list] = None, counts=None,
                 is_prompt: bool = False) -> None:
        # `counts`: what the model counted in the step's program
        # (`ModelRunner.step_counters`), on the device beside `packed`
        self.counts = counts
        self.is_prompt = is_prompt
        self.packed = packed
        self.sampling = sampling
        self.plan = plan
        self.num_steps = num_steps
        self.verify = verify
        self.outputs = outputs

    def is_ready(self) -> bool:
        """Whether the step's program has finished on the device;
        never blocks."""
        return self.packed.is_ready()

    def token_cells(self) -> Dict[int, int]:
        """Where each sequence's sampled token lies in `packed[:, :2]`
        read row by row: sequence id -> 2 * row + column (`fused_sample`
        puts the greedy token in column 0 and the draw in column 1).
        Single-sequence groups, one row each, as the fused path at
        best_of 1 has them."""
        return {
            seq_ids[0]: 2 * row + (
                0 if params.sampling_type == SamplingType.GREEDY else 1)
            for row, (seq_ids, params) in enumerate(
                self.sampling.seq_groups)}


class ModelRunner:
    """Drives one model replica (single chip or one SPMD mesh)."""

    #: the state slots of a model with recurrent state beside its
    #: pages (None: it has none): the state arrays follow the
    #: `page_pairs` pairs of page arrays in `kv_caches`, and a pad
    #: row's slot is the scratch one, `num_state_slots`
    num_state_slots: Optional[int] = None

    def __init__(
        self,
        model,
        params,
        model_config: ModelConfig,
        scheduler_config: SchedulerConfig,
        page_size: int,
        num_slots: int,
        mesh=None,
        kv_scale: float = 1.0,
        sp: Optional[tuple] = None,         # (Mesh, threshold) or None
        kv_cache_dtype=jnp.bfloat16,
        tracer: Optional[tracing.Tracer] = None,
        num_state_slots: Optional[int] = None,
        program_store=None,     # a ProgramStore, or None
    ) -> None:
        # The engine's span accumulators (its own, when built alone).
        self.tracer = tracer or tracing.Tracer()
        # Where the step programs' executables are kept between
        # processes (`executor/program_store.py`); never under a mesh,
        # whose jitted path is left as it is.
        self.program_store = program_store \
            if mesh is None and sp is None else None
        self.model = model
        self.params = params
        self.model_config = model_config
        self.scheduler_config = scheduler_config
        self.page_size = page_size
        self.num_slots = num_slots          # OOB pad value for slots
        self.mesh = mesh
        self.kv_scale = kv_scale            # int8 KV dequant scale
        self.sp = sp                        # ring-prefill routing
        # Mesh sharding plan for the step programs: weights and KV
        # pages arrive committed with their NamedShardings (loader /
        # CacheEngine); every host-built batch input is committed
        # REPLICATED here (dp>1 would shard the batch dim instead —
        # see the README Multichip section for why dp is descoped).
        # Explicit specs on every operand mean GSPMD solves no layout
        # inference for the inputs: the per-layer collectives are the
        # ones the layer annotations (layers/linear.py shard_along)
        # declare, which is what the MULTICHIP ICI cost model priced.
        self._tp = int(mesh.shape["tp"]) if mesh is not None else 1
        self._input_sharding = None
        if mesh is not None:
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P
            self._input_sharding = NamedSharding(mesh, P())
        # Whether the step programs' results are committed to their
        # device: a jit output is iff an operand is, so they follow
        # the weights (a loader's `device_put` commits; weights made
        # by a jitted program, as the dummy ones are, do not), and
        # under a mesh everything is. `_dev(..., committed=True)`
        # follows them, to `_batch_sharding`.
        self._results_committed = mesh is not None or any(
            getattr(leaf, "committed", False)
            for leaf in jax.tree_util.tree_leaves(params))
        self._batch_sharding = self._input_sharding or \
            jax.sharding.SingleDeviceSharding(jax.devices()[0])
        # Whether the Pallas prefill page writer can ever run (TPU +
        # fp page dtype + single-device mesh — the writer is a
        # per-chip program; tp-sharded pages take the scatter path):
        # gates building its cell descriptors at all — ineligible
        # configs skip the host loop and keep ONE jit treedef for
        # aligned and unaligned prompts.
        self._prefill_writer_ok = (
            jax.default_backend() == "tpu" and
            (mesh is None or mesh.size == 1) and
            kv_cache_dtype in (jnp.bfloat16, jnp.float32) and
            page_size % 8 == 0)
        self.sampler = Sampler(model_config.get_vocab_size(),
                               put=self._dev)
        # Block-table width granularity: 8 pages at the default page 16
        # (the Pallas chunk unit), half that for 32-token pages so a
        # short context isn't rounded up to 2x its KV (decode attention
        # is DMA-COUNT bound — bigger pages halve the per-cell DMA
        # count only if the table width doesn't pad back up).
        self.pages_bucket = _PAGES_BUCKET if page_size <= 16 else \
            max(2, _PAGES_BUCKET // 2)
        # What one token of the decode kernel's widest head block
        # holds in K: sizes its work item (choose_pages_per_chunk).
        padded_head = padded_head_size(model_config.get_head_size())
        self.attn_lane_bytes = max(
            lane_bytes_of(h, padded_head, kv_cache_dtype)
            for h in model_config.get_kv_heads_per_layer())
        #: the model's page groups ("full" or "window" each), and the
        #: window; one plain group for most models
        self.page_groups = model_config.get_page_groups()
        self.num_state_slots = num_state_slots
        self.page_pairs = self.page_groups.layers_per_group
        #: what the model counts inside its step programs
        #: (`tracing.NAMES`), pulled with a step's result
        self.step_counters: Tuple[str, ...] = tuple(
            getattr(model, "step_counters", ()))
        #: tokens a chunk of the model's delta-rule layers takes
        #: (`ops/pallas/kda.py`), None for every other model: such a
        #: model's state layers count as `kda.*`, a Mamba model's as
        #: `ssm.*`
        self.kda_chunk_tokens: Optional[int] = getattr(
            model, "kda_chunk_tokens", None)
        #: queries x keys a row from which the model's prompt attention
        #: goes in tiles (`PagedAttention.blocked_from`)
        self.prefill_blocked_from: int = getattr(
            model, "prefill_blocked_from", BLOCKED_FROM)
        #: whether a prompt step's attention is the Pallas flash
        #: kernel, by whether it reads a prefix: K and V are the
        #: chunk's own, in the model's type, or gathered from the pages
        #: (a model whose layers carry an ALiBi bias says `uses_alibi`;
        #: none does today)
        self._prefill_kernel = tuple(
            takes_prefill_kernel(kv_dtype, self._tp, sp,
                                 getattr(model, "uses_alibi", False))
            for kv_dtype in (model_config.dtype, kv_cache_dtype))

        # LoRA: bucket keys carrying slot-stacked adapter tensors, and a
        # slot resolver installed by the executor's WorkerLoRAManager.
        from aphrodite_tpu.lora.layers import LORA_A
        self.lora_buckets = [k for k, b in params.items() if LORA_A in b]
        self.lora_slot_of = None
        # The last decode work list sent, for each kind of page group:
        # (what it was built from, its device copy); see
        # _decode_work_list.
        self._decode_work: Dict[str, tuple] = {}

        # One jitted program per (is_prompt, use_prefix); shape buckets
        # land in XLA's compile cache keyed by array shapes.
        self._step_fn = self._stored(jax.jit(
            self._step,
            static_argnames=("is_prompt", "use_prefix"),
            donate_argnums=(3,),      # kv_caches
        ), donated=(3,))
        # Single-dispatch step+sample: one device program and ONE host
        # sync per scheduling round (the two-program split pays a
        # second dispatch and a second sync every round). Routes
        # needing raw logits (host logits processors, logprobs) use
        # _step_fn instead.
        self._step_sample_fn = self._stored(jax.jit(
            self._step_sample,
            static_argnames=("is_prompt", "use_prefix", "max_best_of",
                             "num_topk"),
            donate_argnums=(3,),      # kv_caches
        ), donated=(3,))
        self._burst_scan_fn = self._stored(jax.jit(
            self._burst_scan,
            static_argnames=("max_best_of", "num_topk", "num_steps"),
            donate_argnums=(3,),      # kv_caches
        ), donated=(3,))
        self._copy_fn = jax.jit(self._copy_blocks, donate_argnums=(0,))
        self._copy_state_fn = jax.jit(self._copy_state,
                                      donate_argnums=(0,))
        # The pooled keys and values of the windows that rows have
        # finished (a model with a pooled page group): a program of
        # its own, so that a step in which no row closes a window
        # pays nothing.
        self._summarise_fn = self._stored(jax.jit(
            self._summarise, donate_argnums=(1,)), donated=(1,))
        # Small, and apart from the step programs on purpose: a decode
        # step whose tokens are still on the device takes them through
        # this, so `_step_sample` keeps its signature and its compiled
        # programs.
        self._feed_fn = self._stored(jax.jit(
            self._feed, out_shardings=self._input_sharding))
        # The prompt-step source of a feed that has none, by shape.
        self._no_source: Dict[tuple, jax.Array] = {}

    def _stored(self, jitted, donated: Tuple[int, ...] = ()):
        """`jitted`, one of this runner's programs, behind the program
        store where there is one: a process that asks for a program
        the store has loads it and neither traces nor lowers.
        `donated`: its `donate_argnums`, the page arrays. They and the
        parameters before them keep their shapes for the process's
        life: the key holds them, a round's lookup skips them. What
        the function reads of this runner beside the engine's
        configurations goes into the key with them."""
        if self.program_store is None:
            return jitted
        return StoredProgram(
            self.program_store, jitted, jitted.__name__,
            closes_over=dict(
                page_size=self.page_size, num_slots=self.num_slots,
                num_state_slots=self.num_state_slots,
                kv_scale=self.kv_scale),
            donate_argnums=donated,
            stable_argnums=(0,) + donated if donated else ())

    # ---- mesh placement helpers ----

    def _dev(self, arr, committed: bool = False):
        """Host array -> device, with the batch-input sharding made
        EXPLICIT under a mesh (replicated NamedSharding; the same one
        host->device transfer jnp.asarray pays, now with a declared
        placement instead of a GSPMD guess). `committed`: committed
        to its device on a single chip too where the step programs'
        results are (`_results_committed`). A program is lowered again
        for an operand that changes between committed and not: the
        packed decode batch, which reaches its step program either
        from here or as a result of `_feed_fn`, is the same both
        ways."""
        if self._input_sharding is None and not (
                committed and self._results_committed):
            return jnp.asarray(arr)
        return jax.device_put(arr, self._batch_sharding)

    def _mesh_ctx(self):
        """Context every jitted dispatch runs under: the mesh (so the
        layer annotations' bare PartitionSpecs resolve at trace time),
        or a no-op for single-chip."""
        return jax.set_mesh(self.mesh) if self.mesh is not None else \
            contextlib.nullcontext()

    # ---- jitted bodies ----

    def _table_width(self, pages: int, rows: int = 1) -> int:
        """A block table's padded width (a step program's key), for a
        decode batch padded to `rows`."""
        if rows >= _WIDE_ROWS:
            pages = max(pages, _WIDE_TABLE)
        bucket = self.pages_bucket if pages <= _WIDE_TABLE \
            else _WIDE_PAGES_BUCKET
        return max(bucket, -(-pages // bucket) * bucket)

    def _unpacked(self, input_ids, positions, metadata):
        """A decode batch reaches the program as ONE int32 array
        (`_send_decode_batch`), in the place of its block tables: each
        row's token, position, slot, context length, then its table.
        The program slices the columns. With page groups the row goes
        on with, for each group, the tokens its table has let go of
        and then the table (`group_layout` has the widths): a group's
        context counts from its table's first page, and its slot is
        read off its table here."""
        if metadata.slot_mapping is not None:
            return input_ids, positions, metadata
        rows = metadata.block_tables
        if not metadata.group_layout:
            return rows[:, 0:1], rows[:, 1:2], metadata.replace(
                slot_mapping=rows[:, 2], context_lens=rows[:, 3],
                block_tables=rows[:, 4:])
        pos, ctx, at, views = rows[:, 1], rows[:, 3], 4, []
        for view, width in zip(metadata.groups, metadata.group_layout):
            let_go, table = rows[:, at], rows[:, at + 1:at + 1 + width]
            at += 1 + width
            own = pos - let_go
            page = jnp.take_along_axis(
                table, (own // self.page_size)[:, None], axis=1)[:, 0]
            views.append(view.replace(
                slot_mapping=jnp.minimum(
                    page * self.page_size + own % self.page_size,
                    self.num_slots),
                block_tables=table, context_lens=ctx - let_go))
        return rows[:, 0:1], rows[:, 1:2], metadata.replace(
            slot_mapping=views[0].slot_mapping,
            block_tables=views[0].block_tables,
            context_lens=views[0].context_lens, groups=tuple(views),
            # (the last column, where the model has state slots)
            state_slots=None if self.num_state_slots is None
            else rows[:, at])

    def _logits(self, params, input_ids, positions, kv_caches, metadata,
                sel_indices):
        """The model step and the logits of the sampled rows (every row
        of a decode batch: it sends no `sel_indices`)."""
        input_ids, positions, metadata = self._unpacked(
            input_ids, positions, metadata)
        hidden, new_caches = self.model(params, input_ids, positions,
                                        kv_caches, metadata)
        rows = hidden.reshape(-1, hidden.shape[-1])
        if sel_indices is not None:
            rows = jnp.take(rows, sel_indices, axis=0)
        return self.model.compute_logits(params, rows), new_caches

    def _with_counts(self, packed):
        """A step's result with what the model counted in the same
        program (`step_counters`) beside it, for the one pull; the
        result alone for a model that counts nothing."""
        if not self.step_counters:
            return packed
        return packed, self.model.take_step_counts()

    def _step(self, params, input_ids, positions, kv_caches, metadata,
              sel_indices, *, is_prompt: bool, use_prefix: bool):
        meta = metadata.replace(is_prompt=is_prompt, use_prefix=use_prefix)
        return self._logits(params, input_ids, positions, kv_caches, meta,
                            sel_indices)

    def _step_sample(self, params, input_ids, positions, kv_caches,
                     metadata, sel_indices, tensors, key_parts, *,
                     is_prompt: bool, use_prefix: bool, max_best_of: int,
                     num_topk: int):
        """_step with the fused sampler in the same program (fast path:
        no host logits processors, no logprob requests)."""
        meta = metadata.replace(is_prompt=is_prompt,
                                use_prefix=use_prefix)
        logits, new_caches = self._logits(params, input_ids, positions,
                                          kv_caches, meta, sel_indices)
        packed, _ = fused_sample(
            logits, tensors, key_parts, max_best_of=max_best_of,
            num_topk=num_topk, need_logprobs=False)
        return self._with_counts(packed), new_caches

    def _burst_step(self, params, input_ids, positions, kv_caches,
                    metadata, tensors, key_parts, greedy_mask, pos_cap,
                    step_salt, *, max_best_of: int, num_topk: int):
        """One multi-step-decode iteration, fully on device: model step,
        fused sampling, and next-step input computation (token feedback,
        advanced positions/slots from the block table) — so K iterations
        chain with zero host syncs between them.

        `pos_cap` [batch, 1] is each row's last reserved position
        (current pos + min(tokens remaining, model-len room)): rows the
        burst overshoots stop advancing and rewrite their own final
        slot with (discarded) garbage instead of walking the block
        table past their reservation — so the scheduler only reserves
        pages a row can actually use (advisor r3)."""
        hidden, new_caches = self.model(params, input_ids, positions,
                                        kv_caches, metadata)
        flat = hidden.reshape(-1, hidden.shape[-1])
        logits = self.model.compute_logits(params, flat)
        packed, _ = fused_sample(
            logits, tensors, key_parts.at[:, 1].add(step_salt),
            max_best_of=max_best_of, num_topk=num_topk,
            need_logprobs=False)
        next_tok = jnp.where(greedy_mask, packed[:, 0], packed[:, 1])
        next_ids = next_tok[:, None].astype(jnp.int32)
        next_pos = jnp.minimum(positions + 1, pos_cap)
        p = next_pos[:, 0]
        page = jnp.take_along_axis(metadata.block_tables,
                                   (p // self.page_size)[:, None],
                                   axis=1)[:, 0]
        next_slots = jnp.minimum(
            page * self.page_size + p % self.page_size, self.num_slots)
        # ctx tracks pos+1 exactly (sliding window never bursts), so the
        # clamp rides along: an overshot row's fused-kernel write pos
        # (ctx-1) pins to its cap slot.
        next_meta = metadata.replace(
            slot_mapping=next_slots,
            context_lens=p + 1)
        return packed, next_ids, next_pos, next_meta, new_caches

    def _burst_scan(self, params, input_ids, positions, kv_caches,
                    metadata, tensors, key_parts, greedy_mask, pos_cap,
                    *, num_steps: int, max_best_of: int, num_topk: int):
        """The whole K-step decode burst as ONE compiled program
        (lax.scan over _burst_step): K separate step dispatches each
        pay a dispatch and a host sync, one scan dispatch pays them
        once. Returns stacked packed results [num_steps, rows, w]."""
        def body(carry, t):
            ids, pos, meta, kv = carry
            packed, ids, pos, meta, kv = self._burst_step(
                params, ids, pos, kv, meta, tensors, key_parts,
                greedy_mask, pos_cap, t,
                max_best_of=max_best_of, num_topk=num_topk)
            return (ids, pos, meta, kv), packed

        (_, _, _, kv_caches), packed = jax.lax.scan(
            body, self._unpacked(input_ids, positions, metadata) +
            (kv_caches,), jnp.arange(num_steps, dtype=jnp.int32))
        return packed, kv_caches

    @staticmethod
    def _feed(rows, decode_packed, prompt_packed):
        """The token column of a packed decode batch, filled on the
        device. A row whose token the step before has sampled and the
        host has not pulled holds -(1 + cell) in that column: `cell`
        counts through `packed[:, :2]` of that round's decode step row
        by row, then through its prompt step's (`StepHandle.
        token_cells`). Every other row keeps the token the host
        wrote."""
        cells = jnp.concatenate([decode_packed[:, :2].reshape(-1),
                                 prompt_packed[:, :2].reshape(-1)])
        token = rows[:, 0]
        fed = jnp.take(cells, jnp.maximum(-token - 1, 0), mode="clip")
        return rows.at[:, 0].set(jnp.where(token < 0, fed, token))

    def _copy_blocks(self, kv_caches, src, dst):
        """(a place's arrays: a K/V pair, or a latent page's one)"""
        pages = self.page_pairs
        return [tuple(_copy_pages_op(array, src, dst) for array in place)
                for place in kv_caches[:pages]] + list(kv_caches[pages:])

    def _copy_state(self, kv_caches, src, dst):
        pages = self.page_pairs
        return list(kv_caches[:pages]) + [
            tuple(a.at[:, dst].set(a[:, src]) for a in arrays)
            for arrays in kv_caches[pages:]]

    def _summarise(self, params, kv_caches, src, dst):
        pages = self.page_pairs
        return self.model.summarise_windows(
            params, kv_caches[:pages], src, dst) + list(kv_caches[pages:])

    def summarise_windows(self, kv_caches,
                          closes: List[Tuple[List[int], List[int]]]):
        """The windows that pooled page groups closed this round
        (`BlockSpaceManager.take_window_closes`): each window's pages
        are pooled into the summary pages taken for them, in every
        layer, before the round's steps and after every step already
        sent (device order), `_SUMMARISE_ROWS` windows a call of the
        one program (pad rows read page 0 and write the out-of-range
        page, which the scatter drops)."""
        with self.tracer.span("runner.summarise"), self._mesh_ctx():
            for at in range(0, len(closes), _SUMMARISE_ROWS):
                kv_caches = self._summarise_fn(
                    self.params, kv_caches, *self._closed_block_lists(
                        closes[at:at + _SUMMARISE_ROWS]))
        return kv_caches

    def _closed_block_lists(self, closes):
        """`(src, dst)` of one call of the summarise program, on the
        device: each closed window's pages and its summary pages, a
        row a window, padded to `_SUMMARISE_ROWS` rows."""
        src = np.zeros((_SUMMARISE_ROWS, len(closes[0][0])), dtype=np.int32)
        dst = np.full((_SUMMARISE_ROWS, len(closes[0][1])),
                      self.num_slots // self.page_size, dtype=np.int32)
        for i, (window, summary) in enumerate(closes):
            src[i], dst[i] = window, summary
        return self._dev(src), self._dev(dst)

    def copy_state(self, kv_caches, copies: List[Tuple[int, int]]):
        """A fork's state: each child's slot takes its parent's rows
        of every state array (every layer's: the slot axis follows the
        layer axis), before the round's steps. Padded to a
        bucket with the scratch slot onto itself."""
        padded = _pow2_bucket(len(copies), lo=8)
        src = np.full((padded,), self.num_state_slots, dtype=np.int32)
        dst = src.copy()
        src[:len(copies)], dst[:len(copies)] = zip(*copies)
        with self._mesh_ctx():
            return self._copy_state_fn(kv_caches, self._dev(src),
                                       self._dev(dst))

    # ---- LoRA slot plumbing ----

    def write_lora_slot(self, bucket_key: str, slot: int, a, b) -> None:
        """Place one adapter's (A [in, r], B [r, out]) into slot
        `slot` of the stacked arrays (rank-padded with zeros)."""
        from aphrodite_tpu.lora.layers import LORA_A, LORA_B
        import numpy as np
        bucket = self.params[bucket_key]
        sa, sb = bucket[LORA_A], bucket[LORA_B]
        a_pad = np.zeros(sa.shape[1:], dtype=np.float32)
        b_pad = np.zeros(sb.shape[1:], dtype=np.float32)
        a_pad[:, :a.shape[1]] = a
        b_pad[:b.shape[0], :] = b
        bucket[LORA_A] = sa.at[slot].set(
            jnp.asarray(a_pad, dtype=sa.dtype))
        bucket[LORA_B] = sb.at[slot].set(
            jnp.asarray(b_pad, dtype=sb.dtype))

    def clear_lora_slot(self, bucket_key: str, slot: int) -> None:
        from aphrodite_tpu.lora.layers import LORA_A, LORA_B
        bucket = self.params[bucket_key]
        bucket[LORA_A] = bucket[LORA_A].at[slot].set(0.0)
        bucket[LORA_B] = bucket[LORA_B].at[slot].set(0.0)

    def _params_with_lora(self, seq_group_metadata_list,
                          padded_batch: int, rows_per_group):
        """Inject this step's per-row adapter slot indices into every
        LoRA bucket (shallow copies; stable pytree structure)."""
        if not self.lora_buckets:
            return self.params
        import numpy as np
        idx = np.full((padded_batch,), -1, dtype=np.int32)
        row = 0
        for md, n_rows in zip(seq_group_metadata_list, rows_per_group):
            if md.lora_request is not None and \
                    self.lora_slot_of is not None:
                slot = self.lora_slot_of(md.lora_request.lora_int_id)
                idx[row:row + n_rows] = slot
            row += n_rows
        from aphrodite_tpu.lora.layers import LORA_IDX
        arr = self._dev(idx)
        params = dict(self.params)
        for key in self.lora_buckets:
            params[key] = {**self.params[key], LORA_IDX: arr}
        return params

    # ---- host batch builders ----

    def _prepare_prompt(
        self, seq_group_metadata_list: List[SequenceGroupMetadata]
    ) -> Tuple[dict, SamplingMetadata]:
        batch = len(seq_group_metadata_list)
        padded_batch = _bucket(batch, _PREFILL_BATCH_BUCKETS)

        prompt_lens: List[int] = []
        ctxs: List[int] = []
        seq_groups, seq_data_map = [], {}
        use_prefix = False
        newly_computed = []
        for md in seq_group_metadata_list:
            seq_id = next(iter(md.seq_data))
            data = md.seq_data[seq_id]
            # Chunk to compute = tokens not yet in cache. The scheduler
            # folds prefix-cache hits into computed_ctx; hand-built
            # metadata (tests) may carry only the prefix, so honor
            # both — clamped so at least the last token is computed
            # (a prefix covering the whole prompt must not produce an
            # empty chunk / out-of-range sampler row). The clamp is
            # PAGE-ALIGNED, mirroring the scheduler's: a full-prefix
            # hit recomputes its last prefix page (identical KV,
            # idempotent) rather than start the chunk mid-page, which
            # would fail the prefill_cells ctx % page gate below and
            # disable whole-page KV writes for the entire round.
            ctx = md.computed_ctx
            if md.prefix is not None and md.prefix.computed:
                ctx = max(ctx, md.prefix.get_length())
            ctx = min(ctx, (data.get_len() - 1) // self.page_size *
                      self.page_size)
            end = data.get_len() if md.chunk_len is None \
                else min(ctx + md.chunk_len, data.get_len())
            if md.prefix is not None and not md.prefix.computed \
                    and end >= md.prefix.get_length():
                # This chunk finishes writing the prefix KV. Marking is
                # DEFERRED until the step is actually dispatched (see
                # mark_prefixes): rows later in this same batch must
                # still compute the prefix themselves, and a bailed
                # dispatch must not leave the pool claiming KV that was
                # never written.
                newly_computed.append(md.prefix)
            use_prefix = use_prefix or ctx > 0
            ctxs.append(ctx)
            prompt_lens.append(end - ctx)
            seq_groups.append(([seq_id], md.sampling_params))
            seq_data_map[seq_id] = data

        max_len = max(prompt_lens)
        padded_len = _pow2_bucket(max_len)

        ids = np.zeros((padded_batch, padded_len), dtype=np.int32)
        pos = np.zeros((padded_batch, padded_len), dtype=np.int32)
        ctx_lens = np.zeros((padded_batch,), dtype=np.int32)
        plens = np.zeros((padded_batch,), dtype=np.int32)

        selected: List[int] = []
        for i, md in enumerate(seq_group_metadata_list):
            seq_id = next(iter(md.seq_data))
            data = md.seq_data[seq_id]
            all_tokens = data.get_token_ids()
            ctx = ctxs[i]
            n = prompt_lens[i]
            chunk = all_tokens[ctx:ctx + n]
            ids[i, :n] = chunk
            pos[i, :n] = np.arange(ctx, ctx + n)
            ctx_lens[i] = ctx
            plens[i] = n
            # Sampler rows: all prompt positions if prompt_logprobs else
            # just the last (reference _prepare_sample, :372-451).
            if md.sampling_params.prompt_logprobs is not None:
                selected.extend(range(i * padded_len,
                                      i * padded_len + n))
            else:
                selected.append(i * padded_len + n - 1)

        # One view of the batch's pages for a plain model; one for each
        # page group else, each with the group's own tables, and
        # positions counted from a table's first page.
        seq_ids = [next(iter(md.seq_data))
                   for md in seq_group_metadata_list]
        if self.page_groups.plain:
            group_rows = [[
                (0, md.block_tables.get(seq_id, []))
                for md, seq_id in zip(seq_group_metadata_list, seq_ids)]]
        else:
            group_rows = [[
                md.group_tables[seq_id][g]
                for md, seq_id in zip(seq_group_metadata_list, seq_ids)]
                for g in range(len(self.page_groups.kinds))]
        views = [self._prompt_view(rows, ctx_lens, plens, padded_len,
                                   self._table_floor(kind))
                 for rows, kind in zip(group_rows, self.page_groups.kinds)]
        self._count_prefill_tiles(group_rows, views, ctx_lens, plens,
                                  padded_len, use_prefix)
        self.tracer.add("attn.prefill_steps")
        if self._prefill_kernel[use_prefix]:
            self.tracer.add("attn.prefill_kernel_steps")

        state_slots = None
        if self.num_state_slots is not None:
            slots = np.full((padded_batch,), self.num_state_slots,
                            dtype=np.int32)
            slots[:batch] = [md.state_slots[seq_id] for md, seq_id in
                             zip(seq_group_metadata_list, seq_ids)]
            state_slots = self._dev(slots)
            # (a row at position 0 starts from zeros in the program)
            self.tracer.add("ssm.state_resets",
                            count=sum(c == 0 for c in ctxs))
            if self.kda_chunk_tokens:
                self.tracer.add("kda.prompt_tokens", count=sum(prompt_lens))
                self.tracer.add("kda.prompt_chunks", count=sum(
                    -(-n // self.kda_chunk_tokens) for n in prompt_lens))
            else:
                self.tracer.add("ssm.prefill_tokens",
                                count=sum(prompt_lens))
        metadata = InputMetadata(
            slot_mapping=views[0].slot_mapping,
            block_tables=views[0].block_tables,
            context_lens=views[0].context_lens,
            state_slots=state_slots,
            prompt_lens=self._dev(plens),
            kv_scale=self.kv_scale,
            sp=self.sp,
            tp=self._tp,
            prefill_cells=views[0].prefill_cells,
            groups=None if self.page_groups.plain else tuple(views),
        )
        prompt_offsets = [int(c) for c in ctx_lens[:batch]]
        sampling = SamplingMetadata(
            seq_groups=seq_groups,
            seq_data=seq_data_map,
            prompt_lens=prompt_lens,
            prompt_offsets=prompt_offsets,
        )
        # Pad sel to a bucket so the jitted step's shape is stable
        # (pad rows repeat index 0; sliced off before sampling).
        num_rows = len(selected)
        padded_rows = -(-num_rows // _PAGES_BUCKET) * _PAGES_BUCKET
        sel = np.zeros((padded_rows,), dtype=np.int32)
        sel[:num_rows] = selected
        inputs = dict(input_ids=self._dev(ids), positions=self._dev(pos),
                      metadata=metadata, sel=self._dev(sel),
                      padded_batch=padded_batch, sample_rows=padded_rows,
                      num_rows=num_rows,
                      is_prompt=True, use_prefix=use_prefix,
                      newly_computed=newly_computed)
        return inputs, sampling

    def _count_prefill_tiles(self, group_rows, views, ctx_lens, plens,
                             padded_len: int, use_prefix: bool) -> None:
        """The (query block, key block) tiles this prompt step's
        attention visits and the tiles of its padded rectangle, summed
        over the attention layers: each page group's view as
        `PagedAttention._prefill` hands it to
        `prefill_attention_blocked`, by that function's own rule, times
        the layers that read the group. Host arithmetic over the rows,
        nothing on the device. A step under the threshold counts
        nothing, nor does a whole prompt under a sequence-parallel
        mesh (the ring's)."""
        if self.sp is not None and not use_prefix:
            return
        visited = padded = 0
        for g, (rows, view) in enumerate(zip(group_rows, views)):
            kv_len = view.block_tables.shape[1] * self.page_size \
                if use_prefix else padded_len
            if not takes_blocked_prefill(padded_len, kv_len,
                                         self.prefill_blocked_from):
                continue
            own_ctx = np.zeros_like(ctx_lens)
            if use_prefix:
                own_ctx[:len(rows)] = ctx_lens[:len(rows)] - \
                    [let_go for let_go, _ in rows]
            got = count_prefill_tiles(
                own_ctx, own_ctx + plens, padded_len, kv_len,
                self.page_groups.window
                if self.page_groups.kinds[g] == "window" else None)
            visited += got[0] * self.page_groups.readers[g]
            padded += got[1] * self.page_groups.readers[g]
        if padded:
            self.tracer.add("attn.prefill_tiles_visited", count=visited)
            self.tracer.add("attn.prefill_tiles_padded", count=padded)

    def _table_floor(self, kind: str, decode: bool = False) -> int:
        """The least width a group of `kind` pads its tables to. A
        pooled group's is past its full window, so that every table
        of it is a wide one (`_WIDE_TABLE`) and the rows of a batch,
        whose tables jump from a window and its summaries to the
        summaries alone at an edge, share one program. A window
        group's decode table is never narrower than the window and a
        page: what it holds once a row has passed the window, but for
        the one step in sixteen at which the window starts on a
        page's edge, which is no program's key."""
        if kind == "pooled":
            return self.page_groups.pooled_pages(self.page_size)[0] + 1
        if kind == "window" and decode:
            return -(-self.page_groups.window // self.page_size) + 1
        return 1

    def _prompt_view(self, rows: List[Tuple[int, List[int]]],
                     ctx_lens: np.ndarray, plens: np.ndarray,
                     padded_len: int, floor: int = 1) -> GroupView:
        """What a prompt step writes to and reads from one page group:
        `rows[i]` is sequence i's (tokens its table has let go of, a
        multiple of the page; page numbers), `ctx_lens` and `plens`
        the padded batch's cached and new tokens. Slots, table and
        the page writer's cells, with the context counted from the
        table's first page; the table no narrower than `floor`."""
        batch, padded_batch = len(rows), len(ctx_lens)
        ps = self.page_size
        num_pages_oob = self.num_slots // ps
        slots = np.full((padded_batch * padded_len,), self.num_slots,
                        dtype=np.int32)
        own_ctx = np.zeros((padded_batch,), dtype=np.int32)
        # Bucket the table width to the longest scheduled table (always
        # — long prompts exceed one bucket regardless of prefix use).
        max_pages = self._table_width(
            max([floor] + [len(table) for _, table in rows]))
        tables = np.full((padded_batch, max_pages), num_pages_oob,
                         dtype=np.int32)
        for i, (let_go, table) in enumerate(rows):
            ctx, n = int(ctx_lens[i]) - let_go, int(plens[i])
            own_ctx[i] = ctx
            tables[i, :len(table)] = table
            # Vectorized slot computation (a per-token Python loop here
            # costs ~100 ms per 16k-token prefill round).
            own_pos = np.arange(ctx, ctx + n)
            table_arr = np.asarray(table, dtype=np.int64)
            slots[i * padded_len:i * padded_len + n] = (
                table_arr[own_pos // ps] * ps + own_pos % ps)

        # Page-writer cells: when every sequence's chunk starts on a
        # page boundary and the padded length is page-aligned, prefill
        # KV writes run as whole-page DMAs (one cell per (seq, page))
        # instead of per-token read-modify-writes.
        prefill_cells = None
        if self._prefill_writer_ok and padded_len % ps == 0 and \
                all(int(c) % ps == 0 for c in own_ctx[:batch]):
            ppp = padded_len // ps               # pages per prompt
            n_cells = padded_batch * ppp
            pid = np.full((n_cells,), num_pages_oob, dtype=np.int32)
            sblk = np.zeros((n_cells,), dtype=np.int32)
            vld = np.zeros((n_cells,), dtype=np.int32)
            for i, (_, table) in enumerate(rows):
                n = int(plens[i])
                ctx_pages = int(own_ctx[i]) // ps
                for p in range(-(-n // ps)):
                    cell = i * ppp + p
                    pid[cell] = table[ctx_pages + p]
                    sblk[cell] = (i * padded_len) // ps + p
                    # The Pallas prefill writer fetches its source rows
                    # by CELL INDEX (identity contract — its in-kernel
                    # block map cannot consult sblk); this layout is
                    # identity by construction, and the check keeps a
                    # future re-layout from silently writing wrong KV.
                    # A real raise, not an assert: it must survive -O.
                    if sblk[cell] != cell:
                        raise AssertionError(
                            f"prefill cell layout not identity: "
                            f"{sblk[cell]} != {cell}")
                    vld[cell] = min(n - p * ps, ps)
            prefill_cells = (self._dev(pid), self._dev(sblk),
                             self._dev(vld))
        return GroupView(
            slot_mapping=self._dev(slots), block_tables=self._dev(tables),
            context_lens=self._dev(own_ctx), prefill_cells=prefill_cells)

    @staticmethod
    def _mark_prefixes(inputs: dict) -> None:
        """Flip prefixes to computed once the step writing their KV has
        actually been enqueued (never at prepare time: a bailed dispatch
        or a same-batch sharer must not see phantom KV)."""
        for prefix in inputs.get("newly_computed", ()):
            prefix.computed = True

    def _prepare_decode(
        self, seq_group_metadata_list: List[SequenceGroupMetadata],
        fed_by: Tuple[StepHandle, ...] = (),
    ) -> Tuple[dict, SamplingMetadata]:
        """`fed_by` is the round in flight, its decode step first: a
        row with a token of it still on the device (`SequenceData.
        in_flight`) is built one position on, and its token comes
        from that step's result through `_feed_fn`."""
        cells: Dict[int, int] = {}
        offset = 0
        for handle in fed_by:
            cells.update((seq_id, offset + cell) for seq_id, cell in
                         handle.token_cells().items())
            offset += 2 * handle.packed.shape[0]
        seq_ids_flat: List[int] = []
        seq_groups, seq_data_map, persistent = [], {}, {}
        tokens, positions, slot_list, ctx_list, tables_list = \
            [], [], [], [], []
        grouped = not self.page_groups.plain
        group_rows: Optional[list] = [] if grouped else None
        state_slots: Optional[List[int]] = \
            None if self.num_state_slots is None else []

        for md in seq_group_metadata_list:
            group_ids = list(md.seq_data.keys())
            seq_groups.append((group_ids, md.sampling_params))
            for seq_id in group_ids:
                data = md.seq_data[seq_id]
                seq_data_map[seq_id] = data
                persistent[seq_id] = md.persistent_data.get(seq_id, {})
                seq_ids_flat.append(seq_id)
                if data.in_flight:
                    tokens.append(-1 - cells[seq_id])
                else:
                    tokens.append(data.get_last_token_id())
                pos = data.get_len() - 1 + data.in_flight
                positions.append(pos)
                ctx_list.append(pos + 1)
                table = md.block_tables[seq_id]
                tables_list.append(table)
                if state_slots is not None:
                    state_slots.append(md.state_slots[seq_id])
                if grouped:
                    # each group's slot is read off its own table, in
                    # the program (`_unpacked`)
                    group_rows.append(md.group_tables[seq_id])
                    slot_list.append(self.num_slots)
                    continue
                page = table[pos // self.page_size]
                slot_list.append(page * self.page_size +
                                 pos % self.page_size)

        # The pipelined decode page-writer (kv_write.py distinct_pages)
        # prefetches cell i+1's page before cell i's writeback lands, so
        # two tokens on one page would silently lose a write. CoW in
        # append_slot makes decode pages sequence-exclusive; this guards
        # the precondition loudly when debugging (advisor r3). Read per
        # call — a bad env value must never kill the import.
        if __debug__ and not grouped and \
                flags.get_bool("APHRODITE_DEBUG_KV"):
            written = [s // self.page_size for s in slot_list]
            assert len(set(written)) == len(written), (
                "decode slots share a page — sequence-exclusive-pages "
                f"precondition violated: {sorted(written)}")

        inputs = self._send_decode_batch(tokens, positions, slot_list,
                                         ctx_list, tables_list,
                                         group_rows=group_rows,
                                         state_slots=state_slots)
        if min(tokens) < 0:
            meta = inputs["metadata"]
            with self._mesh_ctx():
                inputs["metadata"] = meta.replace(
                    block_tables=self._feed_fn(
                        meta.block_tables, fed_by[0].packed,
                        fed_by[1].packed if len(fed_by) > 1 else
                        self._no_prompt_source(fed_by[0].packed)))
        sampling = SamplingMetadata(
            seq_groups=seq_groups,
            seq_data=seq_data_map,
            prompt_lens=[],
            persistent_metadata=PersistentMetadata(persistent),
        )
        return inputs, sampling

    def _prepare_spec_verify(
        self,
        seq_group_metadata_list: List[SequenceGroupMetadata],
        drafts: Dict[int, List[int]],
    ) -> Tuple[dict, SamplingMetadata, np.ndarray]:
        """Build the widened verify batch: each sequence with k_i draft
        tokens contributes k_i+1 contiguous (seq, position) rows to the
        ragged decode work list. Row j carries the token at position
        L-1+j (the real last token for j=0, draft j-1 after) and
        attends with ctx = L+j, so row j sees exactly the tokens the
        classic path would have at that output position — the KV
        scatter for ALL rows lands before attention, and per-row
        context_lens masking keeps later rows invisible to earlier
        ones. Eligibility (single-seq groups, fused-sampler statics
        pinned at best_of=1 / no logprobs, no penalties) is enforced
        by the engine. Returns (inputs, with each group's draft under
        "verify"; sampling; each row's offset for the PRNG salt: the
        acceptance rule consumes salts per OUTPUT POSITION, so row j
        of a sequence gets salt1 = output_len + j, exactly the salt
        the classic path uses when it reaches that position)."""
        seq_groups, seq_data_map, persistent = [], {}, {}
        tokens, positions, slot_list, ctx_list, tables_list = \
            [], [], [], [], []
        row_offsets: List[int] = []
        group_drafts: List[List[int]] = []

        for md in seq_group_metadata_list:
            (seq_id,) = md.seq_data.keys()
            data = md.seq_data[seq_id]
            seq_data_map[seq_id] = data
            persistent[seq_id] = md.persistent_data.get(seq_id, {})
            table = md.block_tables[seq_id]
            draft = drafts.get(seq_id) or []
            group_drafts.append(list(draft))
            base_pos = data.get_len() - 1
            row_tokens = [data.get_last_token_id()] + list(draft)
            for j, tok in enumerate(row_tokens):
                # One single-seq group PER ROW: the sampler plan then
                # derives each row's knobs and seed base independently
                # and finalize emits one output per row.
                seq_groups.append(([seq_id], md.sampling_params))
                tokens.append(int(tok))
                pos = base_pos + j
                positions.append(pos)
                # Direct index (no wrap): the scheduler's speculative
                # page reservation must cover position L-1+k; an
                # IndexError here means the reservation contract broke.
                page = table[pos // self.page_size]
                slot_list.append(page * self.page_size +
                                 pos % self.page_size)
                ctx_list.append(pos + 1)
                tables_list.append(table)
                row_offsets.append(j)

        # Verify rows legitimately SHARE pages (consecutive positions
        # of one sequence); the decode invariant that still holds is
        # slot-exclusivity, which the XLA scatter needs.
        if __debug__ and flags.get_bool("APHRODITE_DEBUG_KV"):
            assert len(set(slot_list)) == len(slot_list), (
                "spec verify rows share a KV slot: "
                f"{sorted(slot_list)}")

        inputs = self._send_decode_batch(tokens, positions, slot_list,
                                         ctx_list, tables_list,
                                         spec_verify=True)
        sampling = SamplingMetadata(
            seq_groups=seq_groups,
            seq_data=seq_data_map,
            prompt_lens=[],
            persistent_metadata=PersistentMetadata(persistent),
        )
        inputs["verify"] = group_drafts
        return inputs, sampling, np.asarray(row_offsets, dtype=np.int32)

    def _no_prompt_source(self, like) -> jax.Array:
        """What `_feed_fn` gets for a round in flight that had no
        prompt step: zeros in the shape of a prompt step's result for
        up to 8 prompts, so that such a round needs no program of its
        own."""
        key = (_PAGES_BUCKET, like.shape[1])
        if key not in self._no_source:
            self._no_source[key] = self._dev(
                np.zeros(key, dtype=np.int32), committed=True)
        return self._no_source[key]

    def _decode_work_list(self, kind: str, page_counts: List[int],
                   padded_batch: int, max_pages: int):
        """The ragged decode work list of one page group, (its device
        copy, pages a chunk, chunks a row): (sequence, chunk) pairs
        flattened over each row's REAL reserved pages so the attention
        grid has no padded cells for short contexts (a dense list
        pads every row to the table's width). Chunk counts come
        from the reserved table lengths — a safe over-approximation of
        any context the burst scan reaches (pos_cap pins rows inside
        their reservation), so the list rides the whole burst. The
        device copy is kept, for each kind of group, while no row's
        chunk count changes (the groups of a kind hold the same
        counts)."""
        ppc = choose_pages_per_chunk(max_pages, self.page_size,
                                     self.attn_lane_bytes)
        chunks = tuple(max(1, -(-c // ppc)) for c in page_counts)
        work_key = (chunks, ppc, self._work_length(
            sum(chunks), padded_batch, max_pages, ppc))
        if self._decode_work.get(kind, (None,))[0] != work_key:
            wi_seq, wi_chunk = build_decode_work_list(
                page_counts, ppc, pad_to=work_key[2])
            self._decode_work[kind] = (work_key, (self._dev(wi_seq),
                                                  self._dev(wi_chunk)))
        return self._decode_work[kind][1], ppc, chunks

    @staticmethod
    def _work_length(items: int, padded_batch: int, max_pages: int,
                     ppc: int) -> int:
        """The length a decode work list is padded to (a step
        program's key): `padded_work_length`'s batch x 2^k; for a wide
        table (`_WIDE_TABLE`) or a batch of `_WIDE_ROWS` rows the dense
        count, a cell for every chunk of every row, once the list is
        within a factor two of it. Rows
        of long contexts are alike, so their lists lie just under the
        dense count, and on which side of the last power of two
        depends on how many rows of the bucket are padding: at 8k
        contexts that was three programs a batch bucket while callers
        joined, for a few dead items the kernel skips."""
        length = padded_work_length(items, padded_batch, max_pages, ppc)
        dense = padded_batch * -(-max_pages // ppc)
        wide = max_pages > _WIDE_TABLE or padded_batch >= _WIDE_ROWS
        if wide and 2 * length >= dense:
            return dense
        return length

    def _send_decode_batch(self, tokens, positions, slot_list, ctx_list,
                           tables_list, spec_verify: bool = False,
                           group_rows: Optional[list] = None,
                           state_slots: Optional[List[int]] = None
                           ) -> dict:
        """Pad a decode (or verify) batch to its buckets and send it:
        ONE [padded_batch, 4 + pages] int32 array (each row's token,
        position, slot, context length, then its block table; pad rows
        hold the out-of-range slot and page, so the cache scatter drops
        them; `_unpacked` slices it inside the program) and the ragged
        work list. Every row is sampled, so there is no `sel`.

        `group_rows` (a model with page groups): each row's `[(tokens
        let go of, page numbers)]`, an entry a group. The row then
        holds, after its first four columns, that number and the table
        for each group in turn, and each group has its work list.
        `state_slots` (a model with recurrent state): each row's state
        slot, the row's last column; a pad row's is the scratch one."""
        batch = len(tokens)
        padded_batch = _bucket(batch, _DECODE_BATCH_BUCKETS)
        pad_rows = [0] * (padded_batch - batch)
        if group_rows is None:
            layout, views = (), None
            widths = [self._table_width(max(len(t) for t in tables_list),
                                        padded_batch)]
        else:
            kinds = self.page_groups.kinds
            widths = [self._table_width(max(
                [self._table_floor(kind, decode=True)] +
                [len(row[g][1]) for row in group_rows]), padded_batch)
                for g, kind in enumerate(kinds)]
            layout, views = tuple(widths), []

        rows = np.zeros((padded_batch, 4 + sum(widths) + len(layout) +
                         (state_slots is not None)), dtype=np.int32)
        rows[:, 2] = self.num_slots
        rows[:, 4:] = self.num_slots // self.page_size
        if state_slots is not None:
            rows[:, -1] = self.num_state_slots
            rows[:batch, -1] = state_slots
            self.tracer.add("kda.decode_rows" if self.kda_chunk_tokens
                            else "ssm.decode_rows", count=batch)
        rows[:batch, 0] = tokens
        rows[:batch, 1] = positions
        rows[:batch, 2] = slot_list
        rows[:batch, 3] = ctx_list
        if group_rows is None:
            for i, t in enumerate(tables_list):
                rows[i, 4:4 + len(t)] = t
            work, ppc, chunks = self._decode_work_list(
                "full", [len(t) for t in tables_list] + pad_rows,
                padded_batch, widths[0])
            # The pages this step's attention copies and the pages that
            # are live, by the kernel's rule (host arithmetic over the
            # rows; `decode_attn_fetch_live_pct`).
            fetched, live = count_decode_pages(
                rows[:, 3], chunks, ppc, self.page_size)
        else:
            fetched = live = shared = 0
            at = 4
            for g, width in enumerate(widths):
                rows[:, at] = 0
                for i, row in enumerate(group_rows):
                    let_go, table = row[g]
                    rows[i, at] = let_go
                    rows[i, at + 1:at + 1 + len(table)] = table
                work, ppc, chunks = self._decode_work_list(
                    kinds[g], [len(row[g][1]) for row in group_rows] +
                    pad_rows, padded_batch, width)
                views.append(GroupView(
                    slot_mapping=None, block_tables=None,
                    context_lens=None, decode_work=work, decode_ppc=ppc))
                got = count_decode_pages(rows[:, 3] - rows[:, at], chunks,
                                         ppc, self.page_size)
                fetched, live = fetched + got[0], live + got[1]
                # (a pooled group's table is a window's pages behind
                # its summaries, and the kernel reads them all alike)
                self.tracer.add(
                    "attn.pages_live." +
                    ("window" if kinds[g] == "pooled" else kinds[g]),
                    count=got[1])
                shared += got[1] * self.page_groups.readers[g]
                if kinds[g] in ("window", "pooled"):
                    # (what the rows' whole contexts take in pages)
                    self.tracer.add(
                        "attn.window_pages_unwindowed",
                        count=int(np.sum(-(-rows[:, 3] // self.page_size))))
                if kinds[g] == "pooled":
                    # a window behind has let go of its pages less
                    # its summaries: the summaries from what is gone
                    held, kept = self.page_groups.pooled_pages(
                        self.page_size)
                    gone = (held - kept) * self.page_size
                    self.tracer.add(
                        "attn.summary_pages_live",
                        count=int(np.sum(rows[:batch, at] // gone)) * kept)
                at += 1 + width
            self.tracer.add("attn.page_reads_shared", count=shared)
            work, ppc = views[0].decode_work, views[0].decode_ppc
        if self.page_groups.latent is not None:
            self.tracer.add("mla.latent_tokens_read",
                            count=int(np.sum(rows[:batch, 3])))
        self.tracer.add("attn.pages_fetched", count=fetched)
        self.tracer.add("attn.pages_live", count=live)
        self.tracer.add("attn.decode_steps", count=1)

        metadata = InputMetadata(
            slot_mapping=None,
            block_tables=self._dev(rows, committed=True),
            context_lens=None,
            kv_scale=self.kv_scale,
            tp=self._tp,
            decode_work=work,
            decode_ppc=ppc,
            spec_verify=spec_verify,
            groups=None if views is None else tuple(views),
            group_layout=layout,
        )
        return dict(input_ids=None, positions=None, metadata=metadata,
                    sel=None, padded_batch=padded_batch,
                    sample_rows=padded_batch, num_rows=batch,
                    is_prompt=False, use_prefix=False)

    # ---- public API ----

    def _apply_block_copies(self, kv_caches, blocks_to_copy):
        """CoW copies scheduled this round, applied before the step.

        The index arrays are padded to a power-of-two bucket: every
        distinct copy count was its own compiled _copy_fn program (a
        compile for one fork burst that will never repeat that exact
        size). Pad lanes carry the OOB page index,
        which copy_blocks' fill/drop gather+scatter modes turn into
        no-ops."""
        if not blocks_to_copy:
            return kv_caches
        src, dst = [], []
        for s, ds in blocks_to_copy.items():
            for d in ds:
                src.append(s)
                dst.append(d)
        oob = self.num_slots // self.page_size
        padded = _pow2_bucket(len(src), lo=8)
        src_arr = np.full((padded,), oob, dtype=np.int32)
        dst_arr = np.full((padded,), oob, dtype=np.int32)
        src_arr[:len(src)] = src
        dst_arr[:len(dst)] = dst
        with self._mesh_ctx():
            return self._copy_fn(kv_caches, self._dev(src_arr),
                                 self._dev(dst_arr))

    def _prepare_step(
        self, seq_group_metadata_list: List[SequenceGroupMetadata],
        fed_by: Tuple[StepHandle, ...] = (), num_steps: int = 1,
        extra_cap: Optional[Dict[int, int]] = None,
        drafts: Optional[Dict[int, List[int]]] = None,
    ):
        """The host half of a step (inside `runner.prepare`): the
        padded batch, the LoRA indices and the sampling plan, which is
        None when a row has host logits processors. A decode batch
        may be a verify round's (`drafts`: k+1 rows a sequence) or a
        burst's (`num_steps` > 1: the scan's own operands ride in
        `inputs["burst"]`); the other arguments are a decode batch's
        too."""
        salt_offsets = None
        if seq_group_metadata_list[0].is_prompt:
            inputs, sampling = self._prepare_prompt(
                seq_group_metadata_list)
            rows_per_group = [1] * len(seq_group_metadata_list)
        elif drafts is not None:
            inputs, sampling, salt_offsets = self._prepare_spec_verify(
                seq_group_metadata_list, drafts)
            rows_per_group = [len(d) + 1 for d in inputs["verify"]]
        else:
            inputs, sampling = self._prepare_decode(
                seq_group_metadata_list, fed_by)
            rows_per_group = [
                len(md.seq_data) for md in seq_group_metadata_list
            ]
        params = self._params_with_lora(
            seq_group_metadata_list, inputs["padded_batch"],
            rows_per_group)
        has_processors = any(
            p.logits_processors for _, p in sampling.seq_groups)
        plan = None if has_processors else \
            self._plan(sampling, inputs["sample_rows"], salt_offsets)
        assert drafts is None or self._fused(plan), \
            "spec verify eligibility broken"
        if num_steps > 1 and not inputs["is_prompt"]:
            inputs["burst"] = self._burst_operands(
                seq_group_metadata_list, inputs["padded_batch"],
                num_steps, extra_cap)
        return inputs, sampling, params, plan

    def _burst_operands(
        self, seq_group_metadata_list: List[SequenceGroupMetadata],
        padded: int, num_steps: int,
        extra_cap: Optional[Dict[int, int]],
    ) -> Tuple[jax.Array, jax.Array, int]:
        """What the burst scan takes beside a decode step's operands:
        which rows feed their greedy token back, and each row's last
        reserved position: pos + the engine's per-seq useful-step cap
        (tokens remaining / model-len room — ONE source of truth,
        computed in AphroditeEngine._burst_steps and used for the page
        reservation), clamped to the burst length. Overshot rows pin
        there instead of walking the block table past their
        reservation (advisor r3); pad rows pin at their pad slot."""
        greedy = np.zeros((padded,), dtype=bool)
        pos_cap = np.zeros((padded, 1), dtype=np.int32)
        cap_of = extra_cap or {}
        row = 0
        for md in seq_group_metadata_list:
            n = len(md.seq_data)
            if md.sampling_params.sampling_type == SamplingType.GREEDY:
                greedy[row:row + n] = True
            for seq_id, data in md.seq_data.items():
                r = min(cap_of.get(seq_id, num_steps), num_steps)
                pos_cap[row, 0] = data.get_len() - 1 + r
                row += 1
        return self._dev(greedy), self._dev(pos_cap), num_steps

    @staticmethod
    def _fused(plan) -> bool:
        """Whether the step runs as ONE program, model and sampler.
        The fused program's sampler statics stay PINNED at the serving
        default (best_of=1, no top-k logprobs): a varying
        best_of/logprobs request must not recompile the whole model
        program; those route through the split path, where only the
        small sampler program recompiles. Host logits processors (no
        plan) need the logits mid-pipeline. (What a request's
        `SamplingParams.needs_raw_logits` says ahead of the plan.)"""
        return plan is not None and not plan.need_logprobs and \
            plan.max_best_of == 1 and plan.num_topk == 0

    def _enqueue(self, inputs: dict, sampling: SamplingMetadata, params,
                 plan, kv_caches, **facts
                 ) -> Tuple[StepHandle,
                            List[Tuple[jax.Array, jax.Array]]]:
        """Dispatch the one program of a prepared step, the fused step
        or the burst's scan over it (which compiles its sampler
        statics from the plan); nothing blocks. `facts` go on the
        dispatch's annotation."""
        burst = inputs.get("burst")
        self.tracer.flight(1)
        with self.tracer.span("runner.dispatch", **facts), \
                self._mesh_ctx():
            if burst is None:
                packed, kv_caches = self._step_sample_fn(
                    params, inputs["input_ids"], inputs["positions"],
                    kv_caches, inputs["metadata"], inputs["sel"],
                    plan.tensors, plan.key_parts,
                    is_prompt=inputs["is_prompt"],
                    use_prefix=inputs["use_prefix"],
                    max_best_of=plan.max_best_of, num_topk=plan.num_topk)
            else:
                greedy_mask, pos_cap, num_steps = burst
                packed, kv_caches = self._burst_scan_fn(
                    params, inputs["input_ids"], inputs["positions"],
                    kv_caches, inputs["metadata"], plan.tensors,
                    plan.key_parts, greedy_mask, pos_cap,
                    num_steps=num_steps, max_best_of=plan.max_best_of,
                    num_topk=plan.num_topk)
        self._mark_prefixes(inputs)
        counts = None
        if self.step_counters and burst is None:
            packed, counts = packed
        return StepHandle(packed, sampling, plan,
                          num_steps=burst[2] if burst else 1,
                          verify=inputs.get("verify"), counts=counts,
                          is_prompt=inputs["is_prompt"]), kv_caches

    def _run_raw(self, inputs: dict, sampling: SamplingMetadata, params,
                 plan, kv_caches
                 ) -> Tuple[StepHandle,
                            List[Tuple[jax.Array, jax.Array]]]:
        """The raw-logits route of a prepared step, synced by nature:
        host logits processors need the logits mid-pipeline; logprob
        requests need the full log-softmax rows. Two device programs,
        and the handle has its outputs."""
        self.tracer.flight(1)
        with self.tracer.span("runner.dispatch"), self._mesh_ctx():
            logits, kv_caches = self._step_fn(
                params, inputs["input_ids"], inputs["positions"],
                kv_caches, inputs["metadata"], inputs["sel"],
                is_prompt=inputs["is_prompt"],
                use_prefix=inputs["use_prefix"])
        self._mark_prefixes(inputs)
        if plan is None:
            # The host processors pull the logits: the wait, the
            # sampler's own small program and its unpacking are one
            # blocking call.
            with self.tracer.span("runner.device_wait"):
                output = self.sampler(logits[:inputs["num_rows"]],
                                      sampling)
            self.tracer.flight(-1)
            return StepHandle(None, sampling, plan,
                              outputs=[output]), kv_caches
        with self.tracer.span("runner.dispatch"), self._mesh_ctx():
            packed, logprobs_dev = _fused_sample_jit(
                logits, plan.tensors, plan.key_parts,
                max_best_of=plan.max_best_of,
                num_topk=plan.num_topk,
                need_logprobs=plan.need_logprobs)
        with self.tracer.span("runner.device_wait"):
            packed_np = np.asarray(packed)
        self.tracer.flight(-1)
        with self.tracer.span("sampler.finalize"):
            output = self.sampler.finalize(sampling, plan, packed_np,
                                           logprobs_dev)
        return StepHandle(None, sampling, plan,
                          outputs=[output]), kv_caches

    def _plan(self, sampling: SamplingMetadata, pad_to: int,
              salt_offsets: Optional[np.ndarray] = None):
        with self.tracer.span("sampler.plan"):
            plan = self.sampler.plan(sampling, pad_to=pad_to,
                                     salt_offsets=salt_offsets)
        if plan.reused:
            self.tracer.add("sampler.plan_reuse")
        return plan

    def dispatch_steps(
        self,
        batches: List[List[SequenceGroupMetadata]],
        kv_caches: List[Tuple[jax.Array, jax.Array]],
        fed_by: Tuple[StepHandle, ...] = (),
        or_raw: bool = False,
        **decode_steps,
    ) -> Tuple[Optional[List[StepHandle]],
               List[Tuple[jax.Array, jax.Array]]]:
        """Enqueue one step for each of `batches` (each all prompt
        chunks or all decode rows), WITHOUT syncing and in the order
        the device runs them: a batch is prepared (its own
        `runner.prepare` span) and its program enqueued before the
        next batch is prepared, so that the host builds a round's
        prompt batch while the device runs its decode step. All of
        them or none. Returns (None, kv_caches untouched) when a
        batch is off the fused program (host logits processors,
        logprobs, best_of>1) — or, `or_raw` and one batch, runs that
        batch through the raw-logits route at once: its handle has
        its outputs. Of one batch the prepared plan says so
        (`_fused`); of several the rows' parameters do, before
        anything goes out (`SamplingParams.needs_raw_logits`, the
        plan's mirror: a plan that then disagrees raises). `fed_by`
        (decode batches): the handles of the round whose results are
        still on the device, its decode step first; rows with a token
        in flight take it from there (`_prepare_decode`).
        `decode_steps`: what a decode batch's program runs if not one
        plain step (`_prepare_step`: `num_steps` and `extra_cap` of a
        burst, a verify round's `drafts`)."""
        alone = len(batches) == 1
        if not alone and any(md.sampling_params.needs_raw_logits
                             for mds in batches for md in mds):
            return None, kv_caches
        handles: List[StepHandle] = []
        for mds in batches:
            with self.tracer.span("runner.prepare"):
                step = self._prepare_step(mds, fed_by, **decode_steps)
            inputs, _, _, plan = step
            if not (self._fused(plan) or
                    (plan is not None and "burst" in inputs)):
                if not alone:
                    raise RuntimeError(
                        "a row's needs_raw_logits let through a step "
                        "that its plan takes off the fused program")
                if not or_raw:
                    return None, kv_caches
                handle, kv_caches = self._run_raw(*step, kv_caches)
                return [handle], kv_caches
            facts = {}
            if handles:
                # The program just enqueued has already finished: this
                # one comes too late to follow it without a gap.
                # (Counted over the rounds `round.ahead.prompt` counts:
                # those that went out with a round in flight.)
                late = handles[-1].is_ready()
                if late and fed_by:
                    self.tracer.add("runner.prompt_late")
                facts["late"] = int(late)
            elif fed_by:
                # The round in flight has finished and nothing is
                # queued behind it: the device waited for this
                # dispatch. Said on the round's first program.
                starved = all(handle.is_ready() for handle in fed_by)
                if starved:
                    self.tracer.add_split("runner.starved")
                facts["starved"] = int(starved)
            handle, kv_caches = self._enqueue(*step, kv_caches, **facts)
            handles.append(handle)
        return handles, kv_caches

    def pull(self, handles: List[StepHandle]) -> List[np.ndarray]:
        """The ONE blocking transfer for the results of `handles`.
        After a round went out ahead (the engine's `pulls` fact) the
        wait is also the host's lead, `pull.blocked`: what the device
        had left to do when the host had nothing."""
        with self.tracer.span("runner.device_wait") as wait:
            pulled, counted = jax.device_get(
                ([h.packed for h in handles],
                 [h.counts for h in handles if h.counts is not None]))
        if "pulls" in self.tracer.facts:
            self.tracer.add_split("pull.blocked", wait.seconds)
        self.tracer.flight(-len(handles))
        for handle, counts in zip(
                (h for h in handles if h.counts is not None), counted):
            self._add_step_counts(handle, counts)
        return [np.asarray(p) for p in pulled]

    def _add_step_counts(self, handle: StepHandle, counts) -> None:
        """What a step's program counted goes to the tracer; of a
        decode step, the experts touched once more, beside the most it
        could have touched; a step whose expert layers walked rows took
        the Pallas kernels (only such a program counts them)."""
        for name, value in zip(self.step_counters, counts):
            self.tracer.add(name, count=int(value))
            if name == "moe.rows_walked":
                self.tracer.add("moe.kernel_steps")
            if name == "moe.experts_touched" and not handle.is_prompt:
                self.tracer.add("moe.decode_experts_touched",
                                count=int(value))
                self.tracer.add("moe.decode_expert_slots",
                                count=self.model.expert_slots)

    def finalize_step(self, handle: StepHandle,
                      packed_np: np.ndarray) -> list:
        """A pulled step's outputs: a SamplerOutput for each device
        iteration it ran (one, or a burst's `num_steps`); of a verify
        step, each group's accepted run (`SpecVerifyResult`)."""
        if handle.num_steps > 1:
            return [
                self.sampler.finalize(handle.sampling, handle.plan,
                                      packed_np[t], None)
                for t in range(handle.num_steps)
            ]
        output = self.sampler.finalize(handle.sampling, handle.plan,
                                       packed_np, None)
        if handle.verify is None:
            return [output]
        # Delta rejection over each drafted suffix, host-side. The
        # emitted distribution is the classic path's by construction:
        # row j sampled from the TARGET with the PRNG salt of output
        # position output_len+j (never a per-step salt), so greedy and
        # seeded streams are bit-equal to `APHRODITE_SPEC=0`.
        results: List[SpecVerifyResult] = []
        row = 0
        for draft in handle.verify:
            rows = output[row:row + len(draft) + 1]
            sampled = [g.samples[0].output_token for g in rows]
            m = delta_rejection_length(sampled, draft)
            results.append(SpecVerifyResult(
                samples=[rows[j].samples[0] for j in range(m + 1)],
                accepted=m, proposed=len(draft)))
            row += len(draft) + 1
        return results
