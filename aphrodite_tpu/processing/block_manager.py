"""Paged KV-cache block accounting (host side) — the page OWNER.

Semantics match the reference's `aphrodite/processing/block_manager.py:10,68`
(ref-counted allocator, watermark admission, copy-on-write fork,
host<->HBM swap planning), but for the sliding window: a sequence holds
one block table for each PAGE GROUP of its model
(`common/config.py::PageGroups`), all from the one free list, and a
window group's table SLIDES. It lets go of every page that lies wholly
before the window of the oldest query still to come, in the round that
passes it, and takes new pages at its end, so it holds the window, the
chunk being written and a page at most. A model-wide window is the
case of one group, a window group; a model without one has one full
group and is served exactly as before. A POOLED group (EVA's chunked
attention: exact keys inside a query's own aligned window of
`pooled_window` tokens, one pooled key a page of tokens behind it)
holds TWO lists a sequence, the current window's pages and the summary
pages of the windows behind, and shows them as one table, `[summary
pages ; window pages]`, counted from its first summary: when a
sequence passes a window's edge the group takes the pages the
window's pooled keys fill, hands both lists to the device
(`take_window_closes`, whose program pools the one into the other
before the round's steps), and lets the WHOLE window go. A model that keeps recurrent
state beside its pages (`common/config.py::StateSpec`) has a second
kind of per-sequence memory here, the STATE SLOT: one id a sequence,
the same row of every state array, from a free list of its own; given
with the prompt's pages, freed with them, and on a fork the child
takes a slot and the device copies the parent's row into it
(`take_state_copies`). Nothing zeroes a slot on the host: the program
of a sequence's first chunk (position 0) starts from zeros whatever
the slot holds, so a slot's next owner, a row preempted by recompute
and a round rolled back all start clean. This module is pure Python
and device-agnostic: it only plans block operations; the executor applies
them to the HBM page arrays (`executor/cache.py`) as batched gathers/
scatters and host transfers — there is no per-block memcpy on TPU, the
swap/copy plans are turned into single vectorized device ops per step.

Ownership contract (machine-enforced by aphrocheck's LEAK/OWN passes):
this module — together with `common/block.py` and `common/prefix.py` —
is the ONLY place `PhysicalTokenBlock.ref_count`, the pool free lists,
and the `block_tables` map may be mutated, and raw block objects never
cross the module boundary: callers see `block_number` ints only
(`get_block_table` / `block_numbers` / the swap mappings). Every
refcount increment is paired with a statically-reachable free seam
(`free`/`reset` for sequence tables, `free_prefix` for prefix pins);
`python -m tools.aphrocheck --ledger` emits the alloc-site -> free-seam
map (OWNERSHIP.json) that tier-1 drift-gates.
"""
from __future__ import annotations

import enum
from typing import Dict, List, Optional, Set, Tuple

from aphrodite_tpu.common import faultinject, tracing
from aphrodite_tpu.common.block import (BlockTable, Device,
                                        PhysicalTokenBlock)
from aphrodite_tpu.common.prefix import Prefix
from aphrodite_tpu.common.sequence import (Sequence, SequenceGroup,
                                           SequenceStatus)


class BlockPool:
    """Free-list allocator with CoW refcounts for one device's pages."""

    def __init__(self, device: int, block_size: int, num_blocks: int) -> None:
        self.device = device
        self.block_size = block_size
        self.num_blocks = num_blocks
        # thread-safe: allocate/free run on the step thread inside
        # step(), or on the event loop (abort paths) strictly BETWEEN
        # steps — engine_step awaits the step future before freeing,
        # so the free list never sees concurrent mutation.
        self._free: List[PhysicalTokenBlock] = [
            PhysicalTokenBlock(device, idx, block_size)
            for idx in range(num_blocks)
        ]

    def allocate(self) -> PhysicalTokenBlock:
        if not self._free:
            raise ValueError("Out of memory! No free blocks are available.")
        block = self._free.pop()
        block.ref_count = 1
        return block

    def free(self, block: PhysicalTokenBlock) -> None:
        if block.ref_count == 0:
            raise ValueError(f"Double free! {block} is already freed.")
        block.ref_count -= 1
        if block.ref_count == 0:
            self._free.append(block)

    def get_num_free_blocks(self) -> int:
        return len(self._free)


# Backwards-compatible alias matching the reference class name.
BlockAllocator = BlockPool


class PageGroupsUnsupported(RuntimeError):
    """What a model with a window, with several page groups, with
    recurrent state beside its pages or with latent pages is refused,
    rather than served half-right."""

    def __init__(self, what: str, instead: str) -> None:
        super().__init__(
            f"{what} is not supported for a model whose KV pages are "
            "in window groups or in more than one group, that keeps "
            "recurrent state beside them, or whose pages are latent: "
            f"{instead}")


class AllocStatus(enum.Enum):
    """Admission verdict for a waiting sequence group."""
    OK = enum.auto()       # fits now
    LATER = enum.auto()    # doesn't fit now, retry after blocks free up
    NEVER = enum.auto()    # larger than the whole cache; must be ignored


class BlockSpaceManager:
    """Maps logical sequence blocks to physical KV pages on HBM/host."""

    def __init__(
        self,
        block_size: int,
        num_gpu_blocks: int,
        num_cpu_blocks: int,
        watermark: float = 0.01,
        sliding_window: Optional[int] = None,
        group_kinds: Optional[Tuple[str, ...]] = None,
        max_chunk_tokens: Optional[int] = None,
        num_state_slots: Optional[int] = None,
        tracer: Optional[tracing.Tracer] = None,
        pooled_window: Optional[int] = None,
        latent: bool = False,
    ) -> None:
        """`latent`: the pages are latent (`PageGroups.latent`): ids
        are counted as ever, and what follows K/V pairs alone is
        refused. `group_kinds`: "full", "window" or "pooled" for each page
        group (one group by default: a window group where
        `sliding_window` is set). `pooled_window`: the tokens of a
        pooled group's aligned window, a multiple of the page squared. `max_chunk_tokens`: the longest prompt chunk the
        scheduler writes at once for a model with a window group
        (None: a prompt may come whole). `num_state_slots`: the state
        slots of a model with recurrent state (None: it has none).
        `tracer`: the engine's, for `ssm.slot_waits`."""
        self.tracer = tracer or tracing.Tracer()
        self.block_size = block_size
        self.num_total_gpu_blocks = num_gpu_blocks
        self.num_total_cpu_blocks = num_cpu_blocks

        self.group_kinds: Tuple[str, ...] = tuple(group_kinds) \
            if group_kinds else (
                ("full",) if sliding_window is None else ("window",))
        #: one full group: block tables, swap, prefix pins and
        #: look-ahead reservations as ever
        self.plain = self.group_kinds == ("full",) and \
            num_state_slots is None and not latent
        self.sliding_window = sliding_window
        if "window" in self.group_kinds and not sliding_window:
            raise ValueError("a window page group needs sliding_window")
        #: the most pages a window group takes for a prompt: the
        #: window, the longest chunk, and a page (neither end of the
        #: two need lie on a page's edge)
        self.window_cap_blocks: Optional[int] = None
        if sliding_window is not None and max_chunk_tokens is not None:
            self.window_cap_blocks = -(-sliding_window // block_size) + \
                -(-max_chunk_tokens // block_size) + 1
        #: pages that window groups have let go of (cumulative)
        self.window_pages_freed = 0
        #: a pooled group's full window in pages, and the summary
        #: pages its pooled keys fill (a key a page of tokens)
        self.pooled_window = pooled_window
        #: windows that pooled groups have closed (cumulative)
        self.windows_closed = 0
        self.window_blocks = self.summary_blocks = 0
        if "pooled" in self.group_kinds:
            if not pooled_window or pooled_window % block_size ** 2:
                raise ValueError(
                    "a pooled page group needs pooled_window, a "
                    "multiple of block_size squared")
            self.window_blocks = pooled_window // block_size
            self.summary_blocks = self.window_blocks // block_size

        assert watermark >= 0.0
        self.watermark = watermark
        self.watermark_blocks = int(watermark * num_gpu_blocks)

        # TPU-native names; the reference's gpu_/cpu_allocator spelling
        # survives as read-only aliases below for parity callers.
        self.hbm_pool = BlockPool(Device.TPU, block_size, num_gpu_blocks)
        self.host_pool = BlockPool(Device.CPU, block_size, num_cpu_blocks)
        # thread-safe: mutated on the step thread inside step() and on
        # the event loop only via abort/free paths that run BETWEEN
        # steps (engine_step awaits the step future first); the two
        # writers are sequenced by the engine loop, never concurrent.
        self.block_tables: Dict[int, BlockTable] = {}
        # A sequence's tables of the groups after the first, and, for
        # every group, how many pages its table has let go of at its
        # start (both only where the groups are not plain).
        # thread-safe: written where `block_tables` is and nowhere
        # else, so the same sequencing by the engine loop holds.
        self.more_tables: Dict[int, List[BlockTable]] = {}
        # thread-safe: as `more_tables`.
        self.first_blocks: Dict[int, List[int]] = {}
        # A sequence's summary pages, a list for each page group (a
        # pooled group's fills as windows close, the others' stay
        # empty), and the (window's pages, summary pages) of the
        # windows closed since the device was last told.
        # thread-safe: as `more_tables`.
        self.summary_tables: Dict[int, List[BlockTable]] = {}
        # thread-safe: as `more_tables`.
        self._window_closes: List[Tuple[List[int], List[int]]] = []
        # State slots: the free ids, each sequence's, and the
        # (parent's, child's) of forks whose device copy is still to
        # be scheduled.
        self.num_state_slots = num_state_slots
        # thread-safe: written where `block_tables` is and nowhere
        # else, so the same sequencing by the engine loop holds.
        self._free_state_slots: List[int] = list(
            range(num_state_slots or 0))[::-1]
        # thread-safe: as `_free_state_slots`.
        self.state_slots: Dict[int, int] = {}
        # thread-safe: as `_free_state_slots`.
        self._state_copies: List[Tuple[int, int]] = []

    @property
    def gpu_allocator(self) -> BlockPool:
        """Reference-parity alias for :attr:`hbm_pool` (read-only)."""
        return self.hbm_pool

    @property
    def cpu_allocator(self) -> BlockPool:
        """Reference-parity alias for :attr:`host_pool` (read-only)."""
        return self.host_pool

    # ------------------------------------------------------------------
    # Prompt admission / allocation
    # ------------------------------------------------------------------

    def _prompt_blocks_needed(self, seq_group: SequenceGroup) -> int:
        seq = seq_group.get_seqs(status=SequenceStatus.WAITING)[0]
        needed = len(seq.logical_token_blocks)
        if not self.plain:
            return sum(self._prompt_peak_of(kind, needed)
                       for kind in self.group_kinds)
        prefix = seq_group.prefix
        if prefix is not None and prefix.allocated:
            needed -= prefix.get_num_blocks()
        return needed

    def _prompt_blocks_of(self, kind: str, prompt_blocks: int) -> int:
        """Pages a group of `kind` takes when a prompt is admitted: a
        window group never needs more than its cap at once, and takes
        the rest as its table slides (`prepare_chunk`)."""
        if kind == "window" and self.window_cap_blocks is not None:
            return min(prompt_blocks, self.window_cap_blocks)
        if kind == "pooled":
            return min(prompt_blocks, self.window_blocks)
        return prompt_blocks

    def _prompt_peak_of(self, kind: str, prompt_blocks: int) -> int:
        """The most pages a group of `kind` holds at once while a
        prompt is written, which is what admission has to find free: a
        pooled group's is met when its last full window closes, the
        summaries of every window behind the prompt's last one beside
        that whole window."""
        if kind != "pooled" or prompt_blocks <= self.window_blocks:
            return self._prompt_blocks_of(kind, prompt_blocks)
        behind = (prompt_blocks - 1) // self.window_blocks
        return behind * self.summary_blocks + self.window_blocks

    def can_allocate(self, seq_group: SequenceGroup,
                     extra_reserved: int = 0) -> AllocStatus:
        """Admission verdict. `extra_reserved` blocks are treated as
        unavailable on top of the watermark hysteresis — the
        scheduler passes its low-watermark reserve (pages held back
        for running sequences' next decode slots) so admitting a
        prompt can never immediately force a preemption."""
        needed = self._prompt_blocks_needed(seq_group)
        free = self.hbm_pool.get_num_free_blocks()
        # The watermark hysteresis avoids admitting a prompt that would
        # immediately force evictions.
        if self.num_total_gpu_blocks - needed < self.watermark_blocks:
            return AllocStatus.NEVER
        if self.num_state_slots is not None:
            # a slot for every sequence the group may come to hold
            # (its forks take theirs later: `Scheduler` holds the
            # running sequences under the number of slots)
            seqs = seq_group.get_max_num_running_seqs()
            if seqs > self.num_state_slots:
                return AllocStatus.NEVER
            if seqs > len(self._free_state_slots):
                if free - needed >= self.watermark_blocks + extra_reserved:
                    # the pages were there: a want of slots alone
                    self.tracer.add("ssm.slot_waits")
                return AllocStatus.LATER
        if free - needed >= self.watermark_blocks + extra_reserved:
            return AllocStatus.OK
        return AllocStatus.LATER

    def allocate(self, seq_group: SequenceGroup) -> None:
        faultinject.fire("block_manager.allocate",
                         detail=seq_group.request_id)
        # All waiting sequences in a group share one prompt, hence one
        # physical block table (forked on first divergent append).
        seq = seq_group.get_seqs(status=SequenceStatus.WAITING)[0]
        num_prompt_blocks = len(seq.logical_token_blocks)
        prefix = seq_group.prefix
        if not self.plain:
            if prefix is not None:
                raise PageGroupsUnsupported(
                    "the prefix cache", "send the request without a "
                    "cached prefix")
            self._allocate_groups(seq_group, num_prompt_blocks)
            return

        block_table: BlockTable = []
        if prefix is not None and prefix.allocated:
            num_prompt_blocks -= prefix.get_num_blocks()
            for block in prefix.block_table:
                block.ref_count += seq_group.num_seqs()
                block_table.append(block)

        num_seqs = seq_group.num_seqs()
        for _ in range(num_prompt_blocks):
            block = self.hbm_pool.allocate()
            block.ref_count = num_seqs
            block_table.append(block)

        if prefix is not None and not prefix.allocated:
            # First request carrying this prefix: pin its leading blocks so
            # later requests can share the computed KV.
            shared = block_table[:prefix.get_num_blocks()]
            for block in shared:
                block.ref_count += 1
            prefix.set_block_table(shared)

        for waiting_seq in seq_group.get_seqs(status=SequenceStatus.WAITING):
            self.block_tables[waiting_seq.seq_id] = block_table.copy()

    def _allocate_groups(self, seq_group: SequenceGroup,
                         num_prompt_blocks: int) -> None:
        """A table for each page group, from the one free list: a
        full group's for the whole prompt, a window group's up to its
        cap."""
        num_seqs = seq_group.num_seqs()
        waiting = seq_group.get_seqs(status=SequenceStatus.WAITING)
        for seq in waiting:
            self.more_tables[seq.seq_id] = []
            self.first_blocks[seq.seq_id] = [0] * len(self.group_kinds)
            if self.summary_blocks:
                self.summary_tables[seq.seq_id] = [
                    [] for _ in self.group_kinds]
        for g, kind in enumerate(self.group_kinds):
            block_table: BlockTable = []
            for _ in range(self._prompt_blocks_of(kind,
                                                  num_prompt_blocks)):
                block = self.hbm_pool.allocate()
                block.ref_count = num_seqs
                block_table.append(block)
            for seq in waiting:
                if g == 0:
                    self.block_tables[seq.seq_id] = block_table.copy()
                else:
                    self.more_tables[seq.seq_id].append(block_table.copy())

    # ------------------------------------------------------------------
    # State slots
    # ------------------------------------------------------------------

    def assign_state(self, seq_group: SequenceGroup) -> None:
        """A state slot for each sequence of a prompt being admitted,
        beside the pages `allocate` gave it."""
        for seq in seq_group.get_seqs(status=SequenceStatus.WAITING):
            self._assign_state_slot(seq.seq_id)

    def _assign_state_slot(self, seq_id: int) -> int:
        if not self._free_state_slots:
            raise ValueError("Out of state slots! The scheduler admits "
                             "no more sequences than there are slots.")
        slot = self.state_slots[seq_id] = self._free_state_slots.pop()
        return slot

    def _free_state_slot(self, seq_id: int) -> None:
        slot = self.state_slots.pop(seq_id, None)
        if slot is not None:
            self._free_state_slots.append(slot)

    def get_state_slot(self, seq: Sequence) -> Optional[int]:
        """The sequence's state slot; None for a model without
        state."""
        return self.state_slots.get(seq.seq_id)

    def get_num_free_state_slots(self) -> int:
        return len(self._free_state_slots)

    def take_state_copies(self) -> List[Tuple[int, int]]:
        """The (from, to) slot copies that forks since the last call
        need on the device before their children's first step."""
        copies, self._state_copies = self._state_copies, []
        return copies

    # ------------------------------------------------------------------
    # The tables follow the sequence: window release, new pages, CoW
    # ------------------------------------------------------------------

    def _tables(self, seq_id: int) -> List[BlockTable]:
        """The sequence's table of each page group."""
        return [self.block_tables[seq_id]] + \
            self.more_tables.get(seq_id, [])

    def release_passed(self, seq: Sequence, first_query: int) -> int:
        """Let the window groups go of every page that lies wholly
        before the window of position `first_query`, the oldest query
        still to come; no later one reaches back further. The pages
        are on the free list at once, for any group of any sequence.
        Returns how many were let go."""
        firsts = self.first_blocks.get(seq.seq_id)
        if firsts is None or self.sliding_window is None:
            return 0
        keep_from = max(0, first_query - self.sliding_window + 1) \
            // self.block_size
        freed = 0
        for g, table in enumerate(self._tables(seq.seq_id)):
            drop = min(keep_from - firsts[g], len(table))
            if self.group_kinds[g] != "window" or drop <= 0:
                continue
            for block in table[:drop]:
                self.hbm_pool.free(block)
            del table[:drop]
            firsts[g] += drop
            freed += drop
        self.window_pages_freed += freed
        return freed

    def _close_windows(self, seq_id: int, pos: int) -> None:
        """A pooled group whose window lies wholly before position
        `pos`, the first still to be written: the pages its pooled
        keys fill are taken, both lists go to `take_window_closes`
        for the device, and the whole window is let go (the device
        reads it before any step of the round can write it again: the
        pooling program is first in device order). A prompt chunk
        never crosses a window's edge (`Scheduler._fit_chunk`), so a
        call closes one window at most."""
        firsts = self.first_blocks[seq_id]
        for g, table in enumerate(self._tables(seq_id)):
            if self.group_kinds[g] != "pooled" or \
                    pos // self.block_size < firsts[g] + self.window_blocks:
                continue
            if len(table) != self.window_blocks or \
                    pos // self.block_size >= \
                    firsts[g] + 2 * self.window_blocks:
                raise AssertionError(
                    f"sequence {seq_id} passes a window's edge at "
                    f"{pos} with {len(table)} of {self.window_blocks} "
                    "window pages")
            with self.tracer.span("cache.window_close"):
                summary = self.summary_tables[seq_id][g]
                taken = []
                for _ in range(self.summary_blocks):
                    block = self.hbm_pool.allocate()
                    summary.append(block)
                    taken.append(block.block_number)
                self._window_closes.append(
                    ([b.block_number for b in table], taken))
                for block in table:
                    self.hbm_pool.free(block)
                del table[:]
                firsts[g] += self.window_blocks
            self.window_pages_freed += self.window_blocks
            self.windows_closed += 1

    def take_window_closes(self) -> List[Tuple[List[int], List[int]]]:
        """The (window's pages in order, summary pages) of the windows
        closed since the last call: the device pools the one into the
        other before the round's steps."""
        closes, self._window_closes = self._window_closes, []
        return closes

    def _cover(self, seq_id: int, last_pos: int) -> None:
        """New pages at every table's end, up to position `last_pos`;
        a pooled group first closes the window that position has
        left.
        (Here and in `append_slots` a table is read off its owned
        container by name and not through `_tables`: the ownership
        ledger, `OWNERSHIP.json`, follows a page from `allocate()` to
        the container it lands in.)"""
        if self.summary_blocks:
            self._close_windows(seq_id, last_pos)
        firsts = self.first_blocks.get(seq_id)
        table = self.block_tables[seq_id]
        for g in range(len(self.group_kinds)):
            if g:
                table = self.more_tables[seq_id][g - 1]
            needed = last_pos // self.block_size + 1 - \
                (firsts[g] if firsts else 0)
            while len(table) < needed:
                table.append(self.hbm_pool.allocate())

    def prepare_chunk(self, seq: Sequence, ctx: int, length: int) -> None:
        """Before the prompt chunk `[ctx, ctx + length)` is written:
        the window groups let go of what its first query no longer
        sees, and take the pages up to its last token (a prompt's
        pages beyond a window group's cap are not taken at
        admission). What they let go covers what they take, once a
        table has reached its cap. A plain model's tables have held
        the whole prompt since admission."""
        if self.plain:
            return
        self.release_passed(seq, ctx)
        self._cover(seq.seq_id, ctx + length - 1)

    def can_append_slot(self, seq_group: SequenceGroup) -> bool:
        # One new block per running sequence and page group is the
        # worst case; a pooled group whose next token opens a window
        # takes the closed one's summary pages before it lets the
        # window go.
        num_seqs = seq_group.num_seqs(status=SequenceStatus.RUNNING)
        needed = num_seqs * len(self.group_kinds)
        if self.summary_blocks:
            pooled = self.group_kinds.count("pooled")
            for seq in seq_group.get_seqs(status=SequenceStatus.RUNNING):
                pos = seq.get_len() - 1 + seq.data.in_flight
                if pos % self.pooled_window == 0:
                    needed += pooled * (self.summary_blocks - 1)
        return needed <= self.hbm_pool.get_num_free_blocks()

    def append_slots(self, seq: Sequence) -> List[Tuple[int, int]]:
        """Reserve a slot for one new token in every page group.

        Returns the (src, dst) physical block pairs of the
        copy-on-writes required (the executor batches all pairs into
        one device copy)."""
        pos = seq.get_len() - 1
        self.release_passed(seq, pos)
        self._cover(seq.seq_id, pos)
        copies = []
        block_table = self.block_tables[seq.seq_id]
        for g in range(len(self.group_kinds)):
            if g:
                block_table = self.more_tables[seq.seq_id][g - 1]
            # (a fork shares a pooled group's summary pages for good:
            # they are never written again; its window's last page is
            # copied on write like any other)
            last_block = block_table[-1]
            assert last_block.device == Device.TPU
            if last_block.ref_count == 1:
                continue
            # Shared tail block (post-fork): copy-on-write.
            new_block = self.hbm_pool.allocate()
            block_table[-1] = new_block
            self.hbm_pool.free(last_block)
            copies.append((last_block.block_number,
                           new_block.block_number))
        return copies

    def burst_blocks_needed(self, seq: Sequence, num_ahead: int) -> int:
        """Blocks to allocate so the table covers positions up to
        seq.get_len()-1+num_ahead (multi-step decode pre-reservation)."""
        table = self.block_tables[seq.seq_id]
        needed = (seq.get_len() - 1 + num_ahead) // self.block_size + 1
        return max(0, needed - len(table))

    def has_unshared_tail(self, seq: Sequence) -> bool:
        if seq.seq_id not in self.block_tables:
            return False
        return all(table and table[-1].ref_count == 1
                   for table in self._tables(seq.seq_id))

    def reserve_slots(self, seq: Sequence, num_ahead: int) -> None:
        """Append enough fresh blocks for `num_ahead` future tokens.

        Only valid for unshared-tail sequences (no CoW can arise); the
        device computes each burst step's slot from the block table, so
        the pages must exist before the burst launches. With page
        groups this is the slot of a token still on the device, which
        is the next query: the window groups let go behind it.
        """
        last_pos = seq.get_len() - 1 + num_ahead
        if not self.plain:
            self.release_passed(seq, last_pos)
        self._cover(seq.seq_id, last_pos)

    def trim_reserved(self, seq: Sequence) -> int:
        """Release look-ahead pages reserved past the sequence's
        current length (the rollback seam for reserve_slots: burst or
        speculative reservations whose tokens were never emitted).
        Only unshared TPU tail blocks are trimmed — a shared or
        swapped tail means the pages are owned by more than this
        reservation. Returns the number of pages freed."""
        table = self.block_tables.get(seq.seq_id)
        if not table or not self.plain:
            return 0
        # (the slot of a token still on the device is no look-ahead:
        # the step that takes it is being scheduled or is in flight)
        needed = (seq.get_len() - 1 + seq.data.in_flight) \
            // self.block_size + 1
        freed = 0
        while len(table) > needed and table[-1].ref_count == 1 and \
                table[-1].device == Device.TPU:
            self.hbm_pool.free(table.pop())
            freed += 1
        return freed

    def fork(self, parent_seq: Sequence, child_seq: Sequence) -> None:
        src_block_table = self.block_tables[parent_seq.seq_id]
        self.block_tables[child_seq.seq_id] = src_block_table.copy()
        for block in src_block_table:
            block.ref_count += 1
        if self.num_state_slots is not None:
            self._state_copies.append(
                (self.state_slots[parent_seq.seq_id],
                 self._assign_state_slot(child_seq.seq_id)))
        if self.plain:
            return
        more = [t.copy() for t in self.more_tables[parent_seq.seq_id]]
        self.more_tables[child_seq.seq_id] = more
        self.first_blocks[child_seq.seq_id] = list(
            self.first_blocks[parent_seq.seq_id])
        for src_block_table in more:
            for block in src_block_table:
                block.ref_count += 1
        if self.summary_blocks:
            shared = [t.copy()
                      for t in self.summary_tables[parent_seq.seq_id]]
            self.summary_tables[child_seq.seq_id] = shared
            for src_block_table in shared:
                for block in src_block_table:
                    block.ref_count += 1

    # ------------------------------------------------------------------
    # Swap planning (preemption-by-swap)
    # ------------------------------------------------------------------

    def _group_physical_blocks(
            self, seq_group: SequenceGroup) -> List[PhysicalTokenBlock]:
        blocks: Set[PhysicalTokenBlock] = set()
        for seq in seq_group.get_seqs():
            if seq.is_finished():
                continue
            blocks.update(self.block_tables[seq.seq_id])
        return list(blocks)

    def can_swap_in(self, seq_group: SequenceGroup) -> bool:
        self._plain_only("preemption by swap")
        blocks = self._group_physical_blocks(seq_group)
        num_swapped_seqs = seq_group.num_seqs(status=SequenceStatus.SWAPPED)
        free = self.hbm_pool.get_num_free_blocks()
        # Each sequence will need one fresh block right after swap-in.
        required = len(blocks) + num_swapped_seqs
        return free - required >= self.watermark_blocks

    def swap_in(self, seq_group: SequenceGroup) -> Dict[int, int]:
        """Plan host->HBM copies; returns {cpu_block: hbm_block}."""
        if seq_group.prefix is not None:
            assert seq_group.prefix.allocated and seq_group.prefix.computed
        mapping: Dict[PhysicalTokenBlock, PhysicalTokenBlock] = {}
        for seq in seq_group.get_seqs(status=SequenceStatus.SWAPPED):
            new_block_table: BlockTable = []
            if seq_group.prefix is not None:
                for block in seq_group.prefix.block_table:
                    new_block_table.append(block)
                    block.ref_count += 1
            for cpu_block in self.block_tables[seq.seq_id]:
                if cpu_block in mapping:
                    hbm_block = mapping[cpu_block]
                    hbm_block.ref_count += 1
                else:
                    hbm_block = self.hbm_pool.allocate()
                    mapping[cpu_block] = hbm_block
                new_block_table.append(hbm_block)
                self.host_pool.free(cpu_block)
            self.block_tables[seq.seq_id] = new_block_table
        return {
            cpu.block_number: hbm.block_number
            for cpu, hbm in mapping.items()
        }

    def can_swap_out(self, seq_group: SequenceGroup) -> bool:
        self._plain_only("preemption by swap")
        blocks = self._group_physical_blocks(seq_group)
        return len(blocks) <= self.host_pool.get_num_free_blocks()

    def swap_out(self, seq_group: SequenceGroup) -> Dict[int, int]:
        """Plan HBM->host copies; returns {hbm_block: cpu_block}."""
        mapping: Dict[PhysicalTokenBlock, PhysicalTokenBlock] = {}
        for seq in seq_group.get_seqs(status=SequenceStatus.RUNNING):
            new_block_table: BlockTable = []
            for hbm_block in self.block_tables[seq.seq_id]:
                if (seq_group.prefix is not None
                        and hbm_block in seq_group.prefix.block_table):
                    # Shared prefix blocks stay resident on HBM.
                    self.hbm_pool.free(hbm_block)
                    continue
                if hbm_block in mapping:
                    cpu_block = mapping[hbm_block]
                    cpu_block.ref_count += 1
                else:
                    cpu_block = self.host_pool.allocate()
                    mapping[hbm_block] = cpu_block
                new_block_table.append(cpu_block)
                self.hbm_pool.free(hbm_block)
            self.block_tables[seq.seq_id] = new_block_table
        return {
            hbm.block_number: cpu.block_number
            for hbm, cpu in mapping.items()
        }

    # ------------------------------------------------------------------
    # Teardown / queries
    # ------------------------------------------------------------------

    def _free_block_table(self, block_table: BlockTable) -> None:
        # Order-preserving dedup: prefix-shared tables repeat blocks,
        # but the frees must land in table order (set order hashes by
        # id, so a reincarnated process would rebuild its free lists
        # in a different order and break the bit-equal replay).
        for block in dict.fromkeys(block_table):
            if block.device == Device.TPU:
                self.hbm_pool.free(block)
            else:
                self.host_pool.free(block)

    def _free_group_tables(self, tables: List[BlockTable]) -> None:
        """A sequence's tables of the page groups after the first."""
        for group_table in tables:
            self._free_block_table(group_table)

    def _plain_only(self, what: str) -> None:
        if not self.plain:
            raise PageGroupsUnsupported(
                what, "a sequence group of several sequences is "
                "preempted by swap: use best_of 1, or give the pool "
                "room (--gpu-memory-utilization, --max-num-seqs)")

    def free(self, seq: Sequence) -> None:
        if seq.seq_id not in self.block_tables:
            # Never scheduled, or already freed.
            return
        self._free_block_table(self.block_tables.pop(seq.seq_id))
        self._free_group_tables(self.more_tables.pop(seq.seq_id, []))
        self._free_group_tables(self.summary_tables.pop(seq.seq_id, []))
        self.first_blocks.pop(seq.seq_id, None)
        self._free_state_slot(seq.seq_id)

    def free_prefix(self, prefix: Prefix) -> int:
        """Release a prefix's pin: the one refcount `allocate` added
        when it first populated the prefix's block table. Returns the
        number of pages whose pin was dropped. Idempotent via the
        un-allocated early return; pages still shared by live
        sequences survive their own tables' refs and return to the
        pool on the last sequence free."""
        if not prefix.allocated:
            return 0
        released = 0
        for block in prefix.block_table:
            self.hbm_pool.free(block)
            released += 1
        prefix.reset_block_table()
        return released

    def reset(self) -> None:
        for block_table in self.block_tables.values():
            self._free_block_table(block_table)
        for tables in self.more_tables.values():
            self._free_group_tables(tables)
        for summaries in self.summary_tables.values():
            self._free_group_tables(summaries)
        self.block_tables.clear()
        self.more_tables.clear()
        self.summary_tables.clear()
        self._window_closes.clear()
        self.first_blocks.clear()
        self._free_state_slots = list(
            range(self.num_state_slots or 0))[::-1]
        self.state_slots.clear()
        self._state_copies.clear()

    def get_block_table(self, seq: Sequence) -> List[int]:
        return [b.block_number for b in self.block_tables[seq.seq_id]]

    def get_group_tables(self, seq: Sequence
                         ) -> Optional[List[Tuple[int, List[int]]]]:
        """For a model whose page groups are not plain: each group's
        (tokens its table has let go of at its start, page numbers);
        None for a plain one, whose table is `get_block_table`'s. A
        pooled group's table is its two lists as one, `[summary pages ;
        window pages]`: a summary page stands in the place of a page
        of pages, so the table has let go of what the windows behind
        held less what their summaries hold, and a position counted
        from its start is `summaries + place in the window`."""
        if self.plain:
            return None
        firsts = self.first_blocks[seq.seq_id]
        summaries = self.summary_tables.get(
            seq.seq_id, [[]] * len(self.group_kinds))
        return [((firsts[g] - len(summaries[g])) * self.block_size,
                 [b.block_number for b in summaries[g] + table])
                for g, table in enumerate(self._tables(seq.seq_id))]

    def block_numbers(self, seq_id: int) -> List[int]:
        """Page numbers for one sequence id — the int-only projection
        callers outside this module must use (raw PhysicalTokenBlock
        objects never cross the owner boundary)."""
        return [b.block_number for b in self.block_tables[seq_id]]

    def get_num_free_gpu_blocks(self) -> int:
        return self.hbm_pool.get_num_free_blocks()

    def get_num_free_cpu_blocks(self) -> int:
        return self.host_pool.get_num_free_blocks()
