"""Block manager tests (reference behavior: processing/block_manager.py)."""
import pytest

from aphrodite_tpu.common.block import Device
from aphrodite_tpu.common.prefix import Prefix, PrefixPool
from aphrodite_tpu.common.sampling_params import SamplingParams
from aphrodite_tpu.common.sequence import (Sequence, SequenceGroup,
                                           SequenceStatus)
from aphrodite_tpu.processing.block_manager import (AllocStatus, BlockPool,
                                                    BlockSpaceManager)

BLOCK_SIZE = 4

_seq_counter = iter(range(10_000))


def make_group(prompt_len, num_seqs=1, request_id="0", best_of=None,
               prefix=None):
    seqs = [
        Sequence(next(_seq_counter), "x", list(range(prompt_len)), BLOCK_SIZE)
        for _ in range(num_seqs)
    ]
    params = SamplingParams(n=num_seqs,
                            best_of=best_of or num_seqs,
                            temperature=1.0)
    return SequenceGroup(request_id, seqs, params, arrival_time=0.0,
                         prefix=prefix)


def test_pool_alloc_free():
    pool = BlockPool(Device.TPU, BLOCK_SIZE, 4)
    blocks = [pool.allocate() for _ in range(4)]
    assert pool.get_num_free_blocks() == 0
    with pytest.raises(ValueError):
        pool.allocate()
    for b in blocks:
        pool.free(b)
    assert pool.get_num_free_blocks() == 4
    with pytest.raises(ValueError):
        pool.free(blocks[0])  # double free


def test_can_allocate_watermark():
    mgr = BlockSpaceManager(BLOCK_SIZE,
                            num_gpu_blocks=100,
                            num_cpu_blocks=10,
                            watermark=0.1)
    assert mgr.can_allocate(make_group(4 * 50)) == AllocStatus.OK
    # Larger than total minus watermark: never schedulable.
    assert mgr.can_allocate(make_group(4 * 95)) == AllocStatus.NEVER
    # Fill up the pool, then a small request must wait.
    big = make_group(4 * 85, request_id="big")
    mgr.allocate(big)
    assert mgr.can_allocate(make_group(4 * 10)) == AllocStatus.LATER


def test_allocate_and_append_slot():
    mgr = BlockSpaceManager(BLOCK_SIZE, 10, 10, watermark=0)
    group = make_group(prompt_len=6)
    mgr.allocate(group)
    seq = group.get_seqs()[0]
    seq.status = SequenceStatus.RUNNING
    assert mgr.get_block_table(seq) is not None
    assert len(mgr.get_block_table(seq)) == 2
    assert mgr.get_num_free_gpu_blocks() == 8

    # Append within last block: no new allocation.
    seq.append_token_id(100, {100: 0.0})  # len 7, fits block 2
    assert mgr.append_slots(seq) == []
    assert mgr.get_num_free_gpu_blocks() == 8
    # Cross the block boundary: new block allocated.
    seq.append_token_id(101, {101: 0.0})  # len 8 -> still 2 blocks
    assert mgr.append_slots(seq) == []
    seq.append_token_id(102, {102: 0.0})  # len 9 -> 3 blocks
    assert mgr.append_slots(seq) == []
    assert mgr.get_num_free_gpu_blocks() == 7


def test_copy_on_write_fork():
    mgr = BlockSpaceManager(BLOCK_SIZE, 10, 10, watermark=0)
    group = make_group(prompt_len=6, num_seqs=1, best_of=2)
    mgr.allocate(group)
    parent = group.get_seqs()[0]
    parent.status = SequenceStatus.RUNNING
    child = parent.fork(new_seq_id=100)
    group.add(child)
    mgr.fork(parent, child)
    # Both tables share blocks; last block is shared => CoW on append.
    parent.append_token_id(7, {7: 0.0})
    ((src, dst),) = mgr.append_slots(parent)
    assert src != dst
    # Child keeps the old block; appending to child now hits ref_count 1.
    child.append_token_id(8, {8: 0.0})
    assert mgr.append_slots(child) == []


def test_sliding_window_table_slides():
    """A model-wide window is one page group, a window group: its
    table slides. A prompt that comes whole takes its pages whole (the
    chunk being written is the prompt); from then on the table lets go
    of every page that lies wholly before the window of the next
    query, so it never holds more than the window and a page."""
    mgr = BlockSpaceManager(BLOCK_SIZE,
                            10,
                            10,
                            watermark=0,
                            sliding_window=8)  # 2 blocks
    assert mgr.group_kinds == ("window",) and not mgr.plain
    group = make_group(prompt_len=16)  # 4 logical blocks
    assert mgr.can_allocate(group) == AllocStatus.OK
    mgr.allocate(group)
    seq = group.get_seqs()[0]
    seq.status = SequenceStatus.RUNNING
    assert mgr.get_num_free_gpu_blocks() == 6
    # Appending past the window: what the table lets go of covers what
    # it takes, and it holds the window and a page at most.
    for tok in range(16, 32):
        seq.append_token_id(tok, {tok: 0.0})
        assert mgr.append_slots(seq) == []
        let_go, table = mgr.get_group_tables(seq)[0]
        pos = seq.get_len() - 1
        assert let_go == max(0, pos - 8 + 1) // BLOCK_SIZE * BLOCK_SIZE
        assert len(table) == pos // BLOCK_SIZE + 1 - let_go // BLOCK_SIZE
        assert len(table) <= 8 // BLOCK_SIZE + 1
    assert mgr.get_num_free_gpu_blocks() == 10 - 2
    assert mgr.window_pages_freed == 6
    mgr.free(seq)
    assert mgr.get_num_free_gpu_blocks() == 10


def test_swap_roundtrip():
    mgr = BlockSpaceManager(BLOCK_SIZE, 10, 10, watermark=0)
    group = make_group(prompt_len=8)
    mgr.allocate(group)
    seq = group.get_seqs()[0]
    seq.status = SequenceStatus.RUNNING
    assert mgr.can_swap_out(group)
    mapping_out = mgr.swap_out(group)
    seq.status = SequenceStatus.SWAPPED
    assert len(mapping_out) == 2
    assert mgr.get_num_free_gpu_blocks() == 10
    assert mgr.get_num_free_cpu_blocks() == 8
    assert mgr.can_swap_in(group)
    mapping_in = mgr.swap_in(group)
    seq.status = SequenceStatus.RUNNING
    assert len(mapping_in) == 2
    assert mgr.get_num_free_cpu_blocks() == 10
    mgr.free(seq)
    assert mgr.get_num_free_gpu_blocks() == 10


def test_a_window_model_refuses_the_prefix_cache_and_swap():
    """A cached prefix pins pages that a window group would let go of,
    and a swapped table has no host copy of what slid away: a model
    with a window group refuses both with a stated error rather than
    be half-right (the wrapped table of the old window code aliased a
    prefix block and clobbered its pin)."""
    from aphrodite_tpu.processing.block_manager import \
        PageGroupsUnsupported
    mgr = BlockSpaceManager(BLOCK_SIZE, 10, 10, watermark=0,
                            sliding_window=8)   # 2-block window
    prefix = Prefix(list(range(BLOCK_SIZE)), BLOCK_SIZE)  # 1 block
    g1 = make_group(20, request_id="g1", prefix=prefix)   # 5 blocks
    with pytest.raises(PageGroupsUnsupported, match="prefix cache"):
        mgr.allocate(g1)
    # nothing was taken, nothing pinned
    assert not prefix.allocated
    assert mgr.get_num_free_gpu_blocks() == 10
    g2 = make_group(20, request_id="g2")
    mgr.allocate(g2)
    for seq in g2.get_seqs():
        seq.status = SequenceStatus.RUNNING
    with pytest.raises(PageGroupsUnsupported, match="swap"):
        mgr.can_swap_out(g2)
    with pytest.raises(PageGroupsUnsupported, match="swap"):
        mgr.can_swap_in(g2)
    for seq in g2.get_seqs():
        mgr.free(seq)
    assert mgr.get_num_free_gpu_blocks() == 10
    # a model without a window serves both as ever
    plain = BlockSpaceManager(BLOCK_SIZE, 10, 10, watermark=0)
    g3 = make_group(20, request_id="g3", prefix=prefix)
    plain.allocate(g3)
    assert prefix.allocated and plain.can_swap_out(g3)


def test_prefix_pool_accounting_and_clear():
    """PrefixPool accounting: `pinned_pages()` tracks allocated
    prefixes exactly, and `clear()` transfers ownership of the
    entries so the pins can be routed through `free_prefix` (the
    Scheduler.clear_prefixes / reincarnate wiring)."""
    mgr = BlockSpaceManager(BLOCK_SIZE, 10, 10, watermark=0)
    pool = PrefixPool(BLOCK_SIZE)
    assert pool.pinned_pages() == 0
    prefix = pool.intern(list(range(8)))        # 2 blocks
    assert prefix is not None
    assert pool.intern(list(range(8))) is prefix   # pooled, not dup
    assert pool.pinned_pages() == 0             # not yet allocated
    group = make_group(12, request_id="p", prefix=prefix)
    mgr.allocate(group)
    assert pool.pinned_pages() == 2
    for seq in group.get_seqs():
        mgr.free(seq)
    # pinned pages survive their sequences — held on purpose
    assert mgr.get_num_free_gpu_blocks() == 8
    entries = pool.clear()
    assert entries == [prefix] and pool.prefixes == {}
    released = sum(mgr.free_prefix(p) for p in entries)
    assert released == 2
    assert mgr.get_num_free_gpu_blocks() == 10
    assert pool.pinned_pages() == 0


def test_block_numbers_projection():
    """The owner's int-only projection matches get_block_table and
    never hands out block objects."""
    mgr = BlockSpaceManager(BLOCK_SIZE, 10, 10, watermark=0)
    group = make_group(8, request_id="n")
    mgr.allocate(group)
    seq = group.get_seqs()[0]
    nums = mgr.block_numbers(seq.seq_id)
    assert nums == mgr.get_block_table(seq)
    assert all(isinstance(n, int) for n in nums)


def test_parity_aliases_still_work():
    """The reference-spelling aliases (gpu_allocator/cpu_allocator,
    PrefixPool.add_or_get_prefix) stay functional for parity
    callers."""
    mgr = BlockSpaceManager(BLOCK_SIZE, 4, 4, watermark=0)
    assert mgr.gpu_allocator is mgr.hbm_pool
    assert mgr.cpu_allocator is mgr.host_pool
    pool = PrefixPool(BLOCK_SIZE)
    assert pool.add_or_get_prefix(list(range(4))) is \
        pool.intern(list(range(4)))


def test_free_and_reset():
    mgr = BlockSpaceManager(BLOCK_SIZE, 10, 10, watermark=0)
    g1, g2 = make_group(8, request_id="1"), make_group(8, request_id="2")
    mgr.allocate(g1)
    mgr.allocate(g2)
    assert mgr.get_num_free_gpu_blocks() == 6
    mgr.free(g1.get_seqs()[0])
    assert mgr.get_num_free_gpu_blocks() == 8
    # Freeing twice is a no-op.
    mgr.free(g1.get_seqs()[0])
    mgr.reset()
    assert mgr.get_num_free_gpu_blocks() == 10
