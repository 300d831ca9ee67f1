"""State slots through the block manager, the scheduler and the
engine's refusals: the second kind of per-sequence memory, beside the
KV pages, of a model that keeps recurrent state
(`common/config.py::StateSpec`). One id a sequence from a free list of
its own; given with the prompt's pages, freed with them; a fork's child
takes a slot and the device copies the parent's rows; nothing is zeroed
on the host. What follows pages alone (swap, prefix pins, bursts,
speculative rounds) is refused or skipped, as for page groups."""
import pytest

from aphrodite_tpu.common.config import (CacheConfig, PageGroups,
                                         SchedulerConfig, StateSpec)
from aphrodite_tpu.common.sampling_params import SamplingParams
from aphrodite_tpu.common.sequence import (Sequence, SequenceGroup,
                                           SequenceStatus)
from aphrodite_tpu.processing.block_manager import (AllocStatus,
                                                    BlockSpaceManager,
                                                    PageGroupsUnsupported)
from aphrodite_tpu.processing.scheduler import Scheduler

BLOCK, WINDOW, CHUNK = 4, 16, 8
#: mamba, window, mamba, full, gated unit, cross: the four kinds
KINDS = [None, "window", None, "full", None, 3]
SPEC = StateSpec(layers=2, arrays=(((3, 8), "bfloat16"),
                                   ((4, 8), "float32")))

_seq_ids = iter(range(10_000))


def make_scheduler(slots, pages=64, max_num_seqs=8):
    cache = CacheConfig(
        block_size=BLOCK, state_spec=SPEC,
        page_groups=PageGroups.of(KINDS, WINDOW, stateful=True))
    cache.num_gpu_blocks, cache.num_cpu_blocks = pages, 0
    cache.num_state_slots = slots
    sched = SchedulerConfig(max_num_batched_tokens=256,
                            max_num_seqs=max_num_seqs, max_model_len=256,
                            max_paddings=256, max_chunk_tokens=CHUNK)
    return Scheduler(sched, cache, None)


def make_group(request_id, prompt_len, best_of=1):
    seq = Sequence(next(_seq_ids), "x", list(range(prompt_len)), BLOCK)
    params = SamplingParams(n=best_of, best_of=best_of,
                            temperature=1.0 if best_of > 1 else 0.0)
    return SequenceGroup(request_id, [seq], params, arrival_time=0.0)


def sampled(out):
    for group in [c.group for c in out.prompt_chunks if c.is_final] + \
            list(out.decode_groups):
        for seq in group.get_seqs(status=SequenceStatus.RUNNING):
            tok = seq.get_len()
            seq.append_token_id(tok, {tok: 0.0})


def test_the_four_layer_kinds_of_page_groups():
    groups = PageGroups.of(KINDS, WINDOW, stateful=True)
    # one window and one full layer hold pages: gcd 1, a group each,
    # one pair of page arrays; the cross layer is the full layer's
    # reader, the others hold nothing
    assert groups.kinds == ("window", "full")
    assert groups.group_of_layer == (-1, 0, -1, 1, -1, 1)
    assert groups.slot_of_layer == (-1, 0, -1, 0, -1, 0)
    assert groups.layers_per_group == 1 and groups.readers == (1, 2)
    assert groups.stateful and not groups.plain
    # state beside one full group is not plain either
    assert not PageGroups.of([None, "full"], None, stateful=True).plain
    # the published layout: 9 groups, a pair of arrays, 8 readers
    flash = PageGroups.of(
        [None if l % 2 == 0 else "window" if l < 16 else
         "full" if l == 17 else 17 for l in range(32)], 512, stateful=True)
    assert flash.kinds == ("window",) * 8 + ("full",)
    assert flash.layers_per_group == 1
    assert flash.readers == (1,) * 8 + (8,)
    with pytest.raises(ValueError, match="comes no earlier or holds none"):
        PageGroups.of(["full", 2, None], None)
    with pytest.raises(ValueError, match="page groups that say so"):
        CacheConfig(block_size=BLOCK, state_spec=SPEC)
    assert SPEC.slot_bytes == 2 * (3 * 8 * 2 + 4 * 8 * 4)


def test_a_slot_comes_with_the_pages_and_goes_with_them():
    sched = make_scheduler(slots=3)
    mgr = sched.block_manager
    groups = [make_group(str(i), 8) for i in range(3)]
    for group in groups:
        sched.add_seq_group(group)
    mds, out = sched.schedule()
    # one prompt a round under the chunk cap; each gets its slot at
    # admission, distinct ids
    for _ in range(4):
        sampled(out)
        mds, out = sched.schedule()
    seqs = [g.get_seqs()[0] for g in groups]
    slots = [mgr.get_state_slot(s) for s in seqs]
    assert sorted(slots) == [0, 1, 2]
    assert mgr.get_num_free_state_slots() == 0
    assert sched.tracer.counts["cache.state_assign"] == 3
    by_id = {md.request_id: md for md in mds}
    for group, seq, slot in zip(groups, seqs, slots):
        if group.request_id in by_id:
            assert by_id[group.request_id].state_slots == \
                {seq.seq_id: slot}
    # freed with its pages, and the next owner takes the same id
    seqs[1].status = SequenceStatus.FINISHED_STOPPED
    sched.free_seq(seqs[1])
    sched.free_finished_seq_groups()
    assert mgr.get_num_free_state_slots() == 1
    assert mgr.get_state_slot(seqs[1]) is None
    late = make_group("late", 8)
    sched.add_seq_group(late)
    sampled(out)
    sched.schedule()
    assert mgr.get_state_slot(late.get_seqs()[0]) == slots[1]


def test_no_slot_no_admission():
    """Pages for ten prompts, slots for two: the third waits, and is
    admitted when a slot comes back. The scheduler holds the running
    sequences under the number of slots, whatever --max-num-seqs."""
    sched = make_scheduler(slots=2, pages=64, max_num_seqs=8)
    mgr = sched.block_manager
    assert sched.max_num_seqs == 2
    groups = [make_group(str(i), 8) for i in range(3)]
    for group in groups:
        sched.add_seq_group(group)
    for _ in range(6):
        _, out = sched.schedule()
        sampled(out)
    assert [g.request_id for g in sched.waiting] == ["2"]
    assert mgr.can_allocate(groups[2]) == AllocStatus.LATER
    # a group of more sequences than there are slots never fits
    assert mgr.can_allocate(make_group("wide", 8, best_of=3)) == \
        AllocStatus.NEVER
    first = groups[0].get_seqs()[0]
    first.status = SequenceStatus.FINISHED_STOPPED
    sched.free_seq(first)
    sched.free_finished_seq_groups()
    for _ in range(2):
        _, out = sched.schedule()
        sampled(out)
    assert not sched.waiting
    assert mgr.get_state_slot(groups[2].get_seqs()[0]) is not None
    with pytest.raises(ValueError, match="Out of state slots"):
        mgr._assign_state_slot(99_999)


@pytest.mark.parametrize("slots,pages,third,waits", [
    (2, 64, 8, True), (8, 16, 24, False)],
    ids=["a-want-of-slots", "a-want-of-pages"])
def test_slot_waits_count_a_want_of_slots_and_not_of_pages(slots, pages,
                                                           third, waits):
    """`ssm.slot_waits` (`aphrodite:ssm_slot_waits_total`) is raised
    where `can_allocate` answers "later" for want of a slot while the
    pages were there, once a round the prompt at the head of the queue
    waits so; a prompt that waits for pages (slots free: its 24 tokens
    take 12 of the 16 pages, and the two rows before it hold 8) raises
    nothing."""
    sched = make_scheduler(slots=slots, pages=pages, max_num_seqs=8)
    mgr, counts = sched.block_manager, sched.tracer.counts
    assert mgr.tracer is sched.tracer
    groups = [make_group("0", 8), make_group("1", 8),
              make_group("2", third)]
    for group in groups:
        sched.add_seq_group(group)
    for _ in range(3):
        _, out = sched.schedule()
        sampled(out)
    assert [g.request_id for g in sched.waiting] == ["2"]
    before = counts["ssm.slot_waits"]
    _, out = sched.schedule()
    assert mgr.can_allocate(groups[2]) == AllocStatus.LATER
    if waits:
        assert mgr.get_num_free_state_slots() == 0
        # one for the round it waited, and one for the ask above
        assert before >= 1 and counts["ssm.slot_waits"] == before + 2
    else:
        assert mgr.get_num_free_state_slots() == slots - 2
        assert counts["ssm.slot_waits"] == 0
    from aphrodite_tpu.engine.metrics import _STAGE_COUNTERS
    exported = {name: total(sched.tracer.seconds, counts)
                for name, _, total in _STAGE_COUNTERS}
    assert exported["aphrodite:ssm_slot_waits_total"] == \
        counts["ssm.slot_waits"]


def test_a_fork_takes_a_slot_and_asks_for_the_copy():
    sched = make_scheduler(slots=4)
    mgr = sched.block_manager
    group = make_group("g", 8, best_of=2)
    sched.add_seq_group(group)
    _, out = sched.schedule()
    assert out.state_copies == []
    parent = group.get_seqs()[0]
    child = parent.fork(next(_seq_ids))
    group.add(child)
    sched.fork_seq(parent, child)
    a, b = mgr.get_state_slot(parent), mgr.get_state_slot(child)
    assert a != b and mgr.get_num_free_state_slots() == 2
    # the copy rides the next round, once
    sampled(out)
    mds, out = sched.schedule()
    assert out.state_copies == [(a, b)] and not out.is_empty()
    (md,) = mds
    assert md.state_slots == {parent.seq_id: a, child.seq_id: b}
    sampled(out)
    _, out = sched.schedule()
    assert out.state_copies == []
    # both go back with their sequences
    for seq in (parent, child):
        seq.status = SequenceStatus.FINISHED_STOPPED
        sched.free_seq(seq)
    assert mgr.get_num_free_state_slots() == 4


def test_preemption_by_recompute_and_reset_give_the_slots_back():
    """A row preempted by recompute starts again from its prompt, at
    position 0, where the program starts from zeros: the host only
    gives the slot back. `reset` (a rolled-back engine) likewise."""
    sched = make_scheduler(slots=2, pages=2 * 2 * 3 + 1)
    mgr = sched.block_manager
    a, b = make_group("a", 12), make_group("b", 12)
    for group in (a, b):
        sched.add_seq_group(group)
    preempted = False
    for _ in range(40):
        _, out = sched.schedule()
        if sched.waiting and out.decode_groups:
            preempted = True
            (back,) = sched.waiting
            seq = back.get_seqs()[0]
            assert mgr.get_state_slot(seq) is None
            assert seq.data.num_computed_tokens == 0
            assert mgr.get_num_free_state_slots() == 1
        sampled(out)
    assert preempted and sched.tracer.counts["preemptions"] >= 1
    mgr.reset()
    assert mgr.get_num_free_state_slots() == 2 and not mgr.state_slots
    assert mgr.get_num_free_gpu_blocks() == mgr.num_total_gpu_blocks
    assert mgr.take_state_copies() == []


def test_what_follows_pages_alone_is_refused():
    sched = make_scheduler(slots=2)
    mgr = sched.block_manager
    group = make_group("g", 8)
    assert not mgr.plain
    for ask in (mgr.can_swap_in, mgr.can_swap_out):
        with pytest.raises(PageGroupsUnsupported,
                           match="recurrent state"):
            ask(group)
    from aphrodite_tpu.common.prefix import Prefix
    group.prefix = Prefix(list(range(4)), BLOCK)
    with pytest.raises(PageGroupsUnsupported, match="the prefix cache"):
        mgr.allocate(group)
    # a manager with state beside ONE full group refuses the same
    alone = BlockSpaceManager(BLOCK, 16, 0, num_state_slots=2)
    assert alone.group_kinds == ("full",) and not alone.plain
    with pytest.raises(PageGroupsUnsupported):
        alone.can_swap_out(make_group("h", 8))
