"""A pooled page group (EVA's chunked attention, `PageGroups` kind
"pooled") through the block manager and the scheduler: two lists a
sequence, the current window's pages and the summary pages of the
windows behind, shown as one table; a window closed WHOLE at its edge
(summary pages taken, both lists handed to the device, the window's
pages let go) in chunked prefill and decode alike; a prompt chunk cut
at the edge; admission, fork, preemption by recompute, free; what
follows pages alone refused."""
import pytest

from aphrodite_tpu.common.config import (CacheConfig, PageGroups,
                                         SchedulerConfig)
from aphrodite_tpu.common.sampling_params import SamplingParams
from aphrodite_tpu.common.sequence import (Sequence, SequenceGroup,
                                           SequenceStatus)
from aphrodite_tpu.processing.block_manager import (AllocStatus,
                                                    BlockSpaceManager,
                                                    PageGroupsUnsupported)
from aphrodite_tpu.processing.scheduler import Scheduler

BLOCK = 4            # the page, and the chunk a pooled key stands for
WINDOW = 32          # tokens: 8 pages, whose 8 pooled keys fill 2 pages
W_PAGES, S_PAGES = WINDOW // BLOCK, WINDOW // BLOCK ** 2
LET_GO = WINDOW - WINDOW // BLOCK    # tokens a closed window's table drops

_seq_ids = iter(range(10_000))


def make_scheduler(pages, layers=4, chunk=12, max_num_seqs=8, budget=256):
    cache = CacheConfig(block_size=BLOCK, page_groups=PageGroups.of(
        ["pooled"] * layers, None, pooled_window=WINDOW))
    cache.num_gpu_blocks, cache.num_cpu_blocks = pages, 0
    sched = SchedulerConfig(max_num_batched_tokens=budget,
                            max_num_seqs=max_num_seqs, max_model_len=256,
                            max_paddings=256, max_chunk_tokens=chunk)
    return Scheduler(sched, cache, None)


def make_group(request_id, prompt_len, **sampling):
    seq = Sequence(next(_seq_ids), "x", list(range(prompt_len)), BLOCK)
    return SequenceGroup(request_id, [seq], SamplingParams(**sampling),
                         arrival_time=0.0)


def sampled(out):
    rows = [c.group for c in out.prompt_chunks if c.is_final] + \
        list(out.decode_groups)
    for group in rows:
        for seq in group.get_seqs(status=SequenceStatus.RUNNING):
            tok = seq.get_len()
            seq.append_token_id(tok, {tok: 0.0})


def held(sched):
    """Pages out of the free list, and page references in the lists
    (equal while no page is shared)."""
    mgr = sched.block_manager
    in_lists = sum(len(t) for seq_id in mgr.block_tables
                   for t in mgr._tables(seq_id)) + \
        sum(len(t) for tables in mgr.summary_tables.values()
            for t in tables)
    return mgr.num_total_gpu_blocks - mgr.get_num_free_gpu_blocks(), \
        in_lists


def test_the_kind_is_one_group_of_every_layer():
    groups = PageGroups.of(["pooled"] * 8, None, pooled_window=2048)
    assert groups.kinds == ("pooled",) and groups.layers_per_group == 8
    assert groups.window is None and groups.pooled_window == 2048
    assert not groups.plain and groups.readers == (8,)
    # EvaByte's: 128 pages a window, whose 128 pooled keys are 8 pages
    assert groups.pooled_pages(16) == (128, 8)
    mixed = PageGroups.of(["pooled", "full", "pooled", "full"], None,
                          pooled_window=64)
    assert mixed.kinds == ("pooled", "full")
    assert mixed.group_of_layer == (0, 1, 0, 1)
    with pytest.raises(ValueError, match="multiple of block_size squared"):
        BlockSpaceManager(8, 64, 0, group_kinds=("pooled",),
                          pooled_window=96)


def test_two_lists_one_table_through_a_sequences_life():
    """A prompt of 76 tokens (two whole windows and 12 tokens of a
    third) written beside another request's decode row (the first
    chunk takes the round's whole budget up to the edge, the rest come
    in chunks of 12 cut at the edges), then 60 decode steps across the
    edges at 96 and 128, then free: at every round the composed table is
    `[summary pages ; window pages]`, counted from its first summary;
    a window closes in the round that writes the position after it;
    the pool's free count returns to where it began."""
    sched = make_scheduler(pages=60)
    mgr = sched.block_manager
    other = make_group("b", 5)
    sched.add_seq_group(other)
    sampled(sched.schedule()[1])
    group = make_group("a", 76)
    seq = group.get_seqs()[0]
    sched.add_seq_group(group)
    chunks, closes = [], []
    for _ in range(80):
        mds, out = sched.schedule()
        mine = [c for c in out.prompt_chunks if c.group is group]
        chunks += [(c.ctx, c.length) for c in mine]
        closes += out.window_closes
        (md,) = [md for md in mds if md.request_id == "a"]
        ((let_go, table),) = md.group_tables[seq.seq_id]
        # the position this round writes last, and its window
        pos = mine[0].ctx + mine[0].length - 1 if mine \
            else seq.get_len() - 1
        behind = pos // WINDOW
        assert let_go == behind * LET_GO
        assert len(table) == behind * S_PAGES + pos % WINDOW // BLOCK + 1
        assert sum(map(len, mgr.summary_tables[seq.seq_id])) == \
            behind * S_PAGES
        assert len(set(table)) == len(table)
        assert held(sched)[0] == held(sched)[1]
        sampled(out)
        if seq.get_output_len() >= 60:
            break
    # no chunk crosses an edge: 32 | 12, 12, 8 | 12
    assert chunks == [(0, 32), (32, 12), (44, 12), (56, 8), (64, 12)]
    # a close hands the device the window's 8 pages in order and the
    # 2 pages taken for its pooled keys (the other row closed two
    # windows meanwhile, at its positions 32 and 64)
    assert len(closes) == 6
    assert all(len(w) == W_PAGES and len(s) == S_PAGES for w, s in closes)
    assert sched.tracer.counts["attn.windows_closed_prompt"] == 2
    assert sched.tracer.counts["attn.windows_closed_decode"] == 4
    assert sched.tracer.counts["cache.window_pages_freed"] == \
        mgr.window_pages_freed == 6 * W_PAGES
    for one in (seq, other.get_seqs()[0]):
        one.status = SequenceStatus.FINISHED_STOPPED
        sched.free_seq(one)
    sched.free_finished_seq_groups()
    assert held(sched) == (0, 0)
    assert not mgr.summary_tables and not mgr.first_blocks


def test_a_round_of_prompts_alone_writes_whole_windows():
    """Without a decode row the round's whole budget is the chunk's,
    and the edge is what cuts it: 32, 32, 12."""
    sched = make_scheduler(pages=40)
    sched.add_seq_group(make_group("a", 76))
    chunks = []
    for _ in range(3):
        _, out = sched.schedule()
        chunks += [(c.ctx, c.length) for c in out.prompt_chunks]
    assert chunks == [(0, 32), (32, 32), (64, 12)]


def test_a_prompt_that_ends_on_an_edge_closes_it_with_its_first_token():
    sched = make_scheduler(pages=40, chunk=32)
    group = make_group("a", 2 * WINDOW)
    seq = group.get_seqs()[0]
    sched.add_seq_group(group)
    seen = []
    for _ in range(4):
        mds, out = sched.schedule()
        seen.append((len(out.window_closes),
                     mds[0].group_tables[seq.seq_id][0]))
        sampled(out)
    # chunk 1, chunk 2 (closes the first window), the first decode row
    # (closes the second: two summaries and one fresh page)
    assert [n for n, _ in seen] == [0, 1, 1, 0]
    let_go, table = seen[2][1]
    assert let_go == 2 * LET_GO and len(table) == 2 * S_PAGES + 1


def test_admission_counts_the_longest_need():
    """A prompt is admitted only where the most pages its writing
    holds at once are free: the summaries of every window behind its
    last beside that whole window; a decode row at an edge needs the
    summary pages before it lets the window go."""
    mgr = BlockSpaceManager(BLOCK, 64, 0, watermark=0.0,
                            group_kinds=("pooled",), pooled_window=WINDOW)
    assert mgr._prompt_peak_of("pooled", 5) == 5            # one window
    assert mgr._prompt_peak_of("pooled", W_PAGES) == W_PAGES
    assert mgr._prompt_peak_of("pooled", 19) == 2 * S_PAGES + W_PAGES
    assert mgr._prompt_peak_of("pooled", 2 * W_PAGES) == \
        S_PAGES + W_PAGES
    group = make_group("big", 76)
    sched = make_scheduler(pages=2 * S_PAGES + W_PAGES - 1)
    assert sched.block_manager.can_allocate(group) == AllocStatus.NEVER
    sched = make_scheduler(pages=2 * S_PAGES + W_PAGES)
    assert sched.block_manager.can_allocate(group) == AllocStatus.OK
    # (what it takes at the door is its first window)
    sched.add_seq_group(group)
    sched.schedule()
    assert held(sched) == (W_PAGES, W_PAGES)
    # a decode row about to open a window: S_PAGES and no fewer
    sched = make_scheduler(pages=W_PAGES + S_PAGES - 1, chunk=32)
    group = make_group("edge", WINDOW)
    sched.add_seq_group(group)
    _, out = sched.schedule()
    sampled(out)
    assert not sched.block_manager.can_append_slot(group)
    sched = make_scheduler(pages=W_PAGES + S_PAGES, chunk=32)
    sched.add_seq_group(group2 := make_group("edge", WINDOW))
    _, out = sched.schedule()
    sampled(out)
    assert sched.block_manager.can_append_slot(group2)


def test_a_fork_shares_summaries_and_copies_the_windows_last_page():
    sched = make_scheduler(pages=60, chunk=32)
    mgr = sched.block_manager
    group = make_group("a", 46, n=2, best_of=2)
    parent = group.get_seqs()[0]
    sched.add_seq_group(group)
    for _ in range(2):
        _, out = sched.schedule()
    sampled(out)
    child = parent.fork(next(_seq_ids))
    group.add(child)
    sched.fork_seq(parent, child)
    ((_, a),) = mgr.get_group_tables(parent)
    ((_, b),) = mgr.get_group_tables(child)
    assert a == b and \
        sum(map(len, mgr.summary_tables[child.seq_id])) == S_PAGES
    _, out = sched.schedule()
    # one copy-on-write: the window's last page; the summary pages
    # (never written again) stay shared
    assert sum(len(d) for d in out.blocks_to_copy.values()) == 1
    ((_, a),) = mgr.get_group_tables(parent)
    ((_, b),) = mgr.get_group_tables(child)
    assert a[:-1] == b[:-1] and a[-1] != b[-1]
    # both rows across the next edge: each closes the shared window
    # for itself, and its pages go when the second lets them go
    for _ in range(30):
        sampled(out)
        _, out = sched.schedule()
    assert sched.tracer.counts["attn.windows_closed_decode"] == 2
    ((_, a),) = mgr.get_group_tables(parent)
    ((_, b),) = mgr.get_group_tables(child)
    assert a[:S_PAGES] == b[:S_PAGES] and not set(a[S_PAGES:]) & set(b)
    for seq in (parent, child):
        seq.status = SequenceStatus.FINISHED_STOPPED
        sched.free_seq(seq)
    assert mgr.get_num_free_gpu_blocks() == 60


def test_preemption_by_recompute_gives_both_lists_back():
    """Two rows in a pool that holds one and a half: the younger is
    preempted by recompute when a decode step finds no page, both its
    lists go back to the free list, and it is admitted again, its
    chunks cut at the edges it had passed, and finishes."""
    sched = make_scheduler(pages=20)
    mgr = sched.block_manager
    a, b = make_group("a", 40), make_group("b", 40)
    sched.add_seq_group(a)
    sched.add_seq_group(b)
    done, preempted = set(), False
    for _ in range(400):
        _, out = sched.schedule()
        for back in sched.waiting:
            seq = back.get_seqs()[0]
            if seq.get_output_len():
                preempted = True
                assert seq.seq_id not in mgr.block_tables
                assert seq.seq_id not in mgr.summary_tables
        assert held(sched)[0] == held(sched)[1]
        sampled(out)
        for group in (a, b):
            seq = group.get_seqs()[0]
            if seq.get_output_len() >= 50 and \
                    group.request_id not in done:
                done.add(group.request_id)
                seq.status = SequenceStatus.FINISHED_STOPPED
                sched.free_seq(seq)
                sched.free_finished_seq_groups()
        if len(done) == 2:
            break
    assert done == {"a", "b"} and preempted
    assert sched.tracer.counts["preemptions"] >= 1
    assert held(sched) == (0, 0)


def test_swap_and_the_prefix_cache_refuse_the_model():
    from aphrodite_tpu.common.prefix import Prefix
    sched = make_scheduler(pages=40)
    mgr = sched.block_manager
    group = make_group("a", 20)
    with pytest.raises(PageGroupsUnsupported, match="preemption by swap"):
        mgr.can_swap_out(group)
    with pytest.raises(PageGroupsUnsupported, match="preemption by swap"):
        mgr.can_swap_in(group)
    group.prefix = Prefix(list(range(8)), BLOCK)
    with pytest.raises(PageGroupsUnsupported, match="the prefix cache"):
        mgr.allocate(group)


def test_a_reset_empties_both_lists_and_the_closes():
    sched = make_scheduler(pages=40, chunk=32)
    mgr = sched.block_manager
    sched.add_seq_group(make_group("a", 50))
    sched.schedule()
    mgr.prepare_chunk(next(iter(sched.prefilling)).get_seqs()[0], 32, 18)
    assert mgr._window_closes and mgr.summary_tables
    mgr.reset()
    assert not mgr._window_closes and not mgr.summary_tables
    assert mgr.get_num_free_gpu_blocks() == 40


# ---- what the runner makes of the tables: one program a bucket ----

def test_every_table_of_a_pooled_group_is_a_wide_one():
    """A row's table jumps from a window and its summaries (144 pages)
    to the summaries alone (24) at an edge: whatever the rows hold, a
    decode bucket and a prompt bucket have ONE table width, past the
    full window, so one program; and the live pages are counted as the
    window counters count them, the summaries among them."""
    import jax.numpy as jnp
    from aphrodite_tpu.common.tracing import Tracer
    from aphrodite_tpu.executor.model_runner import ModelRunner
    from aphrodite_tpu.ops.pallas.paged_attention import lane_bytes_of
    runner = ModelRunner.__new__(ModelRunner)
    runner.page_groups = PageGroups.of(["pooled"] * 8, None,
                                       pooled_window=2048)
    runner.page_size, runner.pages_bucket = 16, 8
    runner.num_slots = 1 << 20
    runner.attn_lane_bytes = lane_bytes_of(32, 128, jnp.bfloat16)
    runner._decode_work, runner.tracer = {}, Tracer()
    runner.kv_scale, runner._tp = 1.0, None
    runner._dev = lambda arr, committed=False: arr
    assert runner._table_floor("pooled") == 129
    assert runner._table_floor("full") == runner._table_floor("window") == 1

    def key(contexts):
        rows = []
        for ctx in contexts:
            behind = (ctx - 1) // 2048
            pages = -(-ctx // 16) - behind * 120
            rows.append([(behind * 1920, list(range(pages)))])
        sent = runner._send_decode_batch(
            [1] * len(rows), [c - 1 for c in contexts],
            [runner.num_slots] * len(rows), contexts, None,
            group_rows=rows)
        meta = sent["metadata"]
        return (sent["padded_batch"], meta.group_layout,
                len(meta.groups[0].decode_work[1]))

    # rows spread over the cell's contexts, a few of them past the
    # edge at 6,144, the bucket's other rows padding
    keys = {key([5377 + (53 * i + step) % 1150 for i in range(rows)])
            for rows in (17, 21, 24) for step in (0, 15, 400, 800)}
    assert keys == {(24, (192,), 144)}
    # (a batch whose rows have ALL just passed the edge holds 25-27
    # pages a row and keeps a short work list, another program: the
    # callers of a group that joined together reach the edge together)
    assert key([6150 + i for i in range(24)]) == (24, (192,), 24)
    # a row at 6,100 holds 16 summary pages and 126 of its window; one
    # at 6,200 holds 24 and 4
    counts = runner.tracer.counts
    before = {k: counts[k] for k in (
        "attn.pages_live.window", "attn.summary_pages_live",
        "attn.window_pages_unwindowed")}
    key([6100, 6200])
    assert counts["attn.pages_live.window"] - \
        before["attn.pages_live.window"] == (16 + 126) + (24 + 4)
    assert counts["attn.summary_pages_live"] - \
        before["attn.summary_pages_live"] == 16 + 24
    assert counts["attn.window_pages_unwindowed"] - \
        before["attn.window_pages_unwindowed"] == 382 + 388
