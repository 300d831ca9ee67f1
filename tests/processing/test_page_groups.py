"""Page groups through the scheduler and the block manager: a table a
group from one free list, window tables that slide during chunked
prefill and decode alike, admission and preemption by recompute over
both kinds of group. A model-wide window is the same code with every
layer in the window group."""
import pytest

from aphrodite_tpu.common.config import (CacheConfig, PageGroups,
                                         SchedulerConfig)
from aphrodite_tpu.common.sampling_params import SamplingParams
from aphrodite_tpu.common.sequence import (Sequence, SequenceGroup,
                                           SequenceStatus)
from aphrodite_tpu.processing.block_manager import (AllocStatus,
                                                    BlockSpaceManager)
from aphrodite_tpu.processing.scheduler import Scheduler

BLOCK = 4
WINDOW = 16          # tokens: 4 pages
CHUNK = 8            # tokens: 2 pages
#: the published pattern (one full layer, three windowed), two periods
#: of it, and a Mistral-style window on every layer
LAYOUTS = {"mixed": [False, True, True, True],
           "two-periods": [False, True, True, True] * 2,
           "model-wide": [True, True]}

_seq_ids = iter(range(10_000))


def make_scheduler(layout, pages, max_num_seqs=8):
    cache = CacheConfig(block_size=BLOCK,
                        page_groups=PageGroups.of(layout, WINDOW))
    cache.num_gpu_blocks, cache.num_cpu_blocks = pages, 0
    sched = SchedulerConfig(max_num_batched_tokens=256,
                            max_num_seqs=max_num_seqs, max_model_len=256,
                            max_paddings=256, max_chunk_tokens=CHUNK)
    return Scheduler(sched, cache, None)


def make_group(request_id, prompt_len):
    seq = Sequence(next(_seq_ids), "x", list(range(prompt_len)), BLOCK)
    return SequenceGroup(request_id, [seq], SamplingParams(),
                         arrival_time=0.0)


def sampled(out):
    """What the engine does after a round: every row that computed a
    token (a final prompt chunk or a decode row) gains it."""
    rows = [c.group for c in out.prompt_chunks if c.is_final] + \
        list(out.decode_groups)
    for group in rows:
        for seq in group.get_seqs(status=SequenceStatus.RUNNING):
            tok = seq.get_len()
            seq.append_token_id(tok, {tok: 0.0})


def held(sched):
    """Pages out of the free list, and pages in the tables."""
    mgr = sched.block_manager
    in_tables = sum(len(t) for seq_id in mgr.block_tables
                    for t in mgr._tables(seq_id))
    return mgr.num_total_gpu_blocks - mgr.get_num_free_gpu_blocks(), \
        in_tables


def test_page_groups_of_a_layout():
    groups = PageGroups.of(LAYOUTS["two-periods"], WINDOW)
    # gcd(2 full, 6 window) = 2 layers a group: one full group, three
    # window groups, every group as deep, so one page id serves all
    assert groups.kinds == ("full", "window", "window", "window")
    assert groups.layers_per_group == 2 and not groups.plain
    assert groups.group_of_layer == (0, 1, 1, 2, 0, 2, 3, 3)
    assert groups.slot_of_layer == (0, 0, 1, 0, 1, 1, 0, 1)
    wide = PageGroups.of(LAYOUTS["model-wide"], WINDOW)
    assert wide.kinds == ("window",) and wide.layers_per_group == 2
    assert wide.window == WINDOW and not wide.plain
    plain = PageGroups.of([False] * 3, None)
    assert plain.kinds == ("full",) and plain.plain
    assert plain.slot_of_layer == (0, 1, 2) and plain.window is None
    # a layout without a window size is a plain model
    assert PageGroups.of([True, False], None).plain


@pytest.mark.parametrize("name", list(LAYOUTS))
def test_a_window_table_holds_the_window_a_chunk_and_a_page(name):
    """Chunked prefill across the window, then decode past two
    windows: in every round each window group holds no more than
    window + chunk + one page of tokens, a full group holds every
    page, and the pages out of the free list are the pages in the
    tables (none stranded, none counted twice)."""
    sched = make_scheduler(LAYOUTS[name], pages=256)
    mgr = sched.block_manager
    kinds = mgr.group_kinds
    cap = WINDOW // BLOCK + CHUNK // BLOCK + 1
    assert mgr.window_cap_blocks == cap
    group = make_group("a", prompt_len=50)
    seq = group.get_seqs()[0]
    sched.add_seq_group(group)
    chunks, freed_in_prefill = [], 0
    for _ in range(50 + 2 * WINDOW):
        before = mgr.window_pages_freed
        metadata, out = sched.schedule()
        assert metadata, "the one request is scheduled in every round"
        chunks += [(c.ctx, c.length) for c in out.prompt_chunks]
        if out.prompt_chunks:
            freed_in_prefill += mgr.window_pages_freed - before
        (md,) = metadata
        tables = md.group_tables[seq.seq_id]
        assert tables == mgr.get_group_tables(seq)
        last = md.computed_ctx + md.chunk_len - 1 if md.is_prompt \
            else seq.get_len() - 1
        for kind, (let_go, table) in zip(kinds, tables):
            first_page = let_go // BLOCK
            # the table covers the newest position it is written at
            assert first_page + len(table) > last // BLOCK
            if kind == "full":
                assert let_go == 0
                assert len(table) >= seq.get_len() // BLOCK
            else:
                assert len(table) <= cap
                # and the oldest key the oldest query of the round sees
                oldest = (md.computed_ctx if md.is_prompt else last) \
                    - WINDOW + 1
                assert let_go <= max(0, oldest)
        out_of_list, in_tables = held(sched)
        assert out_of_list == in_tables
        sampled(out)
    # the prompt went in chunks of the cap, across the window
    assert chunks[:3] == [(0, 8), (8, 8), (16, 8)] and \
        sum(n for _, n in chunks) == 50
    assert freed_in_prefill > 0
    windows = kinds.count("window")
    assert mgr.window_pages_freed == windows * (
        (seq.get_len() - 1 - WINDOW + 1) // BLOCK)
    assert sched.tracer.counts["cache.window_pages_freed"] == \
        mgr.window_pages_freed
    sched.free_seq(seq)
    assert held(sched) == (0, 0)


def test_freed_pages_serve_either_group_in_the_same_round():
    """One free list: with no page free at all, the page a window
    group lets go of in a round is the page the full group (or a
    window group) takes in that same round."""
    mgr = BlockSpaceManager(BLOCK, num_gpu_blocks=11, num_cpu_blocks=0,
                            watermark=0, sliding_window=WINDOW,
                            group_kinds=("full", "window"),
                            max_chunk_tokens=CHUNK)
    group = make_group("a", prompt_len=20)      # 5 pages a group
    assert mgr.can_allocate(group) == AllocStatus.OK
    mgr.allocate(group)
    seq = group.get_seqs()[0]
    seq.status = SequenceStatus.RUNNING
    assert mgr.get_num_free_gpu_blocks() == 1
    before = {kind: set(table) for kind, (_, table) in zip(
        ("full", "window"), mgr.get_group_tables(seq))}
    # Position 20 opens page 5 in both groups with ONE page free: the
    # window group first lets go of its page 0 (positions 0-3 lie
    # before the window of query 20), and the two pages taken are the
    # free one and the one just let go, in the one call.
    seq.append_token_id(20, {20: 0.0})
    assert mgr.append_slots(seq) == []
    assert mgr.get_num_free_gpu_blocks() == 0
    (_, full), (let_go, window) = mgr.get_group_tables(seq)
    assert len(full) == 6 and let_go == BLOCK and len(window) == 5
    let_go_page = (before["window"] - set(window)).pop()
    assert let_go_page in {full[-1], window[-1]}
    assert mgr.window_pages_freed == 1
    # positions 21-23 need no page; 23 passes another
    for tok in range(21, 24):
        seq.append_token_id(tok, {tok: 0.0})
        mgr.append_slots(seq)
    assert mgr.get_num_free_gpu_blocks() == 1
    # a second sequence's full group may take it: 4 tokens, a page a
    # group, and the free page and ... only one is free, so it waits
    other = make_group("b", prompt_len=4)
    assert mgr.can_allocate(other) == AllocStatus.LATER
    mgr.free(seq)
    assert mgr.get_num_free_gpu_blocks() == 11


@pytest.mark.parametrize("name", ["mixed", "model-wide"])
def test_admission_and_preemption_by_recompute_count_every_group(name):
    """Two requests in a pool that holds one and a half: the second
    is admitted only when every group's pages fit, the youngest row is
    preempted by recompute when a decode step finds no page, all its
    tables go back to the free list, and it is admitted again later
    and finishes."""
    layout = LAYOUTS[name]
    kinds = PageGroups.of(layout, WINDOW).kinds
    cap = WINDOW // BLOCK + CHUNK // BLOCK + 1
    # A request grows to 64 tokens: 16 pages in a full group, the cap
    # at most in a window group (while a prompt is written; the window
    # and a page in decode). The pool holds one request at its worst
    # and four pages, which is less than two prompts of 24 tokens (6
    # pages a group each).
    worst = sum(16 if kind == "full" else cap for kind in kinds)
    sched = make_scheduler(layout, pages=worst + 4)
    assert 2 * 6 * len(kinds) > worst + 4
    mgr = sched.block_manager
    a, b = make_group("a", 24), make_group("b", 24)
    sched.add_seq_group(a)
    sched.add_seq_group(b)
    first, done, rounds = {}, set(), 0
    while len(done) < 2 and rounds < 400:
        rounds += 1
        _, out = sched.schedule()
        for group in out.scheduled_seq_groups:
            first.setdefault(group.request_id, rounds)
        out_of_list, in_tables = held(sched)
        assert out_of_list == in_tables
        sampled(out)
        for group in (a, b):
            seq = group.get_seqs()[0]
            if seq.get_output_len() >= 40 and \
                    group.request_id not in done:
                done.add(group.request_id)
                seq.status = SequenceStatus.FINISHED_STOPPED
                sched.free_seq(seq)
                sched.free_finished_seq_groups()
    assert done == {"a", "b"}
    # b was not admitted beside a: not every group's pages fitted
    assert first["a"] == 1 and first["b"] > 24 // CHUNK
    assert held(sched) == (0, 0)
    assert not mgr.more_tables and not mgr.first_blocks


def test_a_row_that_outgrows_the_pool_is_preempted_and_recomputed():
    """Preemption by recompute with page groups: when a decode step
    finds no page for every group, the youngest row gives all its
    tables back and starts again from its prompt."""
    sched = make_scheduler(LAYOUTS["mixed"], pages=2 * 4 * 4 + 2)
    mgr = sched.block_manager
    a, b = make_group("a", 12), make_group("b", 12)     # 3 pages x 4
    for group in (a, b):
        sched.add_seq_group(group)
    saw_preemption = False
    for _ in range(60):
        _, out = sched.schedule()
        waiting = [g.request_id for g in sched.waiting]
        if waiting and out.decode_groups:
            saw_preemption = True
            (back,) = sched.waiting
            seq = back.get_seqs()[0]
            assert seq.status == SequenceStatus.WAITING
            assert seq.seq_id not in mgr.block_tables
            assert seq.seq_id not in mgr.more_tables
            assert seq.seq_id not in mgr.first_blocks
        out_of_list, in_tables = held(sched)
        assert out_of_list == in_tables
        sampled(out)
    assert saw_preemption


# ---- what the runner makes of the tables: a step program's key ----

def _bare_runner(layout, window, page=16):
    """A `ModelRunner` with what `_send_decode_batch` reads and no
    model: the packing is host arithmetic."""
    import jax.numpy as jnp
    from aphrodite_tpu.common.tracing import Tracer
    from aphrodite_tpu.executor.model_runner import ModelRunner
    from aphrodite_tpu.ops.pallas.paged_attention import lane_bytes_of
    runner = ModelRunner.__new__(ModelRunner)
    runner.page_groups = PageGroups.of(layout, window)
    runner.page_size, runner.pages_bucket = page, 8
    runner.num_slots = 1 << 20
    runner.attn_lane_bytes = lane_bytes_of(4, 128, jnp.bfloat16)
    runner._decode_work, runner.tracer = {}, Tracer()
    runner.kv_scale, runner._tp = 1.0, None
    runner._dev = lambda arr, committed=False: arr
    return runner


def _decode_key(runner, contexts, window):
    """The shapes a decode step of rows at `contexts` sends: the
    bucket, each group's table width and work-list length."""
    page = runner.page_size
    rows = []
    for ctx in contexts:
        pages = -(-ctx // page)
        first = max(0, ctx - window) // page
        rows.append([(0, list(range(pages)))] +
                    [(first * page, list(range(first, pages)))] * 3)
    sent = runner._send_decode_batch(
        [1] * len(rows), [c - 1 for c in contexts],
        [runner.num_slots] * len(rows), contexts,
        [r[0][1] for r in rows], group_rows=rows)
    meta = sent["metadata"]
    return (sent["padded_batch"], meta.group_layout,
            tuple(len(v.decode_work[1]) for v in meta.groups))


def test_rows_past_the_window_keep_one_decode_program_a_bucket():
    """At 8k contexts under a window of 4,096 a batch bucket has one
    decode program whatever share of it is padding and wherever the
    rows' windows start: the window groups' tables are the window and
    a page wide at least (257 pages, or 256 once in sixteen steps),
    and the work lists of wide tables are the dense ones."""
    window = 4096
    runner = _bare_runner([False, True, True, True], window)
    keys = {_decode_key(runner, [8193 + 20 * i + step
                                 for i in range(rows)], window)
            for rows in (17, 20, 24) for step in (0, 15, 150, 300)}
    assert keys == {(24, (576, 320, 320, 320), (432, 240, 240, 240))}
    # the step at which a lone row's window starts on a page's edge
    # (256 pages) is the program of the steps around it
    assert _decode_key(runner, [8192 + 4096 - 4096 % 16 + 1], window) == \
        _decode_key(runner, [8192 + 4096 - 4096 % 16 + 2], window)


def test_a_narrow_tables_work_list_keeps_its_few_lengths():
    """Tables of 128 pages and under (the Mistral cell's 72-88) keep
    `padded_work_length`'s batch x 2^k; a wide table whose rows are
    short keeps them too, up to half the dense count."""
    from aphrodite_tpu.executor.model_runner import ModelRunner
    from aphrodite_tpu.ops.pallas.paged_attention import padded_work_length
    for items in (48, 144, 200, 288):
        assert ModelRunner._work_length(items, 48, 88, 16) == \
            padded_work_length(items, 48, 88, 16)
    assert ModelRunner._work_length(24 + 17, 24, 576, 32) == 48
    assert ModelRunner._work_length(24 * 4, 24, 576, 32) == 96
    assert ModelRunner._work_length(24 * 8 + 1, 24, 576, 32) == 24 * 18
    assert ModelRunner._work_length(17 * 17 + 7, 24, 576, 32) == 24 * 18
