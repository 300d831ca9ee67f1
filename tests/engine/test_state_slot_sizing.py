"""`TPUExecutor._size_state_slots`: pages and state slots from the one
budget. As many slots as the largest decode bucket, no more than
`--max-num-seqs`, whose rows, each at `max_model_len` tokens with its
slot, fit; the pages take the rest. Held here at the two served models
with state: Phi-4-mini-flash (a slot of 3,225,600 B beside rows of 520
pages of 81,920 B) and AI21-Jamba2-3B (a slot of 9,318,400 B beside
rows of 256 pages of 16,384 B: the first model whose state outweighs
its pages). A slot TAKES what it is allocated, a fourth row of the
convolution's tail a layer more than it holds (`StateSpec.allocated`):
the budget counts that. Host arithmetic; the one device array is a toy
spec's."""
import types

import numpy as np
import pytest

from aphrodite_tpu.common.config import PageGroups, StateSpec
from aphrodite_tpu.executor.executor import TPUExecutor

GIB = 2 ** 30
MAMBA = (((3, 5120), "bfloat16"), ((16, 5120), "float32"))
#: (state layers, page groups, bytes of a page over its layers)
JAMBA = (26, PageGroups.of(
    [None] * 7 + ["full"] + [None] * 13 + ["full"] + [None] * 6, None,
    stateful=True), 16_384)
PHI = (9, PageGroups.of(
    [None if l % 2 == 0 else "window" if l < 16 else
     "full" if l == 17 else 17 for l in range(32)], 512, stateful=True),
    81_920)


def _sized(model, budget, max_num_seqs, max_model_len):
    layers, groups, block_bytes = model
    executor = TPUExecutor.__new__(TPUExecutor)
    executor.cache_config = types.SimpleNamespace(
        state_spec=StateSpec(layers=layers, arrays=MAMBA),
        page_groups=groups, block_size=16, num_state_slots=None)
    executor.model_config = types.SimpleNamespace(
        max_model_len=max_model_len)
    executor.scheduler_config = types.SimpleNamespace(
        max_num_seqs=max_num_seqs)
    taken = executor._size_state_slots(budget, block_bytes)
    return executor.cache_config.num_state_slots, taken


@pytest.mark.parametrize(
    "model,budget,max_num_seqs,max_model_len,slots", [
        # the cell: --max-num-seqs 128 --max-model-len 4096, some 8 GiB
        (JAMBA, 8 * GIB, 128, 4096, 128),
        # the server's default of 256 sequences: the next bucket fits too
        (JAMBA, 8 * GIB, 256, 4096, 256),
        # the context the model is built for: a row holds 16,384 pages,
        # 268 MB, beside its slot; 24 rows and the scratch one fit
        (JAMBA, 8 * GIB, 128, 262_144, 24),
        # a budget that holds no row at all still gives the one slot
        (JAMBA, 8 * 2 ** 20, 128, 4096, 1),
        # Phi's cell as the chip sized it (PERF.md section 4): 7.04 GiB
        (PHI, int(7.04 * GIB), 256, 4096, 128),
    ], ids=["jamba-128", "jamba-256", "jamba-262144", "jamba-no-room",
            "phi-7GiB"])
def test_slots_by_the_largest_bucket_whose_rows_fit(
        model, budget, max_num_seqs, max_model_len, slots):
    layers, groups, block_bytes = model
    got, taken = _sized(model, budget, max_num_seqs, max_model_len)
    assert got == slots
    spec = StateSpec(layers=layers, arrays=MAMBA)
    assert spec.slot_bytes == layers * (3 * 5120 * 2 + 16 * 5120 * 4) == \
        {26: 9_318_400, 9: 3_225_600}[layers]
    # what is allocated: the tail's three rows as four, 10,240 B a layer
    slot_bytes = spec.allocated_slot_bytes
    assert slot_bytes == spec.slot_bytes + layers * 10_240 == \
        layers * (4 * 5120 * 2 + 16 * 5120 * 4)
    # the scratch slot is paid for too, and the pages take the rest
    assert taken == (slots + 1) * slot_bytes
    pages = (budget - taken) // block_bytes
    if slots > 1:
        longest = -(-max_model_len // 16)
        held = -(-(groups.window or 0) // 16) + 1
        row = sum(min(longest, held) if kind == "window" else longest
                  for kind in groups.kinds)
        # every slot can be fed pages to the longest context admitted
        assert pages >= (slots + 1) * row - row
        assert (slots + 1) * (row * block_bytes + slot_bytes) <= budget


def test_jambas_pool_at_the_cells_arguments():
    """The reckoning of `PERF.md` section 4: 129 slots of 9,584,640 B
    as allocated are 1.24 GB; of a budget of 8 GiB the pages get the
    rest, some
    450,000 pages of 16 tokens at 1,024 B a token: 7 million tokens,
    where 128 rows of 1,536 tokens are 0.2 million. The slots, not the
    pages, are what admission runs out of."""
    slots, taken = _sized(JAMBA, 8 * GIB, 128, 4096)
    assert taken == 129 * 9_584_640 and round(taken / 1e9, 2) == 1.24
    pages = (8 * GIB - taken) // 16_384
    assert 440_000 < pages < 460_000
    assert 128 * 1536 / (pages * 16) < 0.03


def test_a_model_without_state_takes_nothing():
    executor = TPUExecutor.__new__(TPUExecutor)
    executor.cache_config = types.SimpleNamespace(state_spec=None)
    assert executor._size_state_slots(8 * GIB, 16_384) == 0


@pytest.mark.parametrize("rows,allocated", [
    (1, 1), (2, 2), (3, 4), (4, 4), (5, 8), (7, 8), (8, 8), (16, 16)])
def test_an_entrys_rows_are_allocated_as_a_power_of_two(rows, allocated):
    """The layout rule, one for every model: the rows a slot holds of
    an entry, rounded up to what the device tiles without padding.
    What a slot HOLDS (`arrays`, `slot_bytes`) does not move."""
    spec = StateSpec(layers=3, arrays=(((rows, 256), "bfloat16"),
                                       ((16, 256), "float32")))
    assert spec.allocated == (((allocated, 256), "bfloat16"),
                              ((16, 256), "float32"))
    assert spec.arrays[0][0] == (rows, 256)
    assert spec.slot_bytes == 3 * (rows * 512 + 16 * 1024)
    assert spec.allocated_slot_bytes == 3 * (allocated * 512 + 16 * 1024)


def test_the_bytes_taken_are_the_bytes_allocated():
    """`CacheEngine._allocate_state` at a toy width: one array an
    entry for the model, the layers leading, and their bytes together
    what `_size_state_slots` took from the budget."""
    from aphrodite_tpu.executor.cache_engine import CacheEngine
    spec = StateSpec(layers=5, arrays=(((3, 256), "bfloat16"),
                                       ((16, 256), "float32")))
    executor = TPUExecutor.__new__(TPUExecutor)
    executor.cache_config = types.SimpleNamespace(
        state_spec=spec, block_size=16, num_state_slots=None,
        page_groups=PageGroups.of([None, "full"] * 5, None, stateful=True))
    executor.model_config = types.SimpleNamespace(max_model_len=256)
    executor.scheduler_config = types.SimpleNamespace(max_num_seqs=8)
    taken = executor._size_state_slots(2 ** 24, 4096)
    slots = executor.cache_config.num_state_slots
    assert slots == 8
    engine = CacheEngine.__new__(CacheEngine)
    engine.cache_config, engine.mesh = executor.cache_config, None
    (arrays,) = engine._allocate_state()
    assert [(a.shape, a.dtype.name) for a in arrays] == [
        ((5, 9, 4, 256), "bfloat16"), ((5, 9, 16, 256), "float32")]
    assert sum(a.nbytes for a in arrays) == taken == \
        9 * spec.allocated_slot_bytes
    assert not np.asarray(arrays[0], np.float32).any()
