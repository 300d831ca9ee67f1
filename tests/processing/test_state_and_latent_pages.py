"""State slots AND latent pages in one model (Kimi Linear: delta-rule
layers that hold no page and keep a matrix state in the sequence's
state slot, beside latent-attention layers whose pages are ONE array
of 640-lane rows): the page groups state `stateful` and `latent`
together, the pool holds an array for each MLA layer and one `(tail,
state)` pair for the model, pages and slots share the one budget, and
such a model is refused the UNION of what either kind is refused, by
name and with no silent fall-through (`common/config.py::
LATENT_PAGE_REFUSALS` and where the stateful refusals join it)."""
import re
import types

import pytest

from aphrodite_tpu.common.config import (LATENT_PAGE_REFUSALS, CacheConfig,
                                         ModelConfig, PageGroups,
                                         ParallelConfig, SchedulerConfig,
                                         refuse_for_latent_pages)
from aphrodite_tpu.common.sampling_params import SamplingParams
from aphrodite_tpu.common.sequence import Sequence, SequenceGroup
from aphrodite_tpu.executor.cache_engine import CacheEngine
from aphrodite_tpu.executor.executor import TPUExecutor
from aphrodite_tpu.processing.block_manager import PageGroupsUnsupported
from aphrodite_tpu.processing.scheduler import Scheduler
from aphrodite_tpu.transformers_utils.configs import KimiLinearConfig

BLOCK = 16
GIB = 2 ** 30
#: a slot as allocated: six layers of 32 x 128 x 128 float32 and four
#: rows of 12,288 bfloat16 channels
SLOT = 6 * (32 * 128 * 128 * 4 + 4 * 12288 * 2)


def _model_config(dtype="bfloat16", **changed):
    """The benchmark's cut: 8 layers at the published widths."""
    hf = KimiLinearConfig(**{**dict(
        num_hidden_layers=8, num_experts=64, num_routed_experts=256,
        vocab_size=40960), **changed})
    hf.architectures = ["KimiLinearForCausalLM"]
    return ModelConfig("x", dtype=dtype, max_model_len=2048, hf_config=hf)


def _cache_config(model_config, **kwargs):
    return CacheConfig(block_size=BLOCK, swap_space=0.01,
                       page_groups=model_config.get_page_groups(),
                       state_spec=model_config.get_state_spec(), **kwargs)


def test_both_kinds_are_stated_for_the_model():
    model_config = _model_config()
    groups = model_config.get_page_groups()
    assert groups.kinds == ("full",)
    assert groups.latent == 512 and groups.stateful
    assert not groups.plain and groups.arrays_per_page == 1
    # KDA, KDA, KDA, MLA twice: the MLA layers are the group's two
    # places, the KDA layers hold nothing
    assert groups.group_of_layer == (-1, -1, -1, 0) * 2
    assert groups.slot_of_layer == (-1, -1, -1, 0, -1, -1, -1, 1)
    assert groups.layers_per_group == 2
    # (the family publishes `head_dim` 72; a page's row is the latent
    # and the shared key part)
    assert model_config.get_head_size() == 576
    assert model_config.get_kv_heads_per_slot() == [1, 1]
    assert model_config.max_model_len == 2048
    spec = model_config.get_state_spec()
    assert spec.layers == 6
    assert spec.slot_bytes == 6 * (32 * 128 * 128 * 4 + 3 * 12288 * 2) \
        == 13_025_280
    assert spec.allocated_slot_bytes == SLOT == 13_172_736
    # a model states both or either: the other two paths are what they
    # were
    assert PageGroups.of([None, "full"], None, stateful=True).latent is None
    assert not PageGroups.of(["full"], None, latent=512).stateful


def test_bytes_a_page_and_the_pool():
    """A page is 16 x 640 lanes x 2 B x TWO layers (the MLA layers
    alone): 2,560 B a token; the pool is an array for each of them and
    then the model's `(tail, state)` pair."""
    model_config = _model_config()
    cache = _cache_config(model_config)
    size = CacheEngine.get_cache_block_size(cache, model_config,
                                            ParallelConfig(1, 1))
    assert size == 16 * 640 * 2 * 2 == 40_960 and size // BLOCK == 2_560
    toy = _model_config("float32", linear_attn_config={
        "kda_layers": [1, 2, 3, 5, 6, 7], "full_attn_layers": [4, 8],
        "num_heads": 2, "head_dim": 32, "short_conv_kernel_size": 4})
    cache = _cache_config(toy)
    cache.num_gpu_blocks, cache.num_cpu_blocks = 12, 4
    cache.num_state_slots = 3
    engine = CacheEngine(cache, toy, ParallelConfig(1, 1))
    assert [len(entry) for entry in engine.kv_caches] == [1, 1, 2]
    assert engine.num_page_pairs == 2 and engine.arrays_per_page == 1
    for (pages,) in engine.kv_caches[:2]:
        assert pages.shape == (12, BLOCK, 640)
    tail, state = engine.kv_caches[2]
    assert tail.shape == (6, 4, 4, 3 * 64) and state.shape == (6, 4, 2, 32,
                                                               32)
    assert state.dtype.name == "float32" and tail.dtype.name == "float32"
    assert engine._host_pool is None


def test_pages_and_slots_share_the_budget():
    """`_size_state_slots` reckons a row's pages by `block_bytes`, which
    counts the MLA layers alone: 128 pages of 40,960 B and a slot of
    13.2 MB a row at 2,048 tokens; 192 rows and the scratch slot fit
    the cell's budget, and the pages take the rest."""
    model_config = _model_config()
    executor = TPUExecutor.__new__(TPUExecutor)
    executor.cache_config = types.SimpleNamespace(
        state_spec=model_config.get_state_spec(),
        page_groups=model_config.get_page_groups(), block_size=BLOCK,
        num_state_slots=None)
    executor.model_config = model_config
    executor.scheduler_config = types.SimpleNamespace(max_num_seqs=192)
    budget = int(5.5 * GIB)
    taken = executor._size_state_slots(budget, 40_960)
    assert executor.cache_config.num_state_slots == 192
    assert taken == 193 * SLOT
    assert 193 * (128 * 40_960 + SLOT) <= budget
    assert 1.9e9 < budget - taken        # what is left for pages
    # a budget that holds 128 rows and not 192 gives the bucket below
    executor.cache_config.num_state_slots = None
    executor._size_state_slots(int(3.0 * GIB), 40_960)
    assert executor.cache_config.num_state_slots == 128


def _scheduler(pages=40, slots=2):
    model_config = _model_config()
    cache = _cache_config(model_config)
    cache.num_gpu_blocks, cache.num_cpu_blocks = pages, 0
    cache.num_state_slots = slots
    sched = SchedulerConfig(max_num_batched_tokens=2048, max_num_seqs=8,
                            max_model_len=2048, max_paddings=2048)
    return Scheduler(sched, cache, None)


def _group(request_id, prompt_len):
    seq = Sequence(hash(request_id) % 1000, "x", list(range(prompt_len)),
                   BLOCK)
    return SequenceGroup(request_id, [seq], SamplingParams(),
                         arrival_time=0.0)


@pytest.mark.parametrize("what", LATENT_PAGE_REFUSALS[:2])
def test_the_block_manager_refuses_by_name(what):
    """Swap and the prefix cache, refused once for both reasons: the
    message names recurrent state AND latent pages; ids and slots are
    counted as ever."""
    from aphrodite_tpu.common.prefix import Prefix
    mgr = _scheduler().block_manager
    assert not mgr.plain and mgr.group_kinds == ("full",)
    group = _group("a", 40)
    if what == "preemption by swap":
        for ask in (mgr.can_swap_out, mgr.can_swap_in):
            with pytest.raises(PageGroupsUnsupported, match=what):
                ask(group)
    else:
        group.prefix = Prefix(list(range(16)), BLOCK)
        with pytest.raises(PageGroupsUnsupported, match=what):
            mgr.allocate(group)
    with pytest.raises(PageGroupsUnsupported) as refused:
        mgr._plain_only(what)
    assert "recurrent state" in str(refused.value)
    assert "pages are latent" in str(refused.value)
    plain = _group("b", 40)
    mgr.allocate(plain)
    assert mgr.get_num_free_gpu_blocks() == 40 - 3
    mgr.free(plain.get_seqs()[0])
    assert mgr.get_num_free_gpu_blocks() == 40


@pytest.mark.parametrize("what,asked", [
    (LATENT_PAGE_REFUSALS[4], dict(disagg=True)),
    (LATENT_PAGE_REFUSALS[5], dict(world_size=4)),
    (LATENT_PAGE_REFUSALS[6], dict(cache_dtype="fp8"))],
    ids=["kv_handoff", "a-mesh", "fp8"])
def test_the_executor_refuses_the_latent_three_first(what, asked):
    """`tp > 1` is refused BY NAME for this model, as the handoff and
    the 8-bit page types are: the latent list's three stand before
    anything is built, so the stateful refusals of the same two (the
    handoff at `TPUExecutor.__init__`, a mesh at
    `CacheEngine._allocate_state`) are never reached first with another
    wording."""
    import inspect
    groups = _model_config().get_page_groups()
    args = dict(dict(disagg=False, world_size=1, cache_dtype="auto"),
                **asked)
    with pytest.raises(NotImplementedError, match=re.escape(what)):
        refuse_for_latent_pages(groups, **args)
    source = inspect.getsource(TPUExecutor.__init__)
    assert source.index("refuse_for_latent_pages(") < \
        source.index("a model with recurrent state") < \
        source.index("get_model(")


def test_the_state_arrays_refuse_a_mesh_too():
    """Were the latent refusal ever lifted, the state arrays still
    refuse a mesh where they are allocated."""
    import jax
    import numpy as np
    from jax.sharding import Mesh
    toy = _model_config("float32", linear_attn_config={
        "kda_layers": [1, 2, 3, 5, 6, 7], "full_attn_layers": [4, 8],
        "num_heads": 2, "head_dim": 32, "short_conv_kernel_size": 4})
    cache = _cache_config(toy)
    cache.num_gpu_blocks, cache.num_cpu_blocks = 4, 0
    cache.num_state_slots = 1
    mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(1, 1, 1, 2),
                ("dp", "pp", "sp", "tp"))
    with pytest.raises(NotImplementedError, match="recurrent state"):
        CacheEngine(cache, toy, ParallelConfig(1, 2), mesh=mesh)


@pytest.mark.parametrize("what", LATENT_PAGE_REFUSALS[1:4])
def test_the_engine_refuses_by_name(what, tmp_path, monkeypatch):
    """At the engine: a cached prefix is refused at the door; a burst
    and a speculative round are never taken (their eligibility asks for
    plain pages), with `APHRODITE_SPEC` at its default and `multi_step`
    asked for."""
    import pathlib
    monkeypatch.syspath_prepend(
        str(pathlib.Path(__file__).parents[1] / "models"))
    import test_kimi_linear as toy
    monkeypatch.delenv("APHRODITE_SPEC", raising=False)
    served = toy.Served(tmp_path, monkeypatch, multi_step=4)
    monkeypatch.setenv("APHRODITE_SPEC", "1")
    engine = served.engine
    if what == "the prefix cache":
        with pytest.raises(ValueError, match=what):
            engine.add_request("p", None, SamplingParams(max_tokens=4),
                               prompt_token_ids=toy._prompt(0, 40),
                               prefix_pos=16)
        return
    prompt = (toy._prompt(1, 10) * 4)[:40]
    paths, mark = [], engine._mark_path
    monkeypatch.setattr(engine, "_mark_path", lambda path, *a, **k: (
        paths.append(path), mark(path, *a, **k))[1])
    ((reply,),) = served.run([prompt], steps=12)
    assert len(reply) == 12
    assert paths and set(paths) <= {"prompt", "decode", "combined"}
    assert engine._burst_steps([], None)[0] == 1


def test_the_one_list_names_where_the_stateful_refusals_join():
    import inspect
    from aphrodite_tpu.common import config
    source = inspect.getsource(config)
    comment = source[source.index("#: What a model whose pages are latent"):
                     source.index("LATENT_PAGE_REFUSALS = (")]
    for said in ("PageGroups.stateful", "CacheEngine._allocate_state",
                 "TPUExecutor.__init__", "union"):
        assert said in comment, said
    assert "while the model's other layers hold pages" in \
        " ".join(PageGroups.__doc__.split())
