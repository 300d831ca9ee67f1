"""The LATENT page kind (`PageGroups.latent`: multi-head latent
attention, Sarvam-105B) through the configuration, the cache engine,
the block manager and the engine's door: a layer's pages are ONE array
of 640-lane rows, `get_cache_block_size` and the pool's size follow,
ids are counted as ever (admission, fork, preemption by recompute and
free are `tests/models/test_sarvam_mla.py`'s, through the engine), and
what follows K/V pairs alone is refused by name, each in the place the
other kinds' refusals stand (`common/config.py::
LATENT_PAGE_REFUSALS`)."""
import pytest

from aphrodite_tpu.common.config import (LATENT_PAGE_REFUSALS, CacheConfig,
                                         ModelConfig, PageGroups,
                                         ParallelConfig, SchedulerConfig,
                                         refuse_for_latent_pages)
from aphrodite_tpu.common.sampling_params import SamplingParams
from aphrodite_tpu.common.sequence import Sequence, SequenceGroup
from aphrodite_tpu.executor.cache_engine import CacheEngine
from aphrodite_tpu.processing.block_manager import PageGroupsUnsupported
from aphrodite_tpu.processing.scheduler import Scheduler
from aphrodite_tpu.transformers_utils.configs import SarvamMLAConfig

BLOCK = 16


def _model_config(dtype="bfloat16", **changed):
    """The benchmark's cut: 5 layers at the published widths."""
    hf = SarvamMLAConfig(num_hidden_layers=5, num_experts=16,
                         num_routed_experts=128, vocab_size=32768,
                         **changed)
    hf.architectures = ["SarvamMLAForCausalLM"]
    return ModelConfig("x", dtype=dtype, max_model_len=9216, hf_config=hf)


def _cache_config(model_config, **kwargs):
    return CacheConfig(block_size=BLOCK, swap_space=0.01,
                       page_groups=model_config.get_page_groups(), **kwargs)


def test_the_kind_is_stated_once_for_the_model():
    model_config = _model_config()
    groups = model_config.get_page_groups()
    # one model-wide group of kind full, one table a row; the pages
    # latent: a token's value the first 512 lanes of its 576-lane key
    assert groups.kinds == ("full",) and groups.latent == 512
    assert groups.layers_per_group == 5 and groups.arrays_per_page == 1
    assert not groups.plain and not groups.stateful
    assert model_config.get_head_size() == 576
    assert model_config.get_kv_heads_per_slot() == [1] * 5
    # K/V pairs are what every other model has
    pairs = PageGroups.of([False] * 4, None)
    assert pairs.latent is None and pairs.arrays_per_page == 2
    assert pairs.plain
    assert not PageGroups.of([False], None, latent=512).plain


def test_bytes_a_page_and_a_token():
    """`get_cache_block_size` is 16 x 640 lanes x 2 B x 5 layers: one
    array a layer, no pair; 6,400 B a token where K/V pairs of 64
    heads of 192 + 128 would take 204,800."""
    model_config = _model_config()
    cache = _cache_config(model_config)
    size = CacheEngine.get_cache_block_size(cache, model_config,
                                            ParallelConfig(1, 1))
    assert size == 16 * 640 * 2 * 5 == 102400
    assert size // BLOCK == 6400
    assert 64 * (192 + 128) * 2 * 5 == 204800
    # float32 (the CPU tests' type): 4 B a lane
    model32 = _model_config("float32")
    assert CacheEngine.get_cache_block_size(
        _cache_config(model32), model32, ParallelConfig(1, 1)) == 2 * size
    # 9.4 GB of pool hold 1.47 M tokens
    assert 1.46e6 < 9.4e9 // size * BLOCK < 1.48e6


def test_the_pool_is_one_array_a_layer():
    model_config = _model_config("float32")
    cache = _cache_config(model_config)
    cache.num_gpu_blocks, cache.num_cpu_blocks = 12, 4
    engine = CacheEngine(cache, model_config, ParallelConfig(1, 1))
    assert engine.arrays_per_page == 1 and engine.num_page_pairs == 5
    assert len(engine.kv_caches) == 5
    for entry in engine.kv_caches:
        assert len(entry) == 1 and entry[0].shape == (12, BLOCK, 640)
    # no host pool: nothing is swapped
    assert engine._host_pool is None
    for swap in (engine.swap_out, engine.swap_in):
        with pytest.raises(NotImplementedError,
                           match="preemption by swap"):
            swap({0: 1})
    assert engine.kv_handoff([1, 2]) == 0


def _scheduler(pages=40):
    model_config = _model_config()
    cache = _cache_config(model_config)
    cache.num_gpu_blocks, cache.num_cpu_blocks = pages, 0
    sched = SchedulerConfig(max_num_batched_tokens=9216, max_num_seqs=8,
                            max_model_len=9216, max_paddings=9216)
    return Scheduler(sched, cache, None)


def _group(request_id, prompt_len):
    seq = Sequence(hash(request_id) % 1000, "x", list(range(prompt_len)),
                   BLOCK)
    return SequenceGroup(request_id, [seq], SamplingParams(),
                         arrival_time=0.0)


def test_the_one_list_of_refusals():
    assert LATENT_PAGE_REFUSALS == (
        "preemption by swap", "the prefix cache", "bursts",
        "speculative rounds", "kv_handoff (disagg_split)",
        "a mesh (tp > 1)", "--kv-cache-dtype fp8|int8")


@pytest.mark.parametrize("what", LATENT_PAGE_REFUSALS[:2])
def test_the_block_manager_refuses_by_name(what):
    """Swap and the prefix cache, where window groups and state slots
    are refused them: `BlockSpaceManager._plain_only` and `allocate`;
    ids are counted as ever."""
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
    with pytest.raises(PageGroupsUnsupported, match="pages are latent"):
        mgr._plain_only(what)
    # a prompt of 40 tokens takes three ids, and gives them back
    plain = _group("b", 40)
    mgr.allocate(plain)
    assert mgr.get_num_free_gpu_blocks() == 40 - 3
    mgr.free(plain.get_seqs()[0])
    assert mgr.get_num_free_gpu_blocks() == 40


@pytest.mark.parametrize("what,asked", [
    (LATENT_PAGE_REFUSALS[4], dict(disagg=True)),
    (LATENT_PAGE_REFUSALS[5], dict(world_size=4)),
    (LATENT_PAGE_REFUSALS[6], dict(cache_dtype="fp8")),
    (LATENT_PAGE_REFUSALS[6], dict(cache_dtype="int8"))],
    ids=["kv_handoff", "a-mesh", "fp8", "int8"])
def test_the_executor_refuses_by_name(what, asked):
    """The handoff, a mesh and the 8-bit page types, where the
    executor refuses the other kinds theirs
    (`TPUExecutor.__init__` calls `refuse_for_latent_pages` first)."""
    import inspect
    import re
    from aphrodite_tpu.executor.executor import TPUExecutor
    groups = _model_config().get_page_groups()
    args = dict(dict(disagg=False, world_size=1, cache_dtype="auto"),
                **asked)
    with pytest.raises(NotImplementedError, match=re.escape(what)):
        refuse_for_latent_pages(groups, **args)
    # nothing is refused K/V pairs here, nor a latent model that asks
    # for none of it
    refuse_for_latent_pages(PageGroups.of([False], None), **args)
    refuse_for_latent_pages(groups, False, 1, "auto")
    source = inspect.getsource(TPUExecutor.__init__)
    assert source.index("refuse_for_latent_pages(") < \
        source.index("get_model(")


@pytest.mark.parametrize("what", LATENT_PAGE_REFUSALS[1:4])
def test_the_engine_refuses_by_name(what, tmp_path, monkeypatch):
    """At the engine: a cached prefix is refused at the door as a
    fault of the request; a burst and a speculative round are never
    taken (their eligibility asks for plain pages), with
    `APHRODITE_SPEC` at its default and `multi_step` asked for."""
    import pathlib
    monkeypatch.syspath_prepend(
        str(pathlib.Path(__file__).parents[1] / "models"))
    import test_sarvam_mla as toy
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
    # a prompt that repeats itself, so that the drafter would propose
    prompt = (toy._prompt(1, 10) * 4)[:40]
    paths, mark = [], engine._mark_path
    monkeypatch.setattr(engine, "_mark_path", lambda path, *a, **k: (
        paths.append(path), mark(path, *a, **k))[1])
    ((reply,),) = served.run([prompt], steps=12)
    assert len(reply) == 12
    assert paths and set(paths) <= {"prompt", "decode", "combined"}
    assert engine._burst_steps([], None)[0] == 1
