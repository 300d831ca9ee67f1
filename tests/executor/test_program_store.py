"""The step programs' store (`aphrodite_tpu/executor/program_store.py`):
a second engine on one directory loads what the first built and traces
nothing; what a key holds and what it leaves out; what a trace did
besides making a program comes back with a load; a bad entry is a miss;
where nothing is stored; donation survives a load.

On the CPU at toy size. The module has a compile cache directory of
its own (`program_store_dir`): on the CPU an executable that JAX's
persistent cache answered cannot be serialised again
(`program_store._RESERIALISES`), so a program is stored only from a
cold compile, and two tests that want to store a toy program make
programs that differ.
"""
import logging
import os
import pickle

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aphrodite_tpu.common import tracing, utils
from aphrodite_tpu.common.sampling_params import SamplingParams
from aphrodite_tpu.executor import program_store
from aphrodite_tpu.executor.program_store import (ProgramStore,
                                                   StoredProgram)

PROMPTS = ["the quick brown fox", "hello world"]
#: the runner's functions that go through the store
STORED = ("_step_sample", "_step", "_burst_scan", "_feed", "_summarise")
BUILDS = tracing.BUILDS


def _llm(model_dir, **kwargs):
    from aphrodite_tpu.endpoints.llm import LLM
    return LLM(model=model_dir, load_format="dummy", dtype="float32",
               block_size=16, max_model_len=256, max_num_seqs=16,
               swap_space=0.01, **kwargs)


def _generate(llm):
    """Token ids under greedy and seeded sampling (the fused program)
    and with log probabilities (the raw-logits route, `_step`)."""
    return [
        tuple(out.outputs[0].token_ids)
        for params in (
            SamplingParams(temperature=0.0, max_tokens=10),
            SamplingParams(temperature=0.8, seed=7, max_tokens=10),
            SamplingParams(temperature=0.0, max_tokens=4, logprobs=2))
        for out in llm.generate(PROMPTS, params)]


def _rows():
    """[programs made ready, seconds tracing, lowering, compiling or
    loading] of each stored function, for the whole process."""
    return {fun: list(BUILDS.by_function.get(f"jit({fun})", [0, 0., 0., 0.]))
            for fun in STORED}


def _made_ready(before, after):
    return sum(after[fun][0] - before[fun][0] for fun in STORED)


class _Lines(logging.Handler):
    def __init__(self):
        super().__init__()
        self.lines = []

    def emit(self, record):
        self.lines.append(record.getMessage())


def _entries(directory):
    return sorted(name for name in os.listdir(directory)
                  if name.endswith(".program"))


def _notes(path):
    """The kernel-path notes an entry holds."""
    with open(path, "rb") as f:
        blob = f.read()
    _, _, meta_len, _, _ = program_store._HEADER.unpack_from(blob)
    size = program_store._HEADER.size
    return pickle.loads(blob[size:size + meta_len])["notes"]


@pytest.fixture(scope="module")
def two_engines(program_store_dir, tiny_model_dir):
    """Two engines in turn on one store directory, the same requests
    to each; everything the tests below hold against each other."""
    start, counts0 = _rows(), dict(BUILDS.counts)
    first = _generate(_llm(tiny_model_dir))
    rows1, counts1 = _rows(), dict(BUILDS.counts)
    entries = _entries(program_store_dir)
    said, built = _Lines(), _Lines()
    loggers = (logging.getLogger(utils.__name__),
               logging.getLogger(tracing.__name__))
    # (the log line is made once a process for each choice)
    utils._log_kernel_path.cache_clear()
    loggers[0].addHandler(said)
    loggers[1].addHandler(built)
    try:
        llm = _llm(tiny_model_dir)
        second = _generate(llm)
    finally:
        loggers[0].removeHandler(said)
        loggers[1].removeHandler(built)
    return dict(first=first, second=second, start=start, rows1=rows1,
                rows2=_rows(), counts0=counts0, counts1=counts1,
                counts2=dict(BUILDS.counts), seconds2=dict(BUILDS.seconds),
                entries=entries, said=said.lines, built=built.lines,
                llm=llm, directory=program_store_dir)


def test_the_first_engine_builds_and_keeps_every_step_program(two_engines):
    got = two_engines
    made = _made_ready(got["start"], got["rows1"])
    assert made >= 4     # prompt and decode programs, fused and raw
    assert got["counts1"]["program.store_miss"] - \
        got["counts0"]["program.store_miss"] == made == len(got["entries"])
    assert got["counts1"]["program.store_hit"] == \
        got["counts0"]["program.store_hit"]
    for fun in ("_step_sample", "_step"):
        assert got["rows1"][fun][1] > got["start"][fun][1]


def test_the_second_engine_traces_no_step_program(two_engines):
    got = two_engines
    for fun in ("_step_sample", "_step"):
        # no trace and no lowering seconds, and programs all the same
        assert got["rows2"][fun][1:3] == got["rows1"][fun][1:3]
        assert got["rows2"][fun][0] > got["rows1"][fun][0]
    made = _made_ready(got["rows1"], got["rows2"])
    assert made == _made_ready(got["start"], got["rows1"])
    assert got["counts2"]["program.store_hit"] - \
        got["counts1"]["program.store_hit"] == made
    assert got["counts2"]["program.store_miss"] == \
        got["counts1"]["program.store_miss"]
    # nothing was written again
    assert _entries(got["directory"]) == got["entries"]


def test_the_second_engine_returns_the_first_ones_tokens(two_engines):
    first, second = two_engines["first"], two_engines["second"]
    assert len(first) == 3 * len(PROMPTS) and all(first)
    assert second == first      # greedy, seeded, raw: id for id


def test_a_load_is_filed_as_the_build_it_stands_in_the_place_of(
        two_engines):
    got = two_engines
    hits = got["counts2"]["program.store_hit"] - \
        got["counts1"]["program.store_hit"]
    # each load is one program made ready and one answer from disk
    for name in ("program.compile", "program.cache_hit"):
        assert got["counts2"][name] - got["counts1"][name] >= hits
    assert 0 < got["seconds2"]["program.store_load"] <= \
        got["seconds2"]["program.cache_load"]
    # and logged in the form of a build, with the round that met it
    loads = [ln for ln in got["built"]
             if ln.startswith("program built: ") and "cache=store" in ln]
    assert len(loads) == hits
    for line in loads:
        assert "trace=0.000 lower=0.000" in line and " round=-" not in line
    assert any("fun=jit(_step_sample) " in ln for ln in loads)
    assert any("fun=jit(_step) " in ln for ln in loads)
    summary = BUILDS.summary()
    assert f"the program store {got['counts2']['program.store_hit']} " \
        "hits in " in summary


def test_a_load_says_again_what_its_trace_said_of_the_kernels(
        two_engines):
    got = two_engines
    noted = {note for name in got["entries"]
             for note in _notes(os.path.join(got["directory"], name))}
    assert {family for family, _, _ in noted} >= {
        "kv_write", "decode_attention", "prefill_attention"}
    said = {ln for ln in got["said"] if ln.startswith("kernel path: ")}
    assert said == {f"kernel path: {family} = {side} ({detail})"
                    for family, side, detail in noted}


def test_truncated_entries_are_misses_removed_and_the_steps_still_run(
        two_engines, tiny_model_dir):
    got = two_engines
    for name in got["entries"]:
        path = os.path.join(got["directory"], name)
        with open(path, "rb") as f:
            blob = f.read()
        with open(path, "wb") as f:
            f.write(blob[:len(blob) // 2])
    misses = BUILDS.counts["program.store_miss"]
    assert _generate(_llm(tiny_model_dir)) == got["first"]
    assert BUILDS.counts["program.store_miss"] - misses == \
        len(got["entries"])
    # (JAX's persistent cache answers the rebuilds here, and on the CPU
    # such an executable is not serialised again: the halves are gone
    # and nothing stands in their place)
    assert _entries(got["directory"]) == []


# ---- what a key holds, and what it leaves out ----

def _executor(two_engines):
    return two_engines["llm"].engine.executor


def _flag(patch, executor):
    patch.setenv("APHRODITE_SPEC_K", "7")


def _model_len(patch, executor):
    patch.setattr(executor.model_config, "max_model_len", 128)


def _published(patch, executor):
    patch.setattr(executor.model_config.hf_config, "rope_theta", 5e5)


def _scheduler(patch, executor):
    patch.setattr(executor.scheduler_config, "max_num_seqs", 8)


def _xla_flags(patch, executor):
    patch.setenv("XLA_FLAGS", os.environ.get("XLA_FLAGS", "") +
                 " --xla_cpu_enable_fast_math=false")


def _source(patch, executor):
    patch.setattr(program_store, "_package_digest", lambda: "other")


def _weight_seed(patch, executor):
    patch.setattr(executor.model_config, "seed", 2147483659)


def _model_path(patch, executor):
    patch.setattr(executor.model_config, "model", "/elsewhere/model")
    patch.setattr(executor.model_config, "tokenizer", "/elsewhere/model")
    patch.setattr(executor.model_config.hf_config, "_name_or_path",
                  "/elsewhere/model")


def _directory(patch, executor):
    patch.setenv("APHRODITE_COMPILE_CACHE",
                 os.environ["APHRODITE_COMPILE_CACHE"] + "-moved")


@pytest.mark.parametrize("change, same", [
    (_flag, False), (_model_len, False), (_published, False),
    (_scheduler, False), (_xla_flags, False), (_source, False),
    (_weight_seed, True), (_model_path, True), (_directory, True)],
    ids=lambda v: getattr(v, "__name__", str(v)).strip("_"))
def test_the_engines_part_of_a_key(two_engines, monkeypatch, change, same):
    """A flag of the registry, a field of the model's or the engine's
    configuration, the compiler's flags and the source give another
    key; the weight seed, the model's path and the directory do not."""
    executor = _executor(two_engines)
    before = executor._open_program_store().context
    change(monkeypatch, executor)
    assert (executor._open_program_store().context == before) is same


def test_a_changed_byte_of_a_source_file_is_another_digest(tmp_path):
    (tmp_path / "pkg" / "ops").mkdir(parents=True)
    (tmp_path / "pkg" / "a.py").write_text("x = 1\n")
    (tmp_path / "pkg" / "ops" / "b.py").write_text("y = 2\n")
    (tmp_path / "pkg" / "notes.txt").write_text("not source\n")
    before = program_store.digest_tree(str(tmp_path / "pkg"))
    assert program_store.digest_tree(str(tmp_path / "pkg")) == before
    (tmp_path / "pkg" / "notes.txt").write_text("still not source\n")
    assert program_store.digest_tree(str(tmp_path / "pkg")) == before
    (tmp_path / "pkg" / "ops" / "b.py").write_text("y = 3\n")
    assert program_store.digest_tree(str(tmp_path / "pkg")) != before
    # the package's own is taken once a process
    assert program_store._package_digest() == \
        program_store.digest_tree(program_store._PACKAGE)


_OPERANDS = (jnp.zeros((4, 8), jnp.float32), [jnp.zeros((2,), jnp.int32)])


@pytest.mark.parametrize("other, same", [
    (dict(), True),
    (dict(args=(jnp.ones((4, 8), jnp.float32), [jnp.ones((2,), jnp.int32)])),
     True),     # values are data
    (dict(statics=dict(flag=False)), False),
    (dict(args=(jnp.zeros((4, 9), jnp.float32), _OPERANDS[1])), False),
    (dict(args=(jnp.zeros((4, 8), jnp.bfloat16), _OPERANDS[1])), False),
    (dict(args=(_OPERANDS[0], (_OPERANDS[1][0],))), False),     # the tree
    (dict(args=(jax.device_put(_OPERANDS[0], jax.devices()[0]),
                _OPERANDS[1])), False),     # committed to its device
    (dict(name="g"), False),
    (dict(closes_over=dict(page_size=32)), False),
    (dict(donate_argnums=()), False)],
    ids=["same", "values", "static", "shape", "dtype", "tree", "committed",
         "function", "closed-over", "donated"])
def test_a_programs_part_of_a_key(tmp_path, other, same):
    store = ProgramStore(str(tmp_path), "context")
    base = dict(name="f", closes_over=dict(page_size=16),
                statics=dict(flag=True), args=_OPERANDS,
                donate_argnums=(1,))
    assert (store.key(**{**base, **other}) == store.key(**base)) is same
    assert ProgramStore(str(tmp_path), "another").key(**base) != \
        store.key(**base)


def test_the_static_fields_of_the_metadata_are_in_the_key(tmp_path):
    from aphrodite_tpu.modeling.input_metadata import InputMetadata
    store = ProgramStore(str(tmp_path), "context")
    table = jnp.zeros((2, 8), jnp.int32)

    def key(**fields):
        meta = InputMetadata(slot_mapping=None, block_tables=table,
                             context_lens=None, **fields)
        return store.key("f", None, {}, (meta,), ())
    assert key() == key()
    assert key(spec_verify=True) != key()
    assert key(group_layout=(8,)) != key()


def test_what_cannot_be_described_is_not_described():
    with pytest.raises(program_store.Undescribable):
        program_store.describe(dict(activation=lambda x: x))
    with pytest.raises(program_store.Undescribable):
        program_store.describe([jnp.zeros((2,))])
    assert program_store.describe(
        dict(b=jnp.bfloat16, a=(1, 2.5, None), c={"z", "y"})) == {
        "a": [1, 2.5, None], "b": "dtype:bfloat16", "c": ['"y"', '"z"']}


# ---- where nothing is stored ----

def test_no_store_where_the_compile_cache_is_off(two_engines, monkeypatch):
    executor = _executor(two_engines)
    assert executor._open_program_store() is not None
    monkeypatch.setenv("APHRODITE_COMPILE_CACHE", "0")
    assert program_store.cache_dir() is None
    assert executor._open_program_store() is None
    # the CPU with no directory given
    monkeypatch.delenv("APHRODITE_COMPILE_CACHE")
    assert program_store.cache_dir() is None
    assert executor._open_program_store() is None


def test_no_store_under_a_mesh_and_the_jitted_calls_are_todays(
        two_engines, monkeypatch, cpu_devices):
    from jax.sharding import Mesh
    from aphrodite_tpu.common.config import ParallelConfig
    from aphrodite_tpu.executor.model_runner import ModelRunner
    executor = _executor(two_engines)
    store = executor._open_program_store()
    mesh = Mesh(np.asarray(cpu_devices[:2]).reshape(1, 1, 1, 2),
                ParallelConfig.MESH_AXES)

    def runner(**kwargs):
        return ModelRunner(
            executor.model, executor.params, executor.model_config,
            executor.scheduler_config, page_size=16,
            num_slots=executor.cache_engine.num_slots, **kwargs)
    jitted = type(jax.jit(lambda: 0))
    with_store = runner(program_store=store)
    assert isinstance(with_store._step_sample_fn, StoredProgram)
    for made in (runner(program_store=store, mesh=mesh), runner()):
        assert made.program_store is None
        for fn in (made._step_sample_fn, made._step_fn,
                   made._burst_scan_fn, made._feed_fn, made._summarise_fn):
            assert type(fn) is jitted
    monkeypatch.setattr(executor, "mesh", mesh)
    assert executor._open_program_store() is None


def test_a_model_from_outside_the_package_is_not_stored(two_engines):
    class Elsewhere:
        pass
    Elsewhere.__module__ = "plugins.models"
    assert ProgramStore.open(Elsewhere()) is None
    executor = _executor(two_engines)
    assert ProgramStore.open(executor.model, odd=lambda: 0) is None


# ---- a toy program behind the store ----

def _toy(store, scale, closes_over=None):
    """A program with a donated operand and a static argument; `scale`
    makes the programs of two tests differ."""
    def toy(w, kv, *, flag):
        utils.note_kernel_path("toy", "reference", f"scale {scale}")
        out = w.sum() * scale + (1.0 if flag else 2.0)
        return out, [k + 1.0 for k in kv]
    return StoredProgram(
        store, jax.jit(toy, static_argnames=("flag",), donate_argnums=(1,)),
        "toy", closes_over=closes_over or dict(scale=scale),
        donate_argnums=(1,), stable_argnums=(1,))


def _call(program, flag=True):
    kv = [jnp.zeros((8, 8), jnp.float32)]
    out, new = program(jnp.ones((4,), jnp.float32), kv, flag=flag)
    return float(out), kv[0], new


def _counts():
    return dict(BUILDS.counts)


def test_donation_survives_a_load(program_store_dir, tmp_path):
    store = ProgramStore(str(tmp_path), "context")
    built = _call(_toy(store, 3.0))
    assert len(_entries(tmp_path)) == 1
    before = _counts()
    loaded = _toy(store, 3.0)
    out, given, new = _call(loaded)
    assert BUILDS.counts["program.store_hit"] == \
        before["program.store_hit"] + 1
    assert BUILDS.counts["program.trace"] == before["program.trace"]
    assert out == built[0] == 13.0
    assert given.is_deleted() and built[1].is_deleted()
    assert float(new[0][0, 0]) == 1.0
    # the loaded object serves every later call of the signature
    again = _counts()
    assert _call(loaded)[0] == 13.0 and _counts() == again
    # another static argument is another program and another entry
    assert _call(loaded, flag=False)[0] == 14.0
    assert len(_entries(tmp_path)) == 2


@pytest.mark.parametrize("fault", ["truncated", "not-an-entry", "raises"])
def test_an_entry_that_cannot_be_used_is_a_miss_and_is_removed(
        program_store_dir, tmp_path, monkeypatch, fault):
    scale = {"truncated": 5.0, "not-an-entry": 6.0, "raises": 7.0}[fault]
    store = ProgramStore(str(tmp_path), "context")
    assert _call(_toy(store, scale))[0] == 4 * scale + 1
    (name,) = _entries(tmp_path)
    path = os.path.join(tmp_path, name)
    if fault == "truncated":
        with open(path, "rb") as f:
            blob = f.read()
        with open(path, "wb") as f:
            f.write(blob[:-7])
    elif fault == "not-an-entry":
        with open(path, "wb") as f:
            f.write(b"something else entirely")
    else:
        def refuses(*args, **kwargs):
            raise RuntimeError("the runtime refuses this executable")
        monkeypatch.setattr(program_store.serialize_executable,
                            "deserialize_and_load", refuses)
    before = _counts()
    assert _call(_toy(store, scale))[0] == 4 * scale + 1
    assert BUILDS.counts["program.store_miss"] == \
        before["program.store_miss"] + 1
    assert BUILDS.counts["program.store_hit"] == before["program.store_hit"]
    # removed; and not written again here, where JAX's persistent
    # cache answered the rebuild (the CPU: `_RESERIALISES`)
    assert _entries(tmp_path) == []
    assert len(store._said) == 1


def test_an_entry_is_packed_with_zlib_where_zstandard_is_not_installed(
        program_store_dir, tmp_path, monkeypatch):
    store = ProgramStore(str(tmp_path), "context")
    monkeypatch.setattr(program_store, "zstandard", None)
    assert _call(_toy(store, 8.0))[0] == 33.0
    before = _counts()
    assert _call(_toy(store, 8.0))[0] == 33.0
    assert BUILDS.counts["program.store_hit"] == \
        before["program.store_hit"] + 1
    monkeypatch.undo()
    # written with the one, read with either
    assert _call(_toy(store, 8.0))[0] == 33.0
    assert BUILDS.counts["program.store_hit"] == \
        before["program.store_hit"] + 2


def test_a_directory_that_cannot_be_written_stops_the_writes(
        program_store_dir, tmp_path, monkeypatch):
    store = ProgramStore(str(tmp_path), "context")

    def full(*args, **kwargs):
        raise OSError(28, "No space left on device")
    monkeypatch.setattr(program_store.os, "replace", full)
    assert _call(_toy(store, 9.0))[0] == 37.0
    assert store.writes is False and len(store._said) == 1
    assert os.listdir(tmp_path) == []       # the half is taken away
    monkeypatch.undo()
    # and stays stopped, with nothing more said
    assert _call(_toy(store, 10.0))[0] == 41.0
    assert os.listdir(tmp_path) == [] and len(store._said) == 1


def test_what_a_key_cannot_hold_takes_the_jitted_path(
        program_store_dir, tmp_path):
    store = ProgramStore(str(tmp_path), "context")
    program = _toy(store, 11.0, closes_over=dict(act=lambda x: x))
    before = _counts()
    assert _call(program)[0] == 45.0 and _call(program)[0] == 45.0
    assert os.listdir(tmp_path) == []
    assert BUILDS.counts["program.store_miss"] == \
        before["program.store_miss"]
    (served,) = program._ready.values()
    assert served.func is program.jitted


def test_two_stores_on_one_directory_meet_whole_entries(
        program_store_dir, tmp_path):
    """Two servers that build the same program write the same name,
    each through a name of its own: whichever renames last, a reader
    meets a whole entry."""
    one, two = (ProgramStore(str(tmp_path), "context") for _ in range(2))
    compiled = jax.jit(lambda x: x * 13.0).lower(
        jnp.ones((4,), jnp.float32)).compile()
    one.save("k" * 64, "toy", compiled, [("toy", "reference", "one")])
    two.save("k" * 64, "toy", compiled, [("toy", "reference", "two")])
    assert os.listdir(tmp_path) == ["k" * 64 + ".program"]
    loaded, notes = one.load("k" * 64, (jnp.ones((4,), jnp.float32),))
    assert notes == [("toy", "reference", "two")]
    assert float(loaded(jnp.ones((4,), jnp.float32))[0]) == 13.0


def test_the_kernel_paths_of_a_block_are_noted_logged_before_or_not():
    utils.note_kernel_path("toy", "reference", "said before")
    with utils.kernel_paths_noted() as notes:
        utils.note_kernel_path("toy", "reference", "said before")
        utils.note_kernel_path("toy", "reference", "said before")
        with utils.kernel_paths_noted() as inner:
            utils.note_kernel_path("toy", "pallas", "inside")
        utils.note_kernel_path("toy", "reference", "after")
    assert notes == [("toy", "reference", "said before"),
                     ("toy", "reference", "after")]
    assert inner == [("toy", "pallas", "inside")]
    utils.note_kernel_path("toy", "reference", "outside any block")


def test_the_stores_names_are_exported():
    from aphrodite_tpu.engine import metrics
    exported = {name for name, _, _ in metrics._STAGE_COUNTERS}
    assert {"aphrodite:program_store_hits_total",
            "aphrodite:program_store_misses_total",
            "aphrodite:program_store_load_seconds_total"} <= exported
    assert {"program.store_hit", "program.store_miss",
            "program.store_load"} <= set(tracing.NAMES)
