"""Test configuration: run on a virtual 8-device CPU mesh.

The reference has no CPU-only multi-device story (its distributed tests need
real GPUs + Ray, SURVEY.md §4); here every sharding test runs on
`--xla_force_host_platform_device_count=8` CPU devices, so the full TP/PP
code path is exercised in CI without TPU hardware.
"""
import os

# Must be set before jax is imported anywhere.
os.environ["JAX_PLATFORMS"] = "cpu"

# Opt the suite into the engine's persistent compilation cache
# (aphrodite_engine._enable_compilation_cache skips CPU unless the
# flag is set explicitly). Hundreds of tests build fresh engines
# around the same tiny-model shapes; each fresh engine re-jits the
# same programs, so cross-process/cross-test executable reuse cuts
# the suite's wall time roughly in half on a cold box. Server
# subprocesses (endpoints/fleet tests) inherit the env var and share
# the same cache. The engine appends a per-backend subdirectory, so
# CPU test entries never mix with a chip's.
os.environ.setdefault(
    "APHRODITE_COMPILE_CACHE",
    os.path.join(os.environ.get("XDG_CACHE_HOME",
                                os.path.expanduser("~/.cache")),
                 "aphrodite_tpu", "jax_cache"))
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

# Hold JAX to the CPU at the config level too, whatever the
# environment set before this file ran.
jax.config.update("jax_platforms", "cpu")

import functools  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import pytest  # noqa: E402

# The step programs' store (`executor/program_store.py`) keeps an
# executable under what a trace of it READS: source files, flags,
# configurations, shapes. A test that puts a spy or a stub in the place
# of a function and builds an engine changes none of those, so under
# the store its engine would load the program an earlier test traced
# and the stub would never run. Engines built inside the suite's own
# processes therefore keep no store (the servers the suite starts as
# children, which inherit `APHRODITE_COMPILE_CACHE`, do);
# `tests/executor/test_program_store.py` puts `open_program_store`
# back for its own engines, on a directory of their own.
from aphrodite_tpu.executor.program_store import ProgramStore  # noqa: E402

open_program_store = ProgramStore.__dict__["open"]
ProgramStore.open = classmethod(lambda cls, *args, **configs: None)


@pytest.fixture(scope="session")
def cpu_devices():
    devices = jax.devices()
    assert len(devices) >= 8, devices
    return devices


# ---- shared tiny offline model fixtures (engine/API tests) ----
_CORPUS = [
    "the quick brown fox jumps over the lazy dog",
    "hello world this is a tiny tokenizer training corpus",
    "continuous batching over a paged key value cache",
    "tensor parallel meshes shard attention heads",
    "sampling with top p top k and repetition penalties",
    "0123456789 !?.,:;()[]{}",
] * 4


@pytest.fixture(scope="session")
def tiny_model_dir(tmp_path_factory):
    path = tmp_path_factory.mktemp("tiny-llama")

    # 1. Tokenizer: ByteLevel BPE trained in-process (offline).
    from tokenizers import (Tokenizer, decoders, models, pre_tokenizers,
                            trainers)
    tok = Tokenizer(models.BPE(unk_token=None))
    tok.pre_tokenizer = pre_tokenizers.ByteLevel(add_prefix_space=True)
    tok.decoder = decoders.ByteLevel()
    trainer = trainers.BpeTrainer(
        vocab_size=512,
        special_tokens=["<s>", "</s>", "<pad>"],
        initial_alphabet=pre_tokenizers.ByteLevel.alphabet())
    tok.train_from_iterator(_CORPUS, trainer)
    tok.save(str(path / "tokenizer.json"))
    vocab_size = tok.get_vocab_size()
    (path / "tokenizer_config.json").write_text(json.dumps({
        "tokenizer_class": "PreTrainedTokenizerFast",
        "bos_token": "<s>",
        "eos_token": "</s>",
        "pad_token": "<pad>",
        "model_max_length": 512,
    }))

    # 2. Tiny Llama config.
    (path / "config.json").write_text(json.dumps({
        "architectures": ["LlamaForCausalLM"],
        "model_type": "llama",
        "vocab_size": vocab_size,
        "hidden_size": 64,
        "intermediate_size": 128,
        "num_hidden_layers": 2,
        "num_attention_heads": 4,
        "num_key_value_heads": 2,
        "max_position_embeddings": 512,
        "rms_norm_eps": 1e-6,
        "rope_theta": 10000.0,
        "tie_word_embeddings": False,
        "torch_dtype": "float32",
        "bos_token_id": 0,
        "eos_token_id": 1,
    }))
    return str(path)


@pytest.fixture(scope="session")
def tiny_llm(tiny_model_dir):
    from aphrodite_tpu.endpoints.llm import LLM
    return LLM(model=tiny_model_dir, load_format="dummy", dtype="float32",
               block_size=16, max_model_len=256, max_num_seqs=16,
               swap_space=0.01)


@pytest.fixture(scope="module")
def program_store_dir(tmp_path_factory):
    """A compile cache directory of the module's own, cold: JAX's
    persistent cache and the step programs' store under it, which the
    engines the module builds keep. Yields the store's directory."""
    from jax.experimental.compilation_cache import compilation_cache
    root = tmp_path_factory.mktemp("compile-cache")
    patch = pytest.MonkeyPatch()
    patch.setenv("APHRODITE_COMPILE_CACHE", str(root))
    patch.setattr(ProgramStore, "open", open_program_store)
    before = jax.config.jax_compilation_cache_dir
    # (JAX opens its cache once a process; point it where an engine
    # built under this directory would, so that a test which builds
    # none meets a cold cache too)
    jax.config.update("jax_compilation_cache_dir", str(root / "cpu"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compilation_cache.reset_cache()
    yield str(root / "cpu" / "programs")
    patch.undo()
    jax.config.update("jax_compilation_cache_dir", before)
    compilation_cache.reset_cache()


# ---- the benchmark's manifest, for the tests that pin it ----
#: per-layer metrics appended to `BENCHMARK.json` that every cell
#: reports, oldest first (PR 38's six)
LATER_METRICS = (
    "host_lead_ms.batch", "host_lead_decode_ms.batch",
    "dispatch_starved_pct.batch", "dispatch_starved_prompt_pct.batch",
    "host_dispatch_ms.batch", "host_hops_ms.batch")
#: PR 54's ten, which read the program's account of its own set-up
#: and which every cell reports
SETUP_METRICS = (
    "startup_ready_s", "startup_backend_s", "startup_weights_s",
    "startup_kv_pool_s", "program_trace_s", "program_lower_s",
    "program_compile_s", "program_cache_hit_pct", "programs_built",
    "program_build_in_window_pct.batch")
#: and those appended after them (PR 45's, PR 54's), which every
#: module that pins the manifest is spared
NEWER_METRICS = ("prompt_dispatch_late_pct.batch",) + SETUP_METRICS
#: cells appended since the pinning tests were written, oldest first
#: (PR 41's, PR 43's, PR 48's, PR 52's, PR 56's), each with its
#: configuration and the metrics it alone reports
NEWER_CELLS = ("jamba2-3b-bf16.reason-512", "laguna-s-2.1-bf16.agent-4k",
               "evabyte-6.5b-bf16.doc-5k", "sarvam-105b-bf16.doc-8k",
               "kimi-linear-48b-a3b-bf16.reason-1k")
#: the modules that hold the manifest to a count, a set or its last
#: places -> (the cells, the metrics) appended after what each holds
_PINNED = {
    "test_perf_smallthinker": (NEWER_CELLS, LATER_METRICS + NEWER_METRICS),
    "test_perf_phi4flash": (NEWER_CELLS, LATER_METRICS + NEWER_METRICS),
    "test_perf_jamba": (NEWER_CELLS[1:], NEWER_METRICS),
    "test_perf_laguna": (NEWER_CELLS[2:], NEWER_METRICS),
    "test_perf_evabyte": (NEWER_CELLS[3:], SETUP_METRICS),
    "test_perf_sarvam": (NEWER_CELLS[4:], SETUP_METRICS),
    "test_perf_kimi_linear": ((), SETUP_METRICS),
}
#: the modules that hold the manifest's last places or a list's length
#: to their own and read `BENCHMARK.json` with `json.load` (PR 38's six
#: and three cells; PR 51's two shares and six cells) -> what was
#: appended after what each holds
_PINNED_BY_FILE = {
    "test_perf_host_lead": (NEWER_CELLS, NEWER_METRICS),
    "test_perf_w4a8_share": (NEWER_CELLS[3:], SETUP_METRICS),
}


@functools.lru_cache(maxsize=None)
def _perf_conftest():
    """`tests/perf/conftest.py`, loaded by its path: that file is a
    `conftest` too."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "perf",
                        "conftest.py")
    spec = importlib.util.spec_from_file_location("perf_conftest", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _without(metrics: list, names) -> list:
    return [m for m in metrics if m["name"] not in names]


def _before(bench: dict, cells=NEWER_CELLS, metrics=NEWER_METRICS) -> dict:
    """`bench` as it read before `cells` and `metrics` were appended:
    without the cells, a configuration no other cell runs, their names
    on every `workloads` list, a metric that only they report
    (`tests/perf/conftest.py::without_cells`), and the metrics."""
    bench = _perf_conftest().without_cells(bench, cells=cells)
    bench["per_layer"] = _without(bench["per_layer"], metrics)
    return bench


class _JsonBefore:
    """`json`, for a module that loads `BENCHMARK.json` itself: the
    manifest comes back without the `cells` and `metrics` appended
    after what the module holds, anything else as it is."""

    def __init__(self, cells=NEWER_CELLS, metrics=NEWER_METRICS):
        self._later = (cells, metrics)

    def load(self, f, **kwargs):
        data = json.load(f, **kwargs)
        if isinstance(data, dict) and {"workloads", "per_layer"} <= set(data):
            return _before(data, *self._later)
        return data

    def __getattr__(self, name):
        return getattr(json, name)


@pytest.fixture(autouse=True)
def _the_manifest_without_later_metrics(request, monkeypatch):
    """`tests/perf/test_perf_smallthinker.py` and
    `tests/perf/test_perf_phi4flash.py` hold the per-layer metrics
    their cells report to a count and a set and the `workloads` of the
    metrics their cells joined to a list, which a metric appended for
    every cell, or a cell appended to those lists, breaks; and no PR
    but a `benchmark` PR may edit them. They read the manifest through
    their module's `_bench()` and what a cell reports through
    `cells.load_cell()`; for them both leave `LATER_METRICS` and
    `NEWER_METRICS` out, and `_bench()` leaves `NEWER_CELLS` out, as
    `tests/perf/conftest.py` leaves the later cells out (this fixture
    runs first, so that one wraps this).
    `tests/perf/test_perf_jamba.py` and
    `tests/perf/test_perf_laguna.py` hold the manifest's last
    configuration, cell and two metrics to their own, and what their
    cell reports to a set: they are spared the cells appended after
    theirs and `NEWER_METRICS` the same way (`_PINNED`).
    `tests/perf/test_perf_host_lead.py` holds the six to the
    manifest's last places and the cells to a list of three, and loads
    the file itself: its `json` gives it the manifest without
    `NEWER_CELLS` and `NEWER_METRICS`. PR 54's ten (`SETUP_METRICS`)
    are reported by every cell, so the modules written since then that
    hold a cell's metrics to a count or the manifest's last places to
    their own (`test_perf_evabyte.py`, `test_perf_sarvam.py`,
    `test_perf_w4a8_share.py`) are spared them the same way; their own
    tests are `tests/perf/test_perf_setup.py`. The metrics and the
    cells have tests of their own (`tests/perf/test_perf_host_lead.py`,
    `tests/perf/test_perf_jamba.py`, `tests/perf/test_perf_laguna.py`,
    `tests/perf/test_perf_prompt_late.py`)."""
    module = request.module
    name = module.__name__.rsplit(".", 1)[-1]
    if name in _PINNED_BY_FILE:
        monkeypatch.setattr(module, "json",
                            _JsonBefore(*_PINNED_BY_FILE[name]))
        return
    if name not in _PINNED:
        return
    later_cells, later_metrics = _PINNED[name]
    own_bench, own_load = module._bench, module.cells.load_cell

    def load_cell(*args, **kwargs):
        cell = own_load(*args, **kwargs)
        cell.per_layer = _without(cell.per_layer, later_metrics)
        return cell
    monkeypatch.setattr(
        module, "_bench",
        lambda: _before(own_bench(), later_cells, later_metrics))
    monkeypatch.setattr(module.cells, "load_cell", load_cell)
