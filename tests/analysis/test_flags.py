"""Behavioral tests for the central flag registry
(aphrodite_tpu/common/flags.py): typed accessors, per-call reads,
strict-raise vs warn-and-default, and the generated docs table."""
import os
import re
import warnings

import pytest

from aphrodite_tpu.common import flags
from aphrodite_tpu.common.flags import FlagError


def test_strict_int_raises_clear_error(monkeypatch):
    """A typo'd numeric knob names the flag in the error — never a
    bare int() ValueError."""
    monkeypatch.setenv("APHRODITE_QMM_BLOCK_M", "banana")
    with pytest.raises(FlagError, match="APHRODITE_QMM_BLOCK_M"):
        flags.get_int("APHRODITE_QMM_BLOCK_M", default=512)


def test_strict_minimum_enforced(monkeypatch):
    monkeypatch.setenv("APHRODITE_ATTN_PF", "0")
    with pytest.raises(ValueError, match="APHRODITE_ATTN_PF"):
        flags.get_int("APHRODITE_ATTN_PF")


def test_strict_float_raises(monkeypatch):
    monkeypatch.setenv("APHRODITE_KV_SCALE", "not-a-number")
    with pytest.raises(FlagError, match="APHRODITE_KV_SCALE"):
        flags.get_float("APHRODITE_KV_SCALE", default=1.0)


def test_bool_warns_and_defaults(monkeypatch):
    """Booleans never kill a serving step: bad values warn and fall
    back to the registered default."""
    monkeypatch.setenv("APHRODITE_SPEC", "ture")
    with pytest.warns(RuntimeWarning, match="APHRODITE_SPEC"):
        assert flags.get_bool("APHRODITE_SPEC") is True
    monkeypatch.setenv("APHRODITE_SPEC", "0")
    assert flags.get_bool("APHRODITE_SPEC") is False
    monkeypatch.setenv("APHRODITE_SPEC", "true")
    assert flags.get_bool("APHRODITE_SPEC") is True


def test_choices_warn_and_default(monkeypatch):
    monkeypatch.setenv("APHRODITE_TPU_LOG_LEVEL", "LOUD")
    with pytest.warns(RuntimeWarning, match="APHRODITE_TPU_LOG_LEVEL"):
        assert flags.get_str("APHRODITE_TPU_LOG_LEVEL") == "INFO"
    monkeypatch.setenv("APHRODITE_TPU_LOG_LEVEL", "ERROR")
    assert flags.get_str("APHRODITE_TPU_LOG_LEVEL") == "ERROR"


def test_uppercase_normalization(monkeypatch):
    monkeypatch.setenv("APHRODITE_TPU_LOG_LEVEL", "debug")
    assert flags.get_str("APHRODITE_TPU_LOG_LEVEL") == "DEBUG"


def test_call_site_default_override(monkeypatch):
    monkeypatch.delenv("APHRODITE_QMM_BLOCK_M", raising=False)
    assert flags.get_int("APHRODITE_QMM_BLOCK_M", default=256) == 256
    monkeypatch.setenv("APHRODITE_QMM_BLOCK_M", "128")
    assert flags.get_int("APHRODITE_QMM_BLOCK_M", default=256) == 128


def test_reads_are_per_call(monkeypatch):
    """The registry holds no cached values — two reads straddling an
    env change see both values (the A/B-sweep contract)."""
    monkeypatch.setenv("APHRODITE_ATTN_PF", "2")
    assert flags.get_int("APHRODITE_ATTN_PF") == 2
    monkeypatch.setenv("APHRODITE_ATTN_PF", "7")
    assert flags.get_int("APHRODITE_ATTN_PF") == 7
    monkeypatch.delenv("APHRODITE_ATTN_PF")
    assert flags.get_int("APHRODITE_ATTN_PF") == 6


def test_unregistered_name_is_programming_error():
    with pytest.raises(FlagError, match="not a registered flag"):
        flags.get_bool("APHRODITE_NO_SUCH_FLAG")
    with pytest.raises(FlagError, match="not a registered flag"):
        flags.is_set("APHRODITE_NO_SUCH_FLAG")


def test_is_set(monkeypatch):
    monkeypatch.delenv("APHRODITE_W4A8", raising=False)
    assert flags.is_set("APHRODITE_W4A8") is False
    monkeypatch.setenv("APHRODITE_W4A8", "1")
    assert flags.is_set("APHRODITE_W4A8") is True


def test_empty_string_numeric_means_unset(monkeypatch):
    """`APHRODITE_QMM_BLOCK_N=` behaves like unset (the `or default`
    idiom at the call sites relies on it)."""
    monkeypatch.setenv("APHRODITE_QMM_BLOCK_N", "")
    assert flags.get_int("APHRODITE_QMM_BLOCK_N") == 0


def test_markdown_table_covers_registry():
    md = flags.flags_markdown()
    for name, flag in flags.registry().items():
        assert name in md
        assert flag.description.strip(), f"{name} undocumented"


#: The five flags that picked a kernel path until PR 46: the decode
#: kernel's padded grid and rescale multiply, the W4A8 matmul's grid,
#: rescale and the rescale's VMEM budget. The choice is the code's.
RETIRED_KERNEL_FLAGS = (
    "APHRODITE_ATTN_RAGGED", "APHRODITE_ATTN_AMLA",
    "APHRODITE_QMM_STREAM", "APHRODITE_QMM_DEFERRED",
    "APHRODITE_QMM_DEFERRED_VMEM_MB")


def test_no_flag_picks_a_kernel_path():
    names = set(flags.registry())
    assert len(names) == 41
    assert not names & set(RETIRED_KERNEL_FLAGS)


def test_no_file_spells_a_retired_kernel_flag():
    """The program, its harnesses, the chip smoke and the README name
    none of them (`APHRODITE_QMM_STREAM_PF` stays a flag)."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    spelled = re.compile(
        r"\b(" + "|".join(RETIRED_KERNEL_FLAGS) + r")\b")
    paths = [os.path.join(root, "README.md"),
             os.path.join(root, "tests", "kernels", "tpu_smoke.py")]
    for top in ("aphrodite_tpu", "benchmarks"):
        for dirpath, _, files in os.walk(os.path.join(root, top)):
            paths.extend(os.path.join(dirpath, f) for f in files
                         if f.endswith((".py", ".md")))
    offenders = []
    for path in paths:
        with open(path, encoding="utf-8") as f:
            for lineno, line in enumerate(f, 1):
                if spelled.search(line):
                    offenders.append(
                        f"{os.path.relpath(path, root)}:{lineno}")
    assert not offenders, offenders


def test_registry_defaults_match_types():
    for name, flag in flags.registry().items():
        assert flag.type in ("bool", "int", "float", "str"), name
        if flag.default is not None:
            expected = {"bool": bool, "int": int, "float": (int, float),
                        "str": str}[flag.type]
            assert isinstance(flag.default, expected), name
