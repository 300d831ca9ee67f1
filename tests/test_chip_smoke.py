"""No measurement path may fall back to the CPU: `chip_smoke.py`,
`bench.py` and `tests/kernels/tpu_smoke.py` fail without a chip, print
no result, and say what is missing; an engine asked for a TPU refuses
to build on the CPU. Also the smoke's own log reading, on recorded
lines (the chip run itself happens through the chip tool)."""
import json
import os
import shutil
import subprocess
import sys

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CPU_ENV = {**os.environ, "JAX_PLATFORMS": "cpu"}


def _run(argv, cwd=REPO_ROOT, timeout=300):
    return subprocess.run([sys.executable] + argv, cwd=cwd, env=CPU_ENV,
                          capture_output=True, text=True, timeout=timeout)


def _json_lines(text):
    out = []
    for line in text.splitlines():
        try:
            out.append(json.loads(line))
        except ValueError:
            pass
    return [o for o in out if isinstance(o, dict)]


def test_chip_smoke_fails_without_a_chip():
    r = _run(["chip_smoke.py"])
    assert r.returncode != 0
    assert not _json_lines(r.stdout), r.stdout
    assert "FAILED" in r.stderr
    assert "needs a tpu backend" in r.stderr and "'cpu'" in r.stderr


def test_chip_smoke_fails_outside_a_checkout(tmp_path):
    shutil.copy(os.path.join(REPO_ROOT, "chip_smoke.py"), tmp_path)
    r = _run(["chip_smoke.py"], cwd=str(tmp_path), timeout=60)
    assert r.returncode != 0
    assert not _json_lines(r.stdout), r.stdout
    assert "no aphrodite_tpu package" in r.stderr


def test_chip_smoke_parent_stays_off_jax(tmp_path):
    """The parent builds the model dir and the requests without JAX or
    the engine package in the process: a parent that has touched JAX
    holds the chip its server child needs."""
    code = (
        "import sys, chip_smoke\n"
        f"chip_smoke.write_model_dir({str(tmp_path)!r})\n"
        "chip_smoke.build_requests('m')\n"
        "bad = [m for m in sys.modules if m == 'jax' or "
        "m.startswith(('jax.', 'jaxlib', 'aphrodite_tpu'))]\n"
        "assert not bad, bad\n")
    r = _run(["-c", code], timeout=120)
    assert r.returncode == 0, r.stderr
    config = json.load(open(tmp_path / "config.json"))
    assert config["architectures"] == ["MistralForCausalLM"]
    assert (config["hidden_size"], config["num_hidden_layers"],
            config["intermediate_size"]) == (4096, 32, 14336)
    assert config["source_url"].startswith("https://huggingface.co/")


@pytest.mark.parametrize("argv", [[], ["--tp", "8"]])
def test_bench_fails_without_a_chip(argv):
    r = _run(["bench.py"] + argv, timeout=120)
    assert r.returncode != 0
    assert not _json_lines(r.stdout), r.stdout
    assert "out_tok/s" not in r.stdout + r.stderr
    assert "no chip" in r.stderr and "'cpu'" in r.stderr


def test_tpu_smoke_fails_without_a_chip():
    r = _run([os.path.join("tests", "kernels", "tpu_smoke.py")],
             timeout=120)
    assert r.returncode != 0
    assert "SKIP" not in r.stdout
    assert "platform is 'cpu'" in r.stdout


def test_engine_for_a_tpu_refuses_the_cpu(tiny_model_dir):
    from aphrodite_tpu.common.config import DeviceConfig
    from aphrodite_tpu.engine.aphrodite_engine import AphroditeEngine
    from aphrodite_tpu.engine.args_tools import EngineArgs

    # conftest pins JAX to the CPU, which "auto" respects.
    assert DeviceConfig("auto").resolve() == "cpu"
    assert DeviceConfig("tpu").resolve() == "tpu"
    with pytest.raises(RuntimeError, match="needs a tpu backend"):
        AphroditeEngine.from_engine_args(EngineArgs(
            model=str(tiny_model_dir), load_format="dummy",
            device="tpu"))


# ---- the smoke's reading of a server log ----

_PALLAS_LOG = """
INFO [x] kernel path: quant_matmul = pallas (gptq gptq_matmul_a8)
INFO [x] kernel path: kv_write = pallas (prefill whole-page writer)
INFO [x] kernel path: kv_write = pallas (slot-window writer)
INFO [x] kernel path: prefill_attention = pallas (prefill_flash_attention, own keys)
INFO [x] kernel path: prefill_attention = pallas (prefill_flash_attention, gathered prefix)
INFO [x] kernel path: decode_attention = pallas (paged_decode_attention, fused KV write)
INFO [x] kernel path: decode_attention = pallas (paged_decode_attention, read-only)
"""

_MESH_LOG = """
INFO [x] SPMD mesh {'dp': 1, 'pp': 1, 'sp': 1, 'tp': 4} over 4 tpu devices
INFO [x] kernel path: kv_write = reference (XLA scatter: backend=tpu, tp=4, pages=bfloat16)
INFO [x] kernel path: prefill_attention = reference (jnp functions: backend=tpu, tp=4, K/V=bfloat16, alibi=False)
INFO [x] kernel path: decode_attention = reference (jnp gather path: backend=tpu, tp=4, pages=bfloat16)
INFO [x] Device memory after load: bytes_in_use=[500, 510, 505, 500]
INFO [x] Device memory at drain: bytes_in_use=[520, 530, 525, 520]
"""


def test_smoke_accepts_pallas_on_one_chip_and_rejects_a_reference():
    import chip_smoke
    chip_smoke.check_kernel_paths(_PALLAS_LOG, tp=1)
    leaked = _PALLAS_LOG + (
        "INFO [x] kernel path: kv_write = reference (XLA scatter: "
        "backend=tpu, tp=1, pages=int8)\n")
    with pytest.raises(chip_smoke.SmokeFailure, match="kv_write"):
        chip_smoke.check_kernel_paths(leaked, tp=1)
    no_spec = _PALLAS_LOG.replace("read-only", "fused KV write")
    with pytest.raises(chip_smoke.SmokeFailure, match="speculative"):
        chip_smoke.check_kernel_paths(no_spec, tp=1)
    with pytest.raises(chip_smoke.SmokeFailure, match="quant_matmul"):
        chip_smoke.check_kernel_paths(
            _PALLAS_LOG.replace("quant_matmul", "other"), tp=1)
    # a prompt step whose attention fell back to the jnp functions
    fell_back = _PALLAS_LOG.replace(
        "pallas (prefill_flash_attention, gathered prefix)",
        "reference (jnp functions: backend=tpu, tp=1, K/V=int8, "
        "alibi=False)")
    with pytest.raises(chip_smoke.SmokeFailure, match="prefill_attention"):
        chip_smoke.check_kernel_paths(fell_back, tp=1)


def test_smoke_reads_the_mesh_arm():
    import chip_smoke
    chip_smoke.check_kernel_paths(_MESH_LOG, tp=4)
    chip_smoke.check_mesh(_MESH_LOG, tp=4)
    skewed = _MESH_LOG.replace("[500, 510, 505, 500]",
                               "[900, 510, 505, 500]")
    with pytest.raises(chip_smoke.SmokeFailure, match="unevenly"):
        chip_smoke.check_mesh(skewed, tp=4)
    with pytest.raises(chip_smoke.SmokeFailure, match="mesh"):
        chip_smoke.check_mesh(_MESH_LOG.replace("'tp': 4", "'tp': 2"),
                              tp=4)


def test_smoke_compile_facts_count_step_programs_only():
    import chip_smoke
    log = (
        "W: Finished tracing + transforming _step_sample for pjit in "
        "2.5 sec\n"
        "W: Finished jaxpr to MLIR module conversion jit(_step_sample) "
        "in 1.5 sec\n"
        "W: Finished XLA compilation of jit(_step_sample) in 20.0 sec\n"
        "W: Finished XLA compilation of jit(_uniform) in 9.0 sec\n"
        "W: Finished tracing + transforming _threefry_split for pjit "
        "in 7.0 sec\n")
    assert chip_smoke.compile_facts(log) == (1, 24.0)
