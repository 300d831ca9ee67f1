"""Start the server child, and read what its log says.

Copied from `chip_smoke.py` (the `Server` class, the model directory,
`parse_device`, `check_kernel_paths`, `compile_facts`), which stays the
gate; this copy is the benchmark's and later PRs cannot change it.
This module never imports JAX: the child is the only process that may
hold the chip.
"""
from __future__ import annotations

import json
import os
import re
import signal
import socket
import subprocess
import sys
from typing import Dict, List, Optional, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))

_CORPUS = [
    "the quick brown fox jumps over the lazy dog",
    "continuous batching over a paged key value cache",
    "tensor parallel meshes shard attention heads",
    "0123456789 !?.,:;()[]{}",
] * 4


class RunFailure(Exception):
    """The run cannot produce a result; the process exits non-zero."""


def write_model_dir(path: str, hf_config: dict) -> None:
    """config.json at the configuration's sizes plus an offline-built
    tokenizer (the server cannot answer HTTP without one; ids beyond
    its small vocabulary detokenize to the empty string)."""
    from tokenizers import (Tokenizer, decoders, models, pre_tokenizers,
                            trainers)
    os.makedirs(path, exist_ok=True)
    tok = Tokenizer(models.BPE(unk_token=None))
    tok.pre_tokenizer = pre_tokenizers.ByteLevel(add_prefix_space=True)
    tok.decoder = decoders.ByteLevel()
    tok.train_from_iterator(_CORPUS, trainers.BpeTrainer(
        vocab_size=512, special_tokens=["<unk>", "<s>", "</s>"],
        initial_alphabet=pre_tokenizers.ByteLevel.alphabet()))
    tok.save(os.path.join(path, "tokenizer.json"))
    with open(os.path.join(path, "tokenizer_config.json"), "w") as f:
        json.dump({"tokenizer_class": "PreTrainedTokenizerFast",
                   "bos_token": "<s>", "eos_token": "</s>",
                   "unk_token": "<unk>",
                   "model_max_length":
                       hf_config["max_position_embeddings"]}, f)
    with open(os.path.join(path, "config.json"), "w") as f:
        json.dump(hf_config, f, indent=1)


class Server:
    """The OpenAI-compatible API server, as the one child process."""

    def __init__(self, root: str, model_dir: str, config_path: str,
                 engine_args: List[str],
                 env: Dict[str, str], device: str, seed: int,
                 cache_dir: str, log_path: str) -> None:
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            self.port = s.getsockname()[1]
        self.log_path = log_path
        self.args = ["--model", model_dir, "--load-format", "dummy",
                     "--device", device, "--host", "127.0.0.1",
                     "--port", str(self.port), "--seed", str(seed),
                     *engine_args]
        # The benchmark's own cache directory may not evict: a step
        # program of a 7B model is a 25 MB entry, and under a cap below
        # one cell's programs (a machine may bring one in its
        # environment: 192 MiB was seen) every run misses on every one
        # of them, since each run meets them in one order.
        self.env = {**env, "APHRODITE_COMPILE_CACHE": cache_dir,
                    "JAX_COMPILATION_CACHE_MAX_SIZE": "-1",
                    "JAX_LOG_COMPILES": "1", "PYTHONPATH": root}
        self.log = open(log_path, "w")
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "serve_child.py"),
             config_path] + self.args, cwd=root, env={**os.environ, **self.env},
            stdout=self.log, stderr=subprocess.STDOUT,
            start_new_session=True)

    @property
    def url(self) -> str:
        return f"http://127.0.0.1:{self.port}"

    def log_size(self) -> int:
        return os.path.getsize(self.log_path)

    def read_log(self, start: int = 0, end: Optional[int] = None) -> str:
        with open(self.log_path, "rb") as f:
            f.seek(start)
            data = f.read() if end is None else f.read(end - start)
        return data.decode(errors="replace")

    def exit_code(self) -> Optional[int]:
        return self.proc.poll()

    def last_error(self) -> str:
        """The log's last exception line (e.g. the executor's refusal
        to build without a chip), else its last line."""
        lines = [ln for ln in self.read_log().splitlines() if ln.strip()]
        errs = [ln for ln in lines if re.match(r"^\w*(Error|Exception)", ln)]
        return (errs or lines or ["(empty log)"])[-1]

    def drain(self, timeout: float) -> int:
        """SIGTERM, then wait for the clean exit."""
        self.proc.send_signal(signal.SIGTERM)
        try:
            return self.proc.wait(timeout)
        except subprocess.TimeoutExpired:
            raise RunFailure(
                f"server still running {timeout:.0f} s after SIGTERM")

    def kill(self) -> None:
        """End the child and its whole process group, and wait."""
        if self.proc.poll() is None:
            try:
                os.killpg(self.proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            self.proc.wait()
        self.log.close()


def parse_device(log: str) -> dict:
    m = re.search(r"Initializing engine on platform=(\S+) "
                  r"device_kind='([^']*)' device_count=(\d+)", log)
    if m is None:
        raise RunFailure("the server log has no device line")
    return dict(platform=m.group(1), kind=m.group(2),
                count=int(m.group(3)))


def parse_memory_peak(log: str) -> int:
    """Peak bytes on the fullest chip, from the child's last line."""
    m = re.search(r"perf: device memory peak_bytes_in_use=\[([\d, ]+)\]",
                  log)
    if m is None:
        raise RunFailure("the server log has no memory-peak line")
    return max(int(v) for v in m.group(1).split(","))


def parse_kv_pool(log: str) -> Optional[Tuple[int, float]]:
    """(device pages, GiB) of the KV pool, from the start-up log."""
    m = re.search(r"KV cache: (\d+) device pages, \d+ host pages "
                  r"\(([\d.]+) GiB device\)", log)
    return (int(m.group(1)), float(m.group(2))) if m else None


def check_kernel_paths(log: str, families: List[str]) -> List[str]:
    """Every family the configuration lists must have run its compiled
    Pallas kernel and never the jnp reference; returns the faults."""
    seen: Dict[str, set] = {}
    for family, side in re.findall(
            r"kernel path: (\S+) = (pallas|reference) \(", log):
        seen.setdefault(family, set()).add(side)
    faults = []
    for family in families:
        sides = seen.get(family, set())
        if sides != {"pallas"}:
            faults.append(f"kernel family {family} took "
                          f"{sorted(sides) or 'no'} path, not pallas only")
    return faults


_STEP = r"_(?:step|step_sample|burst_scan)"
_COMPILED = re.compile(rf"Finished XLA compilation of jit\({_STEP}\) in "
                       r"([\d.]+) sec")
_TRACED = re.compile(rf"Finished tracing \+ transforming {_STEP} for pjit "
                     r"in ([\d.]+) sec")
_CACHE_HIT = re.compile(r"Persistent compilation cache hit for "
                        rf"'jit_{_STEP}'")
_LOWERED = re.compile(rf"Finished jaxpr to MLIR module conversion "
                      rf"jit\({_STEP}\) in ([\d.]+) sec")


def compile_facts(log: str) -> dict:
    """Step programs in `log` (JAX_LOG_COMPILES lines): how many were
    traced (every program the process meets, cached or not), how many
    the compiler finished (a hit in the persistent cache is logged
    this way too: a 25 MB entry loads in 2-3 s), how many of those
    were such hits, and the seconds of each stage."""
    traced = [float(x) for x in _TRACED.findall(log)]
    lowered = [float(x) for x in _LOWERED.findall(log)]
    compiled = [float(x) for x in _COMPILED.findall(log)]
    return dict(programs=len(traced), trace_s=sum(traced),
                lower_s=sum(lowered), compiled=len(compiled),
                compile_s=sum(compiled),
                cache_hits=len(_CACHE_HIT.findall(log)))
