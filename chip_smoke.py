#!/usr/bin/env python3
"""Chip smoke: serve Mistral-7B widths over HTTP on one TPU, once.

    python chip_smoke.py            # one chip: GPTQ int4 weights, W4A8
    python chip_smoke.py --tp 4     # four chips: bf16 weights, tp=4 mesh

The quickest proof that the system still starts on the chip. It writes
a `MistralForCausalLM` model directory at Mistral-7B-Instruct-v0.2's
published sizes (full depth, dummy weights from a seed, a tokenizer
built offline) to a temp directory, starts the real server
(`python -m aphrodite_tpu.endpoints.openai.api_server`) as a child,
sends it a handful of concurrent `/v1/completions` requests, checks
every reply, reads `/health`, and drains the server with SIGTERM.

It exits 0 and prints `{"ok": true, "device": {...}}` as its last line
only when every check passed on platform `tpu`. Without a chip the
server refuses to start and this script exits non-zero with no result.
The times it prints are wall-clock facts about this one run, not
metrics: nothing here is a rate.

This process never imports JAX (nor `aphrodite_tpu`): the server child
is the only process that may hold the chip.
"""
from __future__ import annotations

import argparse
import http.client
import json
import os
import random
import re
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
SEED = 0
MAX_TOKENS = 64
#: Wall-clock budget for the whole script (the driver allows 1200 s).
DEADLINE_S = 1150.0
#: --tp arm: the fullest device may hold at most this multiple of the
#: emptiest one's bytes_in_use.
BALANCE_TOLERANCE = 1.10

SOURCE_URL = ("https://huggingface.co/mistralai/Mistral-7B-Instruct-v0.2"
              "/blob/main/config.json")
#: Mistral-7B-Instruct-v0.2 as published: no width and no depth is cut.
MISTRAL_7B = {
    "architectures": ["MistralForCausalLM"],
    "model_type": "mistral",
    "vocab_size": 32000,
    "hidden_size": 4096,
    "intermediate_size": 14336,
    "num_hidden_layers": 32,
    "num_attention_heads": 32,
    "num_key_value_heads": 8,
    "hidden_act": "silu",
    "max_position_embeddings": 32768,
    "rms_norm_eps": 1e-05,
    "rope_theta": 1000000.0,
    "sliding_window": None,
    "tie_word_embeddings": False,
    "torch_dtype": "bfloat16",
    "bos_token_id": 1,
    "eos_token_id": 2,
    "source_url": SOURCE_URL,
}

_CORPUS = [
    "the quick brown fox jumps over the lazy dog",
    "continuous batching over a paged key value cache",
    "tensor parallel meshes shard attention heads",
    "0123456789 !?.,:;()[]{}",
] * 4

#: Kernel families whose dispatchers must have taken the compiled
#: Pallas side on one chip (`note_kernel_path` lines in the server log).
FAMILIES = ("decode_attention", "kv_write", "prefill_attention",
            "quant_matmul")


class SmokeFailure(Exception):
    pass


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def write_model_dir(path: str) -> None:
    """config.json at the published sizes plus an offline-built
    tokenizer (the server cannot answer HTTP without one; ids beyond
    its small vocabulary detokenize to the empty string)."""
    from tokenizers import (Tokenizer, decoders, models, pre_tokenizers,
                            trainers)
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
                   "model_max_length": MISTRAL_7B[
                       "max_position_embeddings"]}, f)
    with open(os.path.join(path, "config.json"), "w") as f:
        json.dump(MISTRAL_7B, f, indent=1)


def build_requests(model: str) -> list:
    """Six requests, all sent at once. Five prompts are 257 to 320 ids
    long, so their contexts stay in one block-table bucket for all 64
    output tokens (fewer programs to compile). The sixth covers 3,000
    distinct ids: the n-gram drafter proposes only when the token just
    generated already occurs in the request's history, and a model
    with random weights never repeats itself, so a repetitive prompt
    alone would leave the speculative verify round untested."""
    rng = random.Random(SEED)
    vocab = MISTRAL_7B["vocab_size"]

    def ids(n):
        return [rng.randrange(3, vocab) for _ in range(n)]

    greedy = dict(temperature=0.0)
    sampled = dict(temperature=0.8, top_p=0.9, seed=1234)
    seeded_prompt = ids(310)
    specs = [
        ("greedy", ids(300), False, greedy),
        ("greedy-repetitive", ids(12) * 24, False, greedy),
        ("greedy-diverse", rng.sample(range(3, vocab), 3000), False,
         greedy),
        ("greedy-stream", ids(270), True, greedy),
        ("seeded-stream-a", seeded_prompt, True, sampled),
        ("seeded-stream-b", seeded_prompt, True, sampled),
    ]
    return [dict(name=name, stream=stream, body=dict(
        model=model, prompt=prompt, max_tokens=MAX_TOKENS,
        ignore_eos=True, stream=stream, **sampling))
        for name, prompt, stream, sampling in specs]


def _post(port: int, body: dict, stream: bool, timeout: float):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    headers = {"Content-Type": "application/json"}
    if stream:
        # Journal records carry the token ids of every streamed chunk.
        headers["X-Aphrodite-Stream-Journal"] = "1"
    conn.request("POST", "/v1/completions", json.dumps(body), headers)
    return conn, conn.getresponse()


def run_request(port: int, req: dict, timeout: float) -> dict:
    """Send one request and check its reply; returns the facts the
    cross-request checks need (the streamed token ids)."""
    name = req["name"]
    conn, resp = _post(port, req["body"], req["stream"], timeout)
    try:
        if resp.status != 200:
            raise SmokeFailure(
                f"{name}: HTTP {resp.status}: {resp.read()[:500]!r}")
        if not req["stream"]:
            out = json.loads(resp.read())
            n = out["usage"]["completion_tokens"]
            reason = out["choices"][0]["finish_reason"]
            if n != MAX_TOKENS or reason != "length":
                raise SmokeFailure(
                    f"{name}: {n} tokens, finish_reason {reason!r}; "
                    f"wanted {MAX_TOKENS} and 'length'")
            return dict(name=name, tokens=n, ids=None)
        token_ids, finish, last_data = [], None, None
        for raw in resp:
            line = raw.decode().rstrip("\n")
            if line.startswith(": aphrodite-journal "):
                rec = json.loads(line[len(": aphrodite-journal "):])
                token_ids.extend(rec["t"])
                finish = rec.get("fin", finish)
            elif line.startswith("data: "):
                last_data = line[len("data: "):]
                if last_data != "[DONE]" and \
                        "error" in json.loads(last_data):
                    raise SmokeFailure(f"{name}: in-band {last_data}")
        if last_data != "[DONE]":
            raise SmokeFailure(
                f"{name}: stream ended with {last_data!r}, not [DONE]")
        vocab = MISTRAL_7B["vocab_size"]
        if len(token_ids) != MAX_TOKENS or finish != "length" or \
                not all(0 <= t < vocab for t in token_ids):
            raise SmokeFailure(
                f"{name}: journal holds {len(token_ids)} ids, finish "
                f"{finish!r}; wanted {MAX_TOKENS} in-vocabulary ids "
                "and 'length'")
        return dict(name=name, tokens=len(token_ids), ids=token_ids)
    finally:
        conn.close()


def run_requests(port: int, requests: list, timeout: float) -> list:
    """Send every request at once, one thread each, and check them."""
    results, errors = [None] * len(requests), []

    def work(i):
        try:
            results[i] = run_request(port, requests[i], timeout)
        except Exception as e:     # surfaced below, on the main thread
            errors.append(f"{requests[i]['name']}: "
                          f"{type(e).__name__}: {e}")

    threads = [threading.Thread(target=work, args=(i,), daemon=True)
               for i in range(len(requests))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout)
    if any(t.is_alive() for t in threads):
        errors.append(f"requests still unanswered after {timeout:.0f} s")
    if errors:
        raise SmokeFailure("; ".join(errors))
    a = next(r for r in results if r["name"] == "seeded-stream-a")
    b = next(r for r in results if r["name"] == "seeded-stream-b")
    if a["ids"] != b["ids"]:
        raise SmokeFailure(
            "the seeded request and its repeat returned different ids: "
            f"{a['ids'][:8]}... vs {b['ids'][:8]}...")
    return results


def get_json(port: int, path: str, timeout: float = 10.0):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read())
    finally:
        conn.close()


class Server:
    """The one chip-holding child: the OpenAI-compatible API server."""

    def __init__(self, model_dir: str, tp: int, log_path: str) -> None:
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            self.port = s.getsockname()[1]
        self.log_path = log_path
        self.args = ["--model", model_dir, "--load-format", "dummy",
                     "--device", "tpu", "--host", "127.0.0.1",
                     "--port", str(self.port)]
        # Cold every time: a compiled program from an earlier run must
        # never stand in for a kernel that no longer compiles. JAX
        # logs each compilation, which is where compile_seconds is
        # read from.
        self.env = {"APHRODITE_COMPILE_CACHE": "0",
                    "JAX_LOG_COMPILES": "1"}
        if tp > 1:
            self.args += ["--tensor-parallel-size", str(tp)]
        else:
            # The one-chip precision of record: int4 weights at rest
            # (GPTQ, g128) with int8 activations into the MXU.
            self.args += ["--quantization", "gptq"]
            self.env["APHRODITE_W4A8"] = "1"
        self.log = open(log_path, "w")
        self.proc = subprocess.Popen(
            [sys.executable, "-m",
             "aphrodite_tpu.endpoints.openai.api_server"] + self.args,
            cwd=HERE, env={**os.environ, **self.env}, stdout=self.log,
            stderr=subprocess.STDOUT, start_new_session=True)

    def read_log(self) -> str:
        self.log.flush()
        with open(self.log_path, errors="replace") as f:
            return f.read()

    def wait_ready(self, deadline: float) -> None:
        while time.monotonic() < deadline:
            if self.proc.poll() is not None:
                raise SmokeFailure(
                    f"server exited with code {self.proc.returncode} "
                    f"before it was ready: {self.last_error()}")
            try:
                if get_json(self.port, "/health?probe=1", 2.0)[0] == 200:
                    return
            except (OSError, http.client.HTTPException, ValueError):
                pass
            time.sleep(1.0)
        raise SmokeFailure("server not ready before the deadline")

    def last_error(self) -> str:
        """The log's last exception line (e.g. the executor's refusal
        to build without a chip), else its last line."""
        lines = [ln for ln in self.read_log().splitlines() if ln.strip()]
        errs = [ln for ln in lines if re.match(r"^\w*(Error|Exception)", ln)]
        return (errs or lines or ["(empty log)"])[-1]

    def drain(self, timeout: float) -> int:
        self.proc.send_signal(signal.SIGTERM)
        try:
            return self.proc.wait(timeout)
        except subprocess.TimeoutExpired:
            raise SmokeFailure(
                f"server still running {timeout:.0f} s after SIGTERM")

    def kill(self) -> None:
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
        raise SmokeFailure("the server log has no device line")
    return dict(platform=m.group(1), kind=m.group(2),
                count=int(m.group(3)))


def check_kernel_paths(log: str, tp: int) -> None:
    """Print the side each kernel dispatcher took. On one chip every
    family must have run its compiled Pallas kernel and none the jnp
    reference; on a mesh every Pallas gate selects the reference by
    design (ROADMAP S6/D5), and the report says so."""
    seen = {}
    for family, side, detail in re.findall(
            r"kernel path: (\S+) = (pallas|reference) \((.*)\)", log):
        seen.setdefault(family, []).append((side, detail))
    for family in sorted(seen):
        for side, detail in seen[family]:
            say(f"kernel path: {family} = {side} ({detail})")
    if tp > 1:
        say(f"tp={tp}: the Pallas kernels are gated off on a mesh by "
            "design; the lines above are the jnp/XLA reference paths")
        missing = [f for f in ("decode_attention", "kv_write")
                   if f not in seen]
        if missing:
            raise SmokeFailure(f"no kernel-path line for {missing}")
        return
    for family in FAMILIES:
        sides = {side for side, _ in seen.get(family, ())}
        if sides != {"pallas"}:
            raise SmokeFailure(
                f"kernel family {family} took {sorted(sides) or 'no'} "
                "path; one chip must run the compiled Pallas kernel only")
    spec = any("read-only" in d for _, d in seen["decode_attention"])
    say(f"speculative verify program traced: {spec}")
    if not spec:
        raise SmokeFailure(
            "no speculative verify round ran (no read-only decode "
            "attention program was traced)")


def compile_facts(log: str):
    """(step programs compiled, seconds JAX spent tracing, lowering
    and compiling them), from the JAX_LOG_COMPILES lines."""
    name = r"_(?:step|step_sample|burst_scan)"
    seconds = re.findall(
        rf"Finished (?:tracing \+ transforming {name} for pjit|jaxpr to "
        rf"MLIR module conversion jit\({name}\)|XLA compilation of "
        rf"jit\({name}\)) in ([\d.]+) sec", log)
    programs = re.findall(
        rf"Finished XLA compilation of jit\({name}\) ", log)
    return len(programs), sum(float(x) for x in seconds)


def check_mesh(log: str, tp: int) -> None:
    used = re.findall(r"Device memory (after load|at drain): "
                      r"bytes_in_use=\[([\d, ]+)\]", log)
    for when, values in used:
        per_dev = [int(v) for v in values.split(",")]
        say(f"device bytes_in_use {when}: {per_dev}")
        if len(per_dev) != tp:
            raise SmokeFailure(f"{len(per_dev)} devices hold the "
                               f"engine, wanted {tp}")
        if max(per_dev) > BALANCE_TOLERANCE * min(per_dev):
            raise SmokeFailure(
                f"devices are unevenly loaded {when}: max/min "
                f"bytes_in_use exceeds {BALANCE_TOLERANCE}")
    if {when for when, _ in used} != {"after load", "at drain"}:
        raise SmokeFailure("the server log lacks a device-memory line")
    if tp > 1:
        m = re.search(r"SPMD mesh (\{[^}]*\})", log)
        want = {"dp": 1, "pp": 1, "sp": 1, "tp": tp}
        if m is None or json.loads(m.group(1).replace("'", '"')) != want:
            raise SmokeFailure(f"no SPMD mesh line for {want}")
        say(f"mesh: {m.group(1)}")


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--tp", type=int, default=1,
                        help="tensor-parallel degree (default 1: one "
                             "chip, GPTQ W4A8; above 1: bf16 weights "
                             "on a (1,1,1,tp) mesh)")
    parser.add_argument("--keep-log", default=None, metavar="PATH",
                        help="copy the server's log to PATH at the end")
    args = parser.parse_args()
    if not os.path.isdir(os.path.join(HERE, "aphrodite_tpu")):
        print("chip_smoke: FAILED: no aphrodite_tpu package beside "
              f"{__file__}; run it from a checkout", file=sys.stderr)
        return 1

    t_start = time.monotonic()
    deadline = t_start + DEADLINE_S
    tmp = tempfile.mkdtemp(prefix="chip-smoke-")
    server = None
    try:
        model_dir = os.path.join(tmp, "mistral-7b-dummy")
        os.makedirs(model_dir)
        write_model_dir(model_dir)
        t_setup = time.monotonic()
        server = Server(model_dir, args.tp, os.path.join(tmp, "server.log"))
        say("model: MistralForCausalLM at Mistral-7B-Instruct-v0.2 "
            f"sizes, {MISTRAL_7B['num_hidden_layers']} layers, dummy "
            f"weights (seed {SEED}); source {SOURCE_URL}")
        say("server flags set: " + " ".join(server.args[2:]))
        say("server environment set: " + " ".join(
            f"{k}={v}" for k, v in sorted(server.env.items())))
        say("every other engine flag is at its default (multi_step=1: "
            "speculative verify rounds and single decode steps, no "
            "burst scan)")

        server.wait_ready(deadline)
        device = parse_device(server.read_log())
        say(f"serving process reports platform={device['platform']} "
            f"device_kind={device['kind']!r} "
            f"device_count={device['count']}")
        if device["platform"] != "tpu":
            raise SmokeFailure(
                f"platform is {device['platform']!r}, not 'tpu': no chip")
        t_ready = time.monotonic()

        t0 = time.monotonic()
        results = run_requests(server.port, build_requests(model_dir),
                               deadline - time.monotonic())
        t_serve = time.monotonic() - t0
        for r in results:
            say(f"{r['name']}: HTTP 200, {r['tokens']} tokens")
        say("the seeded request and its repeat returned identical ids")

        status, health = get_json(server.port, "/health")
        counters = {k: health.get(k) for k in (
            "retries_total", "recovered_steps", "reincarnations_total",
            "requests_lost", "sheds_total")}
        say(f"/health: HTTP {status}, state {health.get('state')}, "
            f"{counters}")
        if status != 200 or health.get("state") != "RUNNING" or \
                any(v != 0 for v in counters.values()):
            raise SmokeFailure(
                "the supervisor absorbed a fault, or the engine is not "
                f"RUNNING: {health}")

        t0 = time.monotonic()
        code = server.drain(min(180.0, deadline - time.monotonic()))
        t_drain = time.monotonic() - t0
        log = server.read_log()
        clean = "Drain complete; exiting." in log
        say(f"SIGTERM: clean-drain line {'found' if clean else 'MISSING'}"
            f", exit code {code}")
        if code != 0 or not clean:
            raise SmokeFailure("the server did not drain cleanly")

        check_kernel_paths(log, args.tp)
        check_mesh(log, args.tp)

        n_programs, t_compile = compile_facts(log)
        say(f"seconds: set-up {t_setup - t_start:.1f} (model dir), "
            f"start-up {t_ready - t_setup:.1f} (weights, KV pool), "
            f"serve {t_serve:.1f} (all six requests, compiles "
            f"included), compile {t_compile:.1f} ({n_programs} step "
            f"programs traced, lowered and compiled), drain "
            f"{t_drain:.1f}, total {time.monotonic() - t_start:.1f}")
        print(json.dumps({"ok": True, "device": device}), flush=True)
        return 0
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        if server is not None:
            tail = server.read_log().splitlines()[-60:]
            print("---- server log tail ----\n" + "\n".join(tail),
                  file=sys.stderr, flush=True)
        return 1
    finally:
        if server is not None:
            server.kill()
            if args.keep_log:
                os.makedirs(os.path.dirname(os.path.abspath(
                    args.keep_log)), exist_ok=True)
                shutil.copyfile(server.log_path, args.keep_log)
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
