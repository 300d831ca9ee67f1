"""One step program of a benchmark cell's model, alone: the REAL
`ModelRunner._step` (the model, no sampler) at the cell's geometry,
built from `perf/configs/<config>.json` with zero weights.

    python benchmarks/step_ab.py jamba2-3b-bf16 [--prompts N] [--aot]
    python benchmarks/step_ab.py mistral-7b-w4a8 --prompts 1 \
        --prompt-len 1024
    python benchmarks/step_ab.py laguna-s-2.1-bf16 --prompts 1 \
        --prompt-len 2048

On the chip (2 minutes): milliseconds a step on the host's clock and
on the device's, and every device operation by seconds, calls and
microseconds a call, from a trace of ten steps. A decode step of the
cell's rows by default, a prompt step of `N` x 512 tokens (or
`--prompt-len`) with `--prompts N`. With `--aot`, on the CPU and without a chip (10 s): the
same program compiled for a described v5e and a census of what the
compiler put around the kernels (async copies and slices by shape:
whole operands staged, re-layouts), the optimised HLO to `--hlo PATH`.

It times the tree it runs in: to compare with another commit, run it
from the root of a `git archive` of that commit (`python
<here>/benchmarks/step_ab.py ...`; it reads what the tree offers, a
`(tail, state)` pair a layer or one for the model). PR 42 found with
it that 0.15 s of whole-array copies in a traced 2 s were worth 0.2%
of a decode step, and a `[rows, 1, channels]` layout 7% (`PERF.md`
section 6).
"""
import argparse
import collections
import json
import os
import re
import sys
import time

sys.path.insert(0, os.getcwd())

#: (rows of a decode step, pages of the pool, context of a row, window
#: pages a window group holds) by configuration
CELLS = {
    # (GPTQ int4 with int8 activations: `perf.env` and
    # `perf.reference_quant` say so, and `_model` follows them)
    "mistral-7b-w4a8": (48, 5077, 1216, 0),
    "jamba2-3b-bf16": (128, 60000, 1000, 0),
    "phi-4-mini-flash-bf16": (48, 40000, 2500, 33),
    "laguna-s-2.1-bf16": (64, 60000, 4400, 33),
    # (a pooled group: two windows behind as 16 summary pages, and the
    # pages of the third up to the context)
    "evabyte-6.5b-bf16": (24, 4780, 6000, 0),
    # (latent pages: ONE array a layer of 640-lane rows)
    "sarvam-105b-bf16": (64, 80000, 8900, 0),
    # (state slots AND latent pages: two MLA layers' arrays beside the
    # six KDA layers' one (tail, state) pair)
    "kimi-linear-48b-a3b-bf16": (192, 100000, 1536, 0),
}
SLOTS = 192


def build(name: str, prompts: int, abstract, prompt_len: int = 512):
    """(the jitted step, its arguments) for a decode step, or a prompt
    step of `prompts` x `prompt_len` tokens; `abstract` maps an array
    to what the program is lowered with (itself on the chip)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from aphrodite_tpu.common.config import ModelConfig, SchedulerConfig
    from aphrodite_tpu.common.sampling_params import SamplingParams
    from aphrodite_tpu.common.sequence import (SequenceData,
                                               SequenceGroupMetadata)
    from aphrodite_tpu.executor.model_runner import ModelRunner
    with open(os.path.join("perf", "configs", name + ".json")) as f:
        config = json.load(f)
    rows, pages, ctx, window_pages = CELLS[name]
    max_len = 8192 if ctx <= 8192 else 9216
    model_config = ModelConfig("x", dtype="bfloat16", max_model_len=max_len,
                               hf_config=_hf(config))
    model = _model(config, model_config)
    shapes = jax.eval_shape(model.init_params)
    params = jax.tree_util.tree_map(abstract, shapes) \
        if abstract is not None else _routed(jax.jit(model.init_params)())
    make = (lambda s, d: abstract(jax.ShapeDtypeStruct(s, d))) \
        if abstract is not None else jnp.zeros
    groups = model_config.get_page_groups()
    runner = ModelRunner(model, params, model_config,
                         SchedulerConfig(None, SLOTS, max_len, 256), 16,
                         pages * 16, num_state_slots=SLOTS)
    spec = model_config.get_state_spec()
    rng = np.random.default_rng(0)

    def table(count):
        return [int(p) for p in rng.integers(0, pages, count)]

    # (K and V each an array of its own: the step donates them; a
    # latent page's one array, its rows padded to the lane tile)
    from aphrodite_tpu.ops.kv_cache import padded_head_size
    kv = [tuple(make((pages, 16, h * padded_head_size(
        model_config.get_head_size())), jnp.bfloat16)
        for _ in range(getattr(groups, "arrays_per_page", 2)))
        for h in model_config.get_kv_heads_per_slot()]
    if spec is None:        # (pages alone)
        pass
    elif hasattr(spec, "allocated"):
        kv.append(tuple(make((spec.layers, SLOTS + 1) + s, jnp.dtype(d))
                        for s, d in spec.allocated))
    else:       # (a tree from before PR 42: a pair a layer)
        kv += [tuple(make((SLOTS + 1,) + s, jnp.dtype(d))
                     for s, d in spec.arrays) for _ in range(spec.layers)]
    step = jax.jit(runner._step, static_argnames=("is_prompt", "use_prefix"),
                   donate_argnums=(3,))
    lowered = (lambda t: jax.tree_util.tree_map(abstract, t)) \
        if abstract is not None else (lambda t: t)
    if prompts:
        groups_n = len(groups.kinds)
        mds = [SequenceGroupMetadata(
            str(i), True,
            {i: SequenceData([5 + j % 50 for j in range(prompt_len)])},
            SamplingParams(temperature=0.0, max_tokens=4), {}, {},
            group_tables={i: [(0, table(prompt_len // 16))] * groups_n},
            state_slots={i: i}) for i in range(prompts)]
        inputs, _ = runner._prepare_prompt(mds)
        args = (lowered(inputs["input_ids"]), lowered(inputs["positions"]),
                kv, lowered(inputs["metadata"]), lowered(inputs["sel"]))
        return step, params, args, dict(is_prompt=True, use_prefix=False)
    pages_a_row = -(-ctx // 16)
    def pooled_row():
        window = groups.pooled_window
        behind = ctx // window
        let_go = behind * (window - window // 16)
        return let_go, table(pages_a_row - let_go // 16)

    group_rows = [[
        (ctx - window_pages * 16, table(window_pages))
        if kind == "window" and pages_a_row > window_pages
        else pooled_row() if kind == "pooled"
        else (0, table(pages_a_row)) for kind in groups.kinds]
        for _ in range(rows)]
    batch = runner._send_decode_batch(
        [5] * rows, [ctx - 1] * rows, [0] * rows, [ctx] * rows, None,
        group_rows=group_rows,
        state_slots=[int(s) for s in rng.permutation(SLOTS)[:rows]])
    args = (None, None, kv, lowered(batch["metadata"]), None)
    return step, params, args, dict(is_prompt=False, use_prefix=False)


def _routed(params):
    """Zero weights send every token to the first experts, and a step
    then reads ten experts a layer where the cell's reads nine in ten
    of them: a layer's own router (`gate` beside the experts) is drawn
    at random, so that the pairs spread as random weights spread them."""
    import jax
    for i, (bucket, leaves) in enumerate(sorted(params.items())):
        if "gate" in leaves and "w_gate" in leaves:
            leaves["gate"] = jax.random.normal(
                jax.random.PRNGKey(i), leaves["gate"].shape,
                leaves["gate"].dtype)
    return params


def _hf(config):
    from aphrodite_tpu.transformers_utils import configs
    if config["model_type"] == "mistral":
        from transformers import MistralConfig
        return MistralConfig(**{k: v for k, v in config.items()
                                if k != "perf"})
    cls = {"jamba": configs.JambaConfig,
           "phi4flash": configs.Phi4FlashConfig,
           "laguna": getattr(configs, "LagunaConfig", None),
           "evabyte": getattr(configs, "EvaByteConfig", None),
           "sarvam_mla": getattr(configs, "SarvamMLAConfig", None),
           "kimi_linear": getattr(configs, "KimiLinearConfig", None),
           }[config["model_type"]]
    return cls(**{k: v for k, v in config.items() if k not in (
        "perf", "architectures", "model_type", "torch_dtype")})


def _model(config, model_config):
    import jax.numpy as jnp
    if config["model_type"] == "mistral":
        from aphrodite_tpu.modeling.layers.quantization.gptq import (
            GPTQConfig, GPTQLinearMethod)
        from aphrodite_tpu.modeling.models.llama import LlamaForCausalLM
        os.environ.update(config["perf"]["env"])    # APHRODITE_W4A8
        quant = config["perf"]["reference_quant"]
        return LlamaForCausalLM(
            model_config.hf_config, jnp.bfloat16,
            linear_method=GPTQLinearMethod(GPTQConfig(
                quant["bits"], quant["group_size"])))
    if config["model_type"] == "jamba":
        from aphrodite_tpu.modeling.models.jamba import \
            JambaForCausalLM as cls
    elif config["model_type"] == "laguna":
        from aphrodite_tpu.modeling.models.laguna import LagunaForCausalLM
        return LagunaForCausalLM(model_config.hf_config, jnp.bfloat16,
                                 max_model_len=model_config.max_model_len)
    elif config["model_type"] == "sarvam_mla":
        from aphrodite_tpu.modeling.models.sarvam_mla import (
            SarvamMLAForCausalLM)
        return SarvamMLAForCausalLM(
            model_config.hf_config, jnp.bfloat16,
            max_model_len=model_config.max_model_len)
    elif config["model_type"] == "evabyte":
        from aphrodite_tpu.modeling.models.evabyte import \
            EvaByteForCausalLM as cls
    elif config["model_type"] == "kimi_linear":
        from aphrodite_tpu.modeling.models.kimi_linear import \
            KimiLinearForCausalLM as cls
    else:
        from aphrodite_tpu.modeling.models.phi4flash import \
            Phi4FlashForCausalLM as cls
    return cls(model_config.hf_config, jnp.bfloat16)


def census(hlo: str) -> None:
    """Async copies, slices and plain copies of the optimised HLO, by
    the shape they move."""
    found = collections.Counter()
    for line in hlo.splitlines():
        match = re.match(
            r"^\s*(?:ROOT )?%?[\w.\-]+ = (\S+?)\{[^ ]* "
            r"(copy-done|copy|slice-done)\(", line)
        if match:
            found[(match.group(2), match.group(1))] += 1
    for (op, shape), count in sorted(found.items(), key=lambda kv: -kv[1]):
        print(f"{count:6d} {op:11s} {shape}")


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("config", choices=sorted(CELLS))
    parser.add_argument("--prompts", type=int, default=0)
    parser.add_argument("--prompt-len", type=int, default=512)
    parser.add_argument("--steps", type=int, default=40)
    parser.add_argument("--aot", action="store_true")
    parser.add_argument("--hlo", default=None)
    args = parser.parse_args()
    if args.aot:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    abstract = None
    if args.aot:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        chip = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])
        # (the dispatchers ask the backend, which is the CPU here)
        jax.default_backend = lambda: "tpu"

        def abstract(a):
            return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip)
    elif jax.default_backend() != "tpu":
        sys.exit("step_ab.py times a chip: no TPU here (try --aot)")
    step, params, (ids, pos, kv, meta, sel), static = build(
        args.config, args.prompts, abstract, args.prompt_len)
    what = f"{args.config}, " + (
        f"a prompt step of {args.prompts} x {args.prompt_len}"
        if args.prompts
        else f"a decode step of {CELLS[args.config][0]} rows")
    if args.aot:
        t0 = time.time()
        compiled = step.lower(params, ids, pos, kv, meta, sel,
                              **static).compile()
        hlo = compiled.as_text()
        print(f"{what}: compiled for a described v5e in "
              f"{time.time() - t0:.1f} s")
        print(f"  {compiled.memory_analysis()}")
        if args.hlo:
            with open(args.hlo, "w") as f:
                f.write(hlo)
        return census(hlo)

    def run(n, kv):
        out = None
        for _ in range(n):
            out, kv = step(params, ids, pos, kv, meta, sel, **static)
        jax.block_until_ready(out)
        return kv

    t0 = time.time()
    kv = run(2, kv)
    print(f"{what}: compiled and warmed in {time.time() - t0:.1f} s",
          flush=True)
    for _ in range(3):
        t0 = time.time()
        kv = run(args.steps, kv)
        print(f"  {(time.time() - t0) / args.steps * 1e3:.3f} ms a step "
              f"(host clock, {args.steps} steps)", flush=True)
    from perf import trace
    trace_dir = f"/tmp/step_ab_{os.getpid()}"
    jax.profiler.start_trace(trace_dir)
    run(10, kv)
    jax.profiler.stop_trace()
    reduced = trace.reduce(trace.load(trace.find_xplane(trace_dir)))
    print(f"  {reduced['busy_s'] * 100:.3f} ms a step busy on the device "
          f"(a trace of 10 steps)")
    for name, (seconds, calls) in sorted(
            reduced["ops"].items(), key=lambda kv: -kv[1][0])[:24]:
        print(f"  {seconds * 100:8.3f} ms a step {calls / 10:6.1f} calls "
              f"{seconds / calls * 1e6:8.1f} us  {name}")


if __name__ == "__main__":
    main()
