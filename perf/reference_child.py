"""The one process that runs a configuration's plain reference. It is
started by `perf/reference.py` while the server drains, imports what
it needs and prepares its inputs, and then waits for a line on its
standard input: the harness writes it once the server has exited, and
only then does this process touch the chip (end of input in its place
means that the run was given up). The harness's own process stays off
JAX.

    python perf/reference_child.py <job.json> <out.json>

The job names the reference (`perf/references/<name>.py`), the
configuration, the seed and the sequences (`prompt` and `reply` ids).
Every sequence goes through each stage in turn, padded to one shape;
a stage's weights are made from the seed on the device inside the
stage's one jitted program (`perf/weights.py`) and are gone when it
returns, so no two layers' float32 weights are in memory together.
Everything is float32 under `jax.default_matmul_precision("highest")`.
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def padded(n: int) -> int:
    """One length for all sequences, from few values: the next power
    of two up to 512, and the next multiple of 512 above."""
    return max(16, 1 << (n - 1).bit_length()) if n <= 512 \
        else -(-n // 512) * 512


def position_facts(logits, chosen):
    """For `logits` `[n, vocab]` and the `n` tokens chosen: the logit of
    each chosen token, the largest logit and the standard deviation of
    the position's logits."""
    import jax.numpy as jnp
    picked = jnp.take_along_axis(logits, chosen[:, None], axis=-1)[:, 0]
    return picked, logits.max(axis=-1), logits.std(axis=-1)


def lowered(spec: dict) -> dict:
    """What a control lowers, from its entry in the configuration's
    `perf.controls`: `kv` names the float type that holds keys and
    values in the place of the configuration's own, `act_bits` the
    width of the integers that go into a quantised matmul (symmetric,
    one scale a row)."""
    import jax.numpy as jnp
    out = {}
    if "kv" in spec:
        out["kv"] = lambda x: x.astype(jnp.dtype(spec["kv"])).astype(
            jnp.float32)
    if "act_bits" in spec:
        top = 2 ** (int(spec["act_bits"]) - 1) - 1

        def act(x):
            scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / top
            return jnp.round(x / jnp.where(scale > 0, scale, 1.0)) * scale
        out["act"] = act
    return out


def main(path_in: str, path_out: str) -> int:
    with open(path_in) as f:
        job = json.load(f)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from perf import cells, weights
    config = job["config"]
    path = os.path.join(job["root"], "perf", "references",
                        job["name"] + ".py")
    ref = cells.load_module(path)
    tree, stages = ref.tree(config), ref.stages(config)
    sides = {"served": ref.Precision(),
             **{name: ref.Precision(**lowered(config["perf"]["controls"]
                                              [name]))
                for name in job["controls"]}}

    # One shape for every run of a cell, so that every program is in
    # the persistent cache after the first: blocks of `rows` sequences
    # (the most a run of the cell keeps; a row that is not there
    # repeats the block's first) by one padded length. A builder who
    # keeps more sequences than `rows` gets more blocks, one after the
    # other through the same programs.
    seqs = [s["prompt"] + s["reply"] for s in job["sequences"]]
    length, per = padded(max(map(len, seqs))), int(job["rows"])
    blocks = []
    for at in range(0, len(seqs), per):
        ids = np.zeros((per, length), np.int32)
        for i in range(per):
            s = seqs[at + i] if at + i < len(seqs) else seqs[at]
            ids[i, :len(s)] = s
        blocks.append(ids)
    # position t predicts token t + 1; reply token j of a prompt of p
    # ids is predicted at p - 1 + j
    where = [(i, len(s["prompt"]) - 1 + j)
             for i, s in enumerate(job["sequences"])
             for j in range(len(s["reply"]))]
    rows, cols = (np.asarray(x) for x in zip(*where))

    # Nothing above has touched a device. The chip is the server's
    # until the harness says that it has exited.
    if not sys.stdin.readline():
        print("reference child: given up before the chip was free",
              file=sys.stderr)
        return 1
    t_released = time.monotonic()
    jax.config.update("jax_compilation_cache_dir", job["cache"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    platform = jax.devices()[0].platform
    if platform != ("cpu" if job["cpu"] else "tpu"):
        print(f"reference child: on {platform!r}, not the platform the "
              "run was on", file=sys.stderr)
        return 1
    t_ready = time.monotonic()

    keys = np.asarray(weights.all_keys(tree, job["seed"]))
    programs = {}

    def stage(fn_name, buckets, side, x, then=None, *more):
        """One jitted program for each (function, shapes of its
        weights, side): the 32 layers share one. `then(x, y, *more)`
        is applied to the stage's input and result inside it."""
        specs = {local: tree[b] for local, b in buckets.items()}
        sig = (fn_name, side, then and then.__name__,
               json.dumps(specs, sort_keys=True))
        if sig not in programs:
            fn = getattr(ref, fn_name)

            def run(sub, x, *more):
                y = fn(config, weights.make(specs, sub), x, sides[side])
                return y if then is None else then(x, y, *more)
            programs[sig] = jax.jit(run)
        return programs[sig](weights.subkeys(tree, keys, buckets), x,
                             *more)

    def with_share(x, y):
        """A stage that maps the residual stream to itself also says
        how much it added: |y - x| / |x| over every position."""
        same = x.shape == y.shape and x.dtype == y.dtype
        return y, (jnp.linalg.norm(y - x) / jnp.linalg.norm(x)
                   if same else jnp.float32(jnp.nan))

    def facts_of(x, logits, tokens):
        flat = position_facts(logits.reshape(-1, logits.shape[-1]),
                              tokens.reshape(-1))
        return logits.argmax(axis=-1), tuple(
            a.reshape(tokens.shape) for a in flat)

    def one_block(ids):
        """Every side's facts `(chosen, best, std)`, each `[rows,
        length]`, of one block of sequences."""
        following = jnp.asarray(np.roll(ids, -1, axis=1))
        hidden, facts = {}, {}
        for side in sides:
            x = jnp.asarray(ids)
            for fn_name, buckets in stages[:-1]:
                t = time.monotonic()
                x, share = jax.block_until_ready(
                    stage(fn_name, buckets, side, x, with_share))
                if side == "served":
                    shares.append(share)
                    stage_s.append(time.monotonic() - t)
            hidden[side] = x
        _, facts["served"] = stage(*stages[-1], "served", hidden["served"],
                                   facts_of, following)
        for side in job["controls"]:
            # on the same prompts and tokens, the tokens that the lower
            # precision puts first, as the reference sees them
            first, _ = stage(*stages[-1], side, hidden[side], facts_of,
                             following)
            _, facts[side] = stage(*stages[-1], "served", hidden["served"],
                                   facts_of, first)
        return jax.device_get(facts)

    shares, stage_s = [], []
    with jax.default_matmul_precision("highest"):
        each = [one_block(ids) for ids in blocks]
    facts = {side: tuple(np.concatenate([np.asarray(b[side][k])
                                         for b in each])
                         for k in range(3)) for side in sides}
    stage_s = stage_s[:len(stages) - 1]      # the first block's
    shares = [s for s in map(float, shares) if s == s]
    t_done = time.monotonic()
    result = dict(
        platform=platform, positions=where, padded_length=length,
        layer_share=sum(shares) / len(shares) if shares else None,
        # from the harness's word that the chip is free to a device
        start_s=t_ready - t_released, compute_s=t_done - t_ready,
        # seconds of each stage before the last: a stage's first call
        # loads or compiles its program
        stage_s=stage_s,
        **{side: dict(zip(("chosen", "best", "std"),
                          (np.asarray(a)[rows, cols].tolist()
                           for a in got)))
           for side, got in facts.items()})
    with open(path_out, "w") as f:
        json.dump(result, f)
    return 0


if __name__ == "__main__":
    code = main(sys.argv[1], sys.argv[2])
    sys.stdout.flush()
    sys.stderr.flush()
    # the result is written: leave without the seconds the device
    # runtime takes to shut down
    os._exit(code)
