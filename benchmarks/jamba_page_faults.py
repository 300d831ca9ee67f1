"""What the benchmark's `correct` can see of Jamba's two page-holding
layers (PERF.md §2, PR 41): on the CPU, in the reference's own float32
arithmetic at the published widths (`perf/references/jamba.py`,
`perf/configs/jamba2-3b-bf16.json`, weights from the seed as the
benchmark draws them), faults planted in K and V of both attention
layers, one row a fault, read as `perf/run.py --control` reads a
control: at each position after the prompt, the gap under the sound
logits of the token the faulted side puts first. Counts of arithmetic,
never device numbers; 12 GB of float32 weights and two to four minutes.

    python benchmarks/jamba_page_faults.py [seed]
"""
import json
import os
import sys
import time

os.environ["JAX_PLATFORMS"] = "cpu"
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
import numpy as np
import jax
import jax.numpy as jnp

from perf import cells, weights
ref = cells.load_module(os.path.join(ROOT, "perf/references/jamba.py"))
config = json.load(open(os.path.join(ROOT, "perf/configs/jamba2-3b-bf16.json")))
SEED = int(sys.argv[1]) if len(sys.argv) > 1 else 11
PROMPT, GEN, PAGE = 512, 256, 16
T = PROMPT + GEN
VARIANTS = ["sound", "kv8", "table_shifted_a_page", "two_pages_exchanged",
            "a_foreign_page", "attention_zeroed", "newest_token_unseen",
            "four_foreign_pages", "pages_unwritten_from_256"]
t0 = time.time()
tree, stages = ref.tree(config), ref.stages(config)
params = weights.whole(tree, stages, SEED)
print("weights", round(time.time() - t0), "s", flush=True)
rng = np.random.default_rng(SEED)
ids = jnp.asarray(rng.integers(3, config["vocab_size"], (1, T)), jnp.int32)
ids = jnp.tile(ids, (len(VARIANTS), 1))
P = ref.Precision()
z = ref._sizes(config)

def attn_variants(w, h):
    b, t, _ = h.shape
    d, heads, kvh = z["head"], z["heads"], z["kv_heads"]
    q, k, v = jnp.split(ref.linear(w["self_attn.qkv_proj"], h, P),
                        [heads * d, (heads + kvh) * d], axis=-1)
    def fault(x):
        x = x.reshape(b, t, kvh, d)
        rows = [x[0]]
        rows.append(x[1].astype(jnp.float8_e5m2).astype(jnp.float32))
        # every page holds what belongs a page later (a table off by one)
        rows.append(jnp.roll(x[2], -PAGE, axis=0))
        # pages 10 and 20 of the row exchanged (each a wrong page)
        sw = x[3]
        a, c = sw[10 * PAGE:11 * PAGE], sw[20 * PAGE:21 * PAGE]
        sw = sw.at[10 * PAGE:11 * PAGE].set(c).at[20 * PAGE:21 * PAGE].set(a)
        rows.append(sw)
        # page 20 holds what no query before 752 may see (page 47's)
        far = x[4][47 * PAGE:48 * PAGE]
        rows.append(x[4].at[20 * PAGE:21 * PAGE].set(far))
        rows.append(x[5])
        # a context one short: index i holds i-1's, a query misses its own
        rows.append(jnp.roll(x[6], 1, axis=0))
        f = x[7]
        for page in (8, 16, 24, 31):
            f = f.at[page * PAGE:(page + 1) * PAGE].set(far)
        rows.append(f)
        rows.append(x[8].at[256:].set(0.0))
        return jnp.stack(rows)
    out = ref.attention(q.reshape(b, t, kvh, heads // kvh, d), fault(k), fault(v), d ** -0.5)
    out = ref.linear(w["self_attn.o_proj"], out.reshape(b, t, -1), P)
    return out.at[5].set(0.0)

def layer_attention(w, x):
    out = attn_variants(w, ref._normed(config, w, x))
    return ref._feed_forward(config, w, x + out, P)

progs = {}
x = ids
with jax.default_matmul_precision("highest"):
    for n, (fn, buckets) in enumerate(stages):
        w = {local: params[b] for local, b in buckets.items()}
        if fn == "layer_attention":
            x = jax.jit(layer_attention)(w, x)
        elif fn == "logits":
            x = x[:, PROMPT - 1:T - 1]       # the positions that generate
            x = jax.jit(lambda w, x: getattr(ref, fn)(config, w, x, P))(w, x)
        else:
            if fn not in progs:
                progs[fn] = jax.jit(lambda w, x, fn=fn: getattr(ref, fn)(config, w, x, P))
            x = progs[fn](w, x)
        x.block_until_ready()
        print(n, fn, round(time.time() - t0), "s", flush=True)
L = np.asarray(x)[..., :config["vocab_size"]]
sound = L[0]
std = sound.std(-1)
top = sound.max(-1)
lim = config["perf"]["reference_tolerances"]
print("limits", {k: lim[k] for k in ("gap_mean", "gap_share", "gap_worst")})
res = {}
for i, name in enumerate(VARIANTS):
    first = L[i].argmax(-1)
    gap = (top - np.take_along_axis(sound, first[:, None], -1)[:, 0]) / std
    moved = float(np.abs(L[i] - sound).max(-1).mean() / std.mean())
    res[name] = dict(gap_mean=float(gap.mean()), gap_share=float((gap > lim["gap_threshold"]).mean()),
                     gap_worst=float(gap.max()), flipped=float((first != sound.argmax(-1)).mean()),
                     largest_logit_move_in_spreads=moved)
    print(name, res[name], flush=True)
