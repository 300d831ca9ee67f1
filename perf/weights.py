"""The weights are data: the benchmark makes them from `--seed`, for
the server and for the reference alike, so that neither takes what
the other has made.

A reference file's `tree(config)` names every leaf the server holds
as `(shape, dtype name, draw)`; `draw` is `[low, high]` for a float
leaf (uniform between the two, in the leaf's own type), `"bits"` for
packed integers (random words) and `"zeros"` for index-like integers.
The ranges are the reference's to state, and they are chosen so that
every layer counts: norm gains about 1, matrices at a fan-in scale, so
that attention and the MLP each add a good share of the residual
stream and a fault inside a layer reaches the logits. (The program's
own `--load-format dummy` draws every float leaf in +-1e-3, under
which a layer is a thousandth of the stream and the logits are the
embedding, the final norm and the head: PERF.md section 6, PR 27.)

The recipe: `PRNGKey(seed)` split over the leaves in the order
`jax.tree_util` flattens the tree (keys sorted at both levels), one
key a leaf. `stage_maker` makes one stage's leaves in one jitted
program; the server gets the whole tree stage by stage
(`perf/serve_child.py`), the reference child makes a stage's leaves
inside that stage's program and drops them (`perf/reference_child.py`).
"""
from __future__ import annotations

import json
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp


def order(tree: Dict[str, Dict[str, tuple]]) -> List[Tuple[str, str]]:
    """`(bucket, leaf)` in the order `jax.tree_util` flattens the
    server's tree of dictionaries: keys sorted at both levels."""
    return [(b, name) for b in sorted(tree) for name in sorted(tree[b])]


def leaf(key: jax.Array, shape, dtype: str, draw) -> jax.Array:
    dtype = jnp.dtype(dtype)
    if jnp.issubdtype(dtype, jnp.floating):
        low, high = draw
        return jax.random.uniform(key, tuple(shape), dtype, minval=low,
                                  maxval=high)
    if draw == "bits":
        info = jnp.iinfo(dtype)
        return jax.random.randint(key, tuple(shape), info.min, info.max,
                                  dtype=dtype)
    if draw != "zeros":
        raise ValueError(f"an integer leaf is drawn as 'bits' or 'zeros', "
                         f"not {draw!r}")
    return jnp.zeros(tuple(shape), dtype)


def all_keys(tree: Dict[str, Dict[str, tuple]], seed: int) -> jax.Array:
    return jax.random.split(jax.random.PRNGKey(int(seed)), len(order(tree)))


def subkeys(tree: Dict[str, Dict[str, tuple]], keys,
            buckets: Dict[str, str]) -> Dict[str, Dict[str, object]]:
    """The key of every leaf of `buckets` (`{local name: bucket}`),
    under the local names; `keys` is `all_keys(tree, seed)`."""
    index = {at: i for i, at in enumerate(order(tree))}
    return {local: {name: keys[index[bucket, name]]
                    for name in tree[bucket]}
            for local, bucket in buckets.items()}


def make(specs: Dict[str, Dict[str, tuple]],
         sub: Dict[str, Dict[str, jax.Array]]
         ) -> Dict[str, Dict[str, jax.Array]]:
    """The leaves of `specs` (`{local name: {leaf: spec}}`) from their
    keys; runs inside the program that uses them."""
    return {local: {name: leaf(sub[local][name], *spec)
                    for name, spec in leaves.items()}
            for local, leaves in specs.items()}


def whole(tree: Dict[str, Dict[str, tuple]], stages, seed: int
          ) -> Dict[str, Dict[str, jax.Array]]:
    """Every leaf of `tree` on the default device, a stage at a time:
    one jitted program for each distinct stage (the layers share one)
    and one call a stage."""
    keys, programs, out = all_keys(tree, seed), {}, {}
    for _, buckets in stages:
        specs = {local: tree[b] for local, b in buckets.items()}
        sig = json.dumps(specs, sort_keys=True)
        if sig not in programs:
            programs[sig] = jax.jit(lambda sub, specs=specs: make(specs, sub))
        made = programs[sig](subkeys(tree, keys, buckets))
        for local, bucket in buckets.items():
            out[bucket] = made[local]
    missing = set(tree) - set(out)
    if missing:
        raise ValueError(f"no stage makes the buckets {sorted(missing)}")
    return out
