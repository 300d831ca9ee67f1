"""The one general traffic generator: request shapes from a data file.

Every seed gets the same work in another order. A block of `n`
requests takes its prompt lengths, output lengths and inter-arrival
gaps at evenly spaced quantiles of the distributions the traffic file
names, so the multiset of sizes and gaps is a function of the file and
`n` alone; the seed only permutes each of them (independently) and
draws the prompt ids. Runs with different seeds then differ by order
and content, not by how much work the window holds.

Distributions (`kind`): `fixed` (value), `uniform` (min, max),
`lognormal` (median, sigma, clipped to min..max), `exponential`
(mean 1; gaps only). The gaps of a block are therefore not a Poisson
process: they are the `n` quantiles of the exponential distribution,
in an order the seed draws, scaled so that they sum to the block's
span. Every window holds exactly `n` arrivals.
"""
from __future__ import annotations

import statistics
from typing import List, Optional

import numpy as np

_NORMAL = statistics.NormalDist()


def _quantiles(spec: dict, n: int) -> np.ndarray:
    """`n` values at the quantiles (i + 0.5) / n of `spec`."""
    u = (np.arange(n) + 0.5) / n
    kind = spec["kind"]
    if kind == "fixed":
        return np.full(n, float(spec["value"]))
    if kind == "uniform":
        return spec["min"] + u * (spec["max"] - spec["min"])
    if kind == "lognormal":
        z = np.array([_NORMAL.inv_cdf(x) for x in u])
        return np.clip(spec["median"] * np.exp(spec["sigma"] * z),
                       spec["min"], spec["max"])
    if kind == "exponential":
        return -np.log1p(-u)
    raise ValueError(f"unknown distribution kind {kind!r}")


def _lengths(spec: dict, n: int) -> np.ndarray:
    return np.maximum(1, np.round(_quantiles(spec, n))).astype(int)


def block(params: dict, seed: int, index: int, n: int,
          span: Optional[float], vocab: int) -> List[dict]:
    """Block `index` of the stream that `seed` names: `n` request
    shapes. With a `span` (open loop) each carries `offset`, its due
    time in seconds from the block's start; the gaps sum to `span`, so
    blocks follow one another seamlessly at `n / span` requests a
    second. Without one (closed loop) `offset` is None."""
    rng = np.random.default_rng([int(seed), int(index) + 1])
    prompt_lens = rng.permutation(_lengths(params["prompt_len"], n))
    output_lens = rng.permutation(_lengths(params["output_len"], n))
    kinds = rng.permutation(np.arange(n) % len(params["sampling"]))
    offsets = [None] * n
    if span is not None:
        gaps = _quantiles(params["gaps"], n)
        gaps = rng.permutation(gaps * (span / gaps.sum()))
        offsets = np.concatenate([[0.0], np.cumsum(gaps)[:-1]]).tolist()
    shapes = []
    for i in range(n):
        sampling = dict(params["sampling"][int(kinds[i])])
        if sampling.get("seed") == "per_request":
            sampling["seed"] = int(rng.integers(1, 2 ** 31 - 1))
        shapes.append(dict(
            offset=offsets[i],
            prompt=rng.integers(3, vocab, int(prompt_lens[i])).tolist(),
            max_tokens=int(output_lens[i]),
            stream=bool(params["stream"]), sampling=sampling))
    return shapes

