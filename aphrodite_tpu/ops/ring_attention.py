"""Ring attention: causal self-attention with the SEQUENCE dimension
sharded across mesh devices (sequence/context parallelism).

The reference scales long prompts only by gpu-count-x-memory via NCCL
tensor parallelism; this is the TPU-native long-context path the survey
plans for (SURVEY.md §5 "long-context"): each device holds one sequence
shard of Q/K/V, K/V shards rotate around the ring with
`jax.lax.ppermute` over ICI while every device accumulates its queries'
online softmax — peak memory per device is O(seq/devices), compute
overlaps the collective, and the result is numerically equivalent to
dense causal attention (tested to 1e-4 on the virtual 8-device CPU
mesh; the online-softmax association order differs, so not bit-equal).

Usage (inside shard_map over axis `axis_name`, one sequence shard per
device):

    out = ring_attention_shard(q, k, v, scale, axis_name="sp")

or the convenience wrapper `ring_prefill_attention(q, k, v, mesh, ...)`
which shard_maps over the given axis with sequence sharding.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

_NEG_INF = -2.0**30


def _block_attend(q, k, v, scale, q_pos, k_pos):
    """Scores + masked online-softmax stats for one (q-shard, k-shard)
    pair. q [b, sq, Hq, d]; k/v [b, sk, Hkv, d] with Hq = Hkv * group
    (GQA broadcasts in the einsum — K/V are NEVER materialized at Hq, so
    the ring rotates Hkv-sized shards). Positions are GLOBAL so
    causality holds across shards. Returns (m, l, acc) with head axes
    [b, Hkv, group, q(, d)]."""
    b, sq, hq, d = q.shape
    hkv = k.shape[2]
    group = hq // hkv
    qg = q.reshape(b, sq, hkv, group, d)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    mask = k_pos[None, None, None, None, :] <= \
        q_pos[None, None, None, :, None]
    s = jnp.where(mask, s, _NEG_INF)
    m = jnp.max(s, axis=-1)                          # [b, Hkv, g, q]
    p = jnp.exp(s - m[..., None])
    p = jnp.where(mask, p, 0.0)
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bhgqk,bkhd->bhgqd", p, v.astype(jnp.float32))
    return m, l, acc


def ring_attention_shard(q: jax.Array, k: jax.Array, v: jax.Array,
                         scale: float, axis_name: str) -> jax.Array:
    """Per-device body: q [batch, seq_shard, Hq, d] and k/v
    [batch, seq_shard, Hkv, d] are THIS device's sequence shard;
    returns this shard's attention output [batch, seq_shard, Hq, d].

    K/V rotate around the ring: at step t each device holds the shard
    originally on device (i - t) mod N and folds it into its running
    (m, l, acc) with the standard two-way online-softmax merge. GQA
    models rotate Hkv-sized K/V shards (the group broadcast happens in
    the score einsum, never in the ppermute payload)."""
    n_dev = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, sq, hq, d = q.shape
    hkv = k.shape[2]
    group = hq // hkv
    q_pos = idx * sq + jnp.arange(sq)

    def merge(state, m2, l2, acc2):
        m1, l1, acc1 = state
        m = jnp.maximum(m1, m2)
        c1 = jnp.exp(m1 - m)
        c2 = jnp.exp(m2 - m)
        return (m, l1 * c1 + l2 * c2,
                acc1 * c1[..., None] + acc2 * c2[..., None])

    def fold(t, state, kt, vt):
        m, l, acc = state
        src = (idx - t) % n_dev                  # whose shard we hold
        k_pos = src * sq + jnp.arange(sq)
        m2, l2, acc2 = _block_attend(q, kt, vt, scale, q_pos, k_pos)
        return merge((m, l, acc), m2, l2, acc2)

    def body(t, carry):
        m, l, acc, kt, vt = carry
        m, l, acc = fold(t, (m, l, acc), kt, vt)
        # Rotate: receive the next shard from the previous device.
        perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]
        kt = jax.lax.ppermute(kt, axis_name, perm)
        vt = jax.lax.ppermute(vt, axis_name, perm)
        return m, l, acc, kt, vt

    def _varying(x):
        # The softmax state is per-device (varies over the ring axis);
        # an unvarying init would type-mismatch the loop carry.
        return jax.lax.pcast(x, axis_name, to="varying")

    init = _varying(
        (jnp.full((b, hkv, group, sq), _NEG_INF, jnp.float32),
         jnp.zeros((b, hkv, group, sq), jnp.float32),
         jnp.zeros((b, hkv, group, sq, d), jnp.float32))) + (k, v)
    # Peel the last step: its rotation's result would be discarded, and
    # a full K+V shard over ICI per layer is not free.
    m, l, acc, kt, vt = jax.lax.fori_loop(0, n_dev - 1, body, init)
    m, l, acc = fold(n_dev - 1, (m, l, acc), kt, vt)
    l_safe = jnp.where(l == 0.0, 1.0, l)
    out = acc / l_safe[..., None]                   # [b, Hkv, g, q, d]
    return out.transpose(0, 3, 1, 2, 4).reshape(
        b, sq, hq, d).astype(q.dtype)


def make_ring_fn(mesh: Mesh, scale: float, axis_name: str = "sp"):
    """shard_map-wrapped ring over `axis_name` (sequence dim): the ONE
    dispatch construction shared by the serving layer (inside jit, where
    GSPMD inserts any resharding) and the standalone wrapper below."""
    spec = P(None, axis_name, None, None)
    return jax.shard_map(
        functools.partial(ring_attention_shard, scale=scale,
                          axis_name=axis_name),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )


def ring_prefill_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                           mesh: Mesh, *, scale: float,
                           axis_name: str = "sp") -> jax.Array:
    """Convenience wrapper: shard q [batch, seq, Hq, d] and k/v
    [batch, seq, Hkv, d] over `axis_name` on the sequence dim and run
    the ring. seq must divide by the axis size."""
    fn = make_ring_fn(mesh, scale, axis_name)
    sharding = NamedSharding(mesh, P(None, axis_name, None, None))
    q = jax.device_put(q, sharding)
    k = jax.device_put(k, sharding)
    v = jax.device_put(v, sharding)
    return fn(q, k, v)
