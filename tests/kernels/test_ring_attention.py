"""Ring attention (sequence parallelism) vs dense causal attention on
the virtual 8-device CPU mesh."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh


def dense_causal(q, k, v, scale):
    group = q.shape[2] // k.shape[2]
    if group > 1:                       # GQA: broadcast kv heads
        k = np.repeat(k, group, axis=2)
        v = np.repeat(v, group, axis=2)
    s = np.einsum("bqhd,bkhd->bhqk", q.astype(np.float64),
                  k.astype(np.float64)) * scale
    n = q.shape[1]
    mask = np.tril(np.ones((n, n), bool))
    s = np.where(mask[None, None], s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    out = np.einsum("bhqk,bkhd->bhqd", p, v.astype(np.float64))
    return out.transpose(0, 2, 1, 3)


@pytest.mark.parametrize("n_dev", [2, 4, 8])
def test_ring_matches_dense(n_dev, cpu_devices):
    from aphrodite_tpu.ops.ring_attention import ring_prefill_attention

    rs = np.random.RandomState(0)
    b, seq, H, d = 2, 8 * n_dev, 4, 16
    q = rs.randn(b, seq, H, d).astype(np.float32) * 0.3
    k = rs.randn(b, seq, H, d).astype(np.float32) * 0.3
    v = rs.randn(b, seq, H, d).astype(np.float32) * 0.3
    scale = d ** -0.5

    mesh = Mesh(np.asarray(jax.devices()[:n_dev]), ("sp",))
    got = np.asarray(ring_prefill_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), mesh,
        scale=scale))
    want = dense_causal(q, k, v, scale)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_ring_gqa_rotates_kv_heads(cpu_devices):
    """GQA: K/V carry Hkv heads around the ring (the group broadcast
    lives in the score einsum) and results match dense GQA attention."""
    from aphrodite_tpu.ops.ring_attention import ring_prefill_attention

    rs = np.random.RandomState(3)
    n_dev, b, seq, Hq, Hkv, d = 4, 2, 32, 8, 2, 16
    q = rs.randn(b, seq, Hq, d).astype(np.float32) * 0.3
    k = rs.randn(b, seq, Hkv, d).astype(np.float32) * 0.3
    v = rs.randn(b, seq, Hkv, d).astype(np.float32) * 0.3
    scale = d ** -0.5
    mesh = Mesh(np.asarray(jax.devices()[:n_dev]), ("sp",))
    got = np.asarray(ring_prefill_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), mesh,
        scale=scale))
    want = dense_causal(q, k, v, scale)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_ring_inside_jit(cpu_devices):
    """The shard must compose under jit with mesh context (how the
    engine would call it)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from aphrodite_tpu.ops.ring_attention import ring_attention_shard
    import functools

    rs = np.random.RandomState(1)
    n_dev, b, seq, H, d = 4, 1, 32, 2, 8
    q = jnp.asarray(rs.randn(b, seq, H, d).astype(np.float32))
    mesh = Mesh(np.asarray(jax.devices()[:n_dev]), ("sp",))
    spec = P(None, "sp", None, None)
    fn = jax.jit(jax.shard_map(
        functools.partial(ring_attention_shard, scale=0.35,
                          axis_name="sp"),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec))
    sharding = NamedSharding(mesh, spec)
    qd = jax.device_put(q, sharding)
    out = np.asarray(fn(qd, qd, qd))
    want = dense_causal(np.asarray(q), np.asarray(q), np.asarray(q),
                        0.35)
    np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-4)
