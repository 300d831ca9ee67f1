"""AMLA mul-by-add online-softmax rescale (ISSUE 14 tentpole, arxiv
2509.25224): the decode-attention kernels track the running max as an
INTEGER in the base-2 score domain, so the per-chunk correction
2^(m_prev - m_new) is an exact power of two — applied as an
exponent-bias ADD on the l/acc planes (what every served call runs)
or as the classic VPU multiply (amla=False, kept as the reference of
these tests).

Because the correction is an exact power of two either way, the two
arms are BIT-IDENTICAL away from underflow — the strongest possible
A/B contract, pinned here at fp32 tolerance zero across the ragged
--ctx-mix geometries (multi-chunk, GQA, int8 KV, ALiBi) and a call
without a work list. `_mul_pow2` itself is unit-tested bit-exact
against the multiply. All kernels run in interpret mode on CPU
(tier-1)."""
import jax.numpy as jnp
import numpy as np
import pytest

from aphrodite_tpu.ops.pallas.paged_attention import (
    _mul_pow2, build_decode_work_list, paged_decode_attention)

from test_attention import make_problem, numpy_paged_attention
from test_ragged_attention import RAGGED_CTX, ragged_problem


# ------------------------------------------------- _mul_pow2 unit --

def test_mul_pow2_bit_exact_vs_multiply():
    """x * 2^delta via exponent-bias add == the f32 multiply, bit for
    bit, for normal values (delta <= 0, the online-softmax case)."""
    rs = np.random.RandomState(3)
    x = jnp.asarray((rs.randn(16, 128) * 10 ** rs.uniform(
        -20, 20, (16, 128))).astype(np.float32))
    for d in (0, -1, -7, -31, -60):
        delta = jnp.full((16, 1), float(d), jnp.float32)
        got = np.asarray(_mul_pow2(x, delta))
        want = np.asarray(x) * np.float32(2.0 ** d)
        # entries the multiply would denormalize flush to exact zero
        normal = np.abs(want) >= np.finfo(np.float32).tiny
        np.testing.assert_array_equal(got[normal], want[normal])
        assert np.all(got[~normal] == 0.0)


def test_mul_pow2_zero_and_underflow_map_to_zero():
    x = jnp.asarray(np.array([[0.0, 1.0, -2.5, 1e-38]], np.float32))
    got = np.asarray(_mul_pow2(x, jnp.full((1, 1), -200.0)))
    np.testing.assert_array_equal(got, 0.0)
    # delta == 0 is the identity on normals and keeps zeros zero
    got0 = np.asarray(_mul_pow2(x, jnp.zeros((1, 1), jnp.float32)))
    np.testing.assert_array_equal(got0[:, :3], np.asarray(x)[:, :3])


# ------------------------------- AMLA vs classic rescale (A/B) -----

def _run(q, kp, vp, bt, ctx, amla, work=None, slopes=None,
         kv_scale=1.0, ppc=2):
    return np.asarray(paged_decode_attention(
        jnp.array(q), jnp.array(kp), jnp.array(vp), jnp.array(bt),
        jnp.array(ctx),
        None if slopes is None else jnp.array(slopes),
        scale=0.1, kv_scale=kv_scale, pages_per_chunk=ppc,
        work_items=work, amla=amla, interpret=True))


@pytest.mark.parametrize("num_q_heads,num_kv_heads,ppc",
                         [(8, 2, 2),     # GQA group 4, multi-chunk
                          (8, 1, 4),     # MQA
                          (12, 12, 2)])  # hb=6, two head blocks
def test_amla_equals_classic_ragged_ctx_mix(num_q_heads, num_kv_heads,
                                            ppc):
    """The ragged --ctx-mix geometry (single-token, pad, multi-chunk
    rows): AMLA and classic rescale agree bit-for-bit — the correction
    is an exact power of two in both arms — and both match the
    oracle."""
    q, kp, vp, bt, ctx, work = ragged_problem(num_q_heads,
                                              num_kv_heads, ppc)
    a = _run(q, kp, vp, bt, ctx, True, work=work, ppc=ppc)
    c = _run(q, kp, vp, bt, ctx, False, work=work, ppc=ppc)
    np.testing.assert_array_equal(a, c)
    expected = numpy_paged_attention(q, kp, vp, bt,
                                     np.maximum(ctx, 1), 0.1)
    expected[ctx == 0] = 0.0
    mask = ctx > 0
    np.testing.assert_allclose(a[mask], expected[mask], rtol=1e-2,
                               atol=1e-2)


def test_amla_equals_classic_without_a_work_list():
    """Same contract over the dense list a call without work_items
    builds: items wholly beyond a row's context among them."""
    q, kp, vp, bt, ctx, _ = ragged_problem()
    a = _run(q, kp, vp, bt, ctx, True)
    c = _run(q, kp, vp, bt, ctx, False)
    np.testing.assert_array_equal(a, c)


def test_amla_equals_classic_int8_kv():
    """int8 KV dequant: kv_scale folds into the base-2 score scale and
    the epilogue untouched by the rescale rewrite."""
    q, kp, vp, bt, ctx, work = ragged_problem()
    S = 0.05
    k8 = np.clip(np.round(kp / S), -127, 127).astype(np.int8)
    v8 = np.clip(np.round(vp / S), -127, 127).astype(np.int8)
    a = _run(q, k8, v8, bt, ctx, True, work=work, kv_scale=S)
    c = _run(q, k8, v8, bt, ctx, False, work=work, kv_scale=S)
    np.testing.assert_array_equal(a, c)
    expected = numpy_paged_attention(q, k8.astype(np.float32) * S,
                                     v8.astype(np.float32) * S, bt,
                                     np.maximum(ctx, 1), 0.1)
    mask = ctx > 0
    np.testing.assert_allclose(a[mask], expected[mask], rtol=1e-2,
                               atol=1e-2)


def test_amla_equals_classic_alibi():
    """ALiBi slopes carry the log2(e) factor in-kernel; the bias rides
    the base-2 scores identically in both arms."""
    q, kp, vp, bt, ctx, work = ragged_problem()
    slopes = np.array([2.0 ** -(i + 1) for i in range(8)], np.float32)
    a = _run(q, kp, vp, bt, ctx, True, work=work, slopes=slopes)
    c = _run(q, kp, vp, bt, ctx, False, work=work, slopes=slopes)
    np.testing.assert_array_equal(a, c)
    expected = numpy_paged_attention(q, kp, vp, bt,
                                     np.maximum(ctx, 1), 0.1,
                                     alibi_slopes=slopes)
    mask = ctx > 0
    np.testing.assert_allclose(a[mask], expected[mask], rtol=1e-2,
                               atol=1e-2)
