"""Fused GPTQ dequant-matmul Pallas kernel vs the XLA dequantize path
(reference CUDA equivalent: `kernels/quantization/gptq/q_gemm.cu`
reconstruct+gemm; correctness oracle here is `GPTQLinearMethod.dequantize`
which is itself tested against AutoGPTQ layout in
tests/quantization/test_quant_methods.py)."""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from aphrodite_tpu.modeling.layers.quantization.gptq import (
    GPTQConfig, GPTQLinearMethod)
from aphrodite_tpu.ops.pallas.quant_matmul import (gptq_matmul,
                                                   gptq_supported,
                                                   plane_permutation)

rs = np.random.RandomState(7)


def make_inputs(bits, group_size, K, N, m, dtype=np.float32):
    pack = 32 // bits
    G = K // (group_size if group_size != -1 else K)
    qweight = rs.randint(-2**31, 2**31, (K // pack, N), dtype=np.int32)
    qzeros = rs.randint(-2**31, 2**31, (G, N // pack), dtype=np.int32)
    scales = (rs.rand(G, N).astype(dtype) * 0.1 + 0.01)
    x = rs.randn(m, K).astype(dtype)
    g_idx = (np.arange(K) // (group_size if group_size != -1 else K)
             ).astype(np.int32)
    params = {"qweight": jnp.asarray(qweight),
              "qzeros": jnp.asarray(qzeros),
              "scales": jnp.asarray(scales),
              "g_idx": jnp.asarray(g_idx)}
    return params, jnp.asarray(x)


@pytest.mark.parametrize("bits,group_size,K,N,m", [
    (4, 128, 512, 256, 5),      # unpadded m
    (4, 128, 256, 512, 64),
    (8, 128, 256, 128, 33),
    (4, -1, 256, 384, 16),      # single group
    (8, 256, 512, 128, 8),      # multi-row group
])
def test_matches_xla_dequant(bits, group_size, K, N, m):
    params, x = make_inputs(bits, group_size, K, N, m)
    method = GPTQLinearMethod(GPTQConfig(bits, group_size))
    ref = np.asarray(x @ method.dequantize(params, jnp.float32))
    got = np.asarray(gptq_matmul(
        x, params["qweight"], params["qzeros"], params["scales"],
        bits=bits, group_size=group_size, interpret=True))
    rel = np.abs(ref - got).max() / (np.abs(ref).max() + 1e-9)
    assert rel < 2e-5, rel


def test_plane_permutation_is_permutation():
    perm = plane_permutation(512, 128, 4)
    assert sorted(perm.tolist()) == list(range(512))
    # Row j of the plane-unpacked tile is original row
    # (j % R) * pack + j // R within each 128-block.
    assert perm[0] == 0 and perm[1] == 8 and perm[16] == 1


def test_supported_gate():
    assert gptq_supported(4096, 14336, 4, 128, False)
    assert gptq_supported(4096, 4096, 8, 128, False)
    assert not gptq_supported(4096, 14336, 4, 128, True)    # desc_act
    assert not gptq_supported(4096, 14336, 2, 128, False)   # 2-bit
    assert not gptq_supported(4000, 14336, 4, 128, False)   # K % gs
    assert not gptq_supported(4096, 14300, 4, 128, False)   # N % 128


def test_apply_uses_fallback_on_cpu():
    """On CPU the linear method must route to the XLA path (the kernel
    gate checks the backend), and produce the same results."""
    params, x = make_inputs(4, 128, 256, 256, 4)
    method = GPTQLinearMethod(GPTQConfig(4, 128))
    y = np.asarray(method.apply(params, x))
    ref = np.asarray(x @ method.dequantize(params, jnp.float32))
    np.testing.assert_allclose(y, ref, rtol=1e-4, atol=1e-5)


# --------------------------------------------------------------- AWQ --

def make_awq_inputs(group_size, K, N, m, dtype=np.float32):
    G = K // group_size
    qweight = rs.randint(-2**31, 2**31, (K, N // 8), dtype=np.int32)
    qzeros = rs.randint(-2**31, 2**31, (G, N // 8), dtype=np.int32)
    scales = (rs.rand(G, N).astype(dtype) * 0.1 + 0.01)
    x = rs.randn(m, K).astype(dtype)
    params = {"qweight": jnp.asarray(qweight),
              "qzeros": jnp.asarray(qzeros),
              "scales": jnp.asarray(scales)}
    return params, jnp.asarray(x)


@pytest.mark.parametrize("group_size,K,N,m", [
    (128, 256, 1024, 5),        # unpadded m
    (128, 512, 2048, 64),       # block_n = 2048
    (256, 512, 1024, 16),       # multi-row group
    (128, 128, 3072, 8),        # n_tiles = 3 at block_n 1024
])
def test_awq_matches_xla_dequant(group_size, K, N, m):
    from aphrodite_tpu.modeling.layers.quantization.awq import (
        AWQConfig, AWQLinearMethod)
    from aphrodite_tpu.ops.pallas.quant_matmul import awq_matmul
    params, x = make_awq_inputs(group_size, K, N, m)
    method = AWQLinearMethod(AWQConfig(4, group_size))
    ref = np.asarray(x @ method.dequantize(params, jnp.float32))
    got = np.asarray(awq_matmul(
        x, params["qweight"], params["qzeros"], params["scales"],
        group_size=group_size, interpret=True))
    rel = np.abs(ref - got).max() / (np.abs(ref).max() + 1e-9)
    assert rel < 2e-5, rel


def test_awq_supported_gate():
    from aphrodite_tpu.ops.pallas.quant_matmul import awq_supported
    assert awq_supported(4096, 14336 * 2, 128)      # gate_up
    assert awq_supported(14336, 4096, 128)          # down
    assert awq_supported(4096, 6144, 128)           # qkv
    assert not awq_supported(4000, 4096, 128)       # K % gs
    assert not awq_supported(4096, 4096 + 512, 128)  # N % 1024
    assert not awq_supported(4096, 4096, 64)        # group too small


@pytest.mark.parametrize("K,N,m", [
    (256, 512, 5),
    (512, 1024, 64),
])
def test_int8_matmul_matches_xla(K, N, m):
    from aphrodite_tpu.ops.pallas.quant_matmul import int8_matmul
    w = rs.randint(-128, 128, (K, N), dtype=np.int8)
    s = (rs.rand(N).astype(np.float32) * 0.01 + 1e-3)
    x = rs.randn(m, K).astype(np.float32)
    ref = (x @ w.astype(np.float32)) * s
    got = np.asarray(int8_matmul(jnp.asarray(x), jnp.asarray(w),
                                 jnp.asarray(s), interpret=True))
    rel = np.abs(ref - got).max() / (np.abs(ref).max() + 1e-9)
    assert rel < 2e-5, rel


# ------------------------------------------- W4A8 deferred rescale --

def _a8_oracle(x, w_dequant):
    """Reference for the W4A8 kernels: quantize activations exactly the
    way the wrappers do, then a plain f32 dequantize-then-dot."""
    from aphrodite_tpu.ops.pallas.quant_matmul import (
        _quantize_activations_int8)
    x8, xs = _quantize_activations_int8(x)
    return np.asarray((x8.astype(jnp.float32) * xs) @ w_dequant)


@pytest.mark.parametrize("m", [1, 64, 512])
@pytest.mark.parametrize("K", [384, 512])
def test_gptq_a8_deferred_matches_dequant(m, K):
    """Deferred-rescale parity, GPTQ int4 g128: the int32-group-
    accumulator kernel must match (a) the classic a8 kernel to f32
    summation order and (b) the reference dequantize-then-dot within
    the existing W4A8 tolerance, across m in {1, 64, 512} and a
    non-divisible K tail (K=384 -> three single-group k-tiles)."""
    from aphrodite_tpu.ops.pallas.quant_matmul import gptq_matmul_a8
    params, x = make_inputs(4, 128, K, 256, m)
    method = GPTQLinearMethod(GPTQConfig(4, 128))
    w = method.dequantize(params, jnp.float32)
    oracle = _a8_oracle(x, w)
    got = {}
    for deferred in (False, True):
        got[deferred] = np.asarray(gptq_matmul_a8(
            x, params["qweight"], params["qzeros"], params["scales"],
            bits=4, group_size=128, interpret=True, deferred=deferred))
        rel = np.abs(oracle - got[deferred]).max() / \
            (np.abs(oracle).max() + 1e-9)
        assert rel < 2e-2, (deferred, rel)
    rel_cd = np.abs(got[True] - got[False]).max() / \
        (np.abs(got[False]).max() + 1e-9)
    assert rel_cd < 1e-5, rel_cd


@pytest.mark.parametrize("m", [1, 64, 512])
@pytest.mark.parametrize("K", [384, 512])
def test_awq_a8_deferred_matches_dequant(m, K):
    """Deferred-rescale parity for the AWQ lane-plane layout — same
    contract as the GPTQ case."""
    from aphrodite_tpu.modeling.layers.quantization.awq import (
        AWQConfig, AWQLinearMethod)
    from aphrodite_tpu.ops.pallas.quant_matmul import awq_matmul_a8
    params, x = make_awq_inputs(128, K, 1024, m)
    method = AWQLinearMethod(AWQConfig(4, 128))
    w = method.dequantize(params, jnp.float32)
    oracle = _a8_oracle(x, w)
    got = {}
    for deferred in (False, True):
        got[deferred] = np.asarray(awq_matmul_a8(
            x, params["qweight"], params["qzeros"], params["scales"],
            group_size=128, interpret=True, deferred=deferred))
        rel = np.abs(oracle - got[deferred]).max() / \
            (np.abs(oracle).max() + 1e-9)
        assert rel < 2e-2, (deferred, rel)
    rel_cd = np.abs(got[True] - got[False]).max() / \
        (np.abs(got[False]).max() + 1e-9)
    assert rel_cd < 1e-5, rel_cd


def test_deferred_resolution_and_vmem_fallback():
    """The deferred selector: explicit arg wins, else the rule by m
    (m > 64); the VMEM-fit check rejects tile footprints the budget
    can't hold."""
    from aphrodite_tpu.ops.pallas.quant_matmul import (
        _deferred_fits, _resolve_deferred)
    assert _resolve_deferred(True, 1) and not _resolve_deferred(False,
                                                                8192)
    assert not _resolve_deferred(None, 64)      # decode keeps classic
    assert _resolve_deferred(None, 512)         # batch goes deferred
    # 4 int32 planes + f32 at 256x1024 = 5 MB fits the 8 MB budget;
    # a 1024x2048 tile (40 MB) does not.
    assert _deferred_fits(256, 1024, 4)
    assert not _deferred_fits(1024, 2048, 4)


@pytest.mark.parametrize("m,want", [
    (1, "stream"), (48, "stream"), (64, "stream"),
    (65, "_gptq_a8_deferred_kernel"),
    (1024, "_gptq_a8_deferred_kernel"),
    (2048, "_gptq_a8_deferred_kernel")])
def test_the_w4a8_kernel_is_chosen_by_m_alone(monkeypatch, m, want):
    """What `gptq_matmul_a8` traces at Mistral's cell's row counts (48
    decode rows; 1,024 and 2,048 prompt tokens) and at the rule's
    edges: the streamed grid with the plain rescale at m <= 64, the
    compiler's grid with the deferred rescale above. The three
    variables that could once overrule it are set against the rule
    (and the budget to a size the deferred planes of a 256 x 1,024
    tile, 3 MB, would not fit) and read by nothing."""
    from aphrodite_tpu.ops.pallas import quant_matmul as qm
    monkeypatch.setenv("APHRODITE_QMM_STREAM", "0" if m <= 64 else "1")
    monkeypatch.setenv("APHRODITE_QMM_DEFERRED",
                       "1" if m <= 64 else "0")
    monkeypatch.setenv("APHRODITE_QMM_DEFERRED_VMEM_MB", "1")
    chosen = []

    def stream_call(*args, deferred, padded_m, N, out_dtype, **kw):
        chosen.append("stream-deferred" if deferred else "stream")
        return jnp.zeros((padded_m, N), out_dtype)
    monkeypatch.setattr(qm, "_stream_call", stream_call)
    for name in ("_gptq_a8_kernel", "_gptq_a8_deferred_kernel"):
        def spy(*args, _name=name, _real=getattr(qm, name), **kw):
            chosen.append(_name)
            return _real(*args, **kw)
        monkeypatch.setattr(qm, name, spy)
    K, N = 256, 1024
    shapes = [jax.ShapeDtypeStruct(s, d) for s, d in (
        ((m, K), jnp.bfloat16), ((K // 8, N), jnp.int32),
        ((K // 128, N // 8), jnp.int32), ((K // 128, N), jnp.bfloat16))]
    # the undecorated function: a trace another test left in the jit's
    # cache would run none of this
    jax.eval_shape(functools.partial(
        qm.gptq_matmul_a8.__wrapped__, bits=4, group_size=128), *shapes)
    assert chosen == [want]


def test_awq_apply_fallback_on_cpu():
    from aphrodite_tpu.modeling.layers.quantization.awq import (
        AWQConfig, AWQLinearMethod)
    params, x = make_awq_inputs(128, 256, 1024, 4)
    method = AWQLinearMethod(AWQConfig(4, 128))
    y = np.asarray(method.apply(params, x))
    ref = np.asarray(x @ method.dequantize(params, jnp.float32))
    np.testing.assert_allclose(y, ref, rtol=1e-4, atol=1e-5)
