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


def test_byte_permutation_is_the_prologues_transpose():
    """`plane_permutation(byte_rows=True)`: per group, the even
    nibbles' columns 8i + 2b at position 4i + b, the odd ones' in the
    second half; `_permute_columns` (what the wrappers apply, a
    blockwise transpose) is that permutation, in both orders."""
    from aphrodite_tpu.ops.pallas.quant_matmul import _permute_columns
    perm = plane_permutation(512, 128, 4, byte_rows=True)
    assert sorted(perm.tolist()) == list(range(512))
    assert perm[:6].tolist() == [0, 2, 4, 6, 8, 10]
    assert perm[64:68].tolist() == [1, 3, 5, 7] and perm[128] == 128
    cols = jnp.arange(512)[None, :]
    for byte_rows in (False, True):
        np.testing.assert_array_equal(
            np.asarray(_permute_columns(cols, 128, 8, byte_rows))[0],
            plane_permutation(512, 128, 4, byte_rows=byte_rows))


#: (zero, codes): every zero 1-16 over words that hold every code, and
#: the bytes' extremes alone (128 + 0 - 16 = 112, 128 + 15 - 1 = 142)
_BYTE_CASES = [(z, None) for z in range(1, 17)] + [(16, 0), (1, 15)]


@pytest.mark.parametrize("zero,code", _BYTE_CASES)
def test_unpack_bytes_is_unpack_planes_minus_zero(zero, code):
    """`_unpack_bytes` against `(_unpack_planes(words) - zero).astype(
    int8)`, row for row under the byte order's permutation: the same
    int8 operand from seven 32-bit operations a word."""
    from aphrodite_tpu.ops.pallas.quant_matmul import (
        _bias_zeros, _unpack_bytes, _unpack_planes)
    gs, lanes = 128, 256
    if code is None:
        words = rs.randint(0, 2**32, (gs // 8, lanes), dtype=np.uint32)
        # every code, in all eight nibbles of a word
        words[0, :16] = np.arange(16, dtype=np.uint32) * 0x11111111
    else:
        words = np.full((gs // 8, lanes), code * 0x11111111, np.uint32)
    words = jnp.asarray(words.view(np.int32))
    z = jnp.full((1, lanes), zero, jnp.int32)
    want = np.asarray(_unpack_planes(words, 4) - z)
    assert want.min() >= -16 and want.max() <= 14
    if code is not None:
        assert (want == code - zero).all()
    natural = np.empty_like(want)
    natural[plane_permutation(gs, gs, 4)] = want
    got = jax.jit(_unpack_bytes)(words, _bias_zeros(z))
    assert got.dtype == jnp.int8 and got.shape == (gs, lanes)
    np.testing.assert_array_equal(
        np.asarray(got),
        natural[plane_permutation(gs, gs, 4, byte_rows=True)])


def test_the_unpack_is_chosen_by_bits_and_grid():
    """`_resolve_unpack`: bytes for 4-bit words on the streamed grid,
    planes for every other width and on the compiler's grid; a keyword
    pins one, and bytes at another width is refused."""
    from aphrodite_tpu.ops.pallas.quant_matmul import _resolve_unpack
    assert _resolve_unpack(None, 4, True) == "bytes"
    assert _resolve_unpack(None, 4, False) == "planes"
    assert _resolve_unpack(None, 8, True) == "planes"
    assert _resolve_unpack("bytes", 4, False) == "bytes"
    assert _resolve_unpack("planes", 4, True) == "planes"
    with pytest.raises(ValueError):
        _resolve_unpack("bytes", 8, True)
    with pytest.raises(ValueError):
        _resolve_unpack("nibbles", 4, True)


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


@pytest.mark.parametrize("m", [1, 48, 64, 65, 1024])
@pytest.mark.parametrize("deferred", [False, True])
@pytest.mark.parametrize("group_size,K", [(128, 384), (-1, 256)])
def test_gptq_a8_bytes_bit_equal_planes_compilers_grid(m, deferred,
                                                       group_size, K):
    """`gptq_matmul_a8` with the operand from `_unpack_bytes` against
    the `_unpack_planes` arm (the kernel as it was before the byte
    unpack) on the compiler's grid, both rescales: the same int8
    values into the same int32 dots in another order of a group's
    rows, so not one bit of the result differs."""
    from aphrodite_tpu.ops.pallas.quant_matmul import gptq_matmul_a8
    params, x = make_inputs(4, group_size, K, 256, m)
    got = [np.asarray(gptq_matmul_a8(
        x, params["qweight"], params["qzeros"], params["scales"],
        bits=4, group_size=group_size, interpret=True, stream=False,
        deferred=deferred, unpack=unpack))
        for unpack in ("planes", "bytes")]
    assert np.isfinite(got[0]).all() and np.abs(got[0]).max() > 0.1
    np.testing.assert_array_equal(got[0], got[1])


def test_qmm_ab_check_rehearses_on_the_cpu(monkeypatch, capsys):
    """`benchmarks/qmm_ab.py --interpret --arms --check`: the byte arm
    held against the plane arm bit for bit at a toy size, the
    wrapper's prologue put back, and nothing timed off the chip."""
    from aphrodite_tpu.ops.pallas import quant_matmul as qm
    from benchmarks import qmm_ab
    prologue = qm._gptq_prologue
    monkeypatch.setattr("sys.argv", ["qmm_ab.py", "--interpret", "--arms",
                                     "--check"])
    qmm_ab.main()
    said = capsys.readouterr().out
    assert said.count("bytes == planes bit for bit: True") == 4
    assert "False" not in said and "time " not in said
    assert qm._gptq_prologue is prologue
    # a call's roofline is the benchmark's count where an N is one
    # layer's, and this K's own where two layers share it
    import json
    from perf.rooflines import gptq_matmul_a8 as roofline
    with open(qmm_ab.CONFIG) as f:
        config = json.load(f)
    peaks = {"hbm_bytes_per_s": 819e9, "int8_ops_per_s": 393e12}
    for rows in (48, 1024):
        moved, computed = roofline.count(config, rows, 28672)
        assert qmm_ab.least_seconds(peaks, rows, 4096, 28672) == \
            pytest.approx(max(moved / 819e9, computed / 393e12))
        both = [qmm_ab.least_seconds(peaks, rows, K, 4096)
                for K in (4096, 14336)]
        moved, computed = roofline.count(config, rows, 4096)
        assert sum(both) / 2 == pytest.approx(
            max(moved / 819e9, computed / 393e12))


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
