"""Streamed skinny-m quant-matmul grid (ISSUE 4 tentpole) vs the
classic compiler-managed grid and the XLA dequantize oracle.

The streamed path flattens the (n, k) tile grid into one work list
and drives an explicit cross-cell weight DMA ring
(`quant_matmul._stream_kernel`); these tests pin:

- parity at m in {1, 8, 64} for gptq AND awq, including the K=384
  tail (three single-group k-tiles at gs 128), group sizes 64/128,
  deferred rescale on/off, and int8 activations (the W4A8 kernels);
- selection: taken at m <= 64, not above, unless the call's keyword
  says otherwise;
- the APHRODITE_QMM_STREAM_PF per-call read warns-and-defaults on a
  malformed value (never kills the call, let alone the import);
- the deep-k VMEM-fit guard: an oversized APHRODITE_QMM_BLOCK_K
  clamps with a correct result instead of failing to compile.

All kernels run in interpret mode on CPU (tier-1)."""
import numpy as np
import pytest

import jax.numpy as jnp

from aphrodite_tpu.modeling.layers.quantization.awq import (
    AWQConfig, AWQLinearMethod)
from aphrodite_tpu.modeling.layers.quantization.gptq import (
    GPTQConfig, GPTQLinearMethod)
from aphrodite_tpu.ops.pallas.quant_matmul import (
    _cell_bytes, _clamp_k_vmem, _quantize_activations_int8,
    _resolve_stream, _stream_pf, awq_matmul, awq_matmul_a8,
    gptq_matmul, gptq_matmul_a8, quantize_activations_int8)

rs = np.random.RandomState(11)


def make_gptq(bits, group_size, K, N, m, dtype=np.float32):
    pack = 32 // bits
    G = K // group_size
    params = {
        "qweight": jnp.asarray(rs.randint(
            -2**31, 2**31, (K // pack, N), dtype=np.int32)),
        "qzeros": jnp.asarray(rs.randint(
            -2**31, 2**31, (G, N // pack), dtype=np.int32)),
        "scales": jnp.asarray(
            rs.rand(G, N).astype(dtype) * 0.1 + 0.01),
        "g_idx": jnp.asarray(
            (np.arange(K) // group_size).astype(np.int32)),
    }
    return params, jnp.asarray(rs.randn(m, K).astype(dtype))


def make_awq(group_size, K, N, m, dtype=np.float32):
    G = K // group_size
    params = {
        "qweight": jnp.asarray(rs.randint(
            -2**31, 2**31, (K, N // 8), dtype=np.int32)),
        "qzeros": jnp.asarray(rs.randint(
            -2**31, 2**31, (G, N // 8), dtype=np.int32)),
        "scales": jnp.asarray(
            rs.rand(G, N).astype(dtype) * 0.1 + 0.01),
    }
    return params, jnp.asarray(rs.randn(m, K).astype(dtype))


def _gptq_dequant(params, group_size):
    method = GPTQLinearMethod(GPTQConfig(4, 128))
    method.config.group_size = group_size
    return method.dequantize(params, jnp.float32)


def _a8_oracle(x, w_dequant):
    x8, xs = _quantize_activations_int8(x)
    return np.asarray((x8.astype(jnp.float32) * xs) @ w_dequant)


def _rel(ref, got):
    return np.abs(ref - got).max() / (np.abs(ref).max() + 1e-9)


# -------------------------------------------------- parity: W4A16 --

@pytest.mark.parametrize("m", [1, 8, 64])
@pytest.mark.parametrize("gs,K", [(128, 512), (128, 384), (64, 384)])
def test_gptq_stream_matches_classic(m, gs, K):
    """Streamed vs classic grid vs the dequantize oracle (W4A16):
    identical integer dequant, f32 accumulation differing only in
    tile-boundary summation order."""
    params, x = make_gptq(4, gs, K, 256, m)
    ref = np.asarray(x @ _gptq_dequant(params, gs))
    got = {}
    for stream in (False, True):
        got[stream] = np.asarray(gptq_matmul(
            x, params["qweight"], params["qzeros"], params["scales"],
            bits=4, group_size=gs, interpret=True, stream=stream))
        assert _rel(ref, got[stream]) < 2e-5, (stream,
                                               _rel(ref, got[stream]))
    assert _rel(got[False], got[True]) < 1e-4


@pytest.mark.parametrize("m", [1, 8, 64])
@pytest.mark.parametrize("gs,K", [(128, 512), (128, 384), (64, 384)])
def test_awq_stream_matches_classic(m, gs, K):
    """Same contract for the AWQ lane-plane layout (min block_n 1024,
    plane-major output un-permute)."""
    params, x = make_awq(gs, K, 1024, m)
    method = AWQLinearMethod(AWQConfig(4, gs))
    ref = np.asarray(x @ method.dequantize(params, jnp.float32))
    got = {}
    for stream in (False, True):
        got[stream] = np.asarray(awq_matmul(
            x, params["qweight"], params["qzeros"], params["scales"],
            group_size=gs, interpret=True, stream=stream))
        assert _rel(ref, got[stream]) < 2e-5, (stream,
                                               _rel(ref, got[stream]))
    assert _rel(got[False], got[True]) < 1e-4


# ----------------------------------- parity: W4A8, deferred on/off --

@pytest.mark.parametrize("m", [1, 8, 64])
@pytest.mark.parametrize("deferred", [False, True])
@pytest.mark.parametrize("gs,K", [(128, 384), (64, 384), (128, 512)])
def test_gptq_a8_stream_parity(m, deferred, gs, K):
    """Streamed W4A8 (int8 activations): both accumulation variants
    ride the ring — int32 group dots are exact, so streamed vs
    classic agree to f32 summation order, and both sit inside the
    W4A8 tolerance vs the dequantize oracle."""
    params, x = make_gptq(4, gs, K, 256, m)
    oracle = _a8_oracle(x, _gptq_dequant(params, gs))
    got = {}
    for stream in (False, True):
        got[stream] = np.asarray(gptq_matmul_a8(
            x, params["qweight"], params["qzeros"], params["scales"],
            bits=4, group_size=gs, interpret=True,
            deferred=deferred, stream=stream))
        assert _rel(oracle, got[stream]) < 2e-2, (stream, deferred)
    assert _rel(got[False], got[True]) < 1e-4


@pytest.mark.parametrize("m", [1, 48, 64, 65])
@pytest.mark.parametrize("deferred", [False, True])
@pytest.mark.parametrize("gs,K", [(128, 384), (-1, 256)])
def test_gptq_a8_bytes_bit_equal_planes_streamed(m, deferred, gs, K):
    """The streamed grid's W4A8 call as it is served (4-bit words: the
    int8 operand from `_unpack_bytes`) against its `_unpack_planes`
    arm, the kernel as it was: bit-equal, both rescales, one group a
    tile and one group a matrix (65 rows are one m tile too, so the
    keyword can send them down the streamed grid)."""
    params, x = make_gptq(4, K if gs == -1 else gs, K, 256, m)
    got = {unpack: np.asarray(gptq_matmul_a8(
        x, params["qweight"], params["qzeros"], params["scales"],
        bits=4, group_size=gs, interpret=True, stream=True,
        deferred=deferred, unpack=unpack))
        for unpack in (None, "planes", "bytes")}
    assert np.isfinite(got[None]).all() and np.abs(got[None]).max() > 0.1
    np.testing.assert_array_equal(got["planes"], got["bytes"])
    np.testing.assert_array_equal(got[None], got["bytes"])


@pytest.mark.parametrize("m", [1, 8, 64])
@pytest.mark.parametrize("deferred", [False, True])
@pytest.mark.parametrize("gs,K", [(128, 384), (64, 384), (128, 512)])
def test_awq_a8_stream_parity(m, deferred, gs, K):
    params, x = make_awq(gs, K, 1024, m)
    method = AWQLinearMethod(AWQConfig(4, gs))
    oracle = _a8_oracle(x, method.dequantize(params, jnp.float32))
    got = {}
    for stream in (False, True):
        got[stream] = np.asarray(awq_matmul_a8(
            x, params["qweight"], params["qzeros"], params["scales"],
            group_size=gs, interpret=True,
            deferred=deferred, stream=stream))
        assert _rel(oracle, got[stream]) < 2e-2, (stream, deferred)
    assert _rel(got[False], got[True]) < 1e-4


# --------------------------------------------- selection + flags --

def test_stream_resolution():
    """Explicit arg wins; else the rule by m: taken at m <= 64
    (decode / bs=1 bursts), not above."""
    assert _resolve_stream(True, 8192) and not _resolve_stream(False, 1)
    assert _resolve_stream(None, 1)
    assert _resolve_stream(None, 64)
    assert not _resolve_stream(None, 65)


def test_stream_pf_bad_value_warns_and_defaults(monkeypatch):
    """The ring depth is read per CALL through the registry's
    non-strict path: a malformed (or too-small) value warns and falls
    back to the default double buffer — it must never kill the call,
    and a fortiori never the import (the PR-2 ATTN_PF lesson)."""
    monkeypatch.setenv("APHRODITE_QMM_STREAM_PF", "banana")
    with pytest.warns(RuntimeWarning, match="APHRODITE_QMM_STREAM_PF"):
        assert _stream_pf() == 2
    monkeypatch.setenv("APHRODITE_QMM_STREAM_PF", "1")
    with pytest.warns(RuntimeWarning, match="APHRODITE_QMM_STREAM_PF"):
        assert _stream_pf() == 2
    # end-to-end: the streamed call still computes, with a warning
    monkeypatch.setenv("APHRODITE_QMM_STREAM_PF", "not-a-depth")
    params, x = make_gptq(4, 128, 256, 256, 3)
    ref = np.asarray(x @ _gptq_dequant(params, 128))
    with pytest.warns(RuntimeWarning, match="APHRODITE_QMM_STREAM_PF"):
        got = np.asarray(gptq_matmul(
            x, params["qweight"], params["qzeros"], params["scales"],
            bits=4, group_size=128, interpret=True, stream=True))
    assert _rel(ref, got) < 2e-5


@pytest.mark.parametrize("depth", ["2", "3", "4"])
def test_stream_pf_depth_sweep(monkeypatch, depth):
    """Deeper rings change only the prefetch distance, never the
    result (every cell waits its own item's copies). Shapes are
    depth-unique so each depth gets its own trace (per-call env
    reads happen at trace time under jit)."""
    K = {"2": 512, "3": 384, "4": 256}[depth]
    monkeypatch.setenv("APHRODITE_QMM_STREAM_PF", depth)
    params, x = make_gptq(4, 128, K, 512, 8)
    ref = np.asarray(x @ _gptq_dequant(params, 128))
    got = np.asarray(gptq_matmul(
        x, params["qweight"], params["qzeros"],
        params["scales"], bits=4, group_size=128,
        interpret=True, stream=True))
    assert _rel(ref, got) < 2e-5, depth


# ------------------------------------------- deep-k VMEM-fit guard --

def test_clamp_k_vmem_steps_down():
    """The footprint pre-check (mirroring _deferred_fits) halves
    block_k until the tile set fits — staying a multiple of gs — and
    leaves fitting tile sets alone."""
    fp = lambda bk: _cell_bytes(
        bk, layout="gptq", block_m=512, block_n=2048, gs=128, pack=8,
        x_bytes=1, s_bytes=2, K=4096, stream_slots=0, deferred=False,
        a16=False)
    assert _clamp_k_vmem(4096, 128, fp, tag="test") < 4096
    clamped = _clamp_k_vmem(4096, 128, fp, tag="test")
    assert clamped % 128 == 0 and fp(clamped) <= 16 << 20
    assert _clamp_k_vmem(1024, 128, fp, tag="test") == 1024


def test_oversized_block_k_env_clamps(monkeypatch):
    """An earlier sweep's note: APHRODITE_QMM_BLOCK_K=4096 used to
    fail the Mosaic compile at the prefill geometry; the prologue's
    footprint pre-check now steps the cap down instead. Checked at
    the tile-sizing layer (the full 512x4096x2048 matmul is too slow
    for interpret mode)."""
    from aphrodite_tpu.ops.pallas.quant_matmul import _gptq_prologue
    monkeypatch.setenv("APHRODITE_QMM_BLOCK_K", "4096")
    x8 = jnp.zeros((512, 4096), jnp.int8)        # one prefill round
    qzeros = jnp.zeros((32, 2048 // 8), jnp.int32)
    scales = jnp.ones((32, 2048), jnp.bfloat16)
    _, _, _, tiles = _gptq_prologue(x8, qzeros, scales, 2048, 4, 128,
                                    jnp.bfloat16)
    block_k = tiles[2]
    assert block_k == 2048, block_k    # stepped down from the env 4096
    # and a small end-to-end call under the same env stays correct
    params, x = make_gptq(4, 128, 512, 256, 16)
    oracle = _a8_oracle(x, _gptq_dequant(params, 128))
    got = np.asarray(gptq_matmul_a8(
        x, params["qweight"], params["qzeros"], params["scales"],
        bits=4, group_size=128, interpret=True, stream=False))
    assert _rel(oracle, got) < 2e-2


# ------------------- double-buffered flush + folded prologue (r7) --

@pytest.mark.parametrize("m", [1, 8, 64])
@pytest.mark.parametrize("layout", ["gptq", "awq"])
@pytest.mark.parametrize("a8,deferred", [(False, False), (True, False),
                                         (True, True)])
def test_parity_plane_flush_multi_column(m, layout, a8, deferred):
    """The ISSUE-14 flush-parity matrix at a >= 3 x 3 (n, k) work
    list: the column-parity accumulator planes alternate across >= 3
    column runs (plane reuse, not just ping-pong once) over the K=384
    tail (three single-group k-tiles), for gptq AND awq, a16 and a8,
    deferred rescale on and off."""
    gs, K = 128, 384
    if layout == "gptq":
        N = 384                      # block_n 128 -> 3 column runs
        params, x = make_gptq(4, gs, K, N, m)
        ref = np.asarray(x @ _gptq_dequant(params, gs))
        if a8:
            ref = _a8_oracle(x, _gptq_dequant(params, gs))
            fn = lambda stream: gptq_matmul_a8(
                x, params["qweight"], params["qzeros"],
                params["scales"], bits=4, group_size=gs,
                interpret=True, deferred=deferred, stream=stream)
        else:
            fn = lambda stream: gptq_matmul(
                x, params["qweight"], params["qzeros"],
                params["scales"], bits=4, group_size=gs,
                interpret=True, stream=stream)
    else:
        N = 3072                     # block_n 1024 -> 3 column runs
        params, x = make_awq(gs, K, N, m)
        method = AWQLinearMethod(AWQConfig(4, gs))
        ref = np.asarray(x @ method.dequantize(params, jnp.float32))
        if a8:
            ref = _a8_oracle(x, method.dequantize(params, jnp.float32))
            fn = lambda stream: awq_matmul_a8(
                x, params["qweight"], params["qzeros"],
                params["scales"], group_size=gs, interpret=True,
                deferred=deferred, stream=stream)
        else:
            fn = lambda stream: awq_matmul(
                x, params["qweight"], params["qzeros"],
                params["scales"], group_size=gs, interpret=True,
                stream=stream)
    tol = 2e-2 if a8 else 2e-5
    got_c = np.asarray(fn(False))
    got_s = np.asarray(fn(True))
    assert _rel(ref, got_c) < tol
    assert _rel(ref, got_s) < tol
    assert _rel(got_c, got_s) < 1e-4


@pytest.mark.parametrize("m", [1, 8, 64])
def test_folded_prologue_quantization_parity(m):
    """The FOLD001 closure contract: the streamed a8 kernel quantizes
    its RESIDENT activation block in the prologue (absmax over the
    permuted rows — permutation-invariant, so identical row scales)
    and must agree with the classic grid fed by the HOST
    `_quantize_activations_int8` to f32 summation order."""
    gs, K, N = 128, 384, 256
    params, x = make_gptq(4, gs, K, N, m)
    host = np.asarray(gptq_matmul_a8(
        x, params["qweight"], params["qzeros"], params["scales"],
        bits=4, group_size=gs, interpret=True, stream=False))
    folded = np.asarray(gptq_matmul_a8(
        x, params["qweight"], params["qzeros"], params["scales"],
        bits=4, group_size=gs, interpret=True, stream=True))
    assert _rel(host, folded) < 1e-4
    oracle = _a8_oracle(x, _gptq_dequant(params, gs))
    assert _rel(oracle, folded) < 2e-2


def test_fused_quantize_kernel_matches_reference_chain():
    """quantize_activations_int8 (the fused one-pass Pallas kernel the
    classic grids use) reproduces the jnp reference chain: int8 codes
    exactly, row scales to 1 ulp (the in-kernel divide may lower as a
    reciprocal multiply) — including the padded-m slice."""
    for m, K in ((1, 256), (5, 384), (48, 512)):
        x = jnp.asarray(rs.randn(m, K).astype(np.float32))
        x8_ref, xs_ref = _quantize_activations_int8(x)
        x8_k, xs_k = quantize_activations_int8(x, interpret=True)
        np.testing.assert_array_equal(np.asarray(x8_ref),
                                      np.asarray(x8_k))
        np.testing.assert_allclose(np.asarray(xs_ref),
                                   np.asarray(xs_k), rtol=2e-7)
