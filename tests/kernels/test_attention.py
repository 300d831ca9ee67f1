"""Attention op tests: jnp implementations vs a numpy oracle that walks
block tables in Python (mirrors the reference's
ref_single_query_cached_kv_attention, tests/kernels/test_attention.py:45-99),
plus the Pallas kernel in interpret mode vs the jnp reference.

These tests pin the CLASSIC padded (batch, head-block) grid (the
APHRODITE_ATTN_RAGGED=0 fallback); the ragged work-list grid and the
routing/config satellites are covered in test_ragged_attention.py.

KV pages are TOKEN-MAJOR: [num_pages, page_size, Hkv * head_dim]
(heads collapsed into lanes — see ops/kv_cache.py)."""
import jax.numpy as jnp
import numpy as np
import pytest

from aphrodite_tpu.ops.attention import (paged_decode_attention_ref,
                                         prefill_attention)
from aphrodite_tpu.ops.pallas.paged_attention import paged_decode_attention


def numpy_paged_attention(q, k_pages, v_pages, block_tables, context_lens,
                          scale, alibi_slopes=None):
    """Oracle: per-sequence python loop over the block table."""
    batch, num_q_heads, dim = q.shape
    _, page_size, hd = k_pages.shape
    num_kv_heads = hd // dim
    group = num_q_heads // num_kv_heads
    out = np.zeros_like(q, dtype=np.float32)
    for b in range(batch):
        ctx = int(context_lens[b])
        keys, values = [], []
        for pos in range(ctx):
            page = block_tables[b][pos // page_size]
            off = pos % page_size
            keys.append(k_pages[page, off].reshape(num_kv_heads, dim))
            values.append(v_pages[page, off].reshape(num_kv_heads, dim))
        keys = np.stack(keys, axis=1)     # [Hkv, ctx, dim]
        values = np.stack(values, axis=1)
        for h in range(num_q_heads):
            kv_h = h // group
            scores = keys[kv_h] @ q[b, h] * scale  # [ctx]
            if alibi_slopes is not None:
                scores = scores + alibi_slopes[h] * np.arange(ctx)
            scores = scores - scores.max()
            probs = np.exp(scores) / np.exp(scores).sum()
            out[b, h] = probs @ values[kv_h]
    return out


def make_problem(batch=3, num_q_heads=4, num_kv_heads=2, dim=32,
                 pages=16, page_size=4, pages_per_seq=8, seed=0):
    rng = np.random.default_rng(seed)
    q = rng.normal(size=(batch, num_q_heads, dim)).astype(np.float32)
    k_pages = rng.normal(size=(pages, page_size,
                               num_kv_heads * dim)).astype(np.float32)
    v_pages = rng.normal(size=(pages, page_size,
                               num_kv_heads * dim)).astype(np.float32)
    context_lens = rng.integers(1, pages_per_seq * page_size,
                                size=(batch, )).astype(np.int32)
    block_tables = np.zeros((batch, pages_per_seq), dtype=np.int32)
    for b in range(batch):
        n_pages = -(-int(context_lens[b]) // page_size)
        # Distinct pages per sequence, as the block manager guarantees.
        block_tables[b, :n_pages] = rng.choice(pages, n_pages,
                                               replace=False)
    return q, k_pages, v_pages, block_tables, context_lens


@pytest.mark.parametrize("num_q_heads,num_kv_heads", [(4, 4), (4, 2), (8, 1)])
def test_paged_decode_ref_matches_oracle(num_q_heads, num_kv_heads):
    q, k_pages, v_pages, bt, ctx = make_problem(num_q_heads=num_q_heads,
                                                num_kv_heads=num_kv_heads)
    scale = 0.3
    expected = numpy_paged_attention(q, k_pages, v_pages, bt, ctx, scale)
    got = paged_decode_attention_ref(jnp.array(q), jnp.array(k_pages),
                                     jnp.array(v_pages), jnp.array(bt),
                                     jnp.array(ctx), scale)
    np.testing.assert_allclose(np.array(got), expected, rtol=2e-5, atol=2e-5)


def test_paged_decode_ref_alibi():
    q, k_pages, v_pages, bt, ctx = make_problem(num_q_heads=4,
                                                num_kv_heads=2)
    slopes = np.array([0.5, 0.25, 0.125, 0.0625], dtype=np.float32)
    expected = numpy_paged_attention(q, k_pages, v_pages, bt, ctx, 0.5,
                                     alibi_slopes=slopes)
    got = paged_decode_attention_ref(jnp.array(q), jnp.array(k_pages),
                                     jnp.array(v_pages), jnp.array(bt),
                                     jnp.array(ctx), 0.5,
                                     alibi_slopes=jnp.array(slopes))
    np.testing.assert_allclose(np.array(got), expected, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("num_q_heads,num_kv_heads,pages_per_chunk",
                         [(4, 4, 2), (4, 2, 4), (8, 1, 8), (8, 2, 1),
                          (32, 8, 4), (32, 32, 4), (12, 12, 2)])
def test_pallas_decode_matches_oracle(num_q_heads, num_kv_heads,
                                      pages_per_chunk):
    """The token-major kernel across GQA/MHA/head-block shapes
    (hb = 8 for H=8/32, hb = 6 for H=12, hb = H for small H).

    Tolerance 1e-2 across this file's pallas-vs-f32-oracle checks: the
    kernel's dot operands are bf16 (f32 accumulation) — the same
    numeric class as the reference CUDA kernel's half operands
    (`kernels/attention/attention_kernels.cu`), bounded by one bf16
    rounding (2^-8) per operand against the f32 numpy oracle."""
    q, k_pages, v_pages, bt, ctx = make_problem(num_q_heads=num_q_heads,
                                                num_kv_heads=num_kv_heads,
                                                dim=128, page_size=8,
                                                pages_per_seq=8, pages=32)
    scale = 1.0 / np.sqrt(128)
    expected = numpy_paged_attention(q, k_pages, v_pages, bt, ctx, scale)
    got = paged_decode_attention(jnp.array(q), jnp.array(k_pages),
                                 jnp.array(v_pages), jnp.array(bt),
                                 jnp.array(ctx),
                                 scale=scale,
                                 pages_per_chunk=pages_per_chunk,
                                 interpret=True)
    np.testing.assert_allclose(np.array(got), expected, rtol=1e-2, atol=1e-2)


def test_pallas_decode_short_context():
    """ctx=1 (single token) exercises the masked single-page case."""
    q, k_pages, v_pages, bt, ctx = make_problem(dim=128, page_size=8,
                                                pages_per_seq=8, pages=32)
    ctx = np.ones_like(ctx)
    expected = numpy_paged_attention(q, k_pages, v_pages, bt, ctx, 0.1)
    got = paged_decode_attention(jnp.array(q), jnp.array(k_pages),
                                 jnp.array(v_pages), jnp.array(bt),
                                 jnp.array(ctx), scale=0.1,
                                 pages_per_chunk=2, interpret=True)
    np.testing.assert_allclose(np.array(got), expected, rtol=1e-2, atol=1e-2)


def test_pallas_decode_single_chunk_cross_cell():
    """pages_per_seq == pages_per_chunk triggers the cross-cell
    prefetch pipeline; ctx == 0 rows must stay zero (their DMAs are
    started by the previous cell and must still be waited)."""
    q, k_pages, v_pages, bt, ctx = make_problem(batch=5, num_q_heads=8,
                                                num_kv_heads=2, dim=128,
                                                page_size=8,
                                                pages_per_seq=8, pages=32)
    ctx = ctx.copy()
    ctx[1] = 0
    expected = numpy_paged_attention(q, k_pages, v_pages, bt,
                                     np.maximum(ctx, 1), 0.1)
    expected[1] = 0.0
    got = paged_decode_attention(jnp.array(q), jnp.array(k_pages),
                                 jnp.array(v_pages), jnp.array(bt),
                                 jnp.array(ctx), scale=0.1,
                                 pages_per_chunk=8, interpret=True)
    got = np.array(got)
    np.testing.assert_allclose(got[1], 0.0, atol=1e-6)
    mask = np.arange(len(ctx)) != 1
    np.testing.assert_allclose(got[mask], expected[mask], rtol=1e-2,
                               atol=1e-2)


def numpy_prefill(q, k, v, context_lens, kv_valid, scale, window=None,
                  slopes=None):
    b, s, Hq, d = q.shape
    Hkv = k.shape[2]
    group = Hq // Hkv
    out = np.zeros_like(q, dtype=np.float32)
    for bi in range(b):
        for h in range(Hq):
            kh = h // group
            for i in range(s):
                abs_q = context_lens[bi] + i
                scores = []
                idxs = []
                for t in range(int(kv_valid[bi])):
                    if t > abs_q:
                        continue
                    if window is not None and t <= abs_q - window:
                        continue
                    sc = q[bi, i, h] @ k[bi, t, kh] * scale
                    if slopes is not None:
                        sc += slopes[h] * t
                    scores.append(sc)
                    idxs.append(t)
                scores = np.array(scores)
                probs = np.exp(scores - scores.max())
                probs /= probs.sum()
                out[bi, i, h] = sum(p * v[bi, t, kh]
                                    for p, t in zip(probs, idxs))
    return out


@pytest.mark.parametrize("window", [None, 6])
def test_prefill_attention(window):
    rng = np.random.default_rng(3)
    b, s, Hq, Hkv, d = 2, 8, 4, 2, 16
    q = rng.normal(size=(b, s, Hq, d)).astype(np.float32)
    k = rng.normal(size=(b, s, Hkv, d)).astype(np.float32)
    v = rng.normal(size=(b, s, Hkv, d)).astype(np.float32)
    ctx = np.zeros(b, dtype=np.int32)
    kv_valid = np.array([s, s - 3], dtype=np.int32)
    scale = 1 / np.sqrt(d)
    expected = numpy_prefill(q, k, v, ctx, kv_valid, scale, window=window)
    got = prefill_attention(jnp.array(q), jnp.array(k), jnp.array(v),
                            jnp.array(ctx), jnp.array(kv_valid), scale,
                            sliding_window=window)
    # Padded query rows (i >= kv_valid) are unspecified; compare valid only.
    for bi in range(b):
        np.testing.assert_allclose(np.array(got)[bi, :kv_valid[bi]],
                                   expected[bi, :kv_valid[bi]],
                                   rtol=2e-5, atol=2e-5)


def test_prefill_with_prefix_context():
    """Prefix-cached prefill: kv = [prefix ; chunk], context_lens > 0
    (the reference's triton context_attention_fwd case)."""
    rng = np.random.default_rng(4)
    b, s_new, prefix, Hq, Hkv, d = 2, 4, 6, 4, 2, 16
    kv_len = prefix + s_new
    q = rng.normal(size=(b, s_new, Hq, d)).astype(np.float32)
    k = rng.normal(size=(b, kv_len, Hkv, d)).astype(np.float32)
    v = rng.normal(size=(b, kv_len, Hkv, d)).astype(np.float32)
    ctx = np.full(b, prefix, dtype=np.int32)
    kv_valid = np.full(b, kv_len, dtype=np.int32)
    scale = 1 / np.sqrt(d)
    expected = numpy_prefill(q, k, v, ctx, kv_valid, scale)
    got = prefill_attention(jnp.array(q), jnp.array(k), jnp.array(v),
                            jnp.array(ctx), jnp.array(kv_valid), scale)
    np.testing.assert_allclose(np.array(got), expected, rtol=2e-5,
                               atol=2e-5)


def test_pallas_decode_int8_kv_scale():
    """int8 KV pages with the scale folded into score/epilogue must
    match the float oracle on the dequantized values."""
    q, k_pages, v_pages, bt, ctx = make_problem(num_q_heads=8,
                                                num_kv_heads=2,
                                                dim=128, page_size=8,
                                                pages_per_seq=8, pages=32)
    S = 0.05
    k_int = np.clip(np.round(k_pages / S), -127, 127).astype(np.int8)
    v_int = np.clip(np.round(v_pages / S), -127, 127).astype(np.int8)
    scale = 1.0 / np.sqrt(128)
    expected = numpy_paged_attention(q, k_int.astype(np.float32) * S,
                                     v_int.astype(np.float32) * S,
                                     bt, ctx, scale)
    got = paged_decode_attention(
        jnp.array(q), jnp.array(k_int), jnp.array(v_int),
        jnp.array(bt), jnp.array(ctx), scale=scale, kv_scale=S,
        pages_per_chunk=4, interpret=True)
    np.testing.assert_allclose(np.array(got), expected, rtol=1e-2,
                               atol=1e-2)


def test_pallas_decode_alibi():
    """In-kernel ALiBi bias matches the numpy oracle."""
    q, k_pages, v_pages, bt, ctx = make_problem(num_q_heads=8,
                                                num_kv_heads=2,
                                                dim=128, page_size=8,
                                                pages_per_seq=8, pages=32)
    slopes = np.array([2.0 ** -(i + 1) for i in range(8)],
                      dtype=np.float32)
    scale = 1.0 / np.sqrt(128)
    expected = numpy_paged_attention(q, k_pages, v_pages, bt, ctx, scale,
                                     alibi_slopes=slopes)
    got = paged_decode_attention(jnp.array(q), jnp.array(k_pages),
                                 jnp.array(v_pages),
                                 jnp.array(bt), jnp.array(ctx),
                                 jnp.array(slopes),
                                 scale=scale, pages_per_chunk=4,
                                 interpret=True)
    np.testing.assert_allclose(np.array(got), expected, rtol=1e-2,
                               atol=1e-2)


@pytest.mark.parametrize("num_q_heads,num_kv_heads,pages_per_chunk", [
    (8, 2, 4),         # multi-chunk
    (8, 2, 8),         # single-chunk cross-cell pipeline
    (32, 8, 8),        # GQA n_hb=1
    (8, 8, 4),         # MHA-ish (n_hb=1, hb=8)
])
def test_pallas_decode_fused_write(num_q_heads, num_kv_heads,
                                   pages_per_chunk):
    """knew/vnew injection: the kernel must produce the same attention
    output as write-then-attend AND leave the pages identically
    updated."""
    from aphrodite_tpu.ops.kv_cache import write_to_kv_cache
    q, k_pages, v_pages, bt, ctx = make_problem(
        num_q_heads=num_q_heads, num_kv_heads=num_kv_heads, dim=128,
        page_size=8, pages_per_seq=8, pages=64, batch=4)
    rng = np.random.default_rng(11)
    B = q.shape[0]
    d = 128
    # The engine guarantees pages are globally sequence-exclusive; the
    # fused write relies on it (make_problem only dedups WITHIN a row).
    perm = rng.permutation(k_pages.shape[0])
    for b in range(B):
        n_pages = -(-int(ctx[b]) // 8)
        bt[b, :n_pages] = perm[b * 8:b * 8 + n_pages]
    # ctx includes the new token (write-then-attend convention); make
    # one row a padded (ctx=0) lane.
    ctx = ctx.copy()
    ctx[1] = 0
    knew = rng.normal(size=(B, num_kv_heads, d)).astype(np.float32)
    vnew = rng.normal(size=(B, num_kv_heads, d)).astype(np.float32)
    slots = np.full((B,), k_pages.shape[0] * 8, dtype=np.int32)
    for b in range(B):
        if ctx[b] > 0:
            pos = ctx[b] - 1
            slots[b] = bt[b][pos // 8] * 8 + pos % 8

    ref_k, ref_v = write_to_kv_cache(
        jnp.asarray(knew), jnp.asarray(vnew), jnp.asarray(k_pages),
        jnp.asarray(v_pages), jnp.asarray(slots))
    want = numpy_paged_attention(q, np.asarray(ref_k),
                                 np.asarray(ref_v), bt,
                                 np.maximum(ctx, 1), 0.1)
    want[ctx == 0] = 0.0

    out, got_k, got_v = paged_decode_attention(
        jnp.asarray(q), jnp.asarray(k_pages), jnp.asarray(v_pages),
        jnp.asarray(bt), jnp.asarray(ctx), None,
        jnp.asarray(knew), jnp.asarray(vnew), scale=0.1,
        pages_per_chunk=pages_per_chunk, interpret=True)
    got = np.asarray(out)
    mask = ctx > 0
    np.testing.assert_allclose(got[mask], want[mask], rtol=1e-2,
                               atol=1e-2)
    np.testing.assert_allclose(got[~mask], 0.0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(got_k), np.asarray(ref_k),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(got_v), np.asarray(ref_v),
                               atol=1e-6)


def test_pallas_decode_fused_write_int8():
    """Fused write with int8 pages quantizes the injected token into
    stored units."""
    from aphrodite_tpu.ops.kv_cache import write_to_kv_cache
    q, k_pages, v_pages, bt, ctx = make_problem(
        num_q_heads=8, num_kv_heads=2, dim=128, page_size=8,
        pages_per_seq=8, pages=32, batch=3)
    S = 0.05
    kp8 = np.clip(np.round(k_pages / S), -127, 127).astype(np.int8)
    vp8 = np.clip(np.round(v_pages / S), -127, 127).astype(np.int8)
    rng = np.random.default_rng(12)
    B = q.shape[0]
    knew = rng.normal(size=(B, 2, 128)).astype(np.float32)
    vnew = rng.normal(size=(B, 2, 128)).astype(np.float32)
    slots = np.zeros((B,), dtype=np.int32)
    for b in range(B):
        pos = ctx[b] - 1
        slots[b] = bt[b][pos // 8] * 8 + pos % 8
    ref_k, ref_v = write_to_kv_cache(
        jnp.asarray(knew), jnp.asarray(vnew), jnp.asarray(kp8),
        jnp.asarray(vp8), jnp.asarray(slots), kv_scale=S)
    want = numpy_paged_attention(
        q, np.asarray(ref_k, np.float32) * S,
        np.asarray(ref_v, np.float32) * S, bt, ctx, 0.1)
    out, got_k, got_v = paged_decode_attention(
        jnp.asarray(q), jnp.asarray(kp8), jnp.asarray(vp8),
        jnp.asarray(bt), jnp.asarray(ctx), None,
        jnp.asarray(knew), jnp.asarray(vnew), scale=0.1, kv_scale=S,
        pages_per_chunk=4, interpret=True)
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-2,
                               atol=1e-2)
    np.testing.assert_array_equal(np.asarray(got_k), np.asarray(ref_k))
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(ref_v))


@pytest.mark.parametrize("d_true", [64, 80, 96])
def test_pallas_decode_padded_head(d_true):
    """Head sizes below the 128-lane tile run with zero-padded pages
    (ops/kv_cache.padded_head_size): pad lanes are inert in scores and
    sliced off the output."""
    dp = 128
    rng = np.random.default_rng(7)
    batch, Hq, Hkv = 3, 8, 2
    pages, page_size, pps = 32, 8, 8
    q = rng.normal(size=(batch, Hq, d_true)).astype(np.float32)
    k4 = rng.normal(size=(pages, page_size, Hkv, d_true)).astype(
        np.float32)
    v4 = rng.normal(size=(pages, page_size, Hkv, d_true)).astype(
        np.float32)
    ctx = rng.integers(1, pps * page_size, size=(batch,)).astype(np.int32)
    bt = np.zeros((batch, pps), dtype=np.int32)
    for b in range(batch):
        n = -(-int(ctx[b]) // page_size)
        bt[b, :n] = rng.choice(pages, n, replace=False)
    scale = 1.0 / np.sqrt(d_true)
    expected = numpy_paged_attention(
        q, k4.reshape(pages, page_size, -1),
        v4.reshape(pages, page_size, -1), bt, ctx, scale)
    qp = np.pad(q, ((0, 0), (0, 0), (0, dp - d_true)))
    kp = np.pad(k4, ((0, 0), (0, 0), (0, 0), (0, dp - d_true))).reshape(
        pages, page_size, -1)
    vp = np.pad(v4, ((0, 0), (0, 0), (0, 0), (0, dp - d_true))).reshape(
        pages, page_size, -1)
    got = paged_decode_attention(jnp.array(qp), jnp.array(kp),
                                 jnp.array(vp), jnp.array(bt),
                                 jnp.array(ctx), scale=scale,
                                 pages_per_chunk=4, interpret=True)
    np.testing.assert_allclose(np.array(got)[..., :d_true], expected,
                               rtol=1e-2, atol=1e-2)


def test_paged_attention_layer_pads_small_heads():
    """PagedAttention end-to-end with head 64: the layer pads writes,
    q, and slices the output; cache pages carry the padded lane dim."""
    from aphrodite_tpu.modeling.input_metadata import InputMetadata
    from aphrodite_tpu.modeling.layers.attention import PagedAttention
    from aphrodite_tpu.ops.kv_cache import padded_head_size
    rng = np.random.default_rng(3)
    B, H, Hkv, d = 2, 4, 2, 64
    dp = padded_head_size(d)
    assert dp == 128
    page_size, num_pages = 8, 16
    layer = PagedAttention(H, d, d ** -0.5, num_kv_heads=Hkv)
    k_pages = jnp.zeros((num_pages, page_size, Hkv * dp), jnp.float32)
    v_pages = jnp.zeros((num_pages, page_size, Hkv * dp), jnp.float32)

    # Prefill 5 tokens, then decode 1: compare against the ref decode
    # over an unpadded cache.
    seq = 5
    tables = np.array([[1, 2], [3, 4]], dtype=np.int32)
    slots = np.array([[t * page_size + p for p in range(seq)]
                      for t in (1, 3)], dtype=np.int32).reshape(-1)
    meta = InputMetadata(
        slot_mapping=jnp.asarray(slots),
        block_tables=jnp.asarray(tables),
        context_lens=jnp.zeros((B,), jnp.int32),
        prompt_lens=jnp.full((B,), seq, jnp.int32),
        is_prompt=True)
    qkv = rng.normal(size=(3, B, seq)).astype(np.float32)
    q = np.repeat(qkv[0][..., None], H * d, axis=-1) * 0.1
    k = np.repeat(qkv[1][..., None], Hkv * d, axis=-1) * 0.1
    v = np.repeat(qkv[2][..., None], Hkv * d, axis=-1) * 0.1
    out, k_pages, v_pages = layer(jnp.asarray(q), jnp.asarray(k),
                                  jnp.asarray(v), k_pages, v_pages, meta)
    assert out.shape == (B, seq, H * d)
    assert k_pages.shape[-1] == Hkv * dp
    # Written pages hold the true values in each head's first d lanes,
    # zeros in the pad lanes.
    kp_np = np.asarray(k_pages).reshape(num_pages, page_size, Hkv, dp)
    assert np.allclose(kp_np[..., d:], 0.0)
    k_true = k.reshape(B, seq, Hkv, d)
    assert np.allclose(kp_np[1, :seq, :, :d], k_true[0], atol=1e-6)

    # Decode step matches the unpadded jnp reference.
    qd = rng.normal(size=(B, 1, H * d)).astype(np.float32) * 0.1
    kd = rng.normal(size=(B, 1, Hkv * d)).astype(np.float32) * 0.1
    vd = rng.normal(size=(B, 1, Hkv * d)).astype(np.float32) * 0.1
    meta_d = InputMetadata(
        slot_mapping=jnp.asarray(
            np.array([1 * page_size + seq, 3 * page_size + seq],
                     dtype=np.int32)),
        block_tables=jnp.asarray(tables),
        context_lens=jnp.full((B,), seq + 1, jnp.int32),
        is_prompt=False)
    out_d, k_pages, v_pages = layer(jnp.asarray(qd), jnp.asarray(kd),
                                    jnp.asarray(vd), k_pages, v_pages,
                                    meta_d)
    assert out_d.shape == (B, 1, H * d)
    # Build unpadded pages for the reference.
    kp_un = np.asarray(k_pages).reshape(
        num_pages, page_size, Hkv, dp)[..., :d].reshape(
        num_pages, page_size, -1)
    vp_un = np.asarray(v_pages).reshape(
        num_pages, page_size, Hkv, dp)[..., :d].reshape(
        num_pages, page_size, -1)
    ref = paged_decode_attention_ref(
        jnp.asarray(qd.reshape(B, H, d)),
        jnp.asarray(kp_un), jnp.asarray(vp_un),
        jnp.asarray(tables), jnp.full((B,), seq + 1, jnp.int32),
        d ** -0.5)
    np.testing.assert_allclose(np.asarray(out_d).reshape(B, H, d),
                               np.asarray(ref), rtol=1e-4, atol=1e-5)


# ---- keys in blocks under an online softmax ----

@pytest.mark.parametrize("window,slopes", [(None, False), (9, False),
                                           (None, True)],
                         ids=["full", "window", "alibi"])
@pytest.mark.parametrize("kv_len", [32, 37])
def test_blocked_prefill_is_plain_prefill(window, slopes, kv_len):
    """`prefill_attention_blocked` (the keys a block at a time, what a
    step program takes from `BLOCKED_FROM` queries x keys a row on) is
    `prefill_attention`: against the numpy oracle for a chunk behind a
    cached prefix, a key count that is no multiple of the block, a
    row whose valid keys end early, a window and ALiBi."""
    from aphrodite_tpu.ops.attention import (BLOCKED_FROM,
                                             prefill_attention_blocked)
    assert BLOCKED_FROM > 4096 * 1024      # Mistral's 1,024-token
    # prompts, four a step, keep the plain function
    rng = np.random.default_rng(5)
    b, s_new, Hq, Hkv, d = 2, 8, 4, 2, 16
    prefix = kv_len - s_new
    q = rng.normal(size=(b, s_new, Hq, d)).astype(np.float32)
    k = rng.normal(size=(b, kv_len, Hkv, d)).astype(np.float32)
    v = rng.normal(size=(b, kv_len, Hkv, d)).astype(np.float32)
    ctx = np.array([prefix, prefix - 5], dtype=np.int32)
    kv_valid = ctx + np.array([s_new, s_new - 2], dtype=np.int32)
    alibi = np.array([0.5, 0.25, 0.125, 0.0625], np.float32) \
        if slopes else None
    scale = 1 / np.sqrt(d)
    expected = numpy_prefill(q, k, v, ctx, kv_valid, scale, window=window,
                             slopes=alibi)
    args = (jnp.array(q), jnp.array(k), jnp.array(v), jnp.array(ctx),
            jnp.array(kv_valid), scale)
    kw = dict(sliding_window=window,
              alibi_slopes=None if alibi is None else jnp.array(alibi))
    got = np.array(prefill_attention_blocked(*args, key_block=8, **kw))
    plain = np.array(prefill_attention(*args, **kw))
    for bi, n in enumerate(kv_valid - ctx):
        np.testing.assert_allclose(got[bi, :n], expected[bi, :n],
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(got[bi, :n], plain[bi, :n],
                                   rtol=2e-5, atol=2e-5)
    assert np.isfinite(got).all()


# ---- a causal window over a table that slides ----

def _windowed_oracle(q, kp, vp, bt, ctx, window, scale=0.1):
    """The oracle over the newest `window` positions of each row."""
    out = np.zeros_like(q, dtype=np.float32)
    for b in range(q.shape[0]):
        first = max(0, int(ctx[b]) - window)
        page = kp.shape[1]
        # drop whole pages before `first`, shift the rest
        skip = first // page
        table = bt[b:b + 1, skip:]
        full = numpy_paged_attention(
            q[b:b + 1], kp, vp, table,
            np.array([ctx[b] - skip * page]), scale)
        if first % page == 0:
            out[b] = full[0]
            continue
        # mask the passed keys of the first kept page by hand
        keep = int(ctx[b]) - first
        ks, vs = [], []
        for pos in range(first, int(ctx[b])):
            ks.append(kp[bt[b][pos // page], pos % page])
            vs.append(vp[bt[b][pos // page], pos % page])
        d = q.shape[2]
        ks = np.stack(ks).reshape(keep, -1, d)
        vs = np.stack(vs).reshape(keep, -1, d)
        group = q.shape[1] // ks.shape[1]
        for h in range(q.shape[1]):
            sc = ks[:, h // group] @ q[b, h] * scale
            p = np.exp(sc - sc.max())
            out[b, h] = (p / p.sum()) @ vs[:, h // group]
    return out


@pytest.mark.parametrize("window", [8, 13, 64])
def test_decode_under_a_window_reference_and_kernel(window):
    """A window layer's decode: the row attends over the newest
    `window` positions of its table (which starts at the page that
    holds the oldest of them, or before it), in the jnp reference and
    in the ragged Pallas kernel alike; the window is a mask, not a
    second kernel."""
    from aphrodite_tpu.ops.pallas.paged_attention import \
        build_decode_work_list
    q, kp, vp, bt, ctx = make_problem(
        batch=4, num_q_heads=8, num_kv_heads=2, dim=128, page_size=8,
        pages_per_seq=8, pages=64, seed=7)
    ctx = np.array([1, 23, 40, 64], dtype=np.int32)
    rng = np.random.default_rng(8)
    for b in range(4):
        bt[b] = rng.choice(64, 8, replace=False)
    want = _windowed_oracle(q, kp, vp, bt, ctx, window)
    got = np.asarray(paged_decode_attention_ref(
        jnp.array(q), jnp.array(kp), jnp.array(vp), jnp.array(bt),
        jnp.array(ctx), 0.1, window=window))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    # the kernel is given the table from the first page it needs
    # (`InputMetadata.for_group`): contexts count from that page
    page = 8
    skip = np.maximum(0, ctx - window) // page
    slid = np.stack([np.concatenate([bt[b, skip[b]:],
                                     np.zeros(skip[b], np.int32)])
                     for b in range(4)]).astype(np.int32)
    own = (ctx - skip * page).astype(np.int32)
    work = build_decode_work_list([-(-int(c) // page) for c in own], 2)
    out = np.asarray(paged_decode_attention(
        jnp.array(q), jnp.array(kp), jnp.array(vp), jnp.array(slid),
        jnp.array(own), scale=0.1, pages_per_chunk=2, work_items=work,
        window=window, interpret=True))
    np.testing.assert_allclose(out, want, rtol=1e-2, atol=1e-2)
    if window < 64:
        # without the mask the first kept page's passed keys count
        wide = np.asarray(paged_decode_attention(
            jnp.array(q), jnp.array(kp), jnp.array(vp), jnp.array(slid),
            jnp.array(own), scale=0.1, pages_per_chunk=2,
            work_items=work, interpret=True))
        assert np.abs(wide - want).max() > 0.05
