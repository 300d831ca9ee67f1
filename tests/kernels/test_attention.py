"""Attention op tests: jnp implementations vs a numpy oracle that walks
block tables in Python (mirrors the reference's
ref_single_query_cached_kv_attention, tests/kernels/test_attention.py:45-99),
plus the Pallas kernel in interpret mode vs the jnp reference.

The kernel's calls here pass no work list, so each runs the dense
list of its table width; lists of the rows' own pages and the
routing/config satellites are covered in test_ragged_attention.py.

KV pages are TOKEN-MAJOR: [num_pages, page_size, Hkv * head_dim]
(heads collapsed into lanes — see ops/kv_cache.py)."""
import jax.numpy as jnp
import numpy as np
import pytest

from aphrodite_tpu.ops.attention import (make_causal_mask,
                                         paged_decode_attention_ref,
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


def test_pallas_decode_one_item_a_row():
    """pages_per_seq == pages_per_chunk: every row is one item, the
    prefetch ring runs across rows; a ctx == 0 row must stay zero (its
    one item copies nothing and still writes its output)."""
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
                if not scores:      # a query that sees no key: zeros
                    continue
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


# ---- queries and keys in blocks under an online softmax ----

def _case(s_new, kv_len, ctx, new=None, window=None, slopes=False):
    """One call of the blocked prefill at `key_block` 8: `s_new` padded
    queries a row behind `ctx` cached tokens, of which `new` (default:
    all) are the row's own, against `kv_len` keys."""
    return dict(s_new=s_new, kv_len=kv_len, ctx=ctx,
                new=new or [s_new] * len(ctx), window=window,
                slopes=slopes)


BLOCKED_CASES = {
    # one query block behind a cached prefix: a key count that is no
    # multiple of the block, a row whose valid keys end early
    **{f"{kv_len}-{name}": _case(8, kv_len, [kv_len - 8, kv_len - 13],
                                 [8, 6], window, slopes)
       for kv_len in (32, 37)
       for name, window, slopes in (("full", None, False),
                                    ("window", 9, False),
                                    ("alibi", None, True))},
    # four query blocks: a chunk at once, twice and three times its
    # length into a table padded far past the valid keys
    "chunk-2-of-a-padded-table": _case(32, 160, [32]),
    "chunk-3-of-a-padded-table": _case(32, 160, [64]),
    "chunk-4-of-a-padded-table": _case(32, 160, [96]),
    "first-chunk-own-keys": _case(32, 32, [0, 0], [32, 27]),
    "rows-at-different-contexts": _case(32, 128, [64, 16]),
    "a-pad-row-beside-a-row": _case(32, 96, [48, 0], [32, 0], window=11),
    "window-under-a-block": _case(32, 96, [32, 40], window=5),
    "window-of-a-block": _case(32, 96, [32, 40], window=8),
    "window-over-a-block": _case(32, 96, [32, 40], window=19),
    "window-alibi-blocks": _case(32, 96, [40, 8], window=19, slopes=True),
    "queries-past-the-prompt": _case(32, 64, [24, 0], [32, 13]),
    "queries-past-the-prompt-window": _case(32, 64, [24, 0], [9, 13],
                                            window=12),
    "chunk-under-a-block": _case(5, 32, [20, 3]),
    "chunk-of-no-whole-blocks": _case(20, 64, [24, 40], window=10),
}


@pytest.mark.parametrize("case", BLOCKED_CASES.values(),
                         ids=list(BLOCKED_CASES))
def test_blocked_prefill_is_plain_prefill(case):
    """`prefill_attention_blocked` (tiles of `key_block` queries x
    `key_block` keys, a query block visiting the key blocks it can see:
    what a step program takes from `BLOCKED_FROM` queries x keys a row
    on) is `prefill_attention`, at every query of the padded chunk, and
    the numpy oracle at every query of the prompt."""
    from aphrodite_tpu.ops.attention import (BLOCKED_FROM,
                                             prefill_attention_blocked)
    assert BLOCKED_FROM > 4096 * 1024      # Mistral's 1,024-token
    # prompts, four a step, keep the plain function
    rng = np.random.default_rng(5)
    s_new, kv_len, window = case["s_new"], case["kv_len"], case["window"]
    b, Hq, Hkv, d = len(case["ctx"]), 4, 2, 16
    q = rng.normal(size=(b, s_new, Hq, d)).astype(np.float32)
    k = rng.normal(size=(b, kv_len, Hkv, d)).astype(np.float32)
    v = rng.normal(size=(b, kv_len, Hkv, d)).astype(np.float32)
    ctx = np.array(case["ctx"], dtype=np.int32)
    kv_valid = ctx + np.array(case["new"], dtype=np.int32)
    alibi = np.array([0.5, 0.25, 0.125, 0.0625], np.float32) \
        if case["slopes"] else None
    scale = 1 / np.sqrt(d)
    expected = numpy_prefill(q, k, v, ctx, kv_valid, scale, window=window,
                             slopes=alibi)
    args = (jnp.array(q), jnp.array(k), jnp.array(v), jnp.array(ctx),
            jnp.array(kv_valid), scale)
    kw = dict(sliding_window=window,
              alibi_slopes=None if alibi is None else jnp.array(alibi))
    got = np.array(prefill_attention_blocked(*args, key_block=8, **kw))
    plain = np.array(prefill_attention(*args, **kw))
    assert got.shape == plain.shape and np.isfinite(got).all()
    np.testing.assert_allclose(got, plain, rtol=2e-5, atol=2e-5)
    for bi, n in enumerate(kv_valid - ctx):
        np.testing.assert_allclose(got[bi, :n], expected[bi, :n],
                                   rtol=2e-5, atol=2e-5)


#: query heads a KV head of the kernel's cases, by turns: Mistral's
#: and Phi's 4, Laguna's 6 and 9, SmallThinker's 7
FLASH_GROUPS = (4, 6, 7, 9)


@pytest.mark.parametrize("case", BLOCKED_CASES.values(),
                         ids=list(BLOCKED_CASES))
def test_flash_kernel_is_plain_prefill(case, monkeypatch):
    """The same calls through `ops/pallas/prefill_attention.py`'s
    kernel, interpreted on the CPU at blocks of 8 queries and 8 keys,
    two sub-blocks a copied block: `prefill_attention` at every query
    of the padded chunk and the numpy oracle at every query of the
    prompt, with NaN in every key and value of the sub-blocks that no
    query block of the row visits (a sub-block outside a row's range
    is not read). The ALiBi cases are the dispatch's: a layer with
    slopes, like one over quantised pages, keeps the `jnp` functions
    on a TPU too."""
    from aphrodite_tpu.modeling.input_metadata import InputMetadata
    from aphrodite_tpu.modeling.layers import attention as layer_mod
    from aphrodite_tpu.ops.attention import prefill_tile_ranges
    from aphrodite_tpu.ops.pallas import prefill_attention as flash
    rng = np.random.default_rng(5)
    s_new, kv_len, window = case["s_new"], case["kv_len"], case["window"]
    at = list(BLOCKED_CASES.values()).index(case)
    group = FLASH_GROUPS[at % len(FLASH_GROUPS)]
    b, Hkv, d = len(case["ctx"]), 1 + at % 2, 16
    Hq = group * Hkv
    q = rng.normal(size=(b, s_new, Hq, d)).astype(np.float32)
    k = rng.normal(size=(b, kv_len, Hkv, d)).astype(np.float32)
    v = rng.normal(size=(b, kv_len, Hkv, d)).astype(np.float32)
    ctx = np.array(case["ctx"], dtype=np.int32)
    kv_valid = ctx + np.array(case["new"], dtype=np.int32)
    scale = 1 / np.sqrt(d)
    if case["slopes"]:
        monkeypatch.setattr(layer_mod.jax, "default_backend",
                            lambda: "tpu")
        monkeypatch.setattr(
            flash, "prefill_flash_attention",
            lambda *a, **kw: pytest.fail("the kernel takes no ALiBi"))
        slopes = np.linspace(0.5, 0.05, Hq).astype(np.float32)
        layer = layer_mod.PagedAttention(
            Hq, d, scale, num_kv_heads=Hkv, alibi_slopes=slopes,
            sliding_window=window)
        meta = InputMetadata(
            slot_mapping=jnp.zeros((b * s_new,), jnp.int32),
            block_tables=jnp.zeros((b, 1), jnp.int32),
            context_lens=jnp.zeros((b,), jnp.int32),
            prompt_lens=jnp.array(case["new"], jnp.int32), is_prompt=True)
        own = [jnp.array(x[:, :s_new]) for x in (k, v)]
        got = layer._prefill(jnp.array(q), *own, None, None, meta)
        np.testing.assert_array_equal(np.array(got), np.array(
            prefill_attention(jnp.array(q), *own, jnp.zeros((b,), jnp.int32),
                              meta.prompt_lens, scale,
                              sliding_window=window,
                              alibi_slopes=jnp.array(slopes))))
        rule = layer_mod.takes_prefill_kernel
        assert rule(jnp.float32, 1, None, False) and \
            rule("bfloat16", 1, None, False)
        assert not any((rule(jnp.float32, 1, None, True),
                        rule(jnp.int8, 1, None, False),
                        rule(jnp.float8_e5m2, 1, None, False),
                        rule(jnp.bfloat16, 2, None, False),
                        rule(jnp.bfloat16, 1, (None, 4096), False)))
        return
    block = 8
    pad_q, pad_k = -s_new % block, -kv_len % block
    keys = kv_len + pad_k
    major = 2 * block if keys % (2 * block) == 0 else block
    unseen = np.ones((b, keys), bool)
    for row in range(b):
        first, stop = prefill_tile_ranges(
            ctx[row:row + 1], kv_valid[row:row + 1], s_new + pad_q, keys,
            block, window, xp=np, query_block=block)
        for lo, hi in zip(first, stop):
            unseen[row, lo * block:max(hi, lo) * block] = False
    for row in range(b):    # (no case's window reaches its first key)
        assert unseen[row].any() == (window is not None or
                                     kv_valid[row] <= keys - block)

    def padded(x, pad, fill=0.0):
        return np.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)),
                      constant_values=fill)
    dirty = [np.where(unseen[:, :, None, None], np.nan, padded(x, pad_k))
             for x in (k, v)]
    got = np.array(flash.prefill_flash_attention(
        jnp.array(padded(q, pad_q)), *map(jnp.array, dirty),
        jnp.array(ctx), jnp.array(kv_valid), scale, window,
        blocks=(block, block, major), interpret=True))[:, :s_new]
    plain = np.array(prefill_attention(
        jnp.array(q), jnp.array(k), jnp.array(v), jnp.array(ctx),
        jnp.array(kv_valid), scale, sliding_window=window))
    expected = numpy_prefill(q, k, v, ctx, kv_valid, scale, window=window)
    assert got.shape == plain.shape and np.isfinite(got).all()
    np.testing.assert_allclose(got, plain, rtol=2e-5, atol=2e-5)
    for bi, n in enumerate(kv_valid - ctx):
        np.testing.assert_allclose(got[bi, :n], expected[bi, :n],
                                   rtol=2e-5, atol=2e-5)
    if pad_q or pad_k:
        # left to itself the wrapper pads to its own tile and chooses
        # the blocks
        whole = np.array(flash.prefill_flash_attention(
            jnp.array(q), jnp.array(k), jnp.array(v), jnp.array(ctx),
            jnp.array(kv_valid), scale, window, interpret=True))
        np.testing.assert_allclose(whole, plain, rtol=2e-5, atol=2e-5)


#: (query heads a KV head, KV heads, d, dv, the case of `BLOCKED_CASES`
#: whose rows, chunk, keys, contexts and window it takes): the values'
#: head width is the values' own
VALUE_WIDTH_CASES = {
    # Sarvam's form: one KV head a query head, 192 + 64 pad lanes of
    # keys to 128 of values, a prompt on its own keys
    "narrower-at-group-1": (1, 4, 32, 16, "first-chunk-own-keys"),
    "narrower-at-group-4": (4, 2, 32, 16, "first-chunk-own-keys"),
    "narrower-under-a-window": (1, 3, 32, 16, "window-over-a-block"),
    # a row's valid keys short of a table padded far past them
    "narrower-behind-a-cached-prefix": (1, 2, 48, 16,
                                        "chunk-3-of-a-padded-table"),
    "narrower-rows-at-different-contexts": (6, 1, 32, 8,
                                            "queries-past-the-prompt"),
    # nothing in the kernel asks that the values be the narrower
    "wider-than-the-keys": (2, 2, 16, 32, "rows-at-different-contexts"),
}


@pytest.mark.parametrize("group,Hkv,d,dv,name", VALUE_WIDTH_CASES.values(),
                         ids=list(VALUE_WIDTH_CASES))
def test_flash_kernel_takes_values_at_their_own_width(group, Hkv, d, dv,
                                                      name):
    """`v` `[b, keys, KV heads, dv]` with `dv` not `q`'s and `k`'s `d`
    (`modeling/layers/mla.py`: 256 lanes of keys a head, 128 of
    values): the output is `[b, s, heads, dv]` and, where `dv < d`,
    what the call at one width gives on the values zero-padded to `d`,
    sliced (the call a tree before PR 53 made: the padded lanes
    multiplied zeros and were thrown away), to the last place of
    float32: the CPU's matmul sums a column's terms in an order that
    follows the column count, the MXU does not, and
    `tests/kernels/tpu_smoke.py` holds the two EQUAL on the chip.
    Against `prefill_attention`, which has one head width, on the
    wider of the two zero-padded, sliced."""
    from aphrodite_tpu.ops.pallas import prefill_attention as flash
    case = BLOCKED_CASES[name]
    rng = np.random.default_rng(53)
    s_new, kv_len, window = case["s_new"], case["kv_len"], case["window"]
    b, Hq = len(case["ctx"]), group * Hkv
    q = rng.normal(size=(b, s_new, Hq, d)).astype(np.float32)
    k = rng.normal(size=(b, kv_len, Hkv, d)).astype(np.float32)
    v = rng.normal(size=(b, kv_len, Hkv, dv)).astype(np.float32)
    ctx = jnp.array(case["ctx"], jnp.int32)
    kv_valid = ctx + jnp.array(case["new"], jnp.int32)
    scale = 1 / np.sqrt(d)
    wide = max(d, dv)

    def lanes(x):
        return jnp.pad(jnp.array(x), ((0, 0),) * 3 +
                       ((0, wide - x.shape[-1]),))

    def kernel(q, k, v):
        return np.array(flash.prefill_flash_attention(
            jnp.array(q), jnp.array(k), jnp.array(v), ctx, kv_valid, scale,
            window, blocks=(8, 8, 16), interpret=True))
    got = kernel(q, k, v)
    assert got.shape == (b, s_new, Hq, dv) and np.isfinite(got).all()
    plain = np.array(prefill_attention(
        lanes(q), lanes(k), lanes(v), ctx, kv_valid, scale,
        sliding_window=window))[..., :dv]
    np.testing.assert_allclose(got, plain, rtol=2e-5, atol=2e-5)
    if dv < d:
        np.testing.assert_allclose(got, kernel(q, k, lanes(v))[..., :dv],
                                   rtol=0, atol=1e-6)
    # left to itself the wrapper pads the tokens and chooses the blocks
    whole = np.array(flash.prefill_flash_attention(
        jnp.array(q), jnp.array(k), jnp.array(v), ctx, kv_valid, scale,
        window, interpret=True))
    np.testing.assert_allclose(whole, plain, rtol=2e-5, atol=2e-5)


#: (queries, keys, query heads a KV head, window) -> (queries a block,
#: keys a sub-block, keys a copied block): the cells' calls
#: (`benchmarks/prefill_ab.py::CELLS`) and the rule's edges
FLASH_BLOCKS = {
    "mistral": ((1024, 1024, 4, None), (512, 512, 1024)),
    "phi-window": ((2048, 2048, 4, 512), (512, 256, 2048)),
    "smallthinker-full": ((2048, 8192, 7, None), (512, 512, 2048)),
    "smallthinker-window-7168": ((2048, 7168, 7, 4096), (512, 512, 1024)),
    "laguna-full": ((2048, 4096, 6, None), (512, 512, 2048)),
    "laguna-window": ((2048, 3072, 9, 512), (256, 256, 1536)),
    "jamba-one-kv-head": ((512, 512, 20, None), (128, 512, 512)),
    "64-heads-on-one": ((2048, 2048, 64, None), (64, 512, 2048)),
    "falcons-71-on-one": ((1024, 1024, 71, None), (32, 512, 1024)),
    "a-chunk-of-one-tile": ((128, 128, 4, None), (128, 128, 128)),
    "lengths-of-odd-tiles": ((384, 1152, 4, None), (128, 128, 1152)),
    "a-window-under-two-tiles": ((2048, 2048, 4, 200), (512, 128, 2048)),
}


@pytest.mark.parametrize("shape,want", FLASH_BLOCKS.values(),
                         ids=list(FLASH_BLOCKS))
def test_the_flash_kernels_blocks_follow_the_calls_shapes(shape, want):
    """`choose_blocks` reads the chunk, the keys, `group` and the
    window and nothing else: the longest query block within 4,096
    rows, key sub-blocks of 512 or half a window, a copied block of at
    most 2,048 keys, each dividing what it tiles; and what a
    sub-block's scores, weights and bfloat16 weights take beside the
    scratch and the copied blocks fits the VMEM the kernel states."""
    from aphrodite_tpu.ops.pallas import prefill_attention as flash
    queries, keys, group, window = shape
    assert flash.choose_blocks(*shape) == want
    query_block, key_block, major = want
    assert queries % query_block == 0 and keys % major == 0 and \
        major % key_block == 0
    rows, d = group * query_block, 128
    need = rows * key_block * (4 + 4 + 2) + \
        rows * (d * 2 + 128 * 4 * 2 + d * 4) + \
        2 * 2 * (query_block * group * d + 2 * major * d) * 2
    assert need <= 0.8 * flash.VMEM_LIMIT


def test_blocked_prefill_never_reads_a_tile_no_query_can_see():
    """NaN in every key and value of the key blocks that no query block
    of the chunk can see (before the first query's window, after the
    last valid key): the output is finite and what zeros there give.
    Scored and masked, as every block was before the walk, a NaN value
    would have reached the sum through `0 * NaN`."""
    from aphrodite_tpu.ops.attention import (prefill_attention_blocked,
                                             prefill_tile_ranges)
    rng = np.random.default_rng(11)
    s_new, kv_len, window, block = 32, 160, 10, 8
    ctx = np.array([64, 72], dtype=np.int32)
    kv_valid = ctx + np.array([32, 20], dtype=np.int32)
    first, stop = prefill_tile_ranges(ctx, kv_valid, s_new, kv_len, block,
                                      window, xp=np)
    seen = np.zeros(kv_len // block, bool)
    for lo, hi in zip(first, stop):
        seen[lo:hi] = True
    assert seen.sum() == 6 and seen[6:12].all()
    q = rng.normal(size=(2, s_new, 4, 16)).astype(np.float32)
    k = rng.normal(size=(2, kv_len, 2, 16)).astype(np.float32)
    v = rng.normal(size=(2, kv_len, 2, 16)).astype(np.float32)
    unseen = np.repeat(~seen, block)
    clean = [np.where(unseen[None, :, None, None], 0.0, x) for x in (k, v)]
    dirty = [np.where(unseen[None, :, None, None], np.nan, x)
             for x in (k, v)]

    def attend(kk, vv):
        return np.array(prefill_attention_blocked(
            jnp.array(q), jnp.array(kk), jnp.array(vv), jnp.array(ctx),
            jnp.array(kv_valid), 0.25, sliding_window=window,
            key_block=block))
    got = attend(*dirty)
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got, attend(*clean))
    assert not np.isfinite(np.array(prefill_attention(
        jnp.array(q), *map(jnp.array, dirty), jnp.array(ctx),
        jnp.array(kv_valid), 0.25, sliding_window=window))).all()


#: (queries, keys, key_block, context, window) -> (visited, padded):
#: ISSUE 39's counts of the two benchmark cells that take the function
TILE_COUNTS = {
    "phi-full-or-cross": ((2048, 2048, 512, 0, None), (10, 16)),
    "phi-window-512": ((2048, 2048, 512, 0, 512), (7, 16)),
    "smallthinker-full-chunk-2": ((2048, 8192, 512, 2048, None), (26, 64)),
    "smallthinker-full-chunk-3": ((2048, 8192, 512, 4096, None), (42, 64)),
    "smallthinker-full-chunk-4": ((2048, 8192, 512, 6144, None), (58, 64)),
    "smallthinker-window-chunk-2": ((2048, 7168, 512, 2048, 4096),
                                    (26, 56)),
    "smallthinker-window-chunk-3": ((2048, 7168, 512, 4096, 4096),
                                    (36, 56)),
    "smallthinker-window-chunk-4": ((2048, 6144, 512, 4096, 4096),
                                    (36, 48)),
}


@pytest.mark.parametrize("shape,want", TILE_COUNTS.values(),
                         ids=list(TILE_COUNTS))
def test_the_tile_count_rule_is_what_the_program_visits(shape, want):
    """`count_prefill_tiles` (the host's count, behind
    `aphrodite:prefill_attn_tiles_visited_total`) at a cell's real
    sizes, and at a 64th of them against the count that the program
    carries out of its loops; a brute-force reading of the mask (a
    tile is live when some query of it sees some key of it) says the
    same, since one row's visible keys are one run."""
    from aphrodite_tpu.ops.attention import (count_prefill_tiles,
                                             prefill_attention_tiles)
    s_new, kv_len, block, ctx, window = shape
    assert count_prefill_tiles([ctx], [ctx + s_new], s_new, kv_len,
                               window, block) == want
    s_new, kv_len, block, ctx = (x // 64 for x in shape[:4])
    window = window and window // 64
    valid = [ctx + s_new]
    assert count_prefill_tiles([ctx], valid, s_new, kv_len, window,
                               block) == want
    rng = np.random.default_rng(2)
    q = rng.normal(size=(1, s_new, 2, 8)).astype(np.float32)
    k = rng.normal(size=(1, kv_len, 1, 8)).astype(np.float32)
    _, visited = prefill_attention_tiles(
        jnp.array(q), jnp.array(k), jnp.array(k),
        jnp.array([ctx], jnp.int32), jnp.array(valid, jnp.int32), 1.0,
        sliding_window=window, key_block=block)
    assert int(visited) == want[0]
    mask = np.array(make_causal_mask(s_new, jnp.array([ctx]), kv_len,
                                     window))[0]
    mask &= np.arange(kv_len) < valid[0]
    live = mask.reshape(s_new // block, block, kv_len // block,
                        block).any(axis=(1, 3))
    assert (int(live.sum()), live.size) == want


def test_a_prompt_steps_tile_count_over_its_layers():
    """Phi-4-mini-flash's prompt step, 8 window layers and 8 that see
    every key: 136 of 256 tiles; a row that holds nothing (a pad row)
    widens no range, and rows at different contexts take the union."""
    from aphrodite_tpu.ops.attention import count_prefill_tiles
    full = count_prefill_tiles([0], [2048], 2048, 2048)
    window = count_prefill_tiles([0], [2048], 2048, 2048, 512)
    assert (8 * full[0] + 8 * window[0], 8 * full[1] + 8 * window[1]) \
        == (136, 256)
    assert count_prefill_tiles([0, 0], [2048, 0], 2048, 2048, 512) == \
        window
    # a row behind 4,096 cached tokens beside one behind none: from
    # the second row's first key to the first row's last (a range, so
    # what lies between the rows' runs is visited too)
    assert count_prefill_tiles([4096, 0], [6144, 2048], 2048, 8192,
                               4096) == (9 + 10 + 11 + 12, 64)
    assert count_prefill_tiles([0], [0], 2048, 2048) == (0, 16)
    # SmallThinker's three blocked chunks, 3 full and 9 window layers:
    # what the cell's counters read over a window, 62.5%
    from benchmarks.prefill_ab import CELLS
    tiles = np.array([count_prefill_tiles(
        [ctx], [ctx + queries], queries, keys, window or None)
        for _, queries, keys, ctx, window, *_ in CELLS[2:8]])
    assert tuple(3 * tiles[:3].sum(0) + 9 * tiles[3:].sum(0)) == \
        (1260, 2016)


def test_prefill_ab_check_arm_rehearses_on_the_cpu(monkeypatch, capsys):
    """`benchmarks/prefill_ab.py --check` at a toy geometry: the call
    is compared with `prefill_attention`, the tiles it visits are said
    by the function's own rule, and nothing is timed off the chip."""
    from benchmarks import prefill_ab
    assert [c[1:5] for c in prefill_ab.CELLS[:2]] == [
        (2048, 2048, 0, 0), (2048, 2048, 0, 512)]
    monkeypatch.setattr("sys.argv", [
        "prefill_ab.py", "--queries", "32", "--keys", "64", "--ctx", "16",
        "--window", "12", "--block", "8", "--heads", "4", "--kv-heads",
        "2", "--head-dim", "16", "--check", "--oracle", "--kernel",
        "--blocked-from", "1024"])
    prefill_ab.main()
    said = capsys.readouterr().out
    assert "window=12: 12 of 32 tiles" in said
    checks = [line for line in said.splitlines() if "check:" in line]
    assert ["|blocked - plain|" in c for c in checks] == [True, False]
    assert "|kernel - plain|" in checks[1]
    for check in checks:        # bfloat16 in, bfloat16 out
        assert "finite: True" in check
        assert float(check.split("=")[1].split()[0]) < 1e-2
        assert float(check.split("=")[2].split()[0]) < 1e-2
    assert "whole call" not in said
    # every cell's call falls on its model's side of the harness's
    # threshold: Mistral's takes the plain function, the others' the walk
    assert [c[1] * c[2] >= 1 << 21 for c in prefill_ab.CELLS] == \
        [True] * 12 + [False] * 3 + [True] * 4
    assert [(c + (1,))[8] for c in prefill_ab.CELLS[12:15]] == [1, 2, 4]
    # Sarvam's calls state their widths: keys 256 lanes a head, values
    # 128, of which 192 + 128 are no padding
    assert [c[9:] for c in prefill_ab.CELLS[15:]] == [(256, 128, 320)] * 4
    assert [c[:9] for c in prefill_ab.CELLS if len(c) > 9] == [
        ("sarvam whole prompt", 8192, 8192, 0, 0, 64, 64, 0.13524, 1),
        ("sarvam chunk 2", 2048, 9216, 2048, 0, 64, 64, 0.13524, 1),
        ("sarvam chunk 3", 2048, 9216, 4096, 0, 64, 64, 0.13524, 1),
        ("sarvam chunk 4", 2048, 9216, 6144, 0, 64, 64, 0.13524, 1)]
    assert prefill_ab.live_pairs(8192, 0, 8192, None) == 8192 * 8193 // 2
    assert prefill_ab.live_pairs(2048, 2048, 4096, None) == \
        2048 * 2048 + 2048 * 2049 // 2
    assert prefill_ab.live_pairs(4, 2, 5, 3) == 3 + 3 + 3 + 2


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
