"""KV-cache op tests vs numpy oracles (reference test model:
tests/kernels/test_cache.py walks block tables in Python).

Pages are token-major: [num_pages, page_size, HEADS * DIM]."""
import jax.numpy as jnp
import numpy as np
import pytest

from aphrodite_tpu.ops.kv_cache import (copy_blocks, gather_pages,
                                        write_to_kv_cache)

HEADS, PAGES, PAGE_SIZE, DIM = 2, 8, 4, 8


def make_pages(seed=0):
    rng = np.random.default_rng(seed)
    k = rng.normal(size=(PAGES, PAGE_SIZE, HEADS * DIM)).astype(np.float32)
    v = rng.normal(size=(PAGES, PAGE_SIZE, HEADS * DIM)).astype(np.float32)
    return jnp.array(k), jnp.array(v)


def test_write_to_kv_cache():
    k_pages, v_pages = make_pages()
    rng = np.random.default_rng(1)
    num_tokens = 5
    key = rng.normal(size=(num_tokens, HEADS, DIM)).astype(np.float32)
    value = rng.normal(size=(num_tokens, HEADS, DIM)).astype(np.float32)
    slots = np.array([0, 5, 13, 31, PAGES * PAGE_SIZE], dtype=np.int32)

    new_k, new_v = write_to_kv_cache(jnp.array(key), jnp.array(value),
                                     k_pages, v_pages, jnp.array(slots))

    expected_k = np.array(k_pages).reshape(-1, HEADS * DIM)
    expected_v = np.array(v_pages).reshape(-1, HEADS * DIM)
    for i, slot in enumerate(slots[:-1]):  # last is OOB padding -> dropped
        expected_k[slot] = key[i].reshape(-1)
        expected_v[slot] = value[i].reshape(-1)
    np.testing.assert_allclose(
        np.array(new_k), expected_k.reshape(PAGES, PAGE_SIZE, HEADS * DIM))
    np.testing.assert_allclose(
        np.array(new_v), expected_v.reshape(PAGES, PAGE_SIZE, HEADS * DIM))


def test_write_oob_dropped():
    k_pages, v_pages = make_pages()
    key = jnp.ones((2, HEADS, DIM))
    slots = jnp.array([PAGES * PAGE_SIZE, PAGES * PAGE_SIZE + 7],
                      dtype=jnp.int32)
    new_k, new_v = write_to_kv_cache(key, key, k_pages, v_pages, slots)
    np.testing.assert_allclose(np.array(new_k), np.array(k_pages))
    np.testing.assert_allclose(np.array(new_v), np.array(v_pages))


def test_copy_blocks():
    k_pages, v_pages = make_pages()
    src = jnp.array([1, 3, PAGES], dtype=jnp.int32)  # last pair padded
    dst = jnp.array([6, 7, PAGES], dtype=jnp.int32)
    new_k, new_v = copy_blocks(k_pages, v_pages, src, dst)
    expected_k = np.array(k_pages)
    expected_v = np.array(v_pages)
    expected_k[6] = expected_k[1]
    expected_k[7] = expected_k[3]
    expected_v[6] = expected_v[1]
    expected_v[7] = expected_v[3]
    np.testing.assert_allclose(np.array(new_k), expected_k)
    np.testing.assert_allclose(np.array(new_v), expected_v)


def test_gather_pages():
    k_pages, _ = make_pages()
    tables = jnp.array([[2, 0, PAGES, PAGES], [5, 6, 7, PAGES]],
                       dtype=jnp.int32)
    out = gather_pages(k_pages, tables, HEADS)
    assert out.shape == (2, HEADS, 4 * PAGE_SIZE, DIM)
    np.testing.assert_allclose(
        np.array(out[0, :, :PAGE_SIZE]),
        np.array(k_pages[2]).reshape(PAGE_SIZE, HEADS, DIM)
        .transpose(1, 0, 2))
    np.testing.assert_allclose(
        np.array(out[1, :, PAGE_SIZE:2 * PAGE_SIZE]),
        np.array(k_pages[6]).reshape(PAGE_SIZE, HEADS, DIM)
        .transpose(1, 0, 2))
    # OOB-padded pages fill with zeros.
    np.testing.assert_allclose(np.array(out[0, :, 2 * PAGE_SIZE:]), 0.0)


@pytest.mark.parametrize("distinct", [False, True])
def test_pallas_writer_interpret(distinct):
    """Token-major Pallas page writers (serialized window RMW and the
    pipelined distinct-page variant) match the XLA scatter path."""
    from aphrodite_tpu.ops.pallas.kv_write import write_kv_pages
    rng = np.random.default_rng(5)
    pages, page_size, hd = 8, 16, 2 * 128
    k_pages = jnp.asarray(
        rng.normal(size=(pages, page_size, hd)), jnp.float32)
    v_pages = jnp.asarray(
        rng.normal(size=(pages, page_size, hd)), jnp.float32)
    num_tokens = 6
    knew = jnp.asarray(rng.normal(size=(num_tokens, hd)), jnp.float32)
    vnew = jnp.asarray(rng.normal(size=(num_tokens, hd)), jnp.float32)
    if distinct:
        # One token per page (the decode contract).
        slots = np.array([0, 17, 39, 111, 64, pages * page_size],
                         dtype=np.int32)
    else:
        slots = np.array([0, 17, 18, 127, 64, pages * page_size],
                         dtype=np.int32)
    got_k, got_v = write_kv_pages(knew, vnew, k_pages, v_pages,
                                  jnp.asarray(slots),
                                  distinct_pages=distinct,
                                  interpret=True)
    exp_k = np.array(k_pages).reshape(-1, hd)
    exp_v = np.array(v_pages).reshape(-1, hd)
    for i, s in enumerate(slots[:-1]):
        exp_k[s] = knew[i]
        exp_v[s] = vnew[i]
    np.testing.assert_allclose(
        np.array(got_k), exp_k.reshape(pages, page_size, hd))
    np.testing.assert_allclose(
        np.array(got_v), exp_v.reshape(pages, page_size, hd))


@pytest.mark.parametrize("hd,page_size,dtype", [
    (256, 8, jnp.float32), (128, 16, jnp.bfloat16)],
    ids=["two-heads-f32", "one-kv-head-bf16"])
def test_pallas_prefill_page_writer_interpret(hd, page_size, dtype):
    """Whole-page prefill writer: full pages, a partial tail page,
    prefix-offset pages, and OOB pad cells, vs a numpy oracle; at two
    heads of 128 in float32, and at ONE KV head of 128 in bfloat16
    pages of 16 tokens (AI21-Jamba2-3B's: a page's lane axis is one
    lane tile)."""
    from aphrodite_tpu.ops.pallas.kv_write import write_kv_pages_prefill
    rng = np.random.default_rng(9)
    pages = 10
    padded_len = 2 * page_size            # 2 page-blocks per sequence
    B = 3
    k_pages = jnp.asarray(
        rng.normal(size=(pages, page_size, hd)), dtype)
    v_pages = jnp.asarray(
        rng.normal(size=(pages, page_size, hd)), dtype)
    knew = np.asarray(jnp.asarray(
        rng.normal(size=(B * padded_len, hd)), dtype), np.float32)
    vnew = np.asarray(jnp.asarray(
        rng.normal(size=(B * padded_len, hd)), dtype), np.float32)
    # seq 0: 16 tokens -> pages 1,2 (both full)
    # seq 1: 11 tokens -> page 4 full, page 5 partial (3 rows)
    # seq 2: padded-out (no cells)
    pid = np.array([1, 2, 4, 5, pages, pages], dtype=np.int32)
    sblk = np.array([0, 1, 2, 3, 0, 0], dtype=np.int32)
    vld = np.array([page_size, page_size, page_size, 3, 0, 0],
                   dtype=np.int32)
    got_k, got_v = write_kv_pages_prefill(
        jnp.asarray(knew, dtype), jnp.asarray(vnew, dtype), k_pages,
        v_pages, jnp.asarray(pid), jnp.asarray(sblk), jnp.asarray(vld),
        interpret=True)
    exp_k = np.array(k_pages, np.float32)
    exp_v = np.array(v_pages, np.float32)
    for c in range(6):
        if pid[c] >= pages:
            continue
        rows = knew[sblk[c] * page_size:(sblk[c] + 1) * page_size]
        rows_v = vnew[sblk[c] * page_size:(sblk[c] + 1) * page_size]
        exp_k[pid[c], :vld[c]] = rows[:vld[c]]
        exp_v[pid[c], :vld[c]] = rows_v[:vld[c]]
    np.testing.assert_allclose(np.array(got_k, np.float32), exp_k)
    np.testing.assert_allclose(np.array(got_v, np.float32), exp_v)


def test_pallas_decode_writer_oob_first_and_last():
    """OOB (padding) tokens at the pipeline edges must not deadlock or
    corrupt: first, middle, and last positions padded."""
    from aphrodite_tpu.ops.pallas.kv_write import write_kv_pages
    rng = np.random.default_rng(6)
    pages, page_size, hd = 6, 8, 128
    k_pages = jnp.asarray(
        rng.normal(size=(pages, page_size, hd)), jnp.float32)
    num_tokens = 5
    knew = jnp.asarray(rng.normal(size=(num_tokens, hd)), jnp.float32)
    oob = pages * page_size
    slots = np.array([oob, 9, oob, 33, oob], dtype=np.int32)
    got_k, _ = write_kv_pages(knew, knew, k_pages, k_pages + 1,
                              jnp.asarray(slots), distinct_pages=True,
                              interpret=True)
    exp_k = np.array(k_pages).reshape(-1, hd)
    exp_k[9] = knew[1]
    exp_k[33] = knew[3]
    np.testing.assert_allclose(
        np.array(got_k), exp_k.reshape(pages, page_size, hd))
