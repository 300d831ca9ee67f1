"""The decode kernel over LATENT pages (`paged_decode_attention`'s
`latent`; `common/config.py::PageGroups.latent`), interpreted: ONE
array of pages, one "head" of 640 lanes a token under 64 query rows,
the values the first 512 lanes of the keys, against the `jnp` path
over the same array as both K and V; NaN in every page no row holds;
the fused write of the one row into the one array; and the whole-page
prompt writer over one array."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aphrodite_tpu.ops.attention import paged_decode_attention_ref
from aphrodite_tpu.ops.kv_cache import (copy_pages, padded_head_size,
                                        write_to_latent_cache)
from aphrodite_tpu.ops.pallas import paged_attention as pa
from aphrodite_tpu.ops.pallas.paged_attention import (
    build_decode_work_list, choose_pages_per_chunk,
    paged_decode_attention)

#: Sarvam-105B's page: `[c 512 | k_r 64]` padded to 640 lanes, 64
#: query rows. Contexts a row, cycled: pad rows, a first token, items
#: that end partly live (513 is an item and a page), two items and a
#: tail.
LANES, LATENT, HEADS, PAGE, WIDTH = 640, 512, 64, 16, 80
CTX = (700, 0, 1, 496, 513, 17, 1100, 64)
SCALE = 192 ** -0.5 * 1.3689 ** 2


def problem(rows, seed=3):
    """(q, pages, table, contexts, page counts, dead pages): bf16
    pages, NaN in every page no row holds (page 0, which the table's
    pad entries point at, among them), pages in shuffled order, the
    lanes past the rotary key zero as the layer writes them."""
    rng = np.random.default_rng(seed)
    ctx = np.array([CTX[b % len(CTX)] for b in range(rows)], np.int32)
    counts = -(-ctx // PAGE)
    pool = 1 + int(counts.sum())
    perm = rng.permutation(pool - 1) + 1
    bt = np.zeros((rows, WIDTH), dtype=np.int32)
    taken = 0
    for b, n in enumerate(counts):
        bt[b, :n] = perm[taken:taken + n]
        taken += n
    dead = np.ones(pool + 4, bool)
    dead[perm] = False
    raw = rng.normal(size=(pool + 4, PAGE, LANES)) * 0.3
    raw[..., 576:] = 0.0
    raw[dead] = np.nan
    q = rng.normal(size=(rows, HEADS, LANES)) * 0.3
    q[..., 576:] = 0.0
    return (jnp.asarray(q, jnp.bfloat16), jnp.asarray(raw, jnp.bfloat16),
            bt, ctx, counts, dead)


def test_the_policy_at_the_latent_page():
    assert padded_head_size(576) == LANES
    assert pa.head_block(1, LANES, jnp.bfloat16) == 1
    assert choose_pages_per_chunk(
        576, PAGE, pa.lane_bytes_of(1, LANES, jnp.bfloat16)) == 32
    assert pa.LATENT_DEVICE_OP_PREFIXES == ("paged-decode-latent",)


@pytest.mark.parametrize("rows,fused", [(1, True), (8, True), (8, False)],
                         ids=["one-row", "eight-rows",
                              "eight-rows-read-only"])
def test_latent_pages_against_the_jnp_path(rows, fused):
    """The output is the `jnp` path's over the array as K and as V
    (lanes 512 on dropped), the page written the scatter's exactly, no
    dead page read (NaN in every page no row holds), pad rows zeros,
    the result `[rows, 64, 512]`."""
    ppc = choose_pages_per_chunk(
        WIDTH, PAGE, pa.lane_bytes_of(1, LANES, jnp.bfloat16))
    q, pages, bt, ctx, counts, dead = problem(rows)
    dead = jnp.asarray(dead)[:, None, None]
    new, want_pages = None, pages
    if fused:
        rng = np.random.default_rng(9)
        row = rng.normal(size=(rows, LANES)) * 0.3
        row[..., 576:] = 0.0
        new = jnp.asarray(row, jnp.bfloat16)
        slots = np.where(
            ctx > 0, bt[np.arange(rows), np.maximum(ctx - 1, 0) // PAGE]
            * PAGE + (ctx - 1) % PAGE, pages.shape[0] * PAGE)
        want_pages = write_to_latent_cache(
            new, pages, jnp.asarray(slots, jnp.int32))
    clean = jnp.where(dead, 0, want_pages)
    want = np.asarray(paged_decode_attention_ref(
        q, clean, clean, jnp.asarray(bt), jnp.asarray(np.maximum(ctx, 1)),
        SCALE)[..., :LATENT], np.float32)
    got = paged_decode_attention(
        q, pages, None, jnp.asarray(bt), jnp.asarray(ctx), None,
        None if new is None else new.reshape(rows, 1, LANES), None,
        scale=SCALE, pages_per_chunk=ppc,
        work_items=build_decode_work_list(counts, ppc), interpret=True,
        latent=LATENT)
    if fused:
        got, got_pages = got
        assert got_pages.shape == pages.shape
        np.testing.assert_array_equal(np.asarray(got_pages, np.float32),
                                      np.asarray(want_pages, np.float32))
    assert got.shape == (rows, HEADS, LATENT)
    got = np.asarray(got, np.float32)
    assert np.isfinite(got).all()
    live = ctx > 0
    np.testing.assert_allclose(got[live], want[live], rtol=2e-2,
                               atol=2e-2)
    np.testing.assert_allclose(got[~live], 0.0, atol=1e-6)


def test_one_array_reads_what_the_pair_of_the_same_array_reads():
    """Step 0's form (the SAME array passed as K pages and as V pages,
    every page copied twice) and the latent form (copied once) give
    the same output bit for bit over lanes 0-511."""
    ppc = 32
    q, pages, bt, ctx, counts, dead = problem(4)
    pages = jnp.where(jnp.asarray(dead)[:, None, None], 0, pages)
    work = build_decode_work_list(counts, ppc)
    args = (jnp.asarray(bt), jnp.asarray(ctx), None)
    twice = paged_decode_attention(
        q, pages, pages, *args, scale=SCALE, pages_per_chunk=ppc,
        work_items=work, interpret=True)
    once = paged_decode_attention(
        q, pages, None, *args, scale=SCALE, pages_per_chunk=ppc,
        work_items=work, interpret=True, latent=LATENT)
    np.testing.assert_array_equal(
        np.asarray(twice[..., :LATENT], np.float32),
        np.asarray(once, np.float32))


@pytest.mark.parametrize("bad", [
    dict(latent=500), dict(latent=768), dict(v_pages=True),
    dict(vnew=True), dict(heads=2)],
    ids=["no-lane-tile", "past-the-row", "a-second-array", "a-second-row",
         "two-heads-a-token"])
def test_what_a_latent_call_refuses(bad):
    q, pages, bt, ctx, counts, _ = problem(2)
    lanes = LANES // bad.get("heads", 1)
    with pytest.raises(ValueError, match="latent pages are one array"):
        paged_decode_attention(
            q[..., :lanes], pages, pages if bad.get("v_pages") else None,
            jnp.asarray(bt), jnp.asarray(ctx), None, None,
            jnp.zeros((2, 1, LANES), jnp.bfloat16) if bad.get("vnew")
            else None, scale=SCALE, pages_per_chunk=32, interpret=True,
            latent=bad.get("latent", LATENT))


def test_the_prompt_writer_over_one_array():
    """`write_kv_pages_prefill` with no V: whole pages and a partial
    tail land in the one array as the scatter puts them, pad cells
    write nothing."""
    from aphrodite_tpu.ops.pallas.kv_write import write_kv_pages_prefill
    rng = np.random.default_rng(1)
    pages = jnp.asarray(rng.normal(size=(12, PAGE, LANES)), jnp.bfloat16)
    tokens = 3 * PAGE + 5                       # three pages and a tail
    rows = jnp.asarray(rng.normal(size=(8 * PAGE, LANES)), jnp.bfloat16)
    page_ids = np.full((8,), 12, np.int32)      # out of range: skipped
    page_ids[:4] = [7, 2, 9, 4]
    valids = np.full((8,), PAGE, np.int32)
    valids[3] = 5
    got = write_kv_pages_prefill(
        rows, None, pages, None, jnp.asarray(page_ids),
        jnp.arange(8, dtype=jnp.int32), jnp.asarray(valids),
        interpret=True)
    slots = np.full((8 * PAGE,), 12 * PAGE, np.int32)
    for t in range(tokens):
        slots[t] = page_ids[t // PAGE] * PAGE + t % PAGE
    want = write_to_latent_cache(rows, pages, jnp.asarray(slots))
    assert got.shape == pages.shape
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


def test_copy_pages_is_one_side_of_copy_blocks():
    from aphrodite_tpu.ops.kv_cache import copy_blocks
    pages = jnp.arange(6 * 2 * 4, dtype=jnp.float32).reshape(6, 2, 4)
    src, dst = jnp.asarray([1, 6]), jnp.asarray([4, 6])   # 6: a pad pair
    one = copy_pages(pages, src, dst)
    pair = copy_blocks(pages, pages * 2, src, dst)
    assert np.array_equal(one, pair[0]) and np.array_equal(one * 2, pair[1])
    assert np.array_equal(one[4], pages[1])
