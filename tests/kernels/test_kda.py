"""The two delta-rule (KDA) kernels of `ops/pallas/kda.py`, interpreted
on the CPU, against the recurrence a token at a time (`kda_chunk_ref`,
`kda_update_ref`: the `jax.numpy` side the dispatchers take off a TPU)
and against numpy's float64: prompts that span several chunks, a chunk
boundary inside a scheduler chunk, padding, fresh and resumed slots,
decays strong enough that `exp(-G)` would overflow; the update in
place, by slot and layer. Float32 on both sides: the limits are a few
ulps of the values' spread times the length of the sums."""
import numpy as np
import pytest

import jax.numpy as jnp

from aphrodite_tpu.ops.pallas import kda

HEADS, D, LAYERS, SLOTS = 2, 128, 3, 9


def _inputs(seed, rows, tokens, decay=(-6.0, 1.5), lens=None):
    """Seeded q, k, v, g, b as the layer hands them in: q and k
    L2-normed a head, `g = -exp(uniform(decay))`, b in (0, 1); `g` and
    `b` zero past a row's `lens`."""
    rng = np.random.default_rng(seed)

    def unit(x):
        return x / np.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)
    q = unit(rng.standard_normal((rows, tokens, HEADS, D))) * D ** -0.5
    k = unit(rng.standard_normal((rows, tokens, HEADS, D)))
    v = rng.standard_normal((rows, tokens, HEADS, D))
    g = -np.exp(rng.uniform(*decay, (rows, tokens, HEADS, D)))
    b = rng.uniform(0, 1, (rows, tokens, HEADS))
    if lens is not None:
        live = np.arange(tokens)[None] < np.asarray(lens)[:, None]
        g = np.where(live[..., None, None], g, 0.0)
        b = np.where(live[..., None], b, 0.0)
    return [jnp.asarray(a, jnp.float32) for a in (q, k, v, g, b)]


def _state(seed):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(
        (LAYERS, SLOTS + 1, HEADS, D, D)), jnp.float32)


def _chunk(args, state, slots, fresh, layer):
    rows, tokens = args[0].shape[:2]
    o, state = kda._kda_chunk_impl(
        *[x.reshape(rows, tokens, -1) for x in args], state,
        jnp.full((1,), layer, jnp.int32), jnp.asarray(slots, jnp.int32),
        jnp.asarray(fresh, jnp.int32), interpret=True)
    return o.reshape(args[2].shape), state


def _float64(args, s0):
    """The recurrence in numpy's float64, one row and head."""
    q, k, v, g, b = (np.asarray(a, np.float64) for a in args)
    s, outs = np.asarray(s0, np.float64), []
    for t in range(q.shape[0]):
        s = np.exp(g[t])[:, None] * s
        s = s + np.outer(k[t], b[t] * (v[t] - s.T @ k[t]))
        outs.append(s.T @ q[t])
    return np.stack(outs), s


@pytest.mark.parametrize("tokens,lens", [(192, [192, 150]), (64, [64, 1]),
                                         (128, [65, 128])],
                         ids=["three-chunks", "one-chunk", "a-boundary"])
def test_the_chunk_kernel_is_the_recurrence(tokens, lens):
    """Fresh and resumed rows side by side, one of them padded past its
    last live token (inside a chunk, and by a whole chunk): outputs at
    the live tokens and the final state are the token-by-token
    recurrence's; no other slot and no other layer is touched."""
    args = _inputs(0, 2, tokens, lens=lens)
    state = _state(1)
    slots, fresh, layer = [2, 0], [0, 1], 1
    want_o, want_s = kda.kda_chunk_ref(
        *args, state, jnp.asarray(slots), jnp.asarray(fresh), layer)
    o, s = _chunk(args, state, slots, fresh, layer)
    live = np.arange(tokens)[None] < np.asarray(lens)[:, None]
    assert float(jnp.abs(want_o).max()) > 0.05
    np.testing.assert_allclose(np.asarray(o)[live], np.asarray(want_o)[live],
                               atol=2e-6)
    np.testing.assert_allclose(s, want_s, atol=2e-5)
    untouched = np.ones((LAYERS, SLOTS + 1), bool)
    untouched[layer, slots] = False
    assert np.array_equal(np.asarray(s)[untouched],
                          np.asarray(state)[untouched])
    # the fresh row took nothing from what its slot held
    other, _ = _chunk(args, state.at[layer, 0].set(jnp.nan), slots, fresh,
                      layer)
    assert np.array_equal(np.asarray(other)[1][live[1]],
                          np.asarray(o)[1][live[1]])


def test_a_prompt_in_two_calls_is_the_prompt_in_one():
    """A scheduler's chunks: 192 tokens whole, or 128 and then 64 from
    the slot's state (a kernel chunk's boundary inside the first call,
    the second resumed): the same outputs and the same final state."""
    args = _inputs(2, 1, 192)
    state = _state(3)
    whole_o, whole_s = _chunk(args, state, [4], [1], 0)
    first_o, mid = _chunk([a[:, :128] for a in args], state, [4], [1], 0)
    second_o, last = _chunk([a[:, 128:] for a in args], mid, [4], [0], 0)
    np.testing.assert_allclose(
        np.concatenate([first_o, second_o], axis=1), whole_o, atol=2e-6)
    np.testing.assert_allclose(last, whole_s, atol=2e-5)


def test_against_float64_under_strong_and_weak_decays():
    """Per-channel decays from `exp(-4.5)` a token (a channel forgets
    within one) to `exp(-0.0001)`: over a chunk `exp(-G)` would reach
    `exp(280)` and overflow float32, so a kernel that exponentiates
    anything but differences reads inf or NaN here. Held to numpy's
    float64 at 1e-5 of the outputs' largest."""
    args = _inputs(4, 1, 128, decay=(-9.0, 1.5))
    state = _state(5)
    o, s = _chunk(args, state, [1], [0], 2)
    assert np.isfinite(np.asarray(o)).all() and np.isfinite(
        np.asarray(s[2, 1])).all()
    for head in range(HEADS):
        want_o, want_s = _float64([a[0, :, head] for a in args],
                                  state[2, 1, head])
        assert np.abs(np.asarray(o)[0, :, head] - want_o).max() <= \
            1e-5 * np.abs(want_o).max()
        assert np.abs(np.asarray(s)[2, 1, head] - want_s).max() <= \
            1e-5 * np.abs(want_s).max()


def test_keys_that_repeat_at_full_strength():
    """The solve's hard case: every key of a sub-block the same vector,
    written at full strength with no decay, where the powers of the
    block grow by binomials before they cancel (the module's docstring
    has the bound). The kernel stays within 1e-3 of float64 there, and
    the recurrence itself within 1e-5."""
    q, k, v, g, b = _inputs(6, 1, 64)
    k = jnp.broadcast_to(k[:, :1], k.shape)
    args = [q, k, v, jnp.zeros_like(g), jnp.ones_like(b)]
    state = jnp.zeros((LAYERS, SLOTS + 1, HEADS, D, D), jnp.float32)
    o, _ = _chunk(args, state, [0], [1], 0)
    want_o, _ = _float64([a[0, :, 0] for a in args], state[0, 0, 0])
    assert np.abs(np.asarray(o)[0, :, 0] - want_o).max() <= \
        1e-3 * np.abs(want_o).max()


@pytest.mark.parametrize("rows", [8, 3])
def test_the_update_kernel_is_one_step_in_place(rows):
    """A decode step: live rows on distinct slots, pad rows on the
    scratch one; each live row's state and tail of the layer named move
    on by one token, the output is `S_t^T q_t`, and no other slot or
    layer changes."""
    channels = 3 * HEADS * D
    rng = np.random.default_rng(7)
    q, k, v, g, b = (a[:, 0] for a in _inputs(8, rows, 1, decay=(-5.0, 0.5)))
    x = jnp.asarray(rng.standard_normal((rows, channels)), jnp.float32)
    state = _state(9)
    tail = jnp.asarray(rng.standard_normal((LAYERS, SLOTS + 1, 4, channels)),
                       jnp.bfloat16)
    live = rows - 1
    slots = jnp.asarray(list(rng.permutation(SLOTS)[:live]) + [SLOTS],
                        jnp.int32)
    layer = 2
    want_o, want_s, want_t = kda.kda_update_ref(x, q, k, v, g, b, state,
                                                tail, slots, layer)
    bb = jnp.broadcast_to(b[..., None], v.shape)
    o, s, t = kda._kda_update_impl(
        kda._row_blocks(x), kda._columns(g, k, q),
        kda._row_blocks((bb * v).reshape(rows, -1)),
        kda._row_blocks(bb.reshape(rows, -1)), state, tail,
        jnp.full((1,), layer, jnp.int32), slots, interpret=True)
    o = np.asarray(o).reshape(rows, HEADS, D)
    held = np.asarray(slots[:live])
    np.testing.assert_allclose(o[:live], np.asarray(want_o)[:live],
                               atol=2e-6)
    np.testing.assert_allclose(np.asarray(s)[layer, held],
                               np.asarray(want_s)[layer, held], atol=2e-6)
    assert np.array_equal(np.asarray(t)[layer, held],
                          np.asarray(want_t)[layer, held])
    # the tail moved on by the new input: the last row is x, the rest
    # shifted up by one
    assert np.array_equal(np.asarray(t)[layer, held, -1],
                          np.asarray(x.astype(jnp.bfloat16))[:live])
    assert np.array_equal(np.asarray(t)[layer, held, :-1],
                          np.asarray(tail)[layer, held, 1:])
    untouched = np.ones((LAYERS, SLOTS + 1), bool)
    untouched[layer, held] = False
    untouched[layer, SLOTS] = False          # the pad rows' scratch
    assert np.array_equal(np.asarray(s)[untouched],
                          np.asarray(state)[untouched])
    assert np.array_equal(np.asarray(t)[untouched],
                          np.asarray(tail)[untouched])


def test_a_chunk_then_updates_is_the_longer_chunk():
    """Prefill then decode through one slot: a 64-token chunk and then
    eight one-token updates leave the state, and give the outputs, of a
    72-token recurrence."""
    args = _inputs(10, 1, 72, decay=(-5.0, 0.5))
    state = _state(11)
    want_o, want_s = kda.kda_chunk_ref(*args, state, jnp.asarray([3]),
                                       jnp.asarray([1]), 1)
    _, s = _chunk([a[:, :64] for a in args], state, [3], [1], 1)
    tail = jnp.zeros((LAYERS, SLOTS + 1, 4, 3 * HEADS * D), jnp.bfloat16)
    for t in range(64, 72):
        q, k, v, g, b = (a[:, t] for a in args)
        bb = jnp.broadcast_to(b[..., None], v.shape)
        o, s, tail = kda._kda_update_impl(
            kda._row_blocks(jnp.zeros((1, 3 * HEADS * D))),
            kda._columns(g, k, q), kda._row_blocks((bb * v).reshape(1, -1)),
            kda._row_blocks(bb.reshape(1, -1)), s, tail,
            jnp.full((1,), 1, jnp.int32), jnp.asarray([3], jnp.int32),
            interpret=True)
        np.testing.assert_allclose(
            np.asarray(o).reshape(HEADS, D), np.asarray(want_o)[0, t],
            atol=2e-6)
    np.testing.assert_allclose(s[1, 3], want_s[1, 3], atol=2e-5)


def test_the_dispatchers_take_the_jnp_side_off_a_tpu():
    """On the CPU both entry points are the recurrence itself, and say
    so (`note_kernel_path`); the padding of a chunk shorter than 64 is
    the kernel side's alone."""
    args = _inputs(12, 1, 10)
    state = _state(13)
    o, s = kda.kda_chunk(*args, state, jnp.asarray([0]), jnp.asarray([1]), 0)
    want_o, want_s = kda.kda_chunk_ref(*args, state, jnp.asarray([0]),
                                       jnp.asarray([1]), 0)
    assert np.array_equal(o, want_o) and np.array_equal(s, want_s)
    assert o.shape == (1, 10, HEADS, D)
    ops, moved = kda.chunk_cost(1, 1024, 32, 128, 128)
    assert ops == 16 * 32 * 2 * (3 * 64 * 64 * 128 + 11 * 64 ** 3 +
                                 2 * 64 * 128 * 128 + 2 * 64 * 64 * 128 +
                                 192 * 128 * 128)
    assert moved == 4 * (1024 * 32 * (5 * 128 + 1) + 2 * 32 * 128 * 128)
