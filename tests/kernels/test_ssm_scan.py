"""The two selective-scan kernels (`ops/pallas/ssm_scan.py`) in
interpret mode against their `jax.numpy` side, and the `jax.numpy` side
against the recurrence written out in numpy: a chunk boundary, padding
passed over, a pad row that leaves every live slot untouched, NaN in
every slot no row holds. Whether Mosaic takes them at the served shape
is `test_mosaic_compile.py`'s; their numbers on the chip are
`tpu_smoke.py`'s.

Float32 on both sides and the same order of operations: the limit,
1e-5 absolute on values of order 1, is some ten times the rounding of
a state summed over a few hundred steps (2e-6 to 5e-6 read).

The arrays are the model's: `[layers, slots + 1, ...]`, a call naming
its layer. The cases below state one layer's `[slots + 1, ...]` part;
`_in_layers` sets it among three, the other two NaN, and every call is
held to leaving those two bit for bit what they were."""
import numpy as np
import pytest

import jax.numpy as jnp

from aphrodite_tpu.ops.pallas import ssm_scan as S

N, CH, SLOTS = 16, 1024, 5
#: the layers of the arrays, and the one the calls name
LAYERS, LAYER = 3, 1
TOL = dict(rtol=1e-5, atol=1e-5)


def _in_layers(part):
    """`part` as layer `LAYER` of an array of `LAYERS`, NaN around
    it."""
    part = np.asarray(part)
    whole = np.full((LAYERS,) + part.shape, np.nan, part.dtype)
    whole[LAYER] = part
    return jnp.asarray(whole)


def _of_layers(whole):
    """The layer's part back, the other layers seen untouched."""
    whole = np.asarray(whole)
    others = np.delete(whole, LAYER, axis=0)
    assert whole.shape[0] == LAYERS and np.isnan(
        others.astype(np.float32)).all()
    return whole[LAYER]


def _inputs(rows, tokens, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *shape: rng.normal(size=shape).astype(np.float32)
    return dict(
        u=f(rows, tokens, CH),
        # softplus of a bias in the served range: 0.002 to 0.1
        delta=np.log1p(np.exp(f(rows, tokens, CH) - 4.0)),
        b=f(rows, tokens, N), c=f(rows, tokens, N),
        a=-np.exp(rng.uniform(-1.5, 1.5, (N, CH)).astype(np.float32)),
        d=rng.uniform(0, 0.5, (CH,)).astype(np.float32))


def _state(seed=1, nan_in=()):
    state = np.random.default_rng(seed).normal(
        size=(SLOTS + 1, N, CH)).astype(np.float32)
    state[list(nan_in)] = np.nan
    return state


def _scan_kernel(x, state, slots, fresh):
    y, state = S._ssm_scan_impl(
        x["u"], x["delta"], np.swapaxes(x["b"], 1, 2),
        np.swapaxes(x["c"], 1, 2), x["a"], x["d"][None],
        _in_layers(state), jnp.asarray([LAYER], jnp.int32),
        jnp.asarray(slots, jnp.int32), jnp.asarray(fresh, jnp.int32),
        interpret=True)
    return y, _of_layers(state)


def _scan_ref(x, state, slots, fresh):
    y, state = S.ssm_scan_ref(
        x["u"], x["delta"], x["b"], x["c"], x["a"], x["d"],
        _in_layers(state), jnp.asarray(slots), jnp.asarray(fresh), LAYER)
    return y, _of_layers(state)


def test_the_jnp_scan_is_the_recurrence_written_out():
    x = _inputs(1, 24)
    s = np.zeros((N, CH), np.float64)
    want = []
    for t in range(24):
        dl, u = x["delta"][0, t].astype(np.float64), x["u"][0, t]
        s = np.exp(dl[None] * x["a"]) * s + \
            (dl * u)[None] * x["b"][0, t][:, None]
        want.append((s * x["c"][0, t][:, None]).sum(0) + x["d"] * u)
    y, state = _scan_ref(x, _state(), [2], [1])
    np.testing.assert_allclose(y[0], np.asarray(want), **TOL)
    np.testing.assert_allclose(state[2], s, **TOL)


@pytest.mark.parametrize("rows,tokens", [(2, 256), (1, 512), (3, 128),
                                         (2, 512)])
def test_the_chunk_scan_kernel_against_the_jnp_side(rows, tokens):
    """Rows that start from their slot and rows that start from zeros
    (whatever their slot holds: NaN here); every slot no row holds is
    NaN before and after, and bit for bit what it was."""
    x = _inputs(rows, tokens, seed=tokens)
    slots = [1, 3, 4][:rows]
    fresh = [0, 1, 0][:rows]
    held = {s for s, f in zip(slots, fresh) if not f}
    state = _state(nan_in=set(range(SLOTS + 1)) - held)
    y_ref, s_ref = _scan_ref(x, state, slots, fresh)
    y, s = _scan_kernel(x, state, slots, fresh)
    assert not np.isnan(np.asarray(y)).any()
    np.testing.assert_allclose(y, y_ref, **TOL)
    np.testing.assert_allclose(np.asarray(s)[slots],
                               np.asarray(s_ref)[slots], **TOL)
    others = sorted(set(range(SLOTS + 1)) - set(slots))
    assert np.isnan(np.asarray(s)[others]).all()


@pytest.mark.parametrize("side", ["kernel", "jnp"])
def test_a_chunk_boundary_hands_the_state_over_through_the_slot(side):
    """384 tokens in one chunk, and in chunks of 128 and 256 through
    the slot: the same outputs and the same last state."""
    scan = _scan_kernel if side == "kernel" else _scan_ref
    x = _inputs(1, 384, seed=7)
    whole_y, whole_s = scan(x, _state(), [2], [1])
    cut = lambda lo, hi: {k: v[:, lo:hi] if k in "u delta b c".split()
                          else v for k, v in x.items()}
    y1, s1 = scan(cut(0, 128), _state(), [2], [1])
    y2, s2 = scan(cut(128, 384), np.asarray(s1), [2], [0])
    np.testing.assert_allclose(
        np.concatenate([y1, y2], axis=1), whole_y, **TOL)
    np.testing.assert_allclose(np.asarray(s2)[2], np.asarray(whole_s)[2],
                               **TOL)


def test_padding_is_passed_over_where_delta_is_zero():
    """A row of 100 live tokens in a chunk of 128: with delta zeroed
    behind them the slot holds the state of token 99."""
    x = _inputs(1, 128, seed=3)
    x["delta"][:, 100:] = 0.0
    short = {k: v[:, :100] if k in "u delta b c".split() else v
             for k, v in x.items()}
    _, s_pad = _scan_kernel(x, _state(), [0], [1])
    _, s_ref = _scan_ref(short, _state(), [0], [1])
    np.testing.assert_allclose(np.asarray(s_pad)[0], np.asarray(s_ref)[0],
                               **TOL)


def _update(fn, x, state, tail, slots, xnew):
    args = (xnew, x["u"][:, 0], x["delta"][:, 0], x["b"][:, 0],
            x["c"][:, 0], x["a"], x["d"])
    if fn is S.ssm_update_ref:
        y, state, tail = fn(*args, _in_layers(state), _in_layers(tail),
                            jnp.asarray(slots), LAYER)
        return y, _of_layers(state), _of_layers(tail)
    xn, u, dl, b, c, a, d = args
    y, state, tail = S._ssm_update_impl(
        S._row_blocks(jnp.asarray(xn, jnp.float32)), S._row_blocks(u),
        S._row_blocks(dl), b.T, c.T, a, d[None], _in_layers(state),
        _in_layers(tail), jnp.asarray([LAYER], jnp.int32),
        jnp.asarray(slots, jnp.int32), interpret=True)
    return y.reshape(u.shape), _of_layers(state), _of_layers(tail)


@pytest.mark.parametrize("tail_dtype,kept", [
    (jnp.bfloat16, 4), (jnp.float32, 4), (jnp.bfloat16, 8),
    (jnp.float32, 3)], ids=["bfloat16", "float32", "bfloat16-8", "float32-3"])
def test_the_decode_update_kernel_against_the_jnp_side(tail_dtype, kept):
    """Four rows: two live ones on slots 3 and 0 and two pad rows on
    the scratch slot (the arrays' last). The live slots move on by one
    token, the convolution's tail by the row's new input, whatever
    number of inputs a slot keeps; every other slot but the scratch
    one is bit for bit what it was, NaN and all."""
    rows = 4
    x = _inputs(rows, 1, seed=11)
    slots = [3, 0, SLOTS, SLOTS]
    state = _state(nan_in={1, 2, 4})
    rng = np.random.default_rng(5)
    tail = np.array(jnp.asarray(
        rng.normal(size=(SLOTS + 1, kept, CH)), tail_dtype))
    tail[[1, 2, 4]] = np.nan
    xnew = rng.normal(size=(rows, CH)).astype(np.float32)
    y_ref, s_ref, t_ref = _update(S.ssm_update_ref, x, state, tail, slots,
                                  xnew)
    y, s, t = _update(None, x, state, tail, slots, xnew)
    np.testing.assert_allclose(y[:2], y_ref[:2], **TOL)
    for live in (3, 0):
        np.testing.assert_allclose(np.asarray(s)[live],
                                   np.asarray(s_ref)[live], **TOL)
        np.testing.assert_array_equal(
            np.asarray(t, np.float32)[live],
            np.asarray(t_ref, np.float32)[live])
    # the tail moved on: its last row is the new input, the ones before
    # are the old tail's but its first
    np.testing.assert_array_equal(
        np.asarray(t, np.float32)[3, :-1],
        np.asarray(tail, np.float32)[3, 1:])
    np.testing.assert_array_equal(
        np.asarray(t, np.float32)[3, -1],
        np.asarray(jnp.asarray(xnew[0], tail_dtype), np.float32))
    assert np.isnan(np.asarray(s)[[1, 2, 4]]).all()
    assert np.isnan(np.asarray(t, np.float32)[[1, 2, 4]]).all()
    assert not np.isnan(np.asarray(y)[:2]).any()


@pytest.mark.parametrize("rows,block", [(8, 8), (12, 12), (16, 8), (24, 8),
                                        (2, 2), (1, 1)])
def test_the_rows_reach_the_update_kernel_eight_a_block(rows, block):
    """`x`, `u`, `delta` and `y` are blocked eight rows at a time (the
    batch whole where it is not eight's multiple): a cell reads its own
    row of the block the cells before it fetched, and writes its own
    row of `y`. Every row on a slot of its own, in shuffled order,
    against the jnp side."""
    assert S._row_blocks(jnp.zeros((rows, 128))).shape == (
        rows // block, block, 128)
    x = _inputs(rows, 1, seed=rows)
    rng = np.random.default_rng(rows)
    slots_n = 24
    slots = [int(v) for v in rng.permutation(slots_n)[:rows]]
    state = rng.normal(size=(slots_n + 1, N, CH)).astype(np.float32)
    tail = np.array(jnp.asarray(
        rng.normal(size=(slots_n + 1, 4, CH)), jnp.bfloat16))
    xnew = np.asarray(jnp.asarray(rng.normal(size=(rows, CH)),
                                  jnp.bfloat16), np.float32)
    y_ref, s_ref, t_ref = _update(S.ssm_update_ref, x, state, tail, slots,
                                  xnew)
    y, s, t = _update(None, x, state, tail, slots, xnew)
    np.testing.assert_allclose(y, y_ref, **TOL)
    np.testing.assert_allclose(s, s_ref, **TOL)
    np.testing.assert_array_equal(np.asarray(t, np.float32),
                                  np.asarray(t_ref, np.float32))


def test_the_decode_update_kernel_at_128_rows():
    """A full decode bucket of 128 rows over 128 slots and the scratch
    one (AI21-Jamba2-3B's cell): 120 live rows on slots in shuffled
    order and 8 pad rows on the scratch slot. Every live slot moves on
    as the jnp side moves it, and the 8 slots no row holds are bit for
    bit what they were, NaN and all."""
    rows, slots_n = 128, 128
    x = _inputs(rows, 1, seed=21)
    rng = np.random.default_rng(6)
    order = rng.permutation(slots_n)
    slots = list(order[:120]) + [slots_n] * 8
    unheld = sorted(int(s) for s in order[120:])
    state = rng.normal(size=(slots_n + 1, N, CH)).astype(np.float32)
    tail = np.array(jnp.asarray(
        rng.normal(size=(slots_n + 1, 4, CH)), jnp.bfloat16))
    state[unheld] = np.nan
    tail[unheld] = np.nan
    xnew = rng.normal(size=(rows, CH)).astype(np.float32)
    y_ref, s_ref, t_ref = _update(S.ssm_update_ref, x, state, tail, slots,
                                  xnew)
    y, s, t = _update(None, x, state, tail, slots, xnew)
    live = [int(v) for v in order[:120]]
    np.testing.assert_allclose(y[:120], y_ref[:120], **TOL)
    np.testing.assert_allclose(np.asarray(s)[live],
                               np.asarray(s_ref)[live], **TOL)
    np.testing.assert_array_equal(np.asarray(t, np.float32)[live],
                                  np.asarray(t_ref, np.float32)[live])
    assert np.isnan(np.asarray(s)[unheld]).all()
    assert np.isnan(np.asarray(t, np.float32)[unheld]).all()
    assert not np.isnan(np.asarray(y)[:120]).any()


def test_a_decode_update_is_one_more_token_of_the_chunk_scan():
    """Prefill 128 tokens, then one decode step: the state and the
    output are those of a chunk of 129... of which the kernels only
    ever see 128 and 1."""
    x = _inputs(1, 136, seed=13)
    x["delta"][:, 129:] = 0.0
    cut = lambda lo, hi: {k: v[:, lo:hi] if k in "u delta b c".split()
                          else v for k, v in x.items()}
    y_all, s_all = _scan_ref(x, _state(), [1], [1])
    _, s128 = _scan_kernel(cut(0, 128), _state(), [1], [1])
    tail = np.zeros((SLOTS + 1, 4, CH), np.float32)
    y, s, _ = _update(None, cut(128, 129), np.asarray(s128), tail, [1],
                      np.zeros((1, CH), np.float32))
    np.testing.assert_allclose(y[0], y_all[0, 128], **TOL)
    np.testing.assert_allclose(np.asarray(s)[1], np.asarray(s_all)[1],
                               **TOL)


def test_the_dispatchers_take_the_jnp_side_off_the_chip(caplog):
    """On the CPU both entry points are the `jax.numpy` side, and say
    so once in the log (`kernel path: ssm_scan = reference`)."""
    x = _inputs(1, 16)
    y, s = S.selective_scan(x["u"], x["delta"], x["b"], x["c"], x["a"],
                            x["d"], _in_layers(_state()),
                            jnp.asarray([2]), jnp.asarray([True]), LAYER)
    y_ref, s_ref = _scan_ref(x, _state(), [2], [1])
    np.testing.assert_array_equal(y, y_ref)
    np.testing.assert_array_equal(_of_layers(s), s_ref)


@pytest.mark.parametrize("rows,kept,itemsize,row_bytes", [
    (128, 4, 2, 819_200),       # Jamba's cell: 788,608 by the roofline
    (48, 4, 2, 819_200),        # Phi's
    (8, 3, 4, 860_160),
])
def test_the_update_kernel_states_what_it_moves(rows, kept, itemsize,
                                                row_bytes):
    """The cost the compiler schedules a decode step by is the call's
    own traffic: a row's state and tail both ways as they are allocated
    (`kept` rows, where `perf/rooflines/ssm_scan.py::update_count` has
    `d_conv - 1`), `x`, `u`, `delta` in and `y` out in float32; seven
    operations a state element, the exponential among them."""
    dtype = {2: jnp.bfloat16, 4: jnp.float32}[itemsize]
    cost = S._update_cost(rows, 16, 5120, kept, dtype)
    assert cost.bytes_accessed == rows * row_bytes
    assert row_bytes == 2 * 16 * 5120 * 4 + 2 * kept * 5120 * itemsize \
        + 4 * 5120 * 4
    assert cost.flops + cost.transcendentals == 7 * rows * 16 * 5120


@pytest.mark.parametrize("rows,tokens", [(1, 512), (4, 512), (1, 2048)])
def test_the_scan_kernel_states_what_it_moves(rows, tokens):
    """A chunk's call: `u`, `delta` in and `y` out, B and C, a row's
    state once each way; what `scan_count` counts but A and D."""
    cost = S._scan_cost(rows, tokens, 16, 5120)
    assert cost.bytes_accessed == rows * tokens * (3 * 5120 * 4 + 2 * 16 * 4) \
        + rows * 2 * 16 * 5120 * 4
    assert cost.flops + cost.transcendentals == \
        7 * rows * tokens * 16 * 5120
