"""The two selective-scan kernels (`ops/pallas/ssm_scan.py`) in
interpret mode against their `jax.numpy` side, and the `jax.numpy` side
against the recurrence written out in numpy: a chunk boundary, padding
passed over, a pad row that leaves every live slot untouched, NaN in
every slot no row holds. Whether Mosaic takes them at the served shape
is `test_mosaic_compile.py`'s; their numbers on the chip are
`tpu_smoke.py`'s.

Float32 on both sides and the same order of operations: the limit,
1e-5 absolute on values of order 1, is some ten times the rounding of
a state summed over a few hundred steps (2e-6 to 5e-6 read)."""
import numpy as np
import pytest

import jax.numpy as jnp

from aphrodite_tpu.ops.pallas import ssm_scan as S

N, CH, SLOTS = 16, 1024, 5
TOL = dict(rtol=1e-5, atol=1e-5)


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
    return S._ssm_scan_impl(
        x["u"], x["delta"], np.swapaxes(x["b"], 1, 2),
        np.swapaxes(x["c"], 1, 2), x["a"], x["d"][None],
        jnp.asarray(state), jnp.asarray(slots, jnp.int32),
        jnp.asarray(fresh, jnp.int32), interpret=True)


def _scan_ref(x, state, slots, fresh):
    return S.ssm_scan_ref(x["u"], x["delta"], x["b"], x["c"], x["a"],
                          x["d"], jnp.asarray(state), jnp.asarray(slots),
                          jnp.asarray(fresh))


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
        return fn(*args, jnp.asarray(state), jnp.asarray(tail),
                  jnp.asarray(slots))
    xn, u, dl, b, c, a, d = args
    y, state, tail = S._ssm_update_impl(
        jnp.asarray(xn)[:, None], u[:, None], dl[:, None], b.T, c.T, a,
        d[None], jnp.asarray(state), jnp.asarray(tail),
        jnp.asarray(slots, jnp.int32), interpret=True)
    return y[:, 0], state, tail


@pytest.mark.parametrize("tail_dtype", [jnp.bfloat16, jnp.float32])
def test_the_decode_update_kernel_against_the_jnp_side(tail_dtype):
    """Four rows: two live ones on slots 3 and 0 and two pad rows on
    the scratch slot (the arrays' last). The live slots move on by one
    token, the convolution's tail by the row's new input; every other
    slot but the scratch one is bit for bit what it was, NaN and all."""
    rows = 4
    x = _inputs(rows, 1, seed=11)
    slots = [3, 0, SLOTS, SLOTS]
    state = _state(nan_in={1, 2, 4})
    rng = np.random.default_rng(5)
    tail = np.array(jnp.asarray(
        rng.normal(size=(SLOTS + 1, 3, CH)), tail_dtype))
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
    # the tail moved on: its last row is the new input, the two before
    # are the old tail's last two
    np.testing.assert_array_equal(
        np.asarray(t, np.float32)[3, :2], np.asarray(tail, np.float32)[3, 1:])
    np.testing.assert_array_equal(
        np.asarray(t, np.float32)[3, 2],
        np.asarray(jnp.asarray(xnew[0], tail_dtype), np.float32))
    assert np.isnan(np.asarray(s)[[1, 2, 4]]).all()
    assert np.isnan(np.asarray(t, np.float32)[[1, 2, 4]]).all()
    assert not np.isnan(np.asarray(y)[:2]).any()


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
        rng.normal(size=(slots_n + 1, 3, CH)), jnp.bfloat16))
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
    tail = np.zeros((SLOTS + 1, 3, CH), np.float32)
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
                            x["d"], jnp.asarray(_state()),
                            jnp.asarray([2]), jnp.asarray([True]))
    y_ref, s_ref = _scan_ref(x, _state(), [2], [1])
    np.testing.assert_array_equal(y, y_ref)
    np.testing.assert_array_equal(s, s_ref)
