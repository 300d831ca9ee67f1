"""FusedMoE dispatch tests: the ragged grouped-GEMM path
(jax.lax.ragged_dot over token-sorted expert bins — the TPU analog of
the reference's moe_align_block_size + fused expert GEMM,
`triton_kernel/fused_moe.py:142,234`) must match the dense all-experts
combine exactly, and the dense path stays for sharded/small configs."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from aphrodite_tpu.modeling.layers.fused_moe import FusedMoE


def make_moe(num_experts, top_k, hidden=32, inter=48, seed=0):
    rs = np.random.RandomState(seed)
    moe = FusedMoE(num_experts, top_k, hidden, inter,
                   dtype=jnp.float32)
    params = {
        "gate": jnp.asarray(rs.randn(hidden, num_experts) * 0.3,
                            jnp.float32),
        "w_gate": jnp.asarray(rs.randn(num_experts, hidden, inter) * 0.1,
                              jnp.float32),
        "w_up": jnp.asarray(rs.randn(num_experts, hidden, inter) * 0.1,
                            jnp.float32),
        "w_down": jnp.asarray(rs.randn(num_experts, inter, hidden) * 0.1,
                              jnp.float32),
    }
    return moe, params


def _routed(logits, routing):
    """Router logits under a routing: as drawn (`random`), with every
    fourth expert out of reach (`idle`: experts without a pair), or
    every token with the first token's (`same`: a few experts hold all
    the pairs)."""
    logits = np.array(logits, np.float32)
    if routing == "idle":
        logits[:, ::4] -= 30.0
    elif routing == "same":
        logits[:] = logits[0]
    return jnp.asarray(logits)


@pytest.mark.parametrize("num_experts,top_k,tokens,routing", [
    (8, 2, 17, "own"),     # Mixtral shape: ragged path engages
    (8, 2, 1, "own"),      # single token
    (16, 4, 33, "own"),    # Deepseek-ish
    (64, 6, 512, "own"),   # a prompt chunk's routing, SmallThinker's
    (64, 6, 40, "idle"),   # some experts get no pair
    (16, 4, 33, "same"),   # every token picks the same experts
    (8, 1, 9, "own"),      # one expert a token
    (8, 1, 9, "same"),     # one group holds every pair
])
def test_ragged_matches_dense(num_experts, top_k, tokens, routing):
    moe, params = make_moe(num_experts, top_k)
    rs = np.random.RandomState(1)
    x = jnp.asarray(rs.randn(tokens, 32) * 0.5, jnp.float32)
    logits = None if routing == "own" else _routed(
        np.asarray(x) @ np.asarray(params["gate"]), routing)

    assert not moe.sharded
    ragged = np.asarray(moe(params, x, router_logits=logits))  # E > 4
    moe.sharded = True
    dense = np.asarray(moe(params, x, router_logits=logits))   # forced
    np.testing.assert_allclose(ragged, dense, rtol=2e-5, atol=2e-5)
    assert np.abs(dense).max() > 1e-2


def test_small_expert_count_uses_dense():
    """E <= 4 keeps the dense combine (ragged overhead not worth it);
    result sanity-checked against a python per-token loop."""
    moe, params = make_moe(4, 2)
    rs = np.random.RandomState(2)
    x = rs.randn(5, 32).astype(np.float32) * 0.5
    out = np.asarray(moe(params, jnp.asarray(x)))

    gate_w = np.asarray(params["gate"])
    logits = x @ gate_w
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    expected = np.zeros_like(x)
    for t in range(x.shape[0]):
        top = np.argsort(-probs[t])[:2]
        w = probs[t][top] / probs[t][top].sum()
        for e, we in zip(top, w):
            g = x[t] @ np.asarray(params["w_gate"][e])
            u = x[t] @ np.asarray(params["w_up"][e])
            act = g / (1 + np.exp(-g)) * u
            expected[t] += we * (act @ np.asarray(params["w_down"][e]))
    np.testing.assert_allclose(out, expected, rtol=2e-4, atol=2e-4)


def test_loader_marks_sharded(tmp_path):
    """Under a tp mesh the loader flags every FusedMoE layer so the
    GSPMD dense combine runs (ragged dispatch needs an all-to-all that
    isn't built yet)."""
    from aphrodite_tpu.modeling.loader import _mark_moe_sharded

    class Block:
        def __init__(self):
            self.moe = FusedMoE(8, 2, 32, 48)

    class Model:
        def __init__(self):
            self.layers = [Block(), Block()]

    m = Model()
    _mark_moe_sharded(m)
    assert all(b.moe.sharded for b in m.layers)


# ---- the gate's activation and a router of the caller's ----

def _plain_moe(x, logits, params, top_k, act):
    """`sum_k w_k W_down,k (act(W_gate,k m) * W_up,k m)` over the
    `top_k` largest logits, `w` the softmax over those logits alone:
    a loop over tokens and experts, in float64."""
    x, logits = np.asarray(x, np.float64), np.asarray(logits, np.float64)
    out = np.zeros_like(x)
    for t in range(x.shape[0]):
        top = np.argsort(-logits[t])[:top_k]
        w = np.exp(logits[t, top] - logits[t, top].max())
        w /= w.sum()
        for e, we in zip(top, w):
            gate = x[t] @ np.asarray(params["w_gate"][e], np.float64)
            up = x[t] @ np.asarray(params["w_up"][e], np.float64)
            out[t] += we * ((act(gate) * up) @
                            np.asarray(params["w_down"][e], np.float64))
    return out


_ACTS = {"relu": lambda g: np.maximum(g, 0.0),
         "silu": lambda g: g / (1.0 + np.exp(-g))}


@pytest.mark.parametrize("activation", ["relu", "silu"])
@pytest.mark.parametrize("num_experts,top_k,tokens,routing", [
    (64, 6, 19, "random"),      # SmallThinker's routing: ragged path
    (4, 2, 5, "random"),        # the dense combine
    (64, 6, 512, "random"),     # a prompt chunk: 3,072 pairs
    (64, 6, 40, "idle"),        # experts without a pair
    (16, 4, 33, "same"),        # every token picks the same experts
    (8, 1, 9, "random"),        # one expert a token
])
def test_gate_activation_and_the_callers_router(activation, num_experts,
                                                top_k, tokens, routing):
    """ReGLU or SwiGLU experts under router logits the caller computed
    from another tensor than the experts' input; the softmax over all
    experts renormalised over the top k is the softmax over the top
    k logits alone. A layer without its own router holds no `gate`."""
    moe, params = make_moe(num_experts, top_k)
    routed = FusedMoE(num_experts, top_k, 32, 48, activation=activation,
                      own_router=False, dtype=jnp.float32)
    assert sorted(routed.init()) == ["w_down", "w_gate", "w_up"]
    assert sorted(routed.specs()) == ["w_down", "w_gate", "w_up"]
    assert sorted(moe.init()) == ["gate", "w_down", "w_gate", "w_up"]
    experts = {k: v for k, v in params.items() if k != "gate"}
    rs = np.random.RandomState(3)
    x = jnp.asarray(rs.randn(tokens, 32) * 0.5, jnp.float32)
    elsewhere = _routed(rs.randn(tokens, num_experts) * 2.0, routing)
    counts = []
    out = np.asarray(routed(experts, x, router_logits=elsewhere,
                            counts=counts))
    want = _plain_moe(x, elsewhere, experts, top_k, _ACTS[activation])
    np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-5)
    # counted in the program: pairs routed and experts with a pair
    (pairs, touched, held), = counts
    assert int(held) == int(pairs)      # no share: every pair is held
    top = np.argsort(-np.asarray(elsewhere), axis=-1)[:, :top_k]
    assert int(pairs) == tokens * top_k
    assert int(touched) == len(np.unique(top))
    # the other activation, or the layer's own input as the router's,
    # is another function
    other = "silu" if activation == "relu" else "relu"
    wrong = FusedMoE(num_experts, top_k, 32, 48, activation=other,
                     own_router=False, dtype=jnp.float32)
    assert np.abs(np.asarray(wrong(experts, x, router_logits=elsewhere))
                  - want).max() > 1e-3
    assert np.abs(np.asarray(moe(params, x)) - want).max() > 1e-3


def _lowered(moe, tokens, counts):
    """StableHLO of one call of the layer, `counts` asked for or not."""
    def layer(params, x, logits):
        got = [] if counts else None
        out = moe(params, x, counts=got,
                  router_logits=None if moe.own_router else logits)
        return out, got
    shapes = (jax.eval_shape(moe.init),
              jax.ShapeDtypeStruct((tokens, moe.hidden_size), moe.dtype),
              jax.ShapeDtypeStruct((tokens, moe.num_experts), jnp.float32))
    # for the TPU, where `ragged_dot` stays one operation
    return jax.jit(layer).trace(*shapes).lower(
        lowering_platforms=("tpu",)).as_text()


@pytest.mark.parametrize("num_experts,top_k,tokens,own_router", [
    (64, 6, 2048, False),       # a chunk of the benchmark's cell
    (64, 6, 24, False),         # its decode step
    (8, 2, 17, True),           # Mixtral
])
def test_the_grouped_path_lowers_without_a_scatter(num_experts, top_k,
                                                   tokens, own_router):
    """What holds the mechanism on the CPU: pairs reach the grouped
    matmuls and come back by two gathers, group sizes and the touched
    count by a comparison. XLA runs a row scatter on the TPU one update
    after another (PERF.md §6, PR 34), so none may come back: not the
    combine, not a `bincount`, not an index update under either."""
    moe = FusedMoE(num_experts, top_k, 64, 32, activation="relu",
                   own_router=own_router)
    for counts in (True, False):
        text = _lowered(moe, tokens, counts)
        assert "scatter" not in text     # `stablehlo.scatter` or any
        assert text.count('"chlo.ragged_dot"(') == 3
        # the pairs' rows out, and a slot's rows back
        assert text.count('"stablehlo.gather"(') == 1 + top_k
    # the dense combine, the mesh path, is another matter and keeps its
    # scatter of the routing weights
    moe.sharded = True
    assert "stablehlo.scatter" in _lowered(moe, tokens, True)


@pytest.mark.parametrize("num_experts,top_k,tokens,routing", [
    (64, 6, 24, "random"),      # a decode step: not every expert
    (64, 6, 512, "random"),     # a chunk: every expert
    (64, 6, 40, "idle"),
    (16, 4, 33, "same"),        # four experts hold every pair
    (4, 2, 5, "random"),        # the dense combine counts too
])
def test_counts_are_what_numpy_counts(num_experts, top_k, tokens, routing):
    """`counts` gains the call's token-expert pairs and the experts
    with a pair, as int32 scalars, on either path."""
    moe, params = make_moe(num_experts, top_k)
    rs = np.random.RandomState(5)
    x = jnp.asarray(rs.randn(3, tokens // 3 + 1, 32)[:, :tokens] * 0.5,
                    jnp.float32)                # a leading batch axis
    logits = _routed(rs.randn(x.shape[0] * x.shape[1], num_experts)
                     * 2.0, routing)
    counts = []
    out = moe(params, x, router_logits=logits.reshape(
        x.shape[:2] + (num_experts,)), counts=counts)
    assert out.shape == x.shape
    (pairs, touched, held), = counts
    assert int(held) == int(pairs)      # no share: every pair is held
    top = np.argsort(-np.asarray(logits), axis=-1)[:, :top_k]
    assert pairs.dtype == touched.dtype == jnp.int32
    assert int(pairs) == top.size == x.shape[0] * x.shape[1] * top_k
    assert int(touched) == len(np.unique(top))
    if routing == "same":
        assert int(touched) == top_k
    if routing == "idle":
        assert int(touched) <= num_experts - num_experts // 4


def test_mixtral_routing_is_what_it_was():
    """The defaults are Mixtral's: SiLU, the layer's own router over
    its own input. The same numbers with and without the new
    arguments spelled out, and an unknown activation is refused."""
    moe, params = make_moe(8, 2)
    rs = np.random.RandomState(4)
    x = jnp.asarray(rs.randn(9, 32) * 0.5, jnp.float32)
    spelled = FusedMoE(8, 2, 32, 48, activation="silu", own_router=True,
                       dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(moe(params, x)),
                                  np.asarray(spelled(params, x)))
    logits = np.asarray(x) @ np.asarray(params["gate"])
    want = _plain_moe(x, logits, params, 2, _ACTS["silu"])
    np.testing.assert_allclose(np.asarray(moe(params, x)), want,
                               rtol=1e-4, atol=1e-5)
    with pytest.raises(ValueError, match="gelu"):
        FusedMoE(8, 2, 32, 48, activation="gelu")
