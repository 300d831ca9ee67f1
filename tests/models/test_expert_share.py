"""An expert layer that holds a share of the experts its router scores
(`FusedMoE(routed_experts=, first_expert=)`, `LagunaConfig`'s two
share keys): one chip's part of an expert-parallel layer, run without
its exchange. What ties the share to the model: the parts that all the
shares give, with what every chip computes alike (attention, the
shared expert) counted once, add up to what the uncut reference gives
for the whole layer. Float32, toy sizes, seeded weights."""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from aphrodite_tpu.modeling.layers.fused_moe import FusedMoE
from perf import cells, weights

ref = cells.load_module(os.path.join(cells.ROOT, "perf", "references",
                                     "laguna.py"))
import tests.models.test_laguna as toy  # noqa: E402  (the toy config)

ROUTED, TOP_K = 16, 4


def _moe_params(rs, experts=ROUTED, hidden=32, inter=16):
    def draw(*shape):
        return jnp.asarray(rs.standard_normal(shape) * shape[-2] ** -0.5,
                           jnp.float32)
    return {"gate": draw(hidden, experts) * 6,
            "w_gate": draw(experts, hidden, inter),
            "w_up": draw(experts, hidden, inter),
            "w_down": draw(experts, inter, hidden)}


def _share_of(params, first, held):
    return dict(params, **{k: params[k][first:first + held]
                           for k in ("w_gate", "w_up", "w_down")})


# ---- the layer: the shares add up ----

@pytest.mark.parametrize("shares", [(8, 8), (4, 4, 4, 4), (12, 4)],
                         ids=["two-halves", "four-quarters", "uneven"])
@pytest.mark.parametrize("tokens", [5, 96])
def test_the_shares_of_the_routed_sum_add_up(shares, tokens):
    """`FusedMoE` alone: every share routes over all sixteen experts,
    computes the pairs of its own, and the shares' outputs add up to
    the layer that holds them all; so do the pairs they count."""
    rs = np.random.default_rng(tokens)
    params = _moe_params(rs)
    x = jnp.asarray(rs.standard_normal((tokens, 32)), jnp.float32)
    whole_counts = []
    whole = FusedMoE(ROUTED, TOP_K, 32, 16, dtype=jnp.float32)(
        params, x, counts=whole_counts)
    total, held_pairs, touched, first = 0.0, 0, 0, 0
    for held in shares:
        counts = []
        moe = FusedMoE(held, TOP_K, 32, 16, routed_experts=ROUTED,
                       first_expert=first, dtype=jnp.float32)
        assert moe.init()["gate"].shape == (32, ROUTED)
        assert moe.init()["w_gate"].shape == (held, 32, 16)
        total = total + moe(_share_of(params, first, held), x,
                            counts=counts)
        (pairs, mine, met), = counts
        assert int(pairs) == tokens * TOP_K
        assert 0 <= int(mine) <= held and int(met) <= int(pairs)
        held_pairs += int(met)
        touched += int(mine)
        first += held
    (pairs, all_touched, all_held), = whole_counts
    assert held_pairs == int(pairs) == int(all_held) == tokens * TOP_K
    assert touched == int(all_touched)
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("routing", ["all-held", "none-held", "one-expert"])
def test_a_share_under_uneven_routing(routing):
    """Every pair on held experts, none of them, or all on one held
    expert: the share computes what its own experts give and nothing
    else, and no row of no group leaks into a token."""
    rs = np.random.default_rng(7)
    params = _moe_params(rs)
    x = jnp.asarray(rs.standard_normal((33, 32)), jnp.float32)
    logits = rs.standard_normal((33, ROUTED)).astype(np.float32)
    lift = {"all-held": slice(8, 16), "none-held": slice(0, 8),
            "one-expert": slice(11, 12)}[routing]
    logits[:, lift] += 20.0
    counts = []
    moe = FusedMoE(8, TOP_K, 32, 16, routed_experts=ROUTED, first_expert=8,
                   own_router=False, dtype=jnp.float32)
    out = np.asarray(moe(
        {k: v for k, v in _share_of(params, 8, 8).items() if k != "gate"},
        x, router_logits=jnp.asarray(logits), counts=counts))
    (pairs, touched, met), = counts
    assert np.isfinite(out).all()
    if routing == "none-held":
        assert int(met) == int(touched) == 0 and not out.any()
        return
    if routing == "all-held":
        assert int(met) == 33 * TOP_K
    else:       # expert 11 first for every token, the other three free
        assert 33 <= int(met) < 33 * TOP_K and int(touched) >= 1
    # the plain sum over the held experts a token chose
    probs = jax.nn.softmax(jnp.asarray(logits), axis=-1)
    top, chosen = jax.lax.top_k(probs, TOP_K)
    top = np.asarray(top / top.sum(axis=-1, keepdims=True))
    want = np.zeros_like(out)
    for t in range(33):
        for w, e in zip(top[t], np.asarray(chosen)[t]):
            if 8 <= e < 16:
                h = jax.nn.silu(x[t] @ params["w_gate"][e]) * \
                    (x[t] @ params["w_up"][e])
                want[t] += w * np.asarray(h @ params["w_down"][e])
    np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-5)


def _lowered(moe, tokens=24):
    def layer(params, x):
        got = []
        return moe(params, x, counts=got), got
    shapes = (jax.eval_shape(moe.init),
              jax.ShapeDtypeStruct((tokens, moe.hidden_size), moe.dtype))
    return jax.jit(layer).trace(*shapes).lower(
        lowering_platforms=("tpu",)).as_text()


def test_a_share_of_all_the_experts_is_todays_layer_bit_for_bit():
    """A layer told that it holds every expert it routes over is the
    layer that is told nothing: the same program (its StableHLO for
    the chip, counts and all) and so the same bits."""
    plain = FusedMoE(64, 6, 64, 32)
    told = FusedMoE(64, 6, 64, 32, routed_experts=64, first_expert=0)
    assert _lowered(plain) == _lowered(told)
    # a share is another program: it masks, and still has no scatter
    share = _lowered(FusedMoE(32, 6, 64, 32, routed_experts=64))
    assert share != _lowered(plain) and "scatter" not in share
    assert share.count('"chlo.ragged_dot"(') == 3
    rs = np.random.default_rng(1)
    params = _moe_params(rs, experts=8)
    x = jnp.asarray(rs.standard_normal((17, 32)), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(FusedMoE(8, 2, 32, 16, dtype=jnp.float32)(params, x)),
        np.asarray(FusedMoE(8, 2, 32, 16, routed_experts=8,
                            dtype=jnp.float32)(params, x)))


def test_a_share_that_is_not_there_is_refused():
    with pytest.raises(ValueError, match="experts 12 to 19 of 16"):
        FusedMoE(8, 2, 32, 16, routed_experts=16, first_expert=12)
    moe = FusedMoE(8, 2, 32, 16, routed_experts=16, dtype=jnp.float32)
    moe.sharded = True      # what the loader sets under tp > 1
    with pytest.raises(NotImplementedError, match="exchange"):
        moe(_share_of(_moe_params(np.random.default_rng(0)), 0, 8),
            jnp.zeros((3, 32), jnp.float32))


# ---- the model: share 0 + share 1 = the uncut reference's layer ----

def test_the_shares_add_up_to_the_uncut_references_layer():
    """One sparse layer of the toy model (window, 18 gated heads):
    the program's layer as the chip of experts 0-7 runs it and as the
    chip of experts 8-15 runs it, against the reference's layer that
    holds all sixteen. Each chip's output is the stream plus attention
    plus the shared expert plus ITS routed part; attention and the
    shared expert are computed alike on both, so they are counted
    once: z_0 + z_1 - (z_0 - 2.5 routed_0) = z_whole."""
    from aphrodite_tpu.modeling.input_metadata import InputMetadata
    uncut = toy._config(num_experts=16, num_routed_experts=16)
    whole = weights.whole(ref.tree(uncut), ref.stages(uncut), 6)
    _, buckets = ref.stages(uncut)[2]
    w = {local: whole[b] for local, b in buckets.items()}
    n = 40
    x = jnp.asarray(np.random.default_rng(0).standard_normal((1, n, 64)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.layer_window_sparse(uncut, w, x,
                                                  ref.Precision()))
    meta = InputMetadata(slot_mapping=jnp.arange(n, dtype=jnp.int32),
                         block_tables=jnp.zeros((1, 1), jnp.int32),
                         context_lens=jnp.zeros((1,), jnp.int32),
                         prompt_lens=jnp.asarray([n], jnp.int32),
                         is_prompt=True)
    outs, met = [], 0
    for first in (0, 8):
        config = toy._config(first_held_expert=first)
        layer = toy._program_model(config).layers[1]
        params = dict(whole)
        key = "model.layers.1.mlp.experts"
        params[key] = _share_of(whole[key], first, 8)
        counts = []
        out, residual, _ = layer(
            params, jnp.arange(n, dtype=jnp.int32)[None], x, None, None,
            meta, counts)
        outs.append(np.asarray(out + residual))
        met += int(counts[0][2])
        # the reference's share, the same cut
        with jax.default_matmul_precision("highest"):
            mine = ref.layer_window_sparse(
                config, dict(w, **{"mlp.experts": params[key]}), x,
                ref.Precision())
        np.testing.assert_allclose(outs[-1], np.asarray(mine), atol=2e-5)
    assert met == n * 4         # every pair is held on one chip or the other
    # what both compute alike, counted once; each chip's routed part is
    # its output less that
    with jax.default_matmul_precision("highest"):
        alike = _alike(uncut, w, x)
    routed = [out - alike for out in outs]
    np.testing.assert_allclose(alike + routed[0] + routed[1], want,
                               atol=5e-5)
    assert all(np.abs(part).max() > 0.05 for part in routed)


def _alike(config, w, x):
    """The stream, attention and the shared expert of the reference's
    layer: the layer with no routed expert chosen (a router that holds
    none of them here)."""
    none = dict(config, num_experts=0, num_routed_experts=16)
    empty = dict(w["mlp.experts"], **{
        k: w["mlp.experts"][k][:0] for k in ("w_gate", "w_up", "w_down")})
    return np.asarray(ref.layer_window_sparse(
        none, dict(w, **{"mlp.experts": empty}), x, ref.Precision()))


# ---- a published-shape config builds the whole model ----

def test_a_published_config_builds_the_whole_model():
    """The publisher's keys alone (256 experts, 48 layers, no share
    keys), at a toy width: every layer holds all 256 experts behind a
    router of 256, the per-layer lists fall into the published
    pattern, and the layers lie in page groups of twelve."""
    from aphrodite_tpu.modeling.models.laguna import LagunaForCausalLM
    from aphrodite_tpu.transformers_utils.configs import LagunaConfig
    import json
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(json.loads(line) for line in f
                   if '"Laguna-S-2.1"' in line)
    published = dict(row["config"], hidden_size=64, head_dim=16,
                     intermediate_size=96, moe_intermediate_size=16,
                     shared_expert_intermediate_size=16, vocab_size=128)
    del published["model_type"]
    hf = LagunaConfig(**published)
    assert (hf.num_experts, hf.num_routed_experts, hf.first_held_expert) \
        == (256, 256, 0)
    model = LagunaForCausalLM(hf, jnp.float32, max_model_len=64)
    shapes = jax.eval_shape(model.init_params)
    assert len(model.layers) == 48 and model.expert_slots == 256 * 47
    assert "model.layers.0.mlp.experts" not in shapes
    assert shapes["model.layers.0.mlp.gate_up_proj"]["weight"].shape == \
        (64, 192)
    for i in (1, 47):
        bucket = shapes[f"model.layers.{i}.mlp.experts"]
        assert bucket["gate"].shape == (64, 256)
        assert bucket["w_gate"].shape == (256, 64, 16)
        assert not model.layers[i].moe.num_experts < \
            model.layers[i].moe.routed_experts
    assert [layer.num_heads for layer in model.layers[:5]] == \
        [48, 72, 72, 72, 48]
    assert model.groups.kinds == ("full", "window", "window", "window")
    assert model.groups.layers_per_group == 12
    # the full layers' table is YaRN's over half a head, no longer
    # than the server's longest sequence
    assert model.layers[0].rotary.cos_sin_cache.shape == (64, 8)
    assert model.layers[0].rotary.mscale == pytest.approx(1.4852030264)
    assert model.layers[1].rotary.cos_sin_cache.shape == (64, 16)
