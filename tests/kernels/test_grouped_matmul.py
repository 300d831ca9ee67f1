"""The expert layer's Pallas kernels (`ops/pallas/grouped_matmul.py`)
in interpret mode on the CPU: `FusedMoE` steered onto its kernel path
(which it takes on one TPU alone) against its own `jax.lax.ragged_dot`
path and against the dense all-experts combine in float32, and the
tile-aligned layout alone against NumPy. Whether Mosaic takes the
kernels at the served shapes is `test_mosaic_compile.py`'s."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aphrodite_tpu.modeling.layers import fused_moe
from aphrodite_tpu.modeling.layers.fused_moe import FusedMoE
from aphrodite_tpu.ops.pallas import grouped_matmul as gm

HIDDEN, WIDTH = 256, 128        # the served widths, cut


def _case(name, tokens, top_k, experts, routed=None, act="silu",
          dtype=jnp.bfloat16, routing="random"):
    return pytest.param(dict(tokens=tokens, top_k=top_k, experts=experts,
                             routed=routed or experts, act=act,
                             dtype=dtype, routing=routing), id=name)


CASES = [
    # the four served calls: SmallThinker's decode step and chunk (64
    # ReGLU experts, 6 a token), Laguna's (128 SiLU experts held of 256,
    # 10 a token)
    _case("smallthinker-decode", 24, 6, 64, act="relu"),
    _case("smallthinker-chunk", 2048, 6, 64, act="relu"),
    _case("laguna-decode", 64, 10, 128, routed=256),
    _case("laguna-chunk", 2048, 10, 128, routed=256),
    _case("float32-silu", 40, 2, 8, dtype=jnp.float32),
    _case("float32-relu-share", 40, 4, 8, routed=16, act="relu",
          dtype=jnp.float32),
    _case("an-expert-without-a-pair", 48, 2, 8, routing="one-empty"),
    _case("one-expert-with-every-pair", 48, 1, 8, routing="all-one"),
    _case("a-group-one-row-over-a-tile", 17, 1, 8, routing="all-one"),
    _case("unheld-pairs-are-all-of-the-step", 12, 4, 8, routed=16,
          routing="all-unheld"),
    _case("one-token", 1, 6, 64, act="relu"),
    _case("one-token-of-a-share", 1, 10, 128, routed=256),
]


def _logits(case, key):
    tokens, experts, routed = case["tokens"], case["experts"], \
        case["routed"]
    logits = np.array(jax.random.normal(key, (tokens, routed),
                                        jnp.float32)) * 3.0
    if case["routing"] == "one-empty":
        logits[:, 3] = -1e9
    elif case["routing"] == "all-one":
        logits[:, 5] = 1e9
    elif case["routing"] == "all-unheld":
        logits[:, :experts] = -1e9
    return jnp.asarray(logits)


def _dense_float32(moe, params, x, logits):
    """Every held expert for every token in float32, under the router's
    weight where the token chose the expert."""
    f32 = {k: np.asarray(v, np.float32) for k, v in params.items()}
    x = np.asarray(x, np.float32)
    _, vals, idx = moe.route(logits)
    vals, idx = np.asarray(vals), np.asarray(idx)
    out = np.zeros_like(x)
    act = {"silu": lambda g: g / (1.0 + np.exp(-g)),
           "relu": lambda g: np.maximum(g, 0.0)}[
               "relu" if moe.act is jax.nn.relu else "silu"]
    for e in range(moe.num_experts):
        weight = np.where(idx == e + moe.first_expert, vals, 0.0).sum(1)
        rows = np.nonzero(weight)[0]
        if rows.size:
            mid = act(x[rows] @ f32["w_gate"][e]) * (x[rows] @
                                                     f32["w_up"][e])
            out[rows] += weight[rows, None] * (mid @ f32["w_down"][e])
    return out


@pytest.fixture
def on_kernel_path(monkeypatch):
    """`FusedMoE` takes its kernel path, the kernels interpreted."""
    monkeypatch.setattr(fused_moe, "takes_expert_kernel",
                        lambda *a, **k: True)
    monkeypatch.setattr(
        gm, "grouped_ffn",
        functools.partial(gm.grouped_ffn, interpret=True))
    return monkeypatch


@pytest.mark.parametrize("case", CASES)
def test_kernel_path_matches_ragged_dot_and_dense(case, on_kernel_path):
    tokens, experts, dtype = case["tokens"], case["experts"], \
        case["dtype"]
    moe = FusedMoE(experts, case["top_k"], HIDDEN, WIDTH,
                   activation=case["act"], own_router=False,
                   routed_experts=case["routed"], dtype=dtype)
    keys = jax.random.split(jax.random.PRNGKey(tokens + experts), 5)

    def draw(key, shape):
        return (jax.random.normal(key, shape, jnp.float32) /
                np.sqrt(shape[-2])).astype(dtype)
    params = {"w_gate": draw(keys[0], (experts, HIDDEN, WIDTH)),
              "w_up": draw(keys[1], (experts, HIDDEN, WIDTH)),
              "w_down": draw(keys[2], (experts, WIDTH, HIDDEN))}
    x = jax.random.normal(keys[3], (tokens, HIDDEN), jnp.float32).astype(
        dtype)
    logits = _logits(case, keys[4])

    counts = []
    got = np.asarray(moe(params, x, router_logits=logits, counts=counts),
                     np.float32)
    (pairs, touched, held, walked), = counts

    # the layer's own ragged_dot path, as the CPU and a mesh-less
    # fallback run it
    top_idx = np.asarray(moe.route(logits)[2])
    sizes = np.bincount(top_idx[top_idx < experts], minlength=experts)
    on_kernel_path.setattr(fused_moe, "takes_expert_kernel",
                           lambda *a, **k: False)
    ragged = np.asarray(moe(params, x, router_logits=logits), np.float32)
    dense = _dense_float32(moe, params, x, logits)

    scale = max(1.0, float(np.abs(dense).max()))
    # bfloat16: the two grouped paths round `act` and the rows out at
    # the same places, and differ by `gate` and `up` kept in float32
    close = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    assert np.isfinite(got).all()
    assert np.abs(got - ragged).max() <= close * scale
    assert np.abs(got - dense).max() <= close * scale

    tile = gm.row_tile(tokens * case["top_k"] * experts // case["routed"],
                       experts)
    assert int(pairs) == tokens * case["top_k"]
    assert int(touched) == int((sizes > 0).sum())
    assert int(held) == int(sizes.sum())
    assert int(walked) == int((-(-sizes // tile)).sum()) * tile


@pytest.mark.parametrize("pairs,experts,tile,spread", [
    (144, 64, 16, "even"), (12288, 64, 256, "even"),
    (640, 128, 16, "half-unheld"), (20480, 128, 128, "half-unheld"),
    (10, 128, 16, "all-unheld"), (300, 4, 16, "one-group"),
    (33, 8, 16, "one-over"), (7, 64, 16, "even")])
def test_aligned_layout_against_numpy(pairs, experts, tile, spread):
    """Every pair with a group lands in a tile of its expert, no two
    pairs on a row, a row's source is the token of the pair that lands
    there, pairs
    keep their order within a group, and the tiles in use are what the
    group sizes need."""
    rng = np.random.default_rng(pairs + experts)
    if spread == "one-group":
        pair_expert = np.full(pairs, 2)
    elif spread == "one-over":
        pair_expert = np.concatenate([np.full(tile + 1, 1),
                                      np.full(pairs - tile - 1, 6)])
    else:
        routed = {"even": experts, "half-unheld": 2 * experts,
                  "all-unheld": experts}[spread]
        pair_expert = rng.integers(0, routed, pairs)
        if spread == "all-unheld":
            pair_expert[:] = experts
    pair_expert = np.minimum(pair_expert, experts).astype(np.int32)
    sizes = np.bincount(pair_expert, minlength=experts + 1)[:experts]

    tokens = max(1, pairs // 3)     # pair p is token p % tokens
    source, dest, tile_expert, used = jax.jit(
        gm.aligned_layout, static_argnums=(2, 3))(
            jnp.asarray(pair_expert), jnp.asarray(sizes, jnp.int32), tile,
            tokens)
    source, dest, tile_expert, used = (np.asarray(a) for a in (
        source, dest, tile_expert, used))

    tiles = gm.num_row_tiles(pairs, experts, tile)
    assert tile_expert.shape == (tiles,) and source.shape == (tiles * tile,)
    assert int(used) == int((-(-sizes // tile)).sum()) <= tiles
    has_group = pair_expert < experts
    mine = dest[has_group]
    assert len(set(mine.tolist())) == mine.size        # a row a pair
    assert (mine < int(used) * tile).all()
    assert (tile_expert[mine // tile] == pair_expert[has_group]).all()
    assert (source[mine] == np.nonzero(has_group)[0] % tokens).all()
    assert (dest[~has_group] == 0).all()
    assert (source >= 0).all() and (source < tokens).all()
    for e in np.nonzero(sizes)[0]:
        rows = dest[pair_expert == e]
        assert rows.min() % tile == 0                  # an aligned start
        assert (np.diff(rows) == 1).all()              # in their order
    # the tiles behind the last one in use name its expert again
    if int(used):
        assert (tile_expert[int(used):] == tile_expert[int(used) - 1]).all()
    assert (np.diff(tile_expert[:int(used)]) >= 0).all()


def test_shapes_the_kernels_take():
    """Whole matrices where experts are narrow, blocks of columns where
    they are wide, nothing where no block fits or a width is no whole
    lane."""
    assert gm.column_block(2560, 768, 2, 2) == 768
    assert gm.column_block(3072, 1024, 2, 2) == 1024
    block = gm.column_block(4096, 14336, 2, 2)
    assert block < 14336 and 14336 % block == 0 and block % 128 == 0
    assert 2 * 4096 * block * 2 <= gm.WEIGHT_BYTES
    assert gm.column_block(1 << 20, 4096, 2, 2) is None
    assert gm.takes_shapes(2560, 768, jnp.bfloat16)
    assert gm.takes_shapes(4096, 14336, jnp.float32)
    assert not gm.takes_shapes(64, 32, jnp.bfloat16)
    assert [gm.row_tile(p, e) for p, e in (
        (144, 64), (12288, 64), (320, 128), (10240, 128), (5, 128))] == \
        [16, 256, 16, 128, 16]


def test_the_counters_of_a_step_that_took_the_kernels(monkeypatch):
    """A model whose expert layers take the kernels carries
    `moe.rows_walked` in its step programs (and no model does on the
    CPU); the runner counts such a step as a kernel step, and both are
    exported."""
    from types import SimpleNamespace
    from aphrodite_tpu.common.tracing import Tracer
    from aphrodite_tpu.engine import metrics
    from aphrodite_tpu.executor.model_runner import ModelRunner
    from aphrodite_tpu.modeling.models import smallthinker
    from aphrodite_tpu.transformers_utils import configs

    config = configs.SmallThinkerConfig(
        vocab_size=256, hidden_size=64, num_hidden_layers=4,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        max_position_embeddings=512, moe_ffn_hidden_size=32,
        moe_num_primary_experts=16, moe_num_active_primary_experts=4,
        sliding_window_size=32)
    model = smallthinker.SmallThinkerForCausalLM(config, jnp.float32)
    assert model.step_counters == smallthinker.STEP_COUNTERS
    monkeypatch.setattr(fused_moe, "takes_expert_kernel",
                        lambda *a, **k: True)
    assert model.step_counters == smallthinker.STEP_COUNTERS + (
        "moe.rows_walked",)
    layers = [(jnp.int32(96), jnp.int32(14), jnp.int32(96), jnp.int32(320)),
              (jnp.int32(96), jnp.int32(12), jnp.int32(96), jnp.int32(288))]
    assert fused_moe.sum_counts(layers, model.step_counters).tolist() == \
        [192, 26, 608]

    runner = object.__new__(ModelRunner)
    runner.tracer = Tracer()
    runner.step_counters = model.step_counters
    runner.model = SimpleNamespace(expert_slots=64)
    for is_prompt in (True, False):
        runner._add_step_counts(SimpleNamespace(is_prompt=is_prompt),
                                [192, 26, 608])
    counts = runner.tracer.counts
    assert counts["moe.kernel_steps"] == 2
    assert counts["moe.rows_walked"] == 1216
    assert counts["moe.decode_experts_touched"] == 26
    exported = {name for name, _, _ in metrics._STAGE_COUNTERS}
    assert {"aphrodite:moe_kernel_steps_total",
            "aphrodite:moe_rows_walked_total"} <= exported
