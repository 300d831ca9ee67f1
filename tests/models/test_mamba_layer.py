"""The Mamba mixer is ONE class, `modeling/layers/mamba.py::MambaMixer`,
used by Phi-4-mini-flash (no inner norms) and by Jamba (an RMSNorm on
each of dt, B and C): the move out of `models/phi4flash.py` leaves
Phi's parameter tree, names, shapes and types, what it was, which is
what the benchmark hands its server leaf for leaf
(`perf/references/phi4flash.py::tree`)."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf import cells

ROOT = cells.ROOT


def _tree_of(model):
    return {b: {n: (tuple(a.shape), a.dtype.name) for n, a in leaves.items()}
            for b, leaves in jax.eval_shape(model.init_params).items()}


def _models():
    from aphrodite_tpu.modeling.models.jamba import JambaForCausalLM
    from aphrodite_tpu.modeling.models.phi4flash import \
        Phi4FlashForCausalLM
    from aphrodite_tpu.transformers_utils.configs import (JambaConfig,
                                                          Phi4FlashConfig)
    return (Phi4FlashForCausalLM(Phi4FlashConfig(), jnp.bfloat16),
            JambaForCausalLM(JambaConfig(), jnp.bfloat16))


def test_both_models_build_the_one_mixer():
    from aphrodite_tpu.modeling.layers import mamba
    from aphrodite_tpu.modeling.models import jamba, phi4flash
    from aphrodite_tpu.ops.pallas import ssm_scan
    assert phi4flash.MambaMixer is mamba.MambaMixer is jamba.MambaMixer
    phi, jam = _models()
    mixers = {name: [l.mixer for l in model.layers if l.kind == "mamba"]
              for name, model in (("phi", phi), ("jamba", jam))}
    assert (len(mixers["phi"]), len(mixers["jamba"])) == (9, 26)
    for mixer in mixers["phi"] + mixers["jamba"]:
        assert type(mixer) is mamba.MambaMixer
        assert (mixer.d_inner, mixer.d_state, mixer.d_conv,
                mixer.dt_rank) == (5120, 16, 4, 160)
    assert not any(m.inner_norms for m in mixers["phi"])
    assert all(m.inner_norms and m.eps == 1e-6 for m in mixers["jamba"])
    # one place the scan functions are looked up, at each call: the
    # kernels' module, where a test replaces one for both models
    assert mamba.ssm_scan is ssm_scan
    assert not hasattr(phi4flash, "selective_scan")


def test_phis_parameter_tree_is_what_it_was_before_the_move():
    """Every bucket, leaf, shape and type of the published
    configuration, against the reference's statement of it (which no
    PR but a `benchmark` PR edits): a renamed or added leaf would end
    Phi's benchmark run at start-up."""
    ref = cells.load_module(os.path.join(ROOT, "perf", "references",
                                         "phi4flash.py"))
    with open(os.path.join(ROOT, "perf", "configs",
                           "phi-4-mini-flash-bf16.json")) as f:
        import json
        config = json.load(f)
    phi, _ = _models()
    have = _tree_of(phi)
    assert have == {b: {n: (tuple(s[0]), s[1]) for n, s in v.items()}
                    for b, v in ref.tree(config).items()}
    mixer = {b.rsplit(".", 1)[1]: sorted(leaves)
             for b, leaves in have.items()
             if b.startswith("model.layers.0.mixer.")}
    assert mixer == {
        "in_proj": ["weight"], "conv1d": ["bias", "weight"],
        "x_proj": ["weight"], "dt_proj": ["bias", "weight"],
        "ssm": ["A_log", "D"], "out_proj": ["weight"]}
    assert not [b for b in have if "layernorm" in b and ".mixer." in b]


def test_jambas_mixer_adds_three_gains_and_nothing_else():
    _, jam = _models()
    have = _tree_of(jam)
    mixer = {b.rsplit(".", 1)[1]: leaves for b, leaves in have.items()
             if b.startswith("model.layers.0.mamba.")}
    assert {k: v for k, v in mixer.items() if "layernorm" in k} == {
        "dt_layernorm": {"weight": ((160,), "bfloat16")},
        "b_layernorm": {"weight": ((16,), "bfloat16")},
        "c_layernorm": {"weight": ((16,), "bfloat16")}}
    phi, _ = _models()
    phis = {b.rsplit(".", 1)[1]: leaves for b, leaves in
            _tree_of(phi).items() if b.startswith("model.layers.0.mixer.")}
    assert {k: v for k, v in mixer.items() if "layernorm" not in k} == phis


# ---- the mixer over the model's one pair of state arrays ----

SLOTS, LAYERS = 3, 3


def _toy_mixer(inner_norms=False):
    import types
    from aphrodite_tpu.modeling.layers.mamba import MambaMixer
    config = types.SimpleNamespace(
        hidden_size=64, mamba_d_inner=128, mamba_d_state=16,
        mamba_d_conv=4, mamba_dt_rank=8)
    mixer = MambaMixer(config, "m", jnp.float32, None,
                       inner_norms=inner_norms)
    rng = np.random.default_rng(3)
    params = jax.tree_util.tree_map(
        lambda a: jnp.asarray(rng.normal(size=a.shape) * 0.2, a.dtype),
        mixer.init())
    return mixer, params, rng


def _meta(slots, prompt_lens=None):
    from aphrodite_tpu.modeling.input_metadata import InputMetadata
    rows = len(slots)
    return InputMetadata(
        slot_mapping=jnp.zeros((rows,), jnp.int32),
        block_tables=jnp.zeros((rows, 1), jnp.int32),
        context_lens=jnp.zeros((rows,), jnp.int32),
        prompt_lens=None if prompt_lens is None
        else jnp.asarray(prompt_lens, jnp.int32),
        state_slots=jnp.asarray(slots, jnp.int32),
        is_prompt=prompt_lens is not None)


def _arrays(rng, kept):
    """(tail, state) of `LAYERS` layers, a slot keeping `kept` inputs:
    the last three the same numbers whatever `kept` is, the rows before
    them NaN."""
    last = rng.normal(size=(LAYERS, SLOTS + 1, 3, 128)).astype(np.float32)
    tail = np.full((LAYERS, SLOTS + 1, kept, 128), np.nan, np.float32)
    tail[:, :, kept - 3:] = last
    state = rng.normal(size=(LAYERS, SLOTS + 1, 16, 128)).astype(np.float32)
    return jnp.asarray(tail), jnp.asarray(state)


@pytest.mark.parametrize("inner_norms", [False, True],
                         ids=["phi", "jamba"])
@pytest.mark.parametrize("branch", ["decode", "prompt"])
def test_the_rows_a_slot_keeps_before_its_taps_reach_nothing(
        branch, inner_norms):
    """A slot allocated four rows (`StateSpec.allocated`) and eight
    against one of the convolution's own three, the rows before the
    taps NaN: the layer's output, `y` and state are bit for bit the
    same, and so are the last three rows of the tail it leaves; a
    layer the call does not name is bit for bit what it was."""
    mixer, params, rng = _toy_mixer(inner_norms)
    layer, slots = 1, [2, 0]
    seq = 1 if branch == "decode" else 8
    h = jnp.asarray(rng.normal(size=(2, seq, 64)), jnp.float32)
    # the second prompt row is five live tokens and three of padding,
    # neither of them a sequence's first chunk
    positions = jnp.full((2, seq), 7, jnp.int32)
    meta = _meta(slots, None if branch == "decode" else [8, 5])
    seed = rng.bit_generator.state
    got = {}
    for kept in (3, 4, 8):
        rng.bit_generator.state = seed
        tail, state = _arrays(rng, kept)
        out, y, (tail2, state2) = mixer(params, h, positions,
                                        (tail, state), meta, layer)
        assert tail2.shape == tail.shape and state2.shape == state.shape
        for other in (0, 2):
            np.testing.assert_array_equal(tail2[other], tail[other])
            np.testing.assert_array_equal(state2[other], state[other])
        # the slot no row holds, and the scratch one
        np.testing.assert_array_equal(tail2[layer, [1, 3]],
                                      tail[layer, [1, 3]])
        got[kept] = [np.asarray(v) for v in (
            out, y, state2, tail2[:, :, kept - 3:])]
        assert not any(np.isnan(v).any() for v in got[kept])
    for kept in (4, 8):
        for have, want in zip(got[kept], got[3]):
            np.testing.assert_array_equal(have, want)


def test_a_prompt_leaves_every_row_a_slot_keeps():
    """A first chunk writes all four rows of its slot (zeros before
    the sequence's first input, never what the slot held), and the
    decode step after it reads the chunk's last three inputs."""
    mixer, params, rng = _toy_mixer()
    tail, state = _arrays(rng, 4)
    h = jnp.asarray(rng.normal(size=(1, 8, 64)), jnp.float32)
    positions = jnp.arange(8, dtype=jnp.int32)[None]
    x = np.asarray(jnp.split(
        mixer.in_proj(params["m.in_proj"], h), 2, axis=-1)[0])[0]
    for lens, want in ((8, x[4:8]), (2, np.concatenate(
            [np.zeros((2, 128), np.float32), x[:2]]))):
        _, _, (tail2, _) = mixer(params, h, positions, (tail, state),
                                 _meta([1], [lens]), 2)
        np.testing.assert_array_equal(np.asarray(tail2[2, 1]), want)


def test_a_forks_copy_is_a_slot_of_every_layer():
    """`ModelRunner._copy_state` on the layer-axis arrays: the child's
    slot takes the parent's rows of every layer of both arrays; no
    other slot and no page moves."""
    from aphrodite_tpu.executor.model_runner import ModelRunner
    runner = ModelRunner.__new__(ModelRunner)
    runner.page_pairs = 1
    rng = np.random.default_rng(0)
    tail, state = _arrays(rng, 4)
    tail = jnp.nan_to_num(tail)
    pages = (jnp.ones((4, 16, 128)), jnp.ones((4, 16, 128)))
    # slot 2 onto slot 0, the scratch slot onto itself (the padding)
    src, dst = jnp.asarray([2, SLOTS]), jnp.asarray([0, SLOTS])
    got_pages, (tail2, state2) = runner._copy_state(
        [pages, (tail, state)], src, dst)
    assert got_pages is pages
    for new, old in ((tail2, tail), (state2, state)):
        assert new.shape == old.shape and new.dtype == old.dtype
        np.testing.assert_array_equal(new[:, 0], old[:, 2])
        np.testing.assert_array_equal(new[:, 1:], old[:, 1:])
