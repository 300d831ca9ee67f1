"""The Mamba mixer is ONE class, `modeling/layers/mamba.py::MambaMixer`,
used by Phi-4-mini-flash (no inner norms) and by Jamba (an RMSNorm on
each of dt, B and C): the move out of `models/phi4flash.py` leaves
Phi's parameter tree, names, shapes and types, what it was, which is
what the benchmark hands its server leaf for leaf
(`perf/references/phi4flash.py::tree`)."""
import os

import jax
import jax.numpy as jnp

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
