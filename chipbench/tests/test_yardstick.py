"""The frozen yardstick: the integer twin against the served program, its
controls, and the benchmark's copy of HAPM against the program's."""
import json

import jax
import numpy as np
import pytest

from chipbench import hapm_select
from chipbench.configs import resnet_cifar as fam
from chipbench.configs import resnet_cifar_ref as ref
from conftest import ROOT, tiny_config


def _gap(y, want):
    return float(np.abs(y - want).max() / np.abs(want).max())


@pytest.fixture(scope="module")
def served():
    """A tiny served model and eight frames through bucket 8."""
    model = fam.Model(tiny_config(), 2 ** 33 + 5)
    x = np.random.default_rng(9).random((8,) + model.frame_shape,
                                        dtype=np.float32)
    return model, x, np.asarray(model.infer(x))


def test_twin_matches_the_streamed_server_bit_for_bit(served):
    model, x, y = served
    assert model.last_level == 0
    np.testing.assert_array_equal(y, fam.reference_logits(model, x, 8))


def test_zeroed_channel_control_fails(served):
    """One live output channel of the last conv zeroed in the reference
    puts it outside the limit."""
    model, x, y = served
    params = jax.tree_util.tree_map(lambda a: a, model.params)
    w = np.array(params["s1b0"]["conv2"]["w"])
    live = np.flatnonzero(np.abs(w).sum(axis=(0, 1, 2)))
    w[..., live[0]] = 0.0
    params["s1b0"]["conv2"]["w"] = w
    fwd = ref.make_forward(params, model.state, model.cfg)
    assert _gap(y, np.asarray(fwd(x))) > model.cfg["limits"]["logit_gap"]


@pytest.mark.parametrize("seed", [0, 1, 2 ** 35 + 3])
def test_int4_control_fails(tiny_cfg, seed):
    """The reference at int4 weights, the step below the configuration's
    int8, is outside the limit on every seed."""
    params, state = fam.make_model(tiny_cfg, seed)
    x = np.random.default_rng(seed).random((16, 16, 16, 3), dtype=np.float32)
    int8 = np.asarray(ref.make_forward(params, state, tiny_cfg)(x))
    int4 = np.asarray(ref.make_forward(params, state, tiny_cfg, w_bits=4)(x))
    assert _gap(int4, int8) > 3 * tiny_cfg["limits"]["logit_gap"]


@pytest.mark.parametrize("name", ["tiny", "resnet21_cifar-hapm50-int8s"])
def test_masks_equal_core_hapm(tiny_cfg, name):
    from repro.core import (HAPMConfig, apply_masks, hapm_element_masks,
                            hapm_epoch_update, hapm_init)
    from repro.models import cnn

    cfg = tiny_cfg if name == "tiny" else json.loads(
        (ROOT / f"chipbench/configs/{name}.json").read_text())
    params, _ = jax.jit(lambda k: fam.init(k, cfg))(fam.seed_key(11))
    n_cu, s = cfg["hapm"]["n_cu"], cfg["hapm"]["sparsity"]
    specs = cnn.conv_group_specs(params, n_cu)
    hcfg = HAPMConfig(s, 1)
    st = hapm_epoch_update(hapm_init(specs, hcfg), specs, params, hcfg)
    theirs = apply_masks(params, hapm_element_masks(specs, st))
    ours = jax.jit(lambda p: hapm_select.prune(p, n_cu, s))(params)
    for a, b in zip(jax.tree_util.tree_leaves(theirs),
                    jax.tree_util.tree_leaves(ours)):
        np.testing.assert_array_equal(np.asarray(a) != 0, np.asarray(b) != 0)
    assert st.groups_pruned == hapm_select.n_pruned(st.total_groups, s)
