"""Needed-work counts: shapes and nonzero weights only."""
import json

import jax
import jax.numpy as jnp

from chipbench import work
from chipbench.configs import resnet_cifar as fam
from conftest import ROOT


def _cfg(name):
    return json.loads((ROOT / f"chipbench/configs/{name}.json").read_text())


def test_dense_resnet21_macs():
    cfg = _cfg("resnet21_cifar-hapm50-int8s")
    params, _ = jax.jit(lambda k: fam.init(k, cfg))(fam.seed_key(0))
    w = fam.conv_work(cfg, params)
    assert len(w.convs) == 21
    macs = w.ops_per_image // 2
    assert macs == 40_812_544                      # ~40.8 M MACs per image
    assert abs(macs - 40.8e6) / 40.8e6 < 0.002


def test_half_mask_halves_operations():
    cfg = _cfg("resnet21_cifar-hapm50-int8s")
    params, _ = jax.jit(lambda k: fam.init(k, cfg))(fam.seed_key(1))
    dense = fam.conv_work(cfg, params)

    def halve(path, leaf):                         # every other output channel
        if leaf.ndim != 4:
            return leaf
        keep = (jnp.arange(leaf.shape[-1]) % 2 == 0).astype(leaf.dtype)
        return leaf * keep
    half = fam.conv_work(cfg, jax.tree_util.tree_map_with_path(halve, params))
    assert 2 * half.ops_per_image == dense.ops_per_image


def test_peaks_table():
    p = work.peaks_for("TPU v5 lite")
    assert (p["int8_ops"], p["bf16_flops"], p["hbm_bytes_per_s"]) == \
        (393e12, 197e12, 819e9)
    try:
        work.peaks_for("cpu")
    except KeyError:
        pass
    else:
        raise AssertionError("a device kind outside the table must be an error")


def test_counts_do_not_follow_the_kernel_route(tiny_cfg):
    """The program binds the same layers to the implicit and to the
    materializing kernel and prices their HBM traffic differently; the
    needed work of the weights each bind holds is the same."""
    from repro.models import cnn

    params, state = fam.make_model(tiny_cfg, 3)
    rcfg = cnn.ResNetConfig(stages=(1, 1), widths=(8, 16), image_size=16)
    folded = cnn.fold_batchnorm(params, state, rcfg)
    counts, priced, routes = [], [], []
    for implicit in (True, False):
        spec = cnn.ExecSpec(**dict(tiny_cfg["exec"], implicit=implicit))
        ex = cnn.bind_execution(folded, rcfg, spec=spec)
        bound = {}
        for path, w in ex.bound_weights.items():
            node = bound
            for k in path[:-1]:
                node = node.setdefault(k, {})
            node[path[-1]] = w
        counts.append(fam.conv_work(tiny_cfg, bound))
        rep = ex.report(rcfg, batch=8)
        priced.append(rep["hbm_bytes"])
        routes.append((rep["layers_implicit"], rep["layers_materializing"]))
    assert routes[0] != routes[1] and priced[0] != priced[1]
    assert counts[0] == counts[1]
    assert [c.nnz for c in counts[0].convs] == \
        [c.nnz for c in fam.conv_work(tiny_cfg, params).convs]
