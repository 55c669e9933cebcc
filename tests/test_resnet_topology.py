"""The one topology walker of ``models/cnn.py`` and the ImageNet ResNets.

ResNet-50 v1.5 comes out of ``cnn.topology`` at its published sizes; the
CIFAR ResNets keep the layer order and param tree they had before the
walker; a small bottleneck network with the ImageNet stem, served by
``CnnServer`` on the int8 streamed contract, equals the integer twin of
``models/resnet_ref.py`` exactly, and on the f32 contract meets the f32
reference within the f32 sweep's 1e-4 of the largest |logit|.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.resnet50_imagenet import CONFIG as RESNET50
from repro.core import (HAPMConfig, apply_masks, hapm_element_masks,
                        hapm_epoch_update, hapm_init)
from repro.core.groups import fpga_conv_groups
from repro.launch.serve_cnn import CnnServer
from repro.models import cnn, resnet_ref
from repro.sparse.conv_plan import conv_gemm_layout

N_CU = 4


def test_resnet50_published_topology():
    """He et al. Table 1 "50-layer" with the v1.5 stride placement: 53
    convs, 25,557,032 parameters, outputs 112/56/28/14/7, and each
    layer's (kernel, stride, cin, cout) in execution order."""
    sites = cnn.conv_sites(RESNET50)
    assert len(sites) == 53
    params, _ = jax.eval_shape(lambda k: cnn.init(k, RESNET50),
                               jax.random.PRNGKey(0))
    assert sum(int(np.prod(leaf.shape))
               for leaf in jax.tree_util.tree_leaves(params)) == 25_557_032
    want = [(("conv0",), 7, 2, 3, 64, 224)]
    cin, size = 64, 56                      # after the stem's max-pool
    for si, (n, width) in enumerate(zip((3, 4, 6, 3), (64, 128, 256, 512))):
        for bi in range(n):
            s = 2 if si > 0 and bi == 0 else 1
            name = f"s{si}b{bi}"
            want += [((name, "conv1"), 1, 1, cin, width, size),
                     ((name, "conv2"), 3, s, width, width, size),
                     ((name, "conv3"), 1, 1, width, 4 * width, size // s)]
            if bi == 0:
                want.append(((name, "proj"), 1, s, cin, 4 * width, size))
            cin, size = 4 * width, size // s
    assert [(c.path, c.k, c.stride, c.cin, c.cout, c.in_size)
            for c in sites] == want
    stem, blocks = cnn.topology(RESNET50)
    assert stem.out_size == 112
    assert [b.convs[-1].out_size for b in blocks] == \
        [56] * 3 + [28] * 4 + [14] * 6 + [7] * 3
    assert [c.relu for c in blocks[0].convs] == [True, True, False]
    assert stem.relu and not blocks[0].proj.relu
    assert [b.proj is not None for b in blocks] == \
        [True, False, False, True, False, False, False,
         True, False, False, False, False, False, True, False, False]


def _tree(tree):
    """``{node: its children with their shapes}``: a conv by its weight's
    shape, a BatchNorm or the head by its channels."""
    def shape(leaf):
        return "x".join(map(str, leaf.shape))

    out = {}
    for k in sorted(tree):
        v = tree[k]
        if not all(isinstance(x, dict) for x in v.values()):
            out[k] = shape(v.get("w", next(iter(v.values()))))
        else:
            out[k] = " ".join(
                f"{n}:{shape(v[n].get('w', next(iter(v[n].values()))))}"
                for n in sorted(v))
    return out


R21_ORDER = (
    "conv0:1:32 s0b0/conv1:1:32 s0b0/conv2:1:32 s0b1/conv1:1:32 "
    "s0b1/conv2:1:32 s0b2/conv1:1:32 s0b2/conv2:1:32 s1b0/conv1:2:32 "
    "s1b0/conv2:1:16 s1b0/proj:2:32 s1b1/conv1:1:16 s1b1/conv2:1:16 "
    "s1b2/conv1:1:16 s1b2/conv2:1:16 s2b0/conv1:2:16 s2b0/conv2:1:8 "
    "s2b0/proj:2:16 s2b1/conv1:1:8 s2b1/conv2:1:8 s2b2/conv1:1:8 "
    "s2b2/conv2:1:8")
R18_ORDER = (
    "conv0:1:32 s0b0/conv1:1:32 s0b0/conv2:1:32 s0b1/conv1:1:32 "
    "s0b1/conv2:1:32 s1b0/conv1:2:32 s1b0/conv2:1:16 s1b0/proj:2:32 "
    "s1b1/conv1:1:16 s1b1/conv2:1:16 s2b0/conv1:2:16 s2b0/conv2:1:8 "
    "s2b0/proj:2:16 s2b1/conv1:1:8 s2b1/conv2:1:8 s3b0/conv1:2:8 "
    "s3b0/conv2:1:4 s3b0/proj:2:8 s3b1/conv1:1:4 s3b1/conv2:1:4")


def _blocks(widths, per_stage, cin):
    """The CIFAR param tree's block nodes, as before the walker."""
    out = {}
    for si, w in enumerate(widths):
        for bi in range(per_stage):
            c = cin if bi == 0 else w
            node = f"bn1:{w} bn2:{w} "
            if c != w:
                node += f"bnp:{w} "
            node += f"conv1:3x3x{c}x{w} conv2:3x3x{w}x{w}"
            if c != w:
                node += f" proj:1x1x{c}x{w}"
            out[f"s{si}b{bi}"] = node
            cin = w
    return out


@pytest.mark.parametrize("name", ["resnet21", "resnet18"])
def test_cifar_layer_order_and_param_tree_unchanged(name):
    """The walker leaves the CIFAR ResNets' conv order and param and
    state trees as the per-function walks gave them."""
    if name == "resnet21":
        cfg, order, widths, per_stage = (cnn.ResNetConfig(), R21_ORDER,
                                         (16, 32, 64), 3)
    else:
        cfg, order, widths, per_stage = (
            cnn.ResNetConfig(stages=(2, 2, 2, 2), widths=(64, 128, 256, 512)),
            R18_ORDER, (64, 128, 256, 512), 2)
    assert " ".join(f"{'/'.join(p[:-1])}:{s}:{f}"
                    for p, s, f in cnn.conv_layer_order(cfg)) == order
    params, state = jax.eval_shape(lambda k: cnn.init(k, cfg),
                                   jax.random.PRNGKey(0))
    blocks = _blocks(widths, per_stage, widths[0])
    w0 = widths[0]
    assert _tree(params) == {"bn0": str(w0), "conv0": f"3x3x3x{w0}",
                             "fc": f"{widths[-1]}x10", **blocks}
    assert _tree(state) == {"bn0": str(w0), **{
        k: " ".join(x for x in v.split() if x.startswith("bn"))
        for k, v in blocks.items()}}
    assert all(set(n) == {"w"} for n in (params["conv0"], params["s0b0"][
        "conv1"])) and set(params["bn0"]) == {"scale", "bias"}
    assert set(state["bn0"]) == {"mean", "var"}


@pytest.mark.parametrize("k, cin, fill", [(1, 2048, 1.0),
                                          (1, 64, 64 / 128),
                                          (3, 512, 72 / 128),
                                          (7, 64, 98 / 128),
                                          (7, 3, (98 + 49) / 256)])
def test_k_row_fill_of_the_packed_layout(k, cin, fill):
    """Rows of a dispatched packed K-tile that hold a weight: a channel's
    ``k*k`` taps in a slot of ``ceil8(k*k)`` rows (one row for a 1x1),
    ``128 // slot`` channels a tile, the last tile holding what is left
    of ``cin``."""
    layout = conv_gemm_layout(fpga_conv_groups((k, k, cin, 64), 12),
                              packed=True)
    assert layout.k_row_fill(np.ones(layout.spec.num_groups)) == fill


@pytest.fixture(scope="module")
def bottleneck():
    """A 32x32 bottleneck network with the ImageNet stem (7x7/2, 3x3/2
    max-pool), stages 2/1 of widths 8/16: a projection block beside an
    identity block, and a stride-2 3x3. BatchNorm statistics are drawn,
    and HAPM prunes half the groups."""
    cfg = cnn.ResNetConfig(stages=(2, 1), widths=(8, 16), image_size=32,
                           block="bottleneck", stem="imagenet")
    params, state = cnn.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(1)

    def draw(tree, lo, hi):
        return jax.tree_util.tree_map(
            lambda a: jnp.asarray(rng.uniform(lo, hi, a.shape), a.dtype), tree)

    for node in [params] + [params[k] for k in params if k.startswith("s")]:
        for k in [k for k in node if k.startswith("bn")]:
            node[k] = {"scale": draw(node[k]["scale"], 0.5, 1.5),
                       "bias": draw(node[k]["bias"], -0.2, 0.2)}
    for node in [state] + [state[k] for k in state if k.startswith("s")]:
        for k in [k for k in node if k.startswith("bn")]:
            node[k] = {"mean": draw(node[k]["mean"], -0.2, 0.2),
                       "var": draw(node[k]["var"], 0.5, 1.5)}
    specs = cnn.conv_group_specs(params, N_CU)
    hcfg = HAPMConfig(0.5, 1)
    st = hapm_epoch_update(hapm_init(specs, hcfg), specs, params, hcfg)
    pruned = apply_masks(params, hapm_element_masks(specs, st))
    frames = rng.random((4, 32, 32, 3), dtype=np.float32)
    return cfg, pruned, state, frames


def test_bottleneck_topology(bottleneck):
    cfg = bottleneck[0]
    stem, blocks = cnn.topology(cfg)
    assert (stem.k, stem.stride, stem.out_size) == (7, 2, 16)
    assert [b.proj is not None for b in blocks] == [True, False, True]
    assert [(c.k, c.stride, c.in_size) for c in blocks[2].convs] == \
        [(1, 1, 8), (3, 2, 8), (1, 1, 4)]


def test_bottleneck_int8_served_equals_the_integer_twin(bottleneck):
    """Every conv on a kernel (7x7/2 stem, 1x1 at stride 1 and 2, a
    stride-2 3x3), the max-pool on codes: the served logits equal the
    integer twin's bit for bit."""
    cfg, pruned, state, frames = bottleneck
    server = CnnServer(pruned, state, cfg, buckets=(4,), spec=cnn.ExecSpec(
        n_cu=N_CU, quantized=True, folded=True, streamed=True,
        dense_fallback=2.0))
    rep = server.report(batch=4, per_layer=True)
    assert rep["layers_implicit"] == len(cnn.conv_sites(cfg))
    assert {p: (d["kernel"], round(d["k_row_fill"], 4))
            for p, d in rep["per_layer"].items()
            if p in ("conv0/w", "s0b1/conv1/w", "s1b0/conv2/w")} == {
        "conv0/w": (7, round(3 * 49 / 256, 4)),
        "s0b1/conv1/w": (1, 32 / 128), "s1b0/conv2/w": (3, 72 / 128)}
    got = np.asarray(server.infer(frames))
    want = np.asarray(resnet_ref.forward_int8(pruned, state, cfg, frames))
    assert np.ptp(want) > 0
    np.testing.assert_array_equal(got, want)


def test_bottleneck_f32_served_meets_the_f32_reference(bottleneck):
    """The f32 contract (folded BatchNorm on the f32 kernels) within 1e-4
    of the largest |logit| of the f32 reference (BatchNorm unfolded,
    highest matmul precision): the f32 sweep's tolerance."""
    cfg, pruned, state, frames = bottleneck
    server = CnnServer(pruned, state, cfg, buckets=(4,), spec=cnn.ExecSpec(
        n_cu=N_CU, folded=True, dense_fallback=2.0))
    got = np.asarray(server.infer(frames))
    want = np.asarray(resnet_ref.forward_f32(pruned, state, cfg, frames))
    scale = np.abs(want).max()
    assert np.abs(got - want).max() <= 1e-4 * scale
    # the integer twin is a different precision: it is not within that
    twin = np.asarray(resnet_ref.forward_int8(pruned, state, cfg, frames))
    assert np.abs(twin - want).max() > 1e-4 * scale
