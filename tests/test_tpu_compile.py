"""The Pallas kernels compile for a TPU v5e, at ResNet-21 widths.

Nothing runs: each test compiles one kernel call for a described (not
attached) v5e chip and checks that the program holds the Mosaic kernel
(``tpu_custom_call``). This is what interpret mode cannot show — layouts,
slices and fast-memory use the chip's compiler refuses. The topology is
described inside a fixture, after the test starts, and every compile
runs in the test's own process; the persistent compilation cache is off
around these tests (entries for a described chip cannot be read back).
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import implicit_conv as IC
from repro.kernels.block_sparse_matmul import (block_sparse_grad_weight,
                                               block_sparse_matmul)
from repro.kernels.conv_lowering import conv_out_size, same_pads
from repro.kernels.int8_matmul import int8_matmul
from repro.sparse.conv_plan import channel_slot


@pytest.fixture(scope="module")
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def topo(no_compile_cache, tmp_path_factory):
    from jax.experimental import topologies
    # libtpu otherwise writes its logs to /tmp ("disabled" does not stop it)
    os.environ.setdefault("TPU_LOG_DIR",
                          str(tmp_path_factory.mktemp("tpu_logs")))
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(one_chip, fn, *shapes):
    """Compile ``fn`` at ``shapes`` (``(shape, dtype)`` or ``None``) for
    one v5e chip; assert the Mosaic kernel is in the program."""
    args = [None if s is None else
            jax.ShapeDtypeStruct(s[0], s[1], sharding=one_chip)
            for s in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


# one geometry of each kind in ResNet-21 (32x32 input, widths 16/32/64):
# (input size, kernel, stride, cin, cout)
GEOMETRIES = {
    "stem": (32, 3, 1, 3, 16),
    "3x3_s1": (32, 3, 1, 16, 16),
    "3x3_s2": (32, 3, 2, 16, 32),
    "1x1_s2": (16, 1, 2, 32, 64),
}
# operand dtype, epilogue rows, activation skip + counter
FORMS = {
    "f32": (jnp.float32, False, False),
    "bf16": (jnp.bfloat16, False, False),
    "int8_scale": (jnp.int8, False, False),
    "int8_out_scale": (jnp.int8, True, False),
    "streamed_dsb": (jnp.int8, True, True),
}


def _compile_implicit(one_chip, geometry, form, batch, fold):
    """Compile one implicit conv of ``geometry`` (input size, kernel,
    stride, cin, cout) in ``form`` at ``batch``; ``fold`` puts whole
    images in one M-block where the geometry allows."""
    h, k, stride, cin, cout = geometry
    dtype, out_scale, dsb = FORMS[form]
    ho = conv_out_size(h, k, stride, "SAME")
    mb = IC.choose_m_block(ho, ho, batch=batch if fold else 1)
    slot = channel_slot(k, k)
    cpk = 128 // slot
    n_kb = -(-cin // cpk)
    rows, cols = IC.window_shape(mb, k, k, stride)
    lo, hi = same_pads(h, k, stride)
    hp = max((mb.bpi - 1) * mb.block_oh * stride + rows, h + lo + hi)
    wp = max(cols, h + lo + hi)
    n = 128 * -(-cout // 128)
    row = ((n,), jnp.float32)
    fn = functools.partial(IC.implicit_block_sparse_conv, kx=k, ky=k,
                           stride=stride, mb=mb, block=(128, 128), cpk=cpk,
                           slot=slot, relu=True, activation_dsb=dsb,
                           count_skips=dsb)
    _compile(one_chip, fn,
             ((batch, hp, wp, n_kb * cpk), dtype),
             ((n_kb * 128, n), dtype),
             ((n // 128, n_kb), jnp.int32), ((n // 128,), jnp.int32),
             row, row if dtype == jnp.int8 else None,
             row if out_scale else None)
    return mb


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("geometry", GEOMETRIES)
def test_implicit_conv_compiles(one_chip, geometry, form):
    _compile_implicit(one_chip, GEOMETRIES[geometry], form, 8, fold=False)


# the layers of ResNet-18 (CIFAR form, widths 64-512) whose M-blocks fold
# whole images at batch 8: (input size, kernel, stride, cin, cout)
FOLDED = {
    "4x4_512": (4, 3, 1, 512, 512),
    "8x8_to_4x4_s2": (8, 3, 2, 256, 512),
    "1x1_8x8_to_4x4_s2": (8, 1, 2, 256, 512),
    "8x8_256": (8, 3, 1, 256, 256),
}


@pytest.mark.parametrize("form", ["int8_scale", "int8_out_scale"])
@pytest.mark.parametrize("geometry", FOLDED)
def test_folded_implicit_conv_compiles(one_chip, geometry, form):
    """The folded gather (one window DMA for the block's images, each tap
    of all of them loaded at once and transposed once) lowers for the
    v5e at the widths of the layers it serves."""
    mb = _compile_implicit(one_chip, FOLDED[geometry], form, 8, fold=True)
    assert mb.ipb > 1 and mb.m_rows <= 128


@pytest.mark.parametrize("form", ["int8_scale", "int8_out_scale"])
def test_folded_pointwise_conv_compiles_at_bucket_128(one_chip, form):
    """ResNet-18's stride-2 projection onto 4x4 outputs at bucket 128:
    the pointwise gather (the window load is the patch tile) of 8
    images folded into a 128-row block lowers for the v5e."""
    mb = _compile_implicit(one_chip, FOLDED["1x1_8x8_to_4x4_s2"], form, 128,
                           fold=True)
    assert mb.ipb == 8 and mb.m_rows == 128


# ResNet-50's geometries at 224x224 that no CIFAR layer has:
# (input size, kernel, stride, cin, cout)
RESNET50 = {
    "stem_7x7_s2": (224, 7, 2, 3, 64),
    "1x1_cin2048": (7, 1, 1, 2048, 512),
    "1x1_s2": (56, 1, 2, 256, 512),
    "3x3_s2_to_7x7": (14, 3, 2, 512, 512),
    "7x7_out": (7, 3, 1, 512, 512),
}


@pytest.mark.parametrize("batch", [1, 128])
@pytest.mark.parametrize("geometry", RESNET50)
def test_resnet50_implicit_conv_compiles(one_chip, geometry, batch):
    """The streamed int8 form of each new geometry lowers for the v5e at
    buckets 1 and 128, inside the VMEM window budget that keeps it on
    the implicit route; 7x7 outputs (49 pixels in 56 rows) run one image
    a block."""
    h, k, stride, _, _ = RESNET50[geometry]
    mb = _compile_implicit(one_chip, RESNET50[geometry], "int8_out_scale",
                           batch, fold=True)
    assert IC.window_vmem_bytes(mb, k, k, stride, 128, 1) <= \
        IC.SLAB_VMEM_BUDGET
    if conv_out_size(h, k, stride, "SAME") == 7:
        assert (mb.bm, mb.ipb) == (56, 1)


@pytest.mark.parametrize("form", ["f32", "int8_scale", "int8_out_scale"])
@pytest.mark.parametrize("bm", [8, 128])
def test_block_sparse_matmul_compiles(one_chip, form, bm):
    dtype, out_scale, _ = FORMS[form]
    row = ((256,), jnp.float32)
    _compile(one_chip, functools.partial(block_sparse_matmul, bm=bm,
                                         relu=True),
             ((bm * 4, 384), dtype), ((384, 256), dtype),
             ((2, 3), jnp.int32), ((2,), jnp.int32),
             row, row if dtype == jnp.int8 else None,
             row if out_scale else None)


def test_block_sparse_grad_weight_compiles(one_chip):
    _compile(one_chip, functools.partial(block_sparse_grad_weight, bm=128),
             ((512, 384), jnp.float32), ((512, 256), jnp.float32),
             ((4,), jnp.int32), ((4,), jnp.int32))


def test_int8_matmul_compiles(one_chip):
    _compile(one_chip, int8_matmul, ((256, 384), jnp.int8),
             ((384, 256), jnp.int8), ((256,), jnp.float32))


def _served_kernel_names(one_chip, monkeypatch, **spec):
    """The layer paths of a tiny served network's convs, and the names of
    the custom calls in its bucket-8 program compiled for a v5e."""
    import re

    from repro.kernels import ops
    from repro.launch.serve_cnn import CnnServer
    from repro.models import cnn

    cfg = cnn.ResNetConfig(stages=(1, 1), widths=(8, 16), image_size=16)
    params, state = cnn.init(jax.random.PRNGKey(0), cfg)
    server = CnnServer(params, state, cfg, buckets=(8,), spec=cnn.ExecSpec(
        n_cu=4, quantized=True, folded=True, dense_fallback=2.0, **spec))
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    entry = server._entry_for(server.rungs[0], 8)
    x = jax.ShapeDtypeStruct((8, 16, 16, 3), jnp.float32, sharding=one_chip)
    text = entry.fn.lower(x).compile().as_text()
    calls = re.findall(r"%(\S+) = \S+ custom-call\(", text)
    want = sorted(cnn.conv_kernel_name(p)
                  for p, _, _ in cnn.conv_layer_order(cfg))
    return want, sorted(re.sub(r"\.\d+$", "", c) for c in calls)


def test_served_forward_names_each_conv_kernel_by_its_layer(one_chip,
                                                            monkeypatch):
    """A bucket program of ``CnnServer`` compiled for a v5e: each conv's
    Mosaic kernel is the custom call named by its layer path, the name a
    device trace gives the op (``conv_s0b0_conv2.<n>``)."""
    want, calls = _served_kernel_names(one_chip, monkeypatch, streamed=True)
    assert calls == want


def test_materializing_conv_kernels_are_named_by_their_layer(one_chip,
                                                             monkeypatch):
    """The same on the materializing kernel (``block_sparse_matmul``),
    bound with ``implicit=False``: no custom call keeps a kernel's own
    name."""
    want, calls = _served_kernel_names(one_chip, monkeypatch, implicit=False)
    assert [c for c in calls if c.startswith("conv_")] == want
    assert not {"block_sparse_matmul", "implicit_block_sparse_conv"} & set(
        calls)
