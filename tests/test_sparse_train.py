"""Training through the block-sparse kernels (interpret mode).

The ``trainable=True`` conv closures carry a ``jax.custom_vjp`` whose
backward runs the transposed-plan GEMM (dX) and the live-tile
``block_sparse_grad_weight`` kernel (dW). These tests pin down:

- gradient parity vs the ``lax.conv`` oracle over stride x padding x
  density {0, 0.3, 1.0} on both layouts and both forward kernels
  (implicit gather / materializing) — the reference differentiates
  through the same element-mask multiply the train step applies, so
  parity includes the pruned-position zeros;
- the HAPM no-resurrection invariant: pruned groups receive *exactly*
  zero gradient (bitwise, not a tolerance);
- an end-to-end jitted sparse train step on a HAPM-pruned tiny ResNet
  that strictly decreases the loss;
- the trainable execution contract (``ExecSpec(trainable=True)``)
  and the exact-count ``cnn.init`` key split on deep configs.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (HAPMConfig, apply_masks, fpga_conv_groups,
                        hapm_element_masks, hapm_epoch_update, hapm_init)
from repro.models import cnn
from repro.sparse.conv_plan import conv_gemm_layout, make_sparse_conv


def _oracle(x, w, stride, padding):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _group_mask(rng, n, density):
    if density <= 0.0:
        return np.zeros(n, np.float32)
    if density >= 1.0:
        return np.ones(n, np.float32)
    return (rng.rand(n) < density).astype(np.float32)


# stride {1,2} x SAME/VALID x density {0, .3, 1} x kernel
GRAD_CASES = [
    # stride padding cin cout n_cu density implicit
    (1, "SAME", 16, 32, 12, 0.3, True),
    (2, "SAME", 16, 32, 12, 0.3, False),
    (1, "VALID", 9, 10, 4, 0.3, True),
    (2, "VALID", 5, 12, 4, 0.3, True),
    (1, "SAME", 3, 10, 4, 0.3, False),          # one K-tile, ragged cout
    (2, "SAME", 5, 12, 4, 0.3, False),
    (1, "SAME", 8, 16, 4, 1.0, True),           # fully dense plan
    (1, "SAME", 16, 32, 12, 0.0, True),         # fully pruned -> zero grads
]


@pytest.mark.parametrize(
    "stride,padding,cin,cout,n_cu,density,implicit", GRAD_CASES)
def test_trainable_conv_grad_parity(stride, padding, cin, cout, n_cu,
                                    density, implicit):
    rng = np.random.RandomState(hash((stride, cin, cout, density)) % 2**31)
    spec = fpga_conv_groups((3, 3, cin, cout), n_cu)
    gm = _group_mask(rng, spec.num_groups, density)
    em = spec.expand(jnp.asarray(gm))                  # element mask
    w = jnp.asarray(rng.randn(3, 3, cin, cout).astype(np.float32))
    x = jnp.asarray(rng.randn(2, 9, 8, cin).astype(np.float32))

    conv = make_sparse_conv(conv_gemm_layout(spec), gm,
                            implicit=implicit, trainable=True)
    assert conv.trainable

    # both losses differentiate through the mask multiply — the train
    # step masks params before the forward, so this IS the trained loss
    def loss_sparse(x, w):
        return jnp.sum(jnp.sin(conv(x, w, stride, padding)))

    def loss_dense(x, w):
        return jnp.sum(jnp.sin(_oracle(x, w * em, stride, padding)))

    fs, (dxs, dws) = jax.value_and_grad(loss_sparse, argnums=(0, 1))(x, w)
    fd, (dxd, dwd) = jax.value_and_grad(loss_dense, argnums=(0, 1))(x, w)
    np.testing.assert_allclose(float(fs), float(fd), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(dxs), np.asarray(dxd),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(dws), np.asarray(dwd),
                               rtol=1e-4, atol=1e-4)
    # no-resurrection: pruned positions get bitwise-zero gradient
    assert float(jnp.max(jnp.abs(dws * (1 - em)))) == 0.0
    if density == 0.0:
        assert float(jnp.max(jnp.abs(dws))) == 0.0
        assert float(jnp.max(jnp.abs(dxs))) == 0.0


def test_trainable_conv_under_jit_and_repeated_shapes():
    """The per-(kx,ky,stride,padding) custom-vjp closures are cached and
    jit-stable; a second call with new weights reuses them (no staleness:
    nothing is prepacked)."""
    rng = np.random.RandomState(3)
    spec = fpga_conv_groups((3, 3, 8, 16), 4)
    gm = _group_mask(rng, spec.num_groups, 0.5)
    em = spec.expand(jnp.asarray(gm))
    conv = make_sparse_conv(conv_gemm_layout(spec), gm,
                            trainable=True)
    x = jnp.asarray(rng.randn(2, 8, 8, 8).astype(np.float32))

    @jax.jit
    def g(w):
        return jax.grad(lambda w: jnp.sum(conv(x, w, 1, "SAME") ** 2))(w)

    w1 = jnp.asarray(rng.randn(3, 3, 8, 16).astype(np.float32))
    w2 = w1 * 2.0
    ref = jax.grad(lambda w: jnp.sum(_oracle(x, w * em, 1, "SAME") ** 2))
    np.testing.assert_allclose(np.asarray(g(w1)), np.asarray(ref(w1)),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(g(w2)), np.asarray(ref(w2)),
                               rtol=1e-4, atol=1e-4)


def test_trainable_rejects_inference_epilogues():
    spec = fpga_conv_groups((3, 3, 8, 16), 4)
    gm = np.ones(spec.num_groups, np.float32)
    with pytest.raises(ValueError, match="inference-only"):
        make_sparse_conv(conv_gemm_layout(spec), gm,
                         trainable=True, relu=True)


def test_exec_spec_trainable_contract():
    s = cnn.ExecSpec(trainable=True)
    assert s == cnn.ExecSpec(trainable=True) and hash(s) == hash(s)
    with pytest.raises(ValueError, match="inference-only"):
        cnn.ExecSpec(trainable=True, quantized=True)
    with pytest.raises(ValueError, match="inference-only"):
        cnn.ExecSpec(trainable=True, folded=True)


def _pruned_tiny(target=0.5, quantized=False):
    cfg = cnn.ResNetConfig(stages=(1, 1), widths=(8, 16), image_size=16,
                           quantized=quantized)
    params, state = cnn.init(jax.random.PRNGKey(0), cfg)
    specs = cnn.conv_group_specs(params, 4)
    hcfg = HAPMConfig(target, 1)
    st = hapm_epoch_update(hapm_init(specs, hcfg), specs, params, hcfg)
    masks = hapm_element_masks(specs, st)
    return cfg, params, state, specs, st, masks


@pytest.mark.parametrize("quantized", [False, True])
def test_model_grad_parity_dense_vs_sparse_exec(quantized):
    """Whole-model check: grads of the masked loss through a trainable
    bind match the dense path (QAT included — the f32 kernels consume the
    fake-quant view)."""
    cfg, params, state, specs, st, masks = _pruned_tiny(0.5, quantized)
    exec_ = cnn.bind_execution(params, cfg,
                               spec=cnn.ExecSpec(trainable=True, n_cu=4),
                               specs=specs, group_masks=st.group_masks)
    x = jax.random.uniform(jax.random.PRNGKey(1), (2, 16, 16, 3))
    y = jnp.asarray([3, 7])

    def loss(p, sparse):
        logits, _ = cnn.apply(apply_masks(p, masks), state, x, cfg,
                              train=True, sparse=sparse)
        lp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(lp, y[:, None], axis=-1))

    gd = jax.grad(lambda p: loss(p, None))(params)
    gs = jax.grad(lambda p: loss(p, exec_))(params)
    for a, b in zip(jax.tree.leaves(gd), jax.tree.leaves(gs)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)
    # pruned groups: exactly zero through the whole model
    for g, m in zip(jax.tree.leaves(gs),
                    jax.tree.leaves(masks, is_leaf=lambda v: v is None)):
        if m is not None:
            assert float(jnp.max(jnp.abs(g * (1 - m)))) == 0.0


def test_jitted_sparse_train_step_decreases_loss():
    """End-to-end: a jitted SGD step through the trainable bind strictly
    decreases the loss and keeps pruned weights at zero."""
    cfg, params, state, specs, st, masks = _pruned_tiny(0.5)
    exec_ = cnn.bind_execution(params, cfg,
                               spec=cnn.ExecSpec(trainable=True, n_cu=4),
                               specs=specs, group_masks=st.group_masks)
    x = jax.random.uniform(jax.random.PRNGKey(2), (4, 16, 16, 3))
    y = jnp.asarray([0, 1, 2, 3])

    @jax.jit
    def step(params):
        def loss(p):
            logits, _ = cnn.apply(apply_masks(p, masks), state, x, cfg,
                                  train=True, sparse=exec_)
            lp = jax.nn.log_softmax(logits)
            return -jnp.mean(jnp.take_along_axis(lp, y[:, None], axis=-1))
        l, g = jax.value_and_grad(loss)(params)
        p = jax.tree.map(lambda p, g: p - 0.05 * g, params, g)
        return apply_masks(p, masks), l

    losses = []
    for _ in range(4):
        params, l = step(params)
        losses.append(float(l))
    assert all(b < a for a, b in zip(losses, losses[1:])), losses
    for p, m in zip(jax.tree.leaves(params),
                    jax.tree.leaves(masks, is_leaf=lambda v: v is None)):
        if m is not None:
            assert float(jnp.max(jnp.abs(p * (1 - m)))) == 0.0


def test_apply_train_rejects_inference_only_exec():
    cfg, params, state, specs, st, masks = _pruned_tiny(0.5)
    pruned = apply_masks(params, masks)
    x = jnp.zeros((1, 16, 16, 3))
    infer_exec = cnn.bind_execution(pruned, cfg, spec=cnn.ExecSpec(n_cu=4))
    with pytest.raises(ValueError, match="inference-only"):
        cnn.apply(pruned, state, x, cfg, train=True, sparse=infer_exec)
    # eval-mode inference through the same exec still fine
    cnn.apply(pruned, state, x, cfg, train=False, sparse=infer_exec)


def test_trainable_bind_prepacks_nothing():
    cfg, params, state, specs, st, masks = _pruned_tiny(0.5)
    exec_ = cnn.bind_execution(params, cfg,
                               spec=cnn.ExecSpec(trainable=True, n_cu=4),
                               specs=specs, group_masks=st.group_masks)
    assert exec_.trainable and exec_.bound_weights is None


def test_init_key_count_matches_deep_configs():
    """init used a fixed split(key, 64); deep configs exhausted it
    (StopIteration). The split is now sized to the layer count."""
    for stages in [(1, 1), (3, 3, 3), (12, 12, 12)]:
        cfg = cnn.ResNetConfig(stages=stages,
                               widths=tuple(8 * 2**i for i in range(len(stages))),
                               image_size=16)
        params, state = cnn.init(jax.random.PRNGKey(0), cfg)
        n_convs = sum(1 for p, l in
                      jax.tree_util.tree_leaves_with_path(params)
                      if cnn.is_conv_weight(tuple(p), l))
        # conv0 + 2 per block + 1 per downsampling projection
        expect = 1 + 2 * sum(stages) + (len(stages) - 1)
        assert n_convs == expect
