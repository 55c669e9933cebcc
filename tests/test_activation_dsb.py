"""Activation-side DSB: the implicit kernel's all-zero-window skip.

The skip is keyed on *exact* int8 codes (post-ReLU zeros are exact on
the quantized wire), so every test here asserts **bitwise** equality —
skip-on == skip-off == the materializing oracle — across density ×
stride × padding × batch, all-zero channels and fully-dead images, plus
skip-counter correctness against a from-scratch numpy reference count of
the kernel's window rule.
"""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fpga_conv_groups
from repro.core import quant as Q
from repro.kernels import implicit_conv as IC
from repro.models import cnn
from repro.sparse.conv_plan import conv_gemm_layout, make_sparse_conv


def _group_mask(rng, n, density):
    if density <= 0.0:
        return np.zeros(n, np.float32)
    if density >= 1.0:
        return np.ones(n, np.float32)
    return (rng.rand(n) < density).astype(np.float32)


def _relu_sparse(rng, shape, dead_channel_frac=0.5, spatial_zero=0.3):
    """Post-ReLU-looking activation: a fraction of channels fully dead
    (what a pruned upstream group emits on the streamed wire) plus
    scattered elementwise zeros. f32 — the bound conv quantizes it to
    exact zero codes on entry."""
    x = rng.randn(*shape).astype(np.float32)
    dead = rng.rand(shape[-1]) < dead_channel_frac
    x[..., dead] = -1.0
    x = np.maximum(x, 0.0)
    x[rng.rand(*shape) < spatial_zero] = 0.0
    return x


def _bound_pair(rng, kshape, n_cu, density, *, relu=False, streamed=False):
    """(conv_dsb, conv_noskip, conv_oracle) bound on the same plan,
    weight and quant spec — only the skip flag (and the kernel choice
    for the oracle) differs."""
    spec = fpga_conv_groups(kshape, n_cu)
    gm = _group_mask(rng, spec.num_groups, density)
    w = jnp.asarray(rng.randn(*kshape).astype(np.float32) * 0.2)
    layout = conv_gemm_layout(spec)
    quant = Q.QuantSpec()
    out_q = Q.QuantSpec() if streamed else None
    mk = lambda **kw: make_sparse_conv(layout, gm, weight=w, quant=quant,
                                       out_quant=out_q, relu=relu, **kw)
    return (mk(implicit=True, activation_dsb=True),
            mk(implicit=True),
            mk(implicit=False))


# density {0, 0.5, 1} x stride {1, 2} x SAME/VALID x batch {1, 2}
SWEEP = list(itertools.product((0.0, 0.5, 1.0), (1, 2),
                               ("SAME", "VALID"), (1, 2)))


@pytest.mark.parametrize("density,stride,padding,batch", SWEEP)
def test_dsb_exactness_sweep(density, stride, padding, batch):
    """skip-on == skip-off == materializing oracle, bitwise, at every
    weight density — the skip only elides MXU passes whose contribution
    is exactly zero, so the int32 accumulator (and everything downstream
    of it) is untouched."""
    rng = np.random.RandomState(hash((density, stride, padding, batch))
                                % 2**31)
    dsb, noskip, oracle = _bound_pair(rng, (3, 3, 16, 24), 8, density)
    x = jnp.asarray(_relu_sparse(rng, (batch, 9, 8, 16)))
    outs = [np.asarray(c(x, stride=stride, padding=padding))
            for c in (dsb, noskip, oracle)]
    np.testing.assert_array_equal(outs[0], outs[1])
    np.testing.assert_array_equal(outs[0], outs[2])
    if density == 0.0:
        assert float(np.abs(outs[0]).max()) == 0.0


def test_dsb_streamed_codes_exact():
    """With the requantizing epilogue the outputs are int8 wire codes —
    the skip must reproduce them code-for-code."""
    rng = np.random.RandomState(7)
    dsb, noskip, oracle = _bound_pair(rng, (3, 3, 16, 24), 8, 0.5,
                                      relu=True, streamed=True)
    x = jnp.asarray(_relu_sparse(rng, (2, 9, 8, 16)))
    y_dsb, y_off = np.asarray(dsb(x)), np.asarray(noskip(x))
    assert y_dsb.dtype == np.int8
    np.testing.assert_array_equal(y_dsb, y_off)
    np.testing.assert_array_equal(y_dsb, np.asarray(oracle(x)))


def test_dsb_skip_counter_matches_numpy_reference():
    """The kernel-side skip counter == a from-scratch numpy count of the
    documented window rule: one skip per (M-block, output tile column,
    live K-tile) whose padded ``(rows, cols, cpk)`` activation window is
    all-zero codes."""
    rng = np.random.RandomState(3)
    kx = ky = 3
    stride, padding, batch = 1, "SAME", 2
    dsb, noskip, _ = _bound_pair(rng, (kx, ky, 16, 24), 8, 0.6)
    cpk = dsb.layout.implicit_geometry()["cpk"]
    xr = _relu_sparse(rng, (batch, 9, 8, 16), dead_channel_frac=0.6)
    xr[..., :cpk] = 0.0  # guarantee at least one fully-dead K-tile
    x = jnp.asarray(xr)
    y, stats = dsb.skip_counts(x, stride=stride, padding=padding)
    assert stats is not None
    np.testing.assert_array_equal(np.asarray(y), np.asarray(dsb(x)))

    # reference count on exactly what the kernel sees: quantized codes,
    # padded, windowed per (M-block, column, live table entry)
    codes = np.asarray(dsb.quant.act_codes(x))
    geo = dsb.layout.implicit_geometry()
    cpk, nKb = geo["cpk"], dsb.layout.tiles[0]
    from repro.kernels.conv_lowering import conv_out_size
    ho = conv_out_size(x.shape[1], kx, stride, padding)
    wo = conv_out_size(x.shape[2], ky, stride, padding)
    mb = IC.choose_m_block(ho, wo)
    xp = np.asarray(IC.pad_input(jnp.asarray(codes), kx, ky, stride,
                                 padding, mb, nKb * cpk))
    rows, cols = IC.window_shape(mb, kx, ky, stride)
    idx, cnt = dsb.plan.idx, dsb.plan.cnt
    expected = 0
    for b in range(batch):
        for p in range(mb.bpi):
            r0 = (p // mb.spi) * mb.block_oh * stride
            q0 = (p % mb.spi) * mb.block_ow * stride
            for j in range(idx.shape[0]):
                for s in range(int(cnt[j])):
                    t = int(idx[j, s])
                    win = xp[b, r0:r0 + rows, q0:q0 + cols,
                             t * cpk:(t + 1) * cpk]
                    expected += int(not win.any())
    assert stats["skipped_steps"] == expected
    assert stats["live_steps"] == batch * mb.bpi * int(cnt.sum())
    assert 0 < expected <= stats["live_steps"]
    # the non-skip bind runs the same counter but never skips
    _, stats_off = noskip.skip_counts(x, stride=stride, padding=padding)
    assert stats_off["skipped_steps"] == 0
    assert stats_off["live_steps"] == stats["live_steps"]


def test_dsb_fully_dead_image_skips_everything():
    """An all-zero input quantizes to all-zero codes: every live step
    skips, and the output still equals the non-skip kernel bitwise."""
    rng = np.random.RandomState(9)
    dsb, noskip, _ = _bound_pair(rng, (3, 3, 16, 24), 8, 0.5, relu=True)
    x = jnp.zeros((1, 9, 8, 16))
    y, stats = dsb.skip_counts(x)
    assert stats["live_steps"] > 0
    assert stats["skipped_steps"] == stats["live_steps"]
    np.testing.assert_array_equal(np.asarray(y), np.asarray(noskip(x)))


def test_dsb_all_zero_channel_blocks_skip_per_tile():
    """Zeroing the channels of one live K-tile kills exactly that tile's
    steps everywhere it appears in the table — the skip granularity is
    (window × K-tile), not whole-image."""
    rng = np.random.RandomState(13)
    dsb, _, _ = _bound_pair(rng, (3, 3, 16, 24), 8, 1.0)
    geo = dsb.layout.implicit_geometry()
    cpk = geo["cpk"]
    x = np.abs(rng.randn(1, 9, 8, 16).astype(np.float32))  # no zeros
    _, dense_stats = dsb.skip_counts(jnp.asarray(x))
    assert dense_stats["skipped_steps"] == 0
    # dead channels covering K-tile 0 exactly
    x2 = x.copy()
    x2[..., :cpk] = 0.0
    _, stats = dsb.skip_counts(jnp.asarray(x2))
    idx, cnt = dsb.plan.idx, dsb.plan.cnt
    appearances = sum(int((idx[j, :int(cnt[j])] == 0).sum())
                      for j in range(idx.shape[0]))
    from repro.kernels.conv_lowering import conv_out_size
    mb = IC.choose_m_block(conv_out_size(9, 3, 1, "SAME"),
                           conv_out_size(8, 3, 1, "SAME"))
    assert stats["skipped_steps"] == mb.bpi * appearances > 0


def test_dsb_rejects_f32_and_materializing():
    """The contract table: f32 operands and the materializing path have
    no exact zero codes / no window to test."""
    rng = np.random.RandomState(1)
    spec = fpga_conv_groups((3, 3, 8, 8), 4)
    gm = _group_mask(rng, spec.num_groups, 0.5)
    layout = conv_gemm_layout(spec)
    w = jnp.asarray(rng.randn(3, 3, 8, 8).astype(np.float32))
    with pytest.raises(ValueError, match="requires[\\s\\S]*quant"):
        make_sparse_conv(layout, gm, weight=w, activation_dsb=True)
    with pytest.raises(ValueError, match="implicit"):
        make_sparse_conv(layout, gm, weight=w, quant=Q.QuantSpec(),
                         implicit=False, activation_dsb=True)
    with pytest.raises(ValueError, match="quantized"):
        cnn.ExecSpec(activation_dsb=True)
    with pytest.raises(ValueError, match="implicit"):
        cnn.ExecSpec(activation_dsb=True, quantized=True, implicit=False)


def _pruned_net(target=0.5, n_cu=12):
    cfg = cnn.ResNetConfig(stages=(1, 1, 2), widths=(16, 32, 64),
                           image_size=16)
    params, state = cnn.init(jax.random.PRNGKey(0), cfg)
    params = jax.tree_util.tree_map_with_path(
        lambda p, l: l / jnp.std(l) * 0.1 if cnn.is_conv_weight(p, l) else l,
        params)
    from repro.core import (HAPMConfig, apply_masks, hapm_element_masks,
                            hapm_epoch_update, hapm_init)
    specs = cnn.conv_group_specs(params, n_cu)
    hcfg = HAPMConfig(target, 1)
    st = hapm_init(specs, hcfg)
    st = hapm_epoch_update(st, specs, params, hcfg)
    pruned = apply_masks(params, hapm_element_masks(specs, st))
    return cfg, pruned, state, specs, st


def test_dsb_end_to_end_streamed_bind():
    """ExecSpec(activation_dsb=True) through bind_execution: served
    streamed traffic is bit-exact vs the non-skip bind, and
    measure_dsb_skip reports a coherent accounting (conv0 skips all its
    live steps on a dead frame)."""
    cfg, pruned, state, specs, st = _pruned_net()
    folded = cnn.fold_batchnorm(pruned, state, cfg)
    bind = lambda **kw: cnn.bind_execution(
        folded, cfg,
        spec=cnn.ExecSpec(n_cu=12, folded=True, quantized=True,
                          streamed=True, dense_fallback=2.0, **kw),
        specs=specs, group_masks=st.group_masks)
    e_off, e_on = bind(), bind(activation_dsb=True)
    assert e_on.activation_dsb and not e_off.activation_dsb
    assert e_on.spec.activation_dsb
    rng = np.random.RandomState(4)
    x = jnp.asarray(_relu_sparse(rng, (2, 16, 16, 3), dead_channel_frac=0.0))
    y_on = cnn.apply_folded(folded, x, cfg, sparse=e_on)
    y_off = cnn.apply_folded(folded, x, cfg, sparse=e_off)
    np.testing.assert_array_equal(np.asarray(y_on), np.asarray(y_off))
    m = e_on.measure_dsb_skip(folded, x, cfg)
    assert 0.0 <= m["dsb_skip_frac"] <= 1.0
    assert m["dsb_skipped_steps"] <= m["dsb_live_steps"]
    assert set(m["dsb_per_layer"]) == {"/".join(k) for k, v
                                       in e_on.table.items() if v is not None}
    # report() merges the measured fields
    rep = e_on.report(cfg, batch=2, dsb_sample=x, dsb_tree=folded)
    assert rep["activation_dsb"] and rep["dsb_skip_frac"] == m["dsb_skip_frac"]
    # dead frame: conv0 ingests all-zero codes -> skips every live step
    md = e_on.measure_dsb_skip(folded, jnp.zeros((1, 16, 16, 3)), cfg)
    c0 = md["dsb_per_layer"]["conv0/w"]
    assert c0["live_steps"] > 0
    assert c0["skipped_steps"] == c0["live_steps"]
    # the non-dsb bind measures zero skips through the same machinery
    assert e_off.measure_dsb_skip(folded, x, cfg)["dsb_skip_frac"] == 0.0
