"""Group-sparse conv path vs the ``lax.conv`` oracle (interpret mode).

Sweeps stride, padding, non-tile-aligned ``cin*kx*ky``, remainder ``cout``
(``n_cu`` not dividing ``cout``), density {0, 0.3, 1.0}, f32/bf16 on the
packed MXU-shaped layout — and the
end-to-end ``cnn.apply(..., sparse=...)`` /
``fold_batchnorm -> apply_folded`` acceptance paths on a HAPM-pruned tiny
ResNet.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (HAPMConfig, apply_masks, fpga_conv_groups,
                        hapm_element_masks, hapm_epoch_update, hapm_init)
from repro.kernels import conv_lowering as CL
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
    gm = (rng.rand(n) < density).astype(np.float32)
    return gm


@pytest.mark.parametrize("stride,padding,kx,H,W", [
    (1, "SAME", 3, 9, 8),
    (2, "SAME", 3, 9, 8),      # odd sizes: asymmetric SAME pads
    (1, "VALID", 3, 7, 7),
    (2, "VALID", 1, 6, 5),
    (2, "SAME", 1, 7, 7),
])
def test_im2col_lowering_matches_lax_conv(stride, padding, kx, H, W):
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(2, H, W, 5).astype(np.float32))
    w = jnp.asarray(rng.randn(kx, kx, 5, 7).astype(np.float32))
    got = CL.conv_via_matmul(x, w, stride, padding)
    np.testing.assert_allclose(np.asarray(got), np.asarray(_oracle(x, w, stride, padding)),
                               rtol=1e-5, atol=1e-5)


# packed-layout sweep: stride {1,2} x SAME/VALID x n_cu {4,12} x f32/bf16
# x density {0, .3, 1}; cin*kx*ky never a multiple of the K tile, cin
# chosen so some cases span multiple K-tiles (cpk=8 channels/tile for
# 3x3) and cout leaves remainder f_blocks (n_cu not dividing cout)
PACKED_CASES = [
    (1, "SAME", 3, 3, 10, 4, 0.3, jnp.float32),
    (2, "SAME", 3, 5, 12, 4, 0.3, jnp.float32),
    (1, "VALID", 3, 4, 10, 4, 0.3, jnp.float32),
    (2, "VALID", 1, 7, 9, 4, 0.3, jnp.float32),
    (1, "SAME", 3, 4, 8, 4, 1.0, jnp.float32),   # fully dense plan
    (2, "SAME", 3, 2, 6, 4, 0.0, jnp.float32),   # fully pruned -> zeros
    (1, "SAME", 3, 3, 10, 4, 0.3, jnp.bfloat16),
    (2, "SAME", 3, 5, 9, 4, 0.3, jnp.bfloat16),
    (1, "SAME", 3, 16, 32, 12, 0.3, jnp.float32),   # 2 K-tiles, ragged f_blocks
    (2, "SAME", 3, 16, 32, 12, 0.3, jnp.float32),
    (1, "VALID", 3, 9, 10, 4, 0.3, jnp.float32),
    (2, "VALID", 3, 5, 12, 4, 0.3, jnp.float32),
    (1, "SAME", 1, 20, 9, 4, 0.3, jnp.float32),     # 1x1: 16 channels/K-tile
    (1, "SAME", 3, 16, 32, 12, 0.0, jnp.float32),   # fully pruned -> zeros
    (2, "SAME", 3, 8, 16, 4, 1.0, jnp.float32),     # fully dense plan
    (1, "SAME", 3, 16, 32, 12, 0.3, jnp.bfloat16),
    (2, "SAME", 3, 9, 10, 4, 0.3, jnp.bfloat16),
]


@pytest.mark.parametrize("stride,padding,kx,cin,cout,n_cu,density,dtype",
                         PACKED_CASES)
def test_packed_sparse_conv_parity(stride, padding, kx, cin, cout, n_cu,
                                   density, dtype):
    """Packed MXU-shaped layout vs the lax.conv oracle, weight prepacked at
    bind time (the closure only packs patches)."""
    rng = np.random.RandomState(hash((stride, kx, cin, cout, n_cu)) % 2**31)
    spec = fpga_conv_groups((kx, kx, cin, cout), n_cu)
    gm = _group_mask(rng, spec.num_groups, density)
    w = jnp.asarray(rng.randn(kx, kx, cin, cout), dtype)
    wm = (w * spec.expand(jnp.asarray(gm)).astype(dtype))
    x = jnp.asarray(rng.randn(2, 9, 8, cin), dtype)

    layout = conv_gemm_layout(spec)
    # bind-time prepacking masks the weight itself: pass the UNMASKED w
    conv = make_sparse_conv(layout, gm, weight=w)
    assert conv.prebound
    out = conv(x, stride=stride, padding=padding)
    expect = _oracle(x, wm, stride, padding)
    assert out.shape == expect.shape and out.dtype == expect.dtype
    tol = 1e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), rtol=tol, atol=tol)
    # per-call path agrees with the prebound path
    out2 = conv(x, w, stride, padding)
    np.testing.assert_allclose(np.asarray(out2, np.float32),
                               np.asarray(out, np.float32), rtol=tol, atol=tol)
    if density == 0.0:
        assert float(jnp.abs(out).max()) == 0.0

    # occupancy-based accounting: packed tiles cover many groups but the
    # schedule-step count is preserved exactly
    live, total = layout.tile_occupancy(gm)
    assert int(live.sum()) == int(gm.sum())
    assert int(total.sum()) == spec.num_groups
    np.testing.assert_array_equal(layout.tile_mask(gm), live > 0)
    # never more grid tiles than groups, nor live tiles than live groups
    assert np.prod(layout.tiles) <= spec.num_groups
    assert int(conv.plan.cnt.sum()) <= int(gm.sum())


def test_packed_epilogue_bias_relu_parity():
    """Fused bias+ReLU epilogue == conv -> +b -> relu on the oracle; bias
    flushes even for fully-pruned output columns (conv(x, 0) + b)."""
    rng = np.random.RandomState(3)
    spec = fpga_conv_groups((3, 3, 16, 32), 12)
    gm = _group_mask(rng, spec.num_groups, 0.3)
    gm.reshape(16, spec.n_fblocks)[:, -1] = 0.0      # kill a whole f_block
    w = jnp.asarray(rng.randn(3, 3, 16, 32).astype(np.float32))
    b = jnp.asarray(rng.randn(32).astype(np.float32))
    wm = w * spec.expand(jnp.asarray(gm))
    x = jnp.asarray(rng.randn(2, 9, 8, 16).astype(np.float32))
    conv = make_sparse_conv(conv_gemm_layout(spec), gm, weight=w, bias=b,
                            relu=True)
    out = conv(x, stride=1, padding="SAME")
    expect = jax.nn.relu(_oracle(x, wm, 1, "SAME") + b)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-4, atol=1e-4)


def test_valid_conv_smaller_than_kernel_raises():
    """VALID with input < kernel must fail loudly with the offending
    shapes, not produce a 0/negative slice bound."""
    x = jnp.ones((1, 2, 5, 3))
    with pytest.raises(ValueError, match=r"smaller than.*\(3, 3\)"):
        CL.im2col_patches(x, 3, 3, 1, "VALID")
    with pytest.raises(ValueError, match="smaller than"):
        CL.conv_out_size(2, 3, 1, "VALID")
    # SAME pads, so the same input is fine
    assert CL.im2col_patches(x, 3, 3, 1, "SAME").shape == (1, 2, 5, 3, 3, 3)
    # and a kernel-sized input has exactly one VALID output pixel
    assert CL.conv_out_size(3, 3, 2, "VALID") == 1


def _pruned_tiny_resnet(target=0.5, n_cu=4):
    cfg = cnn.ResNetConfig(stages=(1, 1), widths=(8, 16), image_size=16)
    params, state = cnn.init(jax.random.PRNGKey(0), cfg)
    # equal per-layer scale: the global sort then spreads groups across layers
    params = jax.tree_util.tree_map_with_path(
        lambda p, l: l / jnp.std(l) * 0.1 if cnn.is_conv_weight(p, l) else l,
        params)
    specs = cnn.conv_group_specs(params, n_cu)
    hcfg = HAPMConfig(target, 1)
    st = hapm_init(specs, hcfg)
    st = hapm_epoch_update(st, specs, params, hcfg)
    pruned = apply_masks(params, hapm_element_masks(specs, st))
    return cfg, pruned, state, specs, st


def test_cnn_apply_sparse_matches_dense():
    """Acceptance: HAPM-pruned tiny ResNet, sparse == dense within 1e-4 and
    live schedule steps at 50 % group sparsity <= 60 % of dense."""
    n_cu = 4
    cfg, pruned, state, specs, st = _pruned_tiny_resnet(0.5, n_cu)
    x = jax.random.uniform(jax.random.PRNGKey(1), (2, 16, 16, 3))
    dense, _ = cnn.apply(pruned, state, x, cfg)

    exec_ = cnn.bind_execution(pruned, spec=cnn.ExecSpec(n_cu=n_cu),
                               specs=specs, group_masks=st.group_masks)
    sparse, _ = cnn.apply(pruned, state, x, cfg, sparse=exec_)
    np.testing.assert_allclose(np.asarray(sparse), np.asarray(dense),
                               rtol=1e-4, atol=1e-4)

    executed, dense_steps = exec_.step_counts(cfg, batch=2)
    assert executed <= dense_steps
    live, total = exec_.schedule_step_counts()
    assert live / total <= 0.6

    # masks derived from the pruned weights' zero slabs give the same plans
    auto_exec = cnn.bind_execution(pruned, spec=cnn.ExecSpec(n_cu=n_cu))
    assert auto_exec.schedule_step_counts() == (live, total)
    auto, _ = cnn.apply(pruned, state, x, cfg, sparse=auto_exec)
    np.testing.assert_allclose(np.asarray(auto), np.asarray(dense),
                               rtol=1e-4, atol=1e-4)


def test_cnn_apply_packed_exec_matches_dense():
    """Packed MXU-shaped exec: same logits, fewer dispatched grid steps
    than schedule groups, exact schedule-step accounting."""
    n_cu = 12
    cfg = cnn.ResNetConfig(stages=(1, 1), widths=(16, 32), image_size=16)
    params, state = cnn.init(jax.random.PRNGKey(0), cfg)
    params = jax.tree_util.tree_map_with_path(
        lambda p, l: l / jnp.std(l) * 0.1 if cnn.is_conv_weight(p, l) else l,
        params)
    specs = cnn.conv_group_specs(params, n_cu)
    hcfg = HAPMConfig(0.5, 1)
    st = hapm_init(specs, hcfg)
    st = hapm_epoch_update(st, specs, params, hcfg)
    pruned = apply_masks(params, hapm_element_masks(specs, st))
    x = jax.random.uniform(jax.random.PRNGKey(1), (2, 16, 16, 3))
    dense, _ = cnn.apply(pruned, state, x, cfg)

    exec_ = cnn.bind_execution(pruned, spec=cnn.ExecSpec(n_cu=n_cu),
                               specs=specs, group_masks=st.group_masks)
    out, _ = cnn.apply(pruned, state, x, cfg, sparse=exec_)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                               rtol=1e-4, atol=1e-4)
    # schedule: occupancy accounting is exact
    live = int(sum(np.asarray(cnn._get_path(st.group_masks, k)).sum()
                   for k in exec_.plans))
    total = sum(np.asarray(cnn._get_path(st.group_masks, k)).size
                for k in exec_.plans)
    assert exec_.schedule_step_counts() == (live, total)
    # grid: packed tiles cover many groups, so the live tiles per M-block
    # are a fraction of the live groups
    assert sum(int(p.cnt.sum()) for p in exec_.plans.values()) * 4 <= live
    # dispatched-tile MAC utilization of the dense plan is a share
    dense_gm = {k: np.ones_like(v) for k, v in exec_.group_masks_np.items()}
    ld = cnn.SparseConvExec(table=exec_.table, plans=exec_.plans, n_cu=n_cu,
                            layouts=exec_.layouts, group_masks_np=dense_gm)
    assert 0.0 < ld.mac_utilization(cfg, batch=2) <= 1.0


def test_fold_batchnorm_sparse_inference_e2e():
    """fold_batchnorm -> folded bind (fused bias/ReLU epilogue)
    matches dense BN inference within 1e-4 and preserves zero groups."""
    n_cu = 4
    cfg, pruned, state, specs, st = _pruned_tiny_resnet(0.5, n_cu)
    x = jax.random.uniform(jax.random.PRNGKey(3), (2, 16, 16, 3))
    ref, _ = cnn.apply(pruned, state, x, cfg, train=False)

    folded = cnn.fold_batchnorm(pruned, state, cfg)
    # folding scales per output channel: HAPM's zero groups survive
    flat = jax.tree_util.tree_flatten_with_path(folded)[0]
    for path, leaf in flat:
        if not cnn.is_conv_weight(path, leaf):
            continue
        keys = tuple(getattr(k, "key", str(k)) for k in path)
        spec = cnn._get_path(specs, keys)
        gm = np.asarray(cnn._get_path(st.group_masks, keys))
        folded_scores = np.asarray(spec.group_scores(leaf))
        assert (folded_scores[gm == 0] == 0).all(), keys

    # dense folded path
    plain = cnn.apply_folded(folded, x, cfg)
    np.testing.assert_allclose(np.asarray(plain), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
    # sparse folded path, in-kernel bias/ReLU epilogue
    inf = cnn.bind_execution(
        folded, cfg, spec=cnn.ExecSpec(folded=True, implicit=True, n_cu=n_cu),
        group_masks=st.group_masks)
    out = cnn.apply_folded(folded, x, cfg, sparse=inf)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
    # the jitted end-to-end inference graph also agrees
    jout = jax.jit(lambda xx: cnn.apply_folded(folded, xx, cfg, sparse=inf))(x)
    np.testing.assert_allclose(np.asarray(jout), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_sparse_true_is_memoized_and_rejects_tracers():
    """Binds are host-side: under jit ``bind_execution`` raises
    :class:`PermanentBindError` instead of silently tracing host-side plan
    construction, while a prebuilt exec is jittable."""
    cfg, pruned, state, _, _ = _pruned_tiny_resnet(0.5, 4)
    e1 = cnn.bind_execution(pruned, spec=cnn.ExecSpec(n_cu=4))

    x = jax.random.uniform(jax.random.PRNGKey(1), (2, 16, 16, 3))
    with pytest.raises(cnn.PermanentBindError, match="outside jit"):
        jax.jit(lambda p: cnn.bind_execution(
            p, spec=cnn.ExecSpec(n_cu=4)).n_cu)(pruned)
    # prebuilt execs ARE jittable (plans become compile-time constants)
    out = jax.jit(lambda p, xx: cnn.apply(p, state, xx, cfg, sparse=e1)[0])(pruned, x)
    dense, _ = cnn.apply(pruned, state, x, cfg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                               rtol=1e-4, atol=1e-4)
    # a quantization mismatch between exec and cfg is rejected loudly
    qcfg = cnn.ResNetConfig(stages=(1, 1), widths=(8, 16), image_size=16,
                            quantized=True)
    with pytest.raises(ValueError, match="quantized"):
        cnn.apply(pruned, state, x, qcfg, sparse=e1)
    # prepacked weights are constants: the sparse path refuses training
    with pytest.raises(ValueError, match="inference-only"):
        cnn.apply(pruned, state, x, cfg, train=True, sparse=e1)
    # ...and refuses a concrete params tree whose conv arrays aren't the
    # bind-time ones (stale exec -> loud error, not silently old weights)
    newp = jax.tree_util.tree_map(lambda l: l * 1.0, pruned)
    with pytest.raises(ValueError, match="stale"):
        cnn.apply(newp, state, x, cfg, sparse=e1)


def test_folded_and_plain_execs_are_not_interchangeable():
    """A fused-epilogue exec in apply() would double-apply BN; a plain exec
    in apply_folded() would drop the folded bias — both rejected loudly."""
    cfg, pruned, state, _, st = _pruned_tiny_resnet(0.5, 4)
    x = jax.random.uniform(jax.random.PRNGKey(1), (2, 16, 16, 3))
    folded = cnn.fold_batchnorm(pruned, state, cfg)
    inf = cnn.bind_execution(
        folded, cfg, spec=cnn.ExecSpec(folded=True, implicit=True, n_cu=4),
        group_masks=st.group_masks)
    with pytest.raises(ValueError, match="apply_folded"):
        cnn.apply(pruned, state, x, cfg, sparse=inf)
    plain = cnn.bind_execution(pruned, spec=cnn.ExecSpec(n_cu=4),
                               group_masks=st.group_masks)
    with pytest.raises(ValueError, match="folded SparseConvExec"):
        cnn.apply_folded(folded, x, cfg, sparse=plain)


def test_cnn_apply_dense_fallback_on_unpruned():
    """Density ~1 layers stay on lax.conv: identical output, no bound kernel."""
    cfg = cnn.ResNetConfig(stages=(1,), widths=(8,), image_size=8)
    params, state = cnn.init(jax.random.PRNGKey(0), cfg)
    exec_ = cnn.bind_execution(params, spec=cnn.ExecSpec(n_cu=4))
    assert all(fn is None for fn in exec_.table.values())
    x = jax.random.uniform(jax.random.PRNGKey(1), (2, 8, 8, 3))
    dense, _ = cnn.apply(params, state, x, cfg)
    sparse, _ = cnn.apply(params, state, x, cfg, sparse=exec_)
    np.testing.assert_array_equal(np.asarray(sparse), np.asarray(dense))
    executed, dense_steps = exec_.step_counts(cfg)
    assert executed == dense_steps


def test_simulator_reports_grid_steps():
    """simulate() reports executed grid steps next to the DSB cycles, and
    per layer the live-tile count equals the cycle model's live-step count."""
    n_cu = 4
    cfg, pruned, state, specs, st = _pruned_tiny_resnet(0.5, n_cu)
    import dataclasses as dc
    from repro.accel import BOARDS, simulate
    accel = dc.replace(BOARDS["zedboard_100mhz_72dsp"], n_cu=n_cu)
    rep = simulate(pruned, state, cfg, accel)
    assert rep.dense_grid_steps > rep.executed_grid_steps > 0
    assert 0.0 < rep.grid_step_ratio < 1.0
    assert 0.0 < rep.dsb_cycle_ratio < 1.0
    assert set(rep.grid_steps_per_layer) == set(rep.group_sparsity_per_layer)
    # packed layout: far fewer dispatched steps for the same masks, and the
    # occupancy-based schedule accounting matches the per-layer live
    # groups (the cycle model's live DSB steps)
    assert rep.packed_dense_grid_steps < rep.dense_grid_steps
    assert rep.packed_executed_grid_steps <= rep.packed_dense_grid_steps
    assert 0 < rep.schedule_steps_live < rep.schedule_steps_total
    per_layer_live = sum(
        v["executed"] // max(-(-l.out_x * l.out_y // 128), 1)
        for v, (_, l) in zip(rep.grid_steps_per_layer.values(),
                             cnn.layer_dims(cfg, pruned)))
    assert per_layer_live == rep.schedule_steps_live
    assert 0.0 < rep.padded_mac_utilization < 1.0
    assert "packed_grid_step_ratio" in rep.row()
    base = simulate(cnn.init(jax.random.PRNGKey(0), cfg)[0], state, cfg, accel)
    assert base.grid_step_ratio == 1.0
    assert base.packed_grid_step_ratio == 1.0
    assert base.schedule_steps_live == base.schedule_steps_total
