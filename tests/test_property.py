"""Hypothesis property tests on system invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="hypothesis not installed")
from hypothesis import given, settings, strategies as st

from repro.accel import AcceleratorConfig, ConvLayerDims, dsb_cycles, min_cycles
from repro.core import (Q2_5, Q3_4, apply_masks, fpga_conv_groups, quantize,
                        tpu_tile_groups)
from repro.core.groups import apply_group_mask
from repro.core.uniform import magnitude_masks
from repro.sparse.block_mask import (plan_from_tile_mask, tile_mask_from_weight,
                                     transpose_plan)
from repro.sparse.conv_plan import conv_gemm_layout

SETTINGS = dict(max_examples=25, deadline=None)


@given(kx=st.integers(1, 4), cin=st.integers(1, 6), cout=st.integers(1, 20),
       n_cu=st.integers(1, 8))
@settings(**SETTINGS)
def test_fpga_groups_partition_weights(kx, cin, cout, n_cu):
    """Groups are a partition: element counts sum to the weight count, and
    expanding an all-zero group mask zeroes everything."""
    spec = fpga_conv_groups((kx, kx, cin, cout), n_cu)
    assert spec.group_elem_counts().sum() == kx * kx * cin * cout
    m0 = np.asarray(spec.expand(jnp.zeros(spec.num_groups)))
    m1 = np.asarray(spec.expand(jnp.ones(spec.num_groups)))
    assert (m0 == 0).all() and (m1 == 1).all()


@given(K=st.integers(1, 400), N=st.integers(1, 400),
       bk=st.sampled_from([32, 128]), bn=st.sampled_from([32, 128]))
@settings(**SETTINGS)
def test_tile_groups_partition(K, N, bk, bn):
    spec = tpu_tile_groups((K, N), (bk, bn))
    assert spec.group_elem_counts().sum() == K * N
    assert spec.num_groups == -(-K // bk) * (-(-N // bn))


@given(data=st.data())
@settings(**SETTINGS)
def test_group_mask_expand_score_consistency(data):
    """Pruned groups score exactly zero after masking; kept groups keep
    their score (mask-apply/score commute)."""
    cin = data.draw(st.integers(1, 4))
    cout = data.draw(st.integers(1, 12))
    spec = fpga_conv_groups((3, 3, cin, cout), n_cu=3)
    rng = np.random.RandomState(data.draw(st.integers(0, 100)))
    w = jnp.asarray(rng.randn(3, 3, cin, cout).astype(np.float32))
    gm = jnp.asarray((rng.rand(spec.num_groups) > 0.5).astype(np.float32))
    wm = w * spec.expand(gm)
    s = np.asarray(spec.group_scores(wm))
    s0 = np.asarray(spec.group_scores(w))
    np.testing.assert_allclose(s, s0 * np.asarray(gm), rtol=1e-5, atol=1e-6)


@given(st.lists(st.floats(-50, 50, allow_nan=False), min_size=1, max_size=64))
@settings(**SETTINGS)
def test_quantize_idempotent_and_bounded(vals):
    x = jnp.asarray(vals, jnp.float32)
    for fmt in (Q2_5, Q3_4):
        q = quantize(x, fmt)
        np.testing.assert_array_equal(np.asarray(quantize(q, fmt)), np.asarray(q))
        assert float(q.max(initial=fmt.min_val)) <= fmt.max_val
        assert float(q.min(initial=fmt.max_val)) >= fmt.min_val
        # error bounded by half a step inside the range
        inside = (x >= fmt.min_val) & (x <= fmt.max_val)
        err = jnp.abs(q - x) * inside
        assert float(err.max()) <= 0.5 / fmt.scale + 1e-6


@given(sparsity=st.floats(0.0, 0.99), n=st.integers(4, 300))
@settings(**SETTINGS)
def test_magnitude_mask_count_exact(sparsity, n):
    rng = np.random.RandomState(n)
    p = {"w": jnp.asarray(rng.randn(n).astype(np.float32))}
    m = magnitude_masks(p, {"w": jnp.ones(n)}, sparsity)
    assert int(jnp.sum(m["w"] == 0)) == int(round(sparsity * n))


@given(nKb=st.integers(1, 6), nNb=st.integers(1, 6), seed=st.integers(0, 99))
@settings(**SETTINGS)
def test_plan_indices_cover_live_tiles(nKb, nNb, seed):
    rng = np.random.RandomState(seed)
    tm = rng.rand(nKb, nNb) < 0.5
    plan = plan_from_tile_mask(tm, (128, 128))
    for j in range(nNb):
        live = set(np.nonzero(tm[:, j])[0])
        listed = set(plan.idx[j, :plan.cnt[j]])
        assert listed == live
    assert plan.cnt.sum() == tm.sum()


@given(nif=st.integers(1, 16), ratio_seed=st.integers(0, 50))
@settings(**SETTINGS)
def test_dsb_cycles_monotone_in_mask(nif, ratio_seed):
    """More pruned groups can never cost more cycles."""
    accel = AcceleratorConfig(n_cu=4)
    layer = ConvLayerDims(18, 18, nif, 8)
    rng = np.random.RandomState(ratio_seed)
    from repro.accel.cycle_model import schedule_counts
    n = schedule_counts(layer, accel).n_steps
    gm = (rng.rand(n) > 0.5).astype(np.float32)
    c1 = dsb_cycles(layer, accel, gm)
    gm2 = gm.copy()
    nz = np.nonzero(gm2)[0]
    if len(nz):
        gm2[nz[0]] = 0
    c2 = dsb_cycles(layer, accel, gm2)
    assert c2 <= c1 <= min_cycles(layer, accel)


@given(nKb=st.integers(1, 6), nNb=st.integers(1, 6), seed=st.integers(0, 99),
       bk=st.sampled_from([16, 128]), bn=st.sampled_from([32, 128]))
@settings(**SETTINGS)
def test_transpose_plan_roundtrip(nKb, nNb, seed, bk, bn):
    """transpose_plan: cnt/idx consistent with the transposed mask, density
    invariant, and transposing twice recovers the original plan."""
    rng = np.random.RandomState(seed)
    tm = rng.rand(nKb, nNb) < 0.5
    plan = plan_from_tile_mask(tm, (bk, bn))
    tp = transpose_plan(plan, tm)
    assert tp.block == (bn, bk) and tp.tiles == (nNb, nKb)
    for j in range(nKb):
        assert set(tp.idx[j, :tp.cnt[j]]) == set(np.nonzero(tm.T[:, j])[0])
    assert tp.cnt.sum() == plan.cnt.sum() == tm.sum()
    assert tp.density == pytest.approx(plan.density)
    back = transpose_plan(tp, tm.T)
    assert back.block == plan.block and back.tiles == plan.tiles
    assert back.max_nnz == plan.max_nnz
    np.testing.assert_array_equal(back.cnt, plan.cnt)
    np.testing.assert_array_equal(back.idx, plan.idx)


@given(kx=st.integers(1, 4), cin=st.integers(1, 5), cout=st.integers(1, 20),
       n_cu=st.integers(1, 8), seed=st.integers(0, 99))
@settings(**SETTINGS)
def test_apply_group_mask_matches_expand_fpga(kx, cin, cout, n_cu, seed):
    """The fused tiled-broadcast masking == materialized expand, including
    ragged remainder f_blocks (n_cu not dividing cout)."""
    spec = fpga_conv_groups((kx, kx, cin, cout), n_cu)
    rng = np.random.RandomState(seed)
    w = jnp.asarray(rng.randn(kx, kx, cin, cout).astype(np.float32))
    gm = jnp.asarray((rng.rand(spec.num_groups) > 0.5).astype(np.float32))
    np.testing.assert_allclose(
        np.asarray(apply_group_mask(spec, w, gm)),
        np.asarray(w * spec.expand(gm)), rtol=1e-6, atol=0)


@given(K=st.integers(1, 300), N=st.integers(1, 300), lead=st.integers(0, 3),
       bk=st.sampled_from([32, 128]), bn=st.sampled_from([32, 128]),
       seed=st.integers(0, 99))
@settings(**SETTINGS)
def test_apply_group_mask_matches_expand_tpu(K, N, lead, bk, bn, seed):
    shape = (lead, K, N) if lead else (K, N)
    spec = tpu_tile_groups(shape, (bk, bn))
    rng = np.random.RandomState(seed)
    w = jnp.asarray(rng.randn(*shape).astype(np.float32))
    gm = jnp.asarray((rng.rand(spec.num_groups) > 0.5).astype(np.float32))
    np.testing.assert_allclose(
        np.asarray(apply_group_mask(spec, w, gm)),
        np.asarray(w * spec.expand(gm)), rtol=1e-6, atol=0)


@given(kx=st.integers(1, 3), cin=st.integers(1, 40), cout=st.integers(1, 40),
       n_cu=st.integers(1, 16), seed=st.integers(0, 99))
@settings(**SETTINGS)
def test_packed_conv_plan_occupancy_exact(kx, cin, cout, n_cu, seed):
    """Packed MXU-shaped layout: per-tile occupancy preserves the paper's
    schedule-step accounting exactly (live groups == occupancy sum) while
    never dispatching more tiles than there are (live) groups."""
    spec = fpga_conv_groups((kx, kx, cin, cout), n_cu)
    rng = np.random.RandomState(seed)
    gm = (rng.rand(spec.num_groups) > 0.5).astype(np.float32)
    packed = conv_gemm_layout(spec)
    live, total = packed.tile_occupancy(gm)
    assert int(live.sum()) == int(gm.sum())
    assert int(total.sum()) == spec.num_groups
    assert (packed.tile_mask(gm) == (live > 0)).all()
    p_plan = packed.plan(gm)
    assert int(p_plan.cnt.sum()) <= int(gm.sum())
    assert np.prod(p_plan.tiles) <= spec.num_groups
    assert packed.k_packed % 8 == 0 and packed.n_packed % 128 == 0


@pytest.mark.parametrize("shape", [
    (7, 7, 3, 64),          # stem
    (1, 1, 64, 256),        # s0 expand / projection
    (1, 1, 256, 64),        # s0 reduce
    (3, 3, 128, 128),       # s1 3x3
    (1, 1, 1024, 2048),     # s3 projection
    (3, 3, 512, 512),       # s3 3x3
])
def test_packed_occupancy_at_resnet50_shapes(shape):
    """ResNet-50 weight shapes, where a 1x1 K-tile holds 128 channels and
    is full, and a layer spans many K- and N-tiles: at n_cu 12 and a
    seeded 50 % group mask, occupancy counts every group once, a tile is
    live iff one of its groups is, and the plan dispatches the live
    tiles."""
    spec = fpga_conv_groups(shape, 12)
    rng = np.random.RandomState(sum(shape))
    gm = (rng.rand(spec.num_groups) < 0.5).astype(np.float32)
    layout = conv_gemm_layout(spec)
    live, total = layout.tile_occupancy(gm)
    assert int(live.sum()) == int(gm.sum())
    assert int(total.sum()) == spec.num_groups
    # a tile is live iff one of the groups it packs is
    cpk = layout.implicit_geometry()["cpk"]
    fpn = layout.block[1] // spec.n_cu
    g = gm.reshape(shape[2], spec.n_fblocks) > 0
    expect = np.zeros(layout.tiles, bool)
    for c, f in zip(*np.nonzero(g)):
        expect[c // cpk, f // fpn] = True
    np.testing.assert_array_equal(layout.tile_mask(gm), expect)
    assert int(layout.plan(gm).cnt.sum()) == int(expect.sum())


@given(seed=st.integers(0, 99))
@settings(**SETTINGS)
def test_apply_masks_idempotent(seed):
    rng = np.random.RandomState(seed)
    p = {"a": jnp.asarray(rng.randn(8, 8).astype(np.float32)), "b": jnp.ones(3)}
    m = {"a": jnp.asarray((rng.rand(8, 8) > 0.3).astype(np.float32)), "b": None}
    once = apply_masks(p, m)
    twice = apply_masks(once, m)
    for k in p:
        np.testing.assert_array_equal(np.asarray(once[k]), np.asarray(twice[k]))
