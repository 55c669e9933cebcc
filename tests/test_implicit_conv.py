"""Implicit-im2col kernel vs the ``conv_via_matmul`` oracle.

The full contract sweep: stride {1,2} × SAME/VALID × f32/bf16 × density
{0, 0.3, 1} × batch {1, 2} on the packed layout, plus the offset-table ↔
im2col-row-mapping round-trip property for ragged shapes, the adaptive
M-blocking invariants, the materializing fallbacks (wide images, VMEM
budget), and the ``out_dtype`` accumulation fix.
"""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fpga_conv_groups, tpu_tile_groups
from repro.kernels import conv_lowering as CL
from repro.kernels import implicit_conv as IC
from repro.models import cnn
from repro.sparse.conv_plan import (adaptive_bm, conv_gemm_layout,
                                    conv_hbm_bytes, conv_m_blocks,
                                    make_sparse_conv)


def _group_mask(rng, n, density):
    if density <= 0.0:
        return np.zeros(n, np.float32)
    if density >= 1.0:
        return np.ones(n, np.float32)
    return (rng.rand(n) < density).astype(np.float32)


# stride {1,2} x SAME/VALID x f32/bf16 x density {0, 0.3, 1} x batch {1,2}
SWEEP = list(itertools.product(
    (1, 2), ("SAME", "VALID"), (jnp.float32, jnp.bfloat16),
    (0.0, 0.3, 1.0), (1, 2)))


@pytest.mark.parametrize("stride,padding,dtype,density,batch", SWEEP)
def test_implicit_conv_parity_sweep(stride, padding, dtype, density, batch):
    """Implicit kernel == conv_via_matmul oracle (f32 accumulation kept via
    out_dtype) over the full contract sweep, packed layout, weight
    prepacked at bind time."""
    kx, cin, cout, n_cu = 3, 9, 10, 4      # ragged: K-tile and f_block tails
    rng = np.random.RandomState(hash((stride, padding, density, batch)) % 2**31)
    spec = fpga_conv_groups((kx, kx, cin, cout), n_cu)
    gm = _group_mask(rng, spec.num_groups, density)
    w = jnp.asarray(rng.randn(kx, kx, cin, cout), dtype)
    wm = w * spec.expand(jnp.asarray(gm)).astype(dtype)
    x = jnp.asarray(rng.randn(batch, 7, 6, cin), dtype)

    conv = make_sparse_conv(conv_gemm_layout(spec, packed=True), gm,
                            weight=w, implicit=True)
    assert conv.implicit and conv.prebound
    out = conv(x, stride=stride, padding=padding)
    expect = CL.conv_via_matmul(x, wm, stride, padding,
                                out_dtype=jnp.float32)
    assert out.shape == expect.shape and out.dtype == dtype
    tol = 1e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect), rtol=tol, atol=tol)
    if density == 0.0:
        assert float(jnp.abs(out.astype(jnp.float32)).max()) == 0.0


def test_implicit_equals_materializing_exactly():
    """Same layout, same plan, same packed weight: the implicit gather and
    the materialized patch matrix feed the MXU identical tiles, so the two
    kernels agree bitwise (not just within tolerance)."""
    rng = np.random.RandomState(0)
    spec = fpga_conv_groups((3, 3, 16, 32), 12)
    gm = _group_mask(rng, spec.num_groups, 0.4)
    w = jnp.asarray(rng.randn(3, 3, 16, 32).astype(np.float32))
    x = jnp.asarray(rng.randn(2, 9, 8, 16).astype(np.float32))
    layout = conv_gemm_layout(spec, packed=True)
    for stride, padding in [(1, "SAME"), (2, "SAME"), (1, "VALID")]:
        outs = {}
        for implicit in (True, False):
            conv = make_sparse_conv(layout, gm, weight=w, implicit=implicit,
                                    bm=128)
            assert conv.implicit == implicit
            outs[implicit] = conv(x, stride=stride, padding=padding)
        np.testing.assert_array_equal(np.asarray(outs[True]),
                                      np.asarray(outs[False]))


# ragged shapes: cin not a multiple of cpk, cout leaving remainder
# f_blocks, 1x1 and 3x3 windows, both fpga layouts
RAGGED = [
    (3, 11, 10, 4, True), (3, 16, 32, 12, True), (1, 20, 9, 4, True),
    (3, 5, 12, 4, False), (1, 7, 9, 4, False),
]


@pytest.mark.parametrize("kx,cin,cout,n_cu,packed", RAGGED)
def test_implicit_index_table_roundtrips_im2col(kx, cin, cout, n_cu, packed):
    """Property: gathering the padded NHWC activation through the
    offset-augmented index table reconstructs exactly the live column
    blocks of the materialized packed im2col matrix — the two kernels'
    shared data contract."""
    rng = np.random.RandomState(kx * 1000 + cin * 10 + n_cu)
    spec = fpga_conv_groups((kx, kx, cin, cout), n_cu)
    layout = conv_gemm_layout(spec, packed=packed)
    gm = _group_mask(rng, spec.num_groups, 0.5)
    entries, cnt, taps = layout.implicit_index_table(gm)
    geo = layout.implicit_geometry()
    plan = layout.plan(gm)
    assert entries.shape == (*plan.idx.shape, 3)
    np.testing.assert_array_equal(cnt, plan.cnt)
    assert taps.shape == (kx * kx, 3)

    stride, padding = 2, "SAME"
    x = rng.randn(2, 7, 6, cin).astype(np.float32)
    # the materialized side of the contract
    patches = CL.im2col_patches(jnp.asarray(x), kx, kx, stride, padding)
    B, Ho, Wo = patches.shape[:3]
    packed_patches = np.asarray(layout.pack_patches(patches))
    # the implicit side: gather via the table from the padded activation
    (pt, pb), (pl_, pr) = (CL.same_pads(7, kx, stride),
                           CL.same_pads(6, kx, stride))
    xp = np.pad(x, ((0, 0), (pt, pb), (pl_, pr), (0, 0)))
    bk = layout.block[0]
    slot, cpk = geo["slot"], geo["cpk"]
    rebuilt = np.zeros_like(packed_patches)
    for j in range(entries.shape[0]):
        for s in range(int(cnt[j])):
            t, c0, cn = entries[j, s]
            for c in range(cn):
                for row_slot, dy, dx in taps:
                    col = t * bk + c * slot + row_slot
                    vals = xp[:, dy:dy + (Ho - 1) * stride + 1:stride,
                              dx:dx + (Wo - 1) * stride + 1:stride, c0 + c]
                    rebuilt[:, col] = vals.reshape(-1)
    # compare live K-tile column blocks (dead tiles are never dispatched)
    live = sorted({int(t) for j in range(entries.shape[0])
                   for t in plan.idx[j, :plan.cnt[j]]})
    for t in live:
        np.testing.assert_array_equal(rebuilt[:, t * bk:(t + 1) * bk],
                                      packed_patches[:, t * bk:(t + 1) * bk],
                                      err_msg=f"K-tile {t}")


def test_implicit_index_table_rejects_tap_major_layouts():
    spec = tpu_tile_groups((3 * 3 * 5, 20), (32, 128))
    layout = conv_gemm_layout(spec)
    with pytest.raises(ValueError, match="channel-major"):
        layout.implicit_index_table(np.ones(spec.num_groups))
    with pytest.raises(ValueError, match="channel-major"):
        make_sparse_conv(layout, np.ones(spec.num_groups), implicit=True)


def test_choose_m_block_invariants():
    """Adaptive M-blocking: bm is the 8-aligned whole-row block under the
    cap, maximal, and the blocks tile the output height."""
    for ho, wo in [(1, 1), (4, 4), (8, 8), (16, 16), (9, 7), (17, 3),
                   (32, 32), (5, 128), (3, 40)]:
        mb = IC.choose_m_block(ho, wo)
        assert mb.spi == 1 and mb.block_ow == wo
        assert mb.bm == -(-mb.block_oh * wo // 8) * 8 and mb.bm <= 128
        assert mb.bpi * mb.block_oh >= ho > (mb.bpi - 1) * mb.block_oh
        if mb.block_oh < ho:       # maximality: one more row would overflow
            assert -(-(mb.block_oh + 1) * wo // 8) * 8 > 128
    # batch-1 tails stop padding to 128
    assert IC.choose_m_block(4, 4).bm == 16
    assert IC.choose_m_block(8, 8).bm == 64
    # wider than the cap: rows split into 8-aligned column segments
    assert IC.choose_m_block(4, 129) == IC.MBlock(1, 128, 2, 128, 8)
    wide = IC.choose_m_block(64, 256)
    assert wide == IC.MBlock(1, 128, 2, 128, 128)
    assert wide.spi * wide.block_ow >= 256
    assert adaptive_bm(16) == 16 and adaptive_bm(3) == 8
    assert adaptive_bm(10_000) == 128
    # the materializing path's accounting blocks on flat B*Ho*Wo rows (the
    # implicit grid is implicit_m_block's: test_images_per_block_rule)
    mb, bm = conv_m_blocks(8, 8, batch=3, bm="auto")
    assert (mb, bm) == (-(-3 * 64 // 128), 128)


# outputs 4x4 and 8x8, (input size, kernel, stride): 3x3 stride 1, 3x3
# stride 2 and 1x1 stride 2 onto each — the layers whose blocks fold
FOLD_GEOMETRIES = [(4, 3, 1), (8, 3, 2), (8, 1, 2),
                   (8, 3, 1), (16, 3, 2), (16, 1, 2)]


@pytest.mark.parametrize("form", ["int8_streamed", "f32"])
@pytest.mark.parametrize("batch", [1, 2, 3, 6, 8, 16])
@pytest.mark.parametrize("h,k,stride", FOLD_GEOMETRIES)
def test_folded_implicit_conv_matches_materializing(h, k, stride, batch,
                                                    form):
    """Whole images folded into one M-block: the implicit kernel still
    equals the materializing path — int8 streamed codes exactly, f32
    within the sweep's tolerance — at folds of 1 up to 8 images."""
    from repro.core import QuantSpec
    rng = np.random.RandomState(h * 1000 + k * 100 + stride * 10 + batch)
    cin, cout, n_cu = 16, 24, 4
    spec = fpga_conv_groups((k, k, cin, cout), n_cu)
    gm = _group_mask(rng, spec.num_groups, 0.6)
    w = jnp.asarray(rng.uniform(-1, 1, (k, k, cin, cout)), jnp.float32)
    x = jnp.asarray(rng.uniform(-2, 2, (batch, h, h, cin)), jnp.float32)
    kw = dict(weight=w, bias=jnp.asarray(rng.uniform(-1, 1, cout),
                                         jnp.float32), relu=True)
    if form == "int8_streamed":
        kw.update(quant=QuantSpec(), out_quant=QuantSpec())
    layout = conv_gemm_layout(spec, packed=True)
    convs = {implicit: make_sparse_conv(layout, gm, implicit=implicit, **kw)
             for implicit in (True, False)}
    outs = {implicit: np.asarray(conv(x, stride=stride))
            for implicit, conv in convs.items()}
    ho = -(-h // stride)
    assert convs[True].m_block(h, h, stride, "SAME", batch).ipb == max(
        d for d in range(1, batch + 1) if batch % d == 0 and d * ho * ho <= 128)
    if form == "int8_streamed":
        assert outs[True].dtype == np.int8
        np.testing.assert_array_equal(outs[True], outs[False])
    else:
        np.testing.assert_allclose(outs[True], outs[False],
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("k,cin,slot,cpk", [
    (1, 64, 1, 128), (1, 256, 1, 128), (1, 2048, 1, 128),
    (3, 512, 16, 8), (7, 3, 56, 2)])
def test_packed_layout_channel_slot(k, cin, slot, cpk):
    """A packed 1x1 K-tile holds 128 channels, one row each, so its tile
    is one 128-lane channel group of the activation; 3x3 and 7x7
    channels keep their 8-aligned slots of 16 and 56 rows."""
    layout = conv_gemm_layout(fpga_conv_groups((k, k, cin, 64), 12),
                              packed=True)
    geo = layout.implicit_geometry()
    assert (geo["slot"], geo["cpk"]) == (slot, cpk)
    assert layout.block[0] == 128 and layout.tiles[0] == -(-cin // cpk)
    assert IC.pointwise(k, k, layout.block[0]) == (k == 1)


# 1x1 convs on the pointwise gather: (input size, stride, cin, batch).
# Outputs 7x7 (one image a block), 14x14 (two blocks an image) and 4x4
# (8 images folded into a block); cin 64 is one part-filled K-tile
POINTWISE = [(7, 1, 2048, 2), (14, 2, 256, 2), (14, 1, 64, 1),
             (28, 2, 256, 1), (4, 1, 256, 8), (8, 2, 64, 8)]


def _pointwise_mask(rng, spec, layout):
    """Groups live at random, with K-tile 1 pruned whole and K-tile 0
    pruned in N-tile 1, so the table skips both where they exist."""
    cin, n_fb = spec.shape[2], spec.n_fblocks
    g = (rng.rand(cin, n_fb) < 0.6).astype(np.float32)
    fpn = layout.block[1] // spec.n_cu
    g[128:256] = 0.0
    g[:128, fpn:2 * fpn] = 0.0
    return g.reshape(-1)


@pytest.mark.parametrize("h,stride,cin,batch", POINTWISE)
def test_pointwise_conv_matches_materializing(h, stride, cin, batch):
    """The 1x1 kernel, whose patch tile is the window load itself, equals
    the materializing oracle code for code in the streamed int8 form, at
    stride 1 and 2, unfolded, split and folded M-blocks; with the
    activation skip on, a K-tile whose window is all zero codes skips
    and the codes still match."""
    from repro.core import QuantSpec
    rng = np.random.RandomState(h * 10_000 + cin * 10 + stride + batch)
    cout, n_cu = 160, 12                    # two N-tiles of 10 f_blocks
    spec = fpga_conv_groups((1, 1, cin, cout), n_cu)
    layout = conv_gemm_layout(spec, packed=True)
    gm = _pointwise_mask(rng, spec, layout)
    plan = layout.plan(gm)
    assert plan.cnt[1] < plan.cnt[0] <= layout.tiles[0]
    w = jnp.asarray(rng.uniform(-1, 1, (1, 1, cin, cout)), jnp.float32)
    x = rng.uniform(-2, 2, (batch, h, h, cin)).astype(np.float32)
    x[..., :min(cin, 128) // 2] = 0.0       # half of K-tile 0's channels
    kw = dict(weight=w, quant=QuantSpec(), out_quant=QuantSpec(), relu=True,
              bias=jnp.asarray(rng.uniform(-1, 1, cout), jnp.float32))
    convs = [make_sparse_conv(layout, gm, implicit=i, **kw)
             for i in (True, False)]
    outs = [np.asarray(c(jnp.asarray(x), stride=stride)) for c in convs]
    assert outs[0].dtype == np.int8 and np.ptp(outs[0]) > 0
    np.testing.assert_array_equal(outs[0], outs[1])
    ho = -(-h // stride)
    mb = convs[0].m_block(h, h, stride, "SAME", batch)
    assert (mb.bpi, mb.ipb) == {7: (1, 1), 14: (2, 1), 4: (1, 8)}[ho]

    dsb = make_sparse_conv(layout, gm, implicit=True, activation_dsb=True,
                           **kw)
    if cin > 128:
        x[..., :128] = 0.0                  # K-tile 0's window all zero
    y, stats = dsb.skip_counts(jnp.asarray(x), stride=stride)
    np.testing.assert_array_equal(np.asarray(y),
                                  np.asarray(convs[1](jnp.asarray(x),
                                                      stride=stride)))
    assert (stats["skipped_steps"] > 0) == (cin > 128)


def test_images_per_block_rule():
    """The fold: the largest divisor of the batch whose images fit the
    128-row cap, only where one image's pixels fill one block, never
    under activation_dsb."""
    assert IC.choose_m_block(8, 8, batch=3).ipb == 1
    assert IC.choose_m_block(8, 8, batch=32).ipb == 2
    assert IC.choose_m_block(4, 4, batch=6).ipb == 6
    assert IC.choose_m_block(4, 4, batch=8).ipb == 8
    assert IC.choose_m_block(4, 4, batch=128).ipb == 8
    assert IC.choose_m_block(4, 4, batch=16, cap=64).ipb == 4
    assert IC.choose_m_block(16, 16, batch=8).ipb == 1     # bpi 2
    assert IC.choose_m_block(32, 32, batch=8).ipb == 1     # bpi 8
    # 49 and 4 pixels are short of their 56- and 8-row blocks
    assert IC.choose_m_block(7, 7, batch=2).ipb == 1
    assert IC.choose_m_block(2, 2, batch=8).ipb == 1
    assert IC.choose_m_block(4, 4, batch=8).m_rows == 128
    # the grid the accounting prices follows the fold
    from repro.core import QuantSpec
    from repro.sparse.conv_plan import implicit_m_block
    spec = fpga_conv_groups((3, 3, 16, 16), 4)
    gm = np.ones(spec.num_groups, np.float32)
    w = jnp.ones((3, 3, 16, 16), jnp.float32)
    layout = conv_gemm_layout(spec, packed=True)
    assert implicit_m_block(layout, 4, 4, 1, "SAME", 1, batch=8).ipb == 8
    assert implicit_m_block(layout, 4, 4, 1, "SAME", 1, batch=8,
                            activation_dsb=True).ipb == 1
    kw = dict(weight=w, quant=QuantSpec(), implicit=True)
    assert make_sparse_conv(layout, gm, **kw).m_block(4, 4, 1, "SAME",
                                                      8).ipb == 8
    dsb = make_sparse_conv(layout, gm, activation_dsb=True, **kw)
    assert dsb.m_block(4, 4, 1, "SAME", 8).ipb == 1
    x = jnp.asarray(np.random.RandomState(0).randint(0, 3, (8, 4, 4, 16)),
                    jnp.int8)
    _, stats = dsb.skip_counts(x)
    assert stats["live_steps"] == 8 * int(dsb.plan.cnt.sum())


def test_fold_shrinks_to_the_vmem_budget(monkeypatch):
    """A fold whose windows exceed the VMEM budget is reduced to the
    largest that fits before the layer leaves the implicit path."""
    from repro.sparse.conv_plan import implicit_m_block
    spec = fpga_conv_groups((3, 3, 16, 16), 4)
    layout = conv_gemm_layout(spec, packed=True)
    full = implicit_m_block(layout, 4, 4, 1, "SAME", 1, batch=8)
    assert full.ipb == 8
    need = {d: IC.window_vmem_bytes(full._replace(ipb=d), 3, 3, 1, 128, 1)
            for d in (1, 2, 4, 8)}
    monkeypatch.setattr(IC, "SLAB_VMEM_BUDGET", need[4])
    assert implicit_m_block(layout, 4, 4, 1, "SAME", 1, batch=8).ipb == 4
    monkeypatch.setattr(IC, "SLAB_VMEM_BUDGET", need[1])
    assert implicit_m_block(layout, 4, 4, 1, "SAME", 1, batch=8).ipb == 1
    monkeypatch.setattr(IC, "SLAB_VMEM_BUDGET", need[1] - 1)
    assert implicit_m_block(layout, 4, 4, 1, "SAME", 1, batch=8) is None


def test_implicit_falls_back_to_materializing(monkeypatch):
    """Over-budget window slabs fall back to the materializing path —
    same closure, same result — while 130-wide rows now *stay* implicit
    via column segmentation."""
    rng = np.random.RandomState(5)
    spec = fpga_conv_groups((1, 1, 4, 8), 4)
    gm = _group_mask(rng, spec.num_groups, 0.5)
    w = jnp.asarray(rng.randn(1, 1, 4, 8).astype(np.float32))
    wm = w * spec.expand(jnp.asarray(gm))
    conv = make_sparse_conv(conv_gemm_layout(spec, packed=True), gm, weight=w,
                            implicit=True)
    # 130-wide rows: segmented M-blocks keep the implicit path
    x = jnp.asarray(rng.randn(1, 2, 130, 4).astype(np.float32))
    out = conv(x, stride=1, padding="SAME")
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(CL.conv_via_matmul(x, wm)),
        rtol=1e-5, atol=1e-5)
    # slab over the VMEM budget: materializing fallback, still exact
    x2 = jnp.asarray(rng.randn(1, 6, 5, 4).astype(np.float32))
    expect = CL.conv_via_matmul(x2, wm)
    monkeypatch.setattr(IC, "SLAB_VMEM_BUDGET", 16)
    out2 = conv(x2, stride=1, padding="SAME")
    np.testing.assert_allclose(np.asarray(out2), np.asarray(expect),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("stride,padding", [(1, "SAME"), (2, "VALID")])
def test_wide_input_keeps_implicit_path(stride, padding):
    """ROADMAP coverage gap (b): a 1×64×256×8 input — one output row is
    wider than the 128-column cap — runs the implicit kernel on column
    segments and matches the materializing oracle."""
    rng = np.random.RandomState(11)
    spec = fpga_conv_groups((3, 3, 8, 8), 4)
    gm = _group_mask(rng, spec.num_groups, 0.5)
    w = jnp.asarray(rng.randn(3, 3, 8, 8).astype(np.float32))
    wm = w * spec.expand(jnp.asarray(gm))
    x = jnp.asarray(rng.randn(1, 64, 256, 8).astype(np.float32))
    layout = conv_gemm_layout(spec, packed=True)
    ho = CL.conv_out_size(64, 3, stride, padding)
    wo = CL.conv_out_size(256, 3, stride, padding)
    mb = IC.choose_m_block(ho, wo)
    if -(-wo // 8) * 8 > 128:
        assert mb is not None and mb.spi > 1    # segmented, not fallback
    conv = make_sparse_conv(layout, gm, weight=w, implicit=True)
    out = conv(x, stride=stride, padding=padding)
    np.testing.assert_allclose(
        np.asarray(out),
        np.asarray(CL.conv_via_matmul(x, wm, stride, padding,
                                      out_dtype=jnp.float32)),
        rtol=1e-4, atol=1e-4)


def test_conv_via_matmul_out_dtype_keeps_f32_accumulation():
    """The default oracle used to downcast through astype(a.dtype); bf16
    callers (e.g. folded-BN comparisons) can now keep the accumulator."""
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(1, 6, 6, 8), jnp.bfloat16)
    w = jnp.asarray(rng.randn(3, 3, 8, 8), jnp.bfloat16)
    out_bf16 = CL.conv_via_matmul(x, w)
    out_f32 = CL.conv_via_matmul(x, w, out_dtype=jnp.float32)
    assert out_bf16.dtype == jnp.bfloat16 and out_f32.dtype == jnp.float32
    # the f32 output carries strictly more precision than its downcast
    np.testing.assert_array_equal(np.asarray(out_f32.astype(jnp.bfloat16)),
                                  np.asarray(out_bf16))
    assert float(jnp.max(jnp.abs(out_f32 - out_f32.astype(jnp.bfloat16)
                                 .astype(jnp.float32)))) > 0.0


def _pruned_tiny_resnet(target=0.5, n_cu=4):
    cfg = cnn.ResNetConfig(stages=(1, 1), widths=(8, 16), image_size=16)
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


def test_implicit_exec_end_to_end_matches_materializing():
    """build_sparse_execution(implicit=True) == implicit=False == dense on
    a HAPM-pruned net, with identical schedule accounting, every layer on
    the requested route, and each exec's analytic HBM bytes priced on
    that route (kernel layers bound on both paths)."""
    n_cu = 4
    cfg, pruned, state, specs, st = _pruned_tiny_resnet(0.5, n_cu)
    x = jax.random.uniform(jax.random.PRNGKey(1), (2, 16, 16, 3))
    dense, _ = cnn.apply(pruned, state, x, cfg)
    execs = {}
    for implicit in (True, False):
        e = cnn.build_sparse_execution(
            pruned, n_cu=n_cu, specs=specs, group_masks=st.group_masks,
            packed=True, implicit=implicit, dense_fallback=2.0)
        out, _ = cnn.apply(pruned, state, x, cfg, sparse=e)
        np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                                   rtol=1e-4, atol=1e-4)
        execs[implicit] = e
    assert execs[True].implicit and not execs[False].implicit
    assert (execs[True].schedule_step_counts()
            == execs[False].schedule_step_counts())
    n_convs = len(cnn.conv_layer_order(cfg))
    assert execs[True].report(cfg)["layers_implicit"] == n_convs
    assert execs[False].report(cfg)["layers_materializing"] == n_convs
    assert (execs[True].hbm_bytes(cfg, batch=1)
            == execs[False].hbm_bytes(cfg, batch=1, implicit=True)
            != execs[False].hbm_bytes(cfg, batch=1))
    # adaptive bm engages on the 8x8 tail layers
    bms = execs[True].bm_effective(cfg, batch=1)
    assert bms["s1b0/conv2/w"] == 64 and bms["conv0/w"] == 128
    # M-padding-aware utilization: adaptive recovers the batch-1 tail
    assert (execs[True].mac_utilization(cfg, batch=1)
            > execs[False].mac_utilization(cfg, batch=1, bm=128))


def test_conv_hbm_bytes_contract():
    """The analytic byte counts encode the contract change, term by term
    as the chip moves the data: the implicit path never pays the
    patch-matrix write but DMAs whole 128-lane windows from a padded
    copy of the activation; the materializing path writes and re-reads
    the patch matrix."""
    spec = fpga_conv_groups((3, 3, 16, 32), 12)
    layout = conv_gemm_layout(spec, packed=True)
    gm = np.ones(spec.num_groups, np.float32)
    imp = conv_hbm_bytes(layout, gm, 1, 16, 16, implicit=True)
    mat = conv_hbm_bytes(layout, gm, 1, 16, 16, implicit=False, bm=128)
    f = 4
    steps = 2 * 2                 # 2 M-blocks x 2 live K-tiles (cpk 8)
    x_read = 16 * 16 * 16 * f
    out_write = 2 * 128 * 128 * f
    # implicit: the padded copy is 18 rows x 24 columns (whole 8-row
    # tiles for the DMA) x 128 lanes; a live step moves one 10 x 24
    # window of the 128-lane group and one 128x128 weight tile
    ingest = x_read + 18 * 24 * 128 * f
    assert imp == (ingest + steps * (10 * 24 * 128 * f + 128 * 128 * f)
                   + out_write)
    # materializing: the (256, 256) patch matrix is written once, and a
    # live step reads one 128x128 patch tile and one weight tile
    assert mat == (x_read + 256 * 256 * f + steps * 2 * 128 * 128 * f
                   + out_write)
    # pruning everything leaves the ingest and the output write
    gm0 = np.zeros(spec.num_groups, np.float32)
    assert conv_hbm_bytes(layout, gm0, 1, 16, 16,
                          implicit=True) == ingest + out_write
