"""Native Q2.5×Q3.4 int8 execution through the block-sparse conv stack.

The quantized parity sweep: stride × padding × density {0, .3, 1} × batch,
implicit vs materializing vs the dense-int8 oracle — *exact code equality*
everywhere accumulation is int32 (the arithmetic is integer, and the
static power-of-two dequant scales make the f32 epilogue exact), plus
≤ quant-tolerance agreement with the unquantized f32 reference. Overflow
edges (all-±127 operands), fully-pruned-column dequant→bias flush, the
end-to-end ``build_sparse_execution(quantized=True)`` == QAT-forward
bit-parity, the calibrated folded-BN inference path, and the int8 HBM
operand pricing.
"""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (HAPMConfig, Q2_5, Q3_4, QuantSpec, apply_masks,
                        fpga_conv_groups, hapm_element_masks,
                        hapm_epoch_update, hapm_init, quantize, to_int8)
from repro.kernels import ref
from repro.models import cnn
from repro.sparse.conv_plan import conv_gemm_layout, conv_hbm_bytes, make_sparse_conv


def _group_mask(rng, n, density):
    if density <= 0.0:
        return np.zeros(n, np.float32)
    if density >= 1.0:
        return np.ones(n, np.float32)
    return (rng.rand(n) < density).astype(np.float32)


def _oracle_f32(x, w, stride, padding):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


# stride {1,2} x SAME/VALID x density {0, .3, 1} x batch {1, 2};
# ragged cin (K-tile tails) and cout (remainder f_blocks)
SWEEP = list(itertools.product((1, 2), ("SAME", "VALID"),
                               (0.0, 0.3, 1.0), (1, 2)))


@pytest.mark.parametrize("stride,padding,density,batch", SWEEP)
def test_quantized_parity_sweep(stride, padding, density, batch):
    """Implicit == materializing == dense-int8 oracle, bitwise; and all
    three within quantization tolerance of the f32 conv."""
    kx, cin, cout, n_cu = 3, 9, 10, 4
    # deterministic seed (str hash is salted per process)
    seed = stride * 10000 + (padding == "SAME") * 1000 + int(density * 10) * 10 + batch
    rng = np.random.RandomState(seed)
    spec = fpga_conv_groups((kx, kx, cin, cout), n_cu)
    gm = _group_mask(rng, spec.num_groups, density)
    w = jnp.asarray(rng.uniform(-2, 2, (kx, kx, cin, cout)).astype(np.float32))
    x = jnp.asarray(rng.uniform(-4, 4, (batch, 7, 6, cin)).astype(np.float32))
    wm = w * spec.expand(jnp.asarray(gm))
    qspec = QuantSpec()
    layout = conv_gemm_layout(spec, packed=True)

    outs = {}
    for implicit in (True, False):
        conv = make_sparse_conv(layout, gm, weight=w, implicit=implicit,
                                quant=qspec)
        assert conv.implicit == implicit and conv.quant is qspec
        outs[implicit] = conv(x, stride=stride, padding=padding)
        assert outs[implicit].dtype == jnp.float32

    # the integer oracle: im2col codes, int32 acc, per-cout dequant row
    expect = ref.int8_conv_ref(qspec.act_codes(x), qspec.weight_codes(wm),
                               np.asarray(qspec.dequant_row(cout)),
                               stride, padding)
    for implicit, out in outs.items():
        np.testing.assert_array_equal(np.asarray(out), np.asarray(expect),
                                      err_msg=f"implicit={implicit}")

    # quant tolerance vs the f32 conv over the same (masked) weights:
    # |err| <= K/2 * (x_lsb*|w| + w_lsb*|x| + lsb cross terms) — generous
    f32 = _oracle_f32(x, wm, stride, padding)
    K = kx * kx * cin
    bound = 0.5 * K * (4.0 / Q3_4.scale + 4.0 / Q2_5.scale + 1.0)
    assert float(jnp.max(jnp.abs(expect - f32))) <= bound
    if density == 0.0:
        assert float(jnp.abs(outs[True]).max()) == 0.0


def test_overflow_edge_all_saturated_codes():
    """All-±127 operands: the int32 accumulator holds K·127² without
    wrapping, and the kernels match the integer oracle exactly."""
    kx, cin, cout, n_cu = 3, 64, 16, 4        # K = 576 -> acc <= 9.3e6
    spec = fpga_conv_groups((kx, kx, cin, cout), n_cu)
    gm = np.ones(spec.num_groups, np.float32)
    qspec = QuantSpec()
    # +max on even couts, -max on odd; activations pinned at +max
    sign = np.where(np.arange(cout) % 2 == 0, 1.0, -1.0)
    w = jnp.asarray(np.broadcast_to(sign * Q2_5.max_val,
                                    (kx, kx, cin, cout)).astype(np.float32))
    x = jnp.full((1, 6, 6, cin), Q3_4.max_val, jnp.float32)
    assert int(jnp.abs(qspec.weight_codes(w)).min()) == 127
    assert int(jnp.abs(qspec.act_codes(x)).min()) == 127
    layout = conv_gemm_layout(spec, packed=True)
    expect = ref.int8_conv_ref(qspec.act_codes(x), qspec.weight_codes(w),
                               np.asarray(qspec.dequant_row(cout)), 1, "SAME")
    assert float(jnp.abs(expect).max()) >= 576 * 127 * 127 / 512 * 0.4
    for implicit in (True, False):
        conv = make_sparse_conv(layout, gm, weight=w, implicit=implicit,
                                quant=qspec)
        np.testing.assert_array_equal(
            np.asarray(conv(x, stride=1, padding="SAME")), np.asarray(expect))


def test_fully_pruned_column_dequant_bias_flush():
    """A fully-pruned f_block still flushes dequant(0) + bias (then ReLU):
    the quantized epilogue matches conv(x, 0) + b exactly."""
    rng = np.random.RandomState(3)
    spec = fpga_conv_groups((3, 3, 16, 32), 12)
    gm = _group_mask(rng, spec.num_groups, 0.4)
    gm.reshape(16, spec.n_fblocks)[:, -1] = 0.0       # kill a whole f_block
    w = jnp.asarray(rng.randn(3, 3, 16, 32).astype(np.float32))
    b = jnp.asarray(rng.randn(32).astype(np.float32))
    x = jnp.asarray(rng.uniform(-4, 4, (2, 9, 8, 16)).astype(np.float32))
    qspec = QuantSpec()
    wm = w * spec.expand(jnp.asarray(gm))
    expect = ref.int8_conv_ref(qspec.act_codes(x), qspec.weight_codes(wm),
                               np.asarray(qspec.dequant_row(32)), 1, "SAME",
                               bias=b, relu=True)
    for layout in (conv_gemm_layout(spec, packed=True), conv_gemm_layout(spec)):
        for implicit in (True, False):
            conv = make_sparse_conv(layout, gm, weight=w, bias=b, relu=True,
                                    implicit=implicit, quant=qspec)
            out = conv(x, stride=1, padding="SAME")
            np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))
    # the dead f_block's lanes are exactly relu(bias)
    dead = np.asarray(expect[..., 24:])               # last f_block (n_cu=12)
    np.testing.assert_array_equal(
        dead, np.broadcast_to(np.maximum(np.asarray(b[24:]), 0), dead.shape))


def test_quantized_exec_matches_qat_forward_exactly():
    """build_sparse_execution(quantized=True): int8 kernels on both paths
    reproduce the dense QAT (fake-quant) forward bit-for-bit, with
    schedule accounting identical to the f32 exec and <= 0.5x the
    f32-operand HBM bytes."""
    n_cu = 4
    cfg = cnn.ResNetConfig(stages=(1, 1), widths=(8, 16), image_size=16,
                           quantized=True)
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
    qat, _ = cnn.apply(pruned, state, x, cfg)

    common = dict(n_cu=n_cu, specs=specs, group_masks=st.group_masks,
                  packed=True, quantized=True, dense_fallback=2.0)
    execs = {imp: cnn.build_sparse_execution(pruned, implicit=imp, **common)
             for imp in (True, False)}
    for imp, e in execs.items():
        out, _ = cnn.apply(pruned, state, x, cfg, sparse=e)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(qat),
                                      err_msg=f"implicit={imp}")
        # every bound conv really is the int8 path
        assert all(fn.quant is not None for fn in e.table.values()
                   if fn is not None)
    # the jitted graph agrees too (codes are traced, plans are constants)
    jout = jax.jit(lambda p, xx: cnn.apply(p, state, xx, cfg,
                                           sparse=execs[True])[0])(pruned, x)
    np.testing.assert_array_equal(np.asarray(jout), np.asarray(qat))

    f32_exec = cnn.build_sparse_execution(
        pruned, n_cu=n_cu, specs=specs, group_masks=st.group_masks,
        packed=True, implicit=True, dense_fallback=2.0)
    assert (execs[True].schedule_step_counts()
            == f32_exec.schedule_step_counts())
    assert (execs[True].step_counts(cfg, batch=1)
            == f32_exec.step_counts(cfg, batch=1))
    # operand bytes: the quantized exec prices int8 slabs/tiles
    q = execs[True].hbm_bytes(cfg, batch=1)
    f = f32_exec.hbm_bytes(cfg, batch=1)
    assert q == execs[True].hbm_bytes(cfg, batch=1, operand_bytes=1)
    assert q < f and execs[True].hbm_bytes(cfg, batch=1, operand_bytes=4) == f

    # a quantized exec refuses an unquantized cfg (and vice versa)
    ucfg = cnn.ResNetConfig(stages=(1, 1), widths=(8, 16), image_size=16)
    with pytest.raises(ValueError, match="quantized"):
        cnn.apply(pruned, state, x, ucfg, sparse=execs[True])
    with pytest.raises(ValueError, match="quant_spec"):
        cnn.build_sparse_execution(pruned, n_cu=n_cu,
                                   quant_spec=QuantSpec())


def test_calibrated_quant_spec_sees_raw_weights():
    """Regression: build_sparse_execution(quant_spec=calibrated) must emit
    codes from the RAW weights — pre-quantizing onto the static Q2.5 grid
    first would clip a wide-range channel to ±4 and then rescale it ~25x
    too small (double quantization)."""
    rng = np.random.RandomState(7)
    w = rng.randn(3, 3, 8, 8).astype(np.float32)
    w[..., 0] *= 50.0                    # far outside the Q2.5 range
    w = jnp.asarray(w)
    cal = QuantSpec.calibrate(w)
    x = jnp.asarray(rng.uniform(-4, 4, (1, 8, 8, 8)).astype(np.float32))
    exec_ = cnn.build_sparse_execution({"c": {"w": w}}, n_cu=4,
                                       quantized=True, quant_spec=cal,
                                       dense_fallback=2.0)
    conv = exec_.table[("c", "w")]
    out = conv(x, stride=1, padding="SAME")
    expect = ref.int8_conv_ref(cal.act_codes(x), cal.weight_codes(w),
                               np.asarray(cal.dequant_row(8)), 1, "SAME")
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))
    # the wide channel keeps its magnitude (vs f32 conv, act-quant noise)
    f32 = _oracle_f32(quantize(x, Q3_4), w, 1, "SAME")
    big = np.abs(np.asarray(f32[..., 0]))
    err0 = np.abs(np.asarray(out[..., 0] - f32[..., 0]))
    assert err0.max() <= 0.05 * max(big.max(), 1.0) + 3 * 9 * 8 * (50 / 127)


def test_quantized_folded_inference_calibrated():
    """fold_batchnorm -> build_sparse_inference(quantized=True): per-cout
    calibrated weight scales absorb the BN folding, the fused
    dequant→bias→ReLU epilogue runs in-kernel, and logits stay within
    activation-quantization tolerance of the float folded path."""
    n_cu = 4
    cfg = cnn.ResNetConfig(stages=(1, 1), widths=(8, 16), image_size=16)
    params, state = cnn.init(jax.random.PRNGKey(0), cfg)
    params = jax.tree_util.tree_map_with_path(
        lambda p, l: l / jnp.std(l) * 0.1 if cnn.is_conv_weight(p, l) else l,
        params)
    specs = cnn.conv_group_specs(params, n_cu)
    hcfg = HAPMConfig(0.5, 1)
    st = hapm_init(specs, hcfg)
    st = hapm_epoch_update(st, specs, params, hcfg)
    pruned = apply_masks(params, hapm_element_masks(specs, st))
    x = jax.random.uniform(jax.random.PRNGKey(3), (2, 16, 16, 3))
    folded = cnn.fold_batchnorm(pruned, state, cfg)
    plain = cnn.apply_folded(folded, x, cfg)
    for implicit in (True, False):
        inf = cnn.build_sparse_inference(folded, cfg, n_cu=n_cu,
                                         group_masks=st.group_masks,
                                         quantized=True, implicit=implicit)
        assert inf.quantized and inf.folded
        out = cnn.apply_folded(folded, x, cfg, sparse=inf)
        # activations quantize to Q3.4 (1/16 LSB) per layer: tolerance is
        # dominated by that, weights carry ~7 calibrated bits per cout
        np.testing.assert_allclose(np.asarray(out), np.asarray(plain),
                                   atol=0.35, rtol=0.0)


def test_conv_hbm_bytes_int8_operand_pricing():
    """operand_bytes=1 shrinks exactly the operand terms: activation
    ingest, window slabs, patch matrix, patch reads and weight tiles —
    never the f32 output write."""
    spec = fpga_conv_groups((3, 3, 16, 32), 12)
    layout = conv_gemm_layout(spec, packed=True)
    gm = np.ones(spec.num_groups, np.float32)
    for implicit in (True, False):
        f32 = conv_hbm_bytes(layout, gm, 1, 16, 16, implicit=implicit, bm=128)
        q = conv_hbm_bytes(layout, gm, 1, 16, 16, implicit=implicit, bm=128,
                           operand_bytes=1)
        out_only = conv_hbm_bytes(layout, np.zeros_like(gm), 1, 16, 16,
                                  implicit=implicit, bm=128)
        out_only_q = conv_hbm_bytes(layout, np.zeros_like(gm), 1, 16, 16,
                                    implicit=implicit, bm=128, operand_bytes=1)
        if implicit:
            # zero density: the activation ingest (a quarter as many bytes
            # as int8 codes) plus the f32 output write of 2 M-blocks
            ingest_q = (out_only - out_only_q) // 3
            assert out_only_q - ingest_q == 2 * 128 * 128 * 4
            # int8 operands are exactly a quarter of the f32 operand bytes
            assert (q - out_only_q) * 4 == f32 - out_only
        else:
            # materializing zero-density still reads x and writes patches
            assert q < f32
        assert q * 2 <= f32                           # >= 2x total reduction
