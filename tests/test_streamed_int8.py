"""End-to-end int8 activation streaming: the requantizing epilogue.

The shared ``flush_epilogue`` gains an ``out_scale`` row that requantizes
the dequant → bias → ReLU flush back to int8 Q3.4 codes inside the
kernel, so chained layers exchange 1-byte codes through HBM instead of
f32. Covered here:

- the epilogue in isolation, on both kernels, across the code-domain
  edges: all-±127 accumulators (the largest representable products),
  fully-pruned columns flushing bias-only, ReLU-clamped negatives, and
  negative codes on no-ReLU layers — emitted codes must equal
  ``round_sat((dequant(acc) + bias)[relu] · out_scale)`` per lane;
- the ``ExecSpec`` contract table: every invalid field pair raises ONE
  coherent error naming the offending fields (and stacked violations all
  appear in the same message);
- the conv-plan binding: ``out_quant`` requires ``quant``, int8 inputs
  skip the per-call quantize (the streamed ingest), implicit ==
  materializing bitwise;
- the whole-model wire: ``apply_folded`` on a streamed exec is
  bit-exact vs the SAME per-layer-quantized kernels with host-side
  requantization at identical program points (``wire_quantize=True`` on
  the non-streamed quantized folded exec), and the streamed HBM
  contract prices every byte term at 1/4 of the f32 implicit figure.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Q3_4, QuantSpec, round_sat, fpga_conv_groups
from repro.kernels import ref
from repro.kernels.block_sparse_matmul import block_sparse_matmul
from repro.models import cnn
from repro.sparse.conv_plan import conv_gemm_layout, make_sparse_conv

WIRE = float(Q3_4.scale)            # 16.0 — the uniform Q3.4 wire scale
MAX_CODE = float(Q3_4.max_code)     # 127


def _epilogue_ref(acc, scale, bias, relu, out_scale):
    """Host twin of flush_epilogue + int8 cast, in the kernel's f32
    arithmetic order (bitwise-comparable on CPU interpret mode)."""
    out = acc.astype(jnp.float32) * scale[None, :]
    out = out + bias[None, :]
    if relu:
        out = jnp.maximum(out, 0.0)
    return round_sat(out * out_scale[None, :], MAX_CODE).astype(jnp.int8)


# --- the epilogue in isolation: block_sparse_matmul ----------------------

# (x-code fill, w-code fill, bias mode, relu) — the code-domain edges:
# all-max-magnitude accumulators in every sign combination, zero
# accumulators with pure-bias flushes, and signs that force the ReLU
# clamp / negative output codes
EDGE_CASES = [
    (127, 127, "zero", False),      # max positive acc
    (127, -127, "zero", False),     # max negative acc -> negative codes
    (-127, -127, "pos", True),      # max positive acc + bias
    (127, -127, "pos", True),       # ReLU clamps the negative acc to 0
    (0, 127, "neg", False),         # zero acc, bias-only negative codes
    (0, 0, "pos", True),            # zero acc, bias-only positive
]


@pytest.mark.parametrize("xv,wv,bias_mode,relu", EDGE_CASES)
def test_matmul_requantize_edges(xv, wv, bias_mode, relu):
    M, K, N, bm = 8, 128, 256, 8
    x = jnp.full((M, K), xv, jnp.int8)
    w = jnp.full((K, N), wv, jnp.int8)
    # column block 0 live, column block 1 fully pruned (bias-only flush)
    idx = jnp.asarray([[0], [0]], jnp.int32)
    cnt = jnp.asarray([1, 0], jnp.int32)
    scale = jnp.full((N,), 1e-4, jnp.float32)   # keeps dequant in Q3.4 range
    bias = {"zero": jnp.zeros((N,), jnp.float32),
            "pos": jnp.full((N,), 1.53125, jnp.float32),
            "neg": jnp.full((N,), -2.0625, jnp.float32)}[bias_mode]
    out_scale = jnp.full((N,), WIRE, jnp.float32)

    got = block_sparse_matmul(x, w, idx, cnt, bias, scale, out_scale,
                              bm=bm, relu=relu, interpret=True)
    assert got.dtype == jnp.int8
    acc = jnp.concatenate([(x.astype(jnp.int32) @ w.astype(jnp.int32))[:, :128],
                           jnp.zeros((M, 128), jnp.int32)], axis=1)
    want = _epilogue_ref(acc, scale, bias, relu, out_scale)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_matmul_requantize_random_codes():
    """Dense random sweep over the code domain: per-lane equality with
    the host epilogue, saturation included (large dequant scale forces
    |codes| past 127)."""
    rng = np.random.RandomState(0)
    M, K, N, bm = 16, 128, 128, 16
    x = jnp.asarray(rng.randint(-127, 128, (M, K)), jnp.int8)
    w = jnp.asarray(rng.randint(-127, 128, (K, N)), jnp.int8)
    idx = jnp.zeros((1, 1), jnp.int32)
    cnt = jnp.ones((1,), jnp.int32)
    for relu in (False, True):
        scale = jnp.asarray(rng.uniform(1e-5, 2e-3, N), jnp.float32)
        bias = jnp.asarray(rng.uniform(-4, 4, N), jnp.float32)
        out_scale = jnp.full((N,), WIRE, jnp.float32)
        got = block_sparse_matmul(x, w, idx, cnt, bias, scale, out_scale,
                                  bm=bm, relu=relu, interpret=True)
        acc = x.astype(jnp.int32) @ w.astype(jnp.int32)
        want = _epilogue_ref(acc, scale, bias, relu, out_scale)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        assert (np.abs(np.asarray(got, np.int32)) <= 127).all()


def test_matmul_out_scale_requires_int8_codes():
    x = jnp.zeros((8, 128), jnp.float32)
    w = jnp.zeros((128, 128), jnp.float32)
    idx = jnp.zeros((1, 1), jnp.int32)
    cnt = jnp.ones((1,), jnp.int32)
    with pytest.raises(AssertionError, match="int8-code contract"):
        block_sparse_matmul(x, w, idx, cnt, None, None,
                            jnp.full((128,), WIRE, jnp.float32),
                            bm=8, interpret=True)


# --- the epilogue through the conv binding (both kernels) ----------------

def _conv_fixture(rng, density=0.4, kx=3, cin=9, cout=10, n_cu=4):
    spec = fpga_conv_groups((kx, kx, cin, cout), n_cu)
    gm = (rng.rand(spec.num_groups) < density).astype(np.float32)
    w = jnp.asarray(rng.uniform(-2, 2, (kx, kx, cin, cout)), jnp.float32)
    layout = conv_gemm_layout(spec)
    return spec, gm, w, layout


@pytest.mark.parametrize("implicit", (True, False))
@pytest.mark.parametrize("relu", (False, True))
def test_conv_requantize_matches_host_epilogue(implicit, relu):
    """Both kernels' in-epilogue requantize == the f32-emitting kernel +
    host-side round_sat, bitwise — including fully-pruned cout columns
    (bias-only codes) and ReLU-clamped lanes."""
    rng = np.random.RandomState(7 + implicit * 2 + relu)
    spec, gm, w, layout = _conv_fixture(rng)
    wm = w * spec.expand(jnp.asarray(gm))
    bias = jnp.asarray(rng.uniform(-1, 1, w.shape[-1]), jnp.float32)
    qspec = QuantSpec()
    x = jnp.asarray(rng.uniform(-4, 4, (2, 7, 6, w.shape[2])), jnp.float32)

    kw = dict(weight=w, bias=bias, relu=relu, implicit=implicit, quant=qspec)
    conv_s = make_sparse_conv(layout, gm, out_quant=QuantSpec(), **kw)
    conv_f = make_sparse_conv(layout, gm, **kw)
    assert conv_s.out_quant is not None and conv_f.out_quant is None

    got = conv_s(x)
    assert got.dtype == jnp.int8
    want = round_sat(conv_f(x) * WIRE, MAX_CODE).astype(jnp.int8)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    # and vs the integer oracle: exact codes of the dense int8 reference
    oracle = ref.int8_conv_ref(qspec.act_codes(x), qspec.weight_codes(wm),
                               np.asarray(qspec.dequant_row(w.shape[-1])),
                               1, "SAME", bias=bias, relu=relu)
    np.testing.assert_array_equal(
        np.asarray(got),
        np.asarray(round_sat(oracle * WIRE, MAX_CODE).astype(jnp.int8)))


def test_conv_int8_ingest_skips_requantize():
    """The streamed ingest: feeding the previous layer's codes directly
    == feeding the f32 activation those codes decode to."""
    rng = np.random.RandomState(11)
    spec, gm, w, layout = _conv_fixture(rng)
    qspec = QuantSpec()
    conv = make_sparse_conv(layout, gm, weight=w, bias=jnp.zeros(w.shape[-1]),
                            relu=True, quant=qspec, out_quant=QuantSpec())
    x = jnp.asarray(rng.uniform(-4, 4, (1, 6, 6, w.shape[2])), jnp.float32)
    codes = qspec.act_codes(x)
    assert codes.dtype == jnp.int8
    from_f32 = conv(x)
    from_codes = conv(codes)
    np.testing.assert_array_equal(np.asarray(from_f32),
                                  np.asarray(from_codes))


def test_conv_out_quant_requires_quant():
    rng = np.random.RandomState(3)
    spec, gm, w, layout = _conv_fixture(rng)
    with pytest.raises(ValueError, match="requires quant"):
        make_sparse_conv(layout, gm, weight=w, out_quant=QuantSpec())


# --- ExecSpec contract table ---------------------------------------------

INVALID_PAIRS = [
    (dict(trainable=True, quantized=True), ["trainable+quantized"]),
    (dict(trainable=True, folded=True), ["trainable+folded"]),
    (dict(trainable=True, streamed=True, quantized=True, folded=True),
     ["trainable+streamed", "trainable+quantized", "trainable+folded"]),
    (dict(streamed=True, folded=True), ["streamed without quantized"]),
    (dict(streamed=True, quantized=True), ["streamed without folded"]),
    (dict(streamed=True), ["streamed without quantized",
                           "streamed without folded"]),
]


@pytest.mark.parametrize("fields,expected", INVALID_PAIRS)
def test_exec_spec_contract_table(fields, expected):
    """One coherent ValueError naming every offending pair — stacked
    violations land in the same message."""
    with pytest.raises(ValueError) as ei:
        cnn.ExecSpec(**fields)
    msg = str(ei.value)
    assert msg.startswith("invalid ExecSpec:")
    for name in expected:
        assert name in msg, f"{name!r} missing from: {msg}"


def test_exec_spec_streamed_valid_and_hashable():
    s = cnn.ExecSpec(streamed=True, quantized=True, folded=True)
    assert s.streamed and hash(s) == hash(s)
    assert s != cnn.ExecSpec(quantized=True, folded=True)  # distinct cache key


def test_exec_spec_scalar_violations_still_named():
    with pytest.raises(ValueError, match="bm"):
        cnn.ExecSpec(bm=1.5)
    with pytest.raises(ValueError, match="n_cu"):
        cnn.ExecSpec(n_cu=0)
    # scalar + pair violations stack into one message
    with pytest.raises(ValueError) as ei:
        cnn.ExecSpec(n_cu=0, trainable=True, quantized=True)
    assert "n_cu" in str(ei.value) and "trainable+quantized" in str(ei.value)


# --- whole-model wire ----------------------------------------------------

def _pruned_model(seed=0, sparsity=0.5):
    cfg = cnn.ResNetConfig(stages=(1, 1), widths=(16, 32), image_size=16)
    params, state = cnn.init(jax.random.PRNGKey(seed), cfg)
    masks = cnn.derive_group_masks(params, 4)
    rng = np.random.RandomState(seed + 1)
    masks = {k: (rng.rand(*m.shape) > sparsity).astype(np.float32)
             for k, m in masks.items()}
    folded = cnn.fold_batchnorm(params, state, cfg)
    return cfg, folded, masks


def _bind(cfg, folded, masks, **kw):
    return cnn.bind_execution(
        folded, cfg,
        spec=cnn.ExecSpec(n_cu=4, folded=True, quantized=True,
                          dense_fallback=2.0, **kw),
        group_masks=masks)


def test_streamed_logits_exact_vs_wire_reference():
    """The tentpole parity contract: in-epilogue requantize (streamed
    kernels) == out-of-kernel requantize at the identical program points
    (wire_quantize=True on the non-streamed quantized folded exec),
    bit-for-bit end-to-end — and implicit == materializing."""
    cfg, folded, masks = _pruned_model()
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 16, 16, 3))
    streamed = cnn.apply_folded(folded, x, cfg,
                                sparse=_bind(cfg, folded, masks,
                                             streamed=True))
    wire_ref = cnn.apply_folded(folded, x, cfg,
                                sparse=_bind(cfg, folded, masks),
                                wire_quantize=True)
    np.testing.assert_array_equal(np.asarray(streamed), np.asarray(wire_ref))
    mat = cnn.apply_folded(folded, x, cfg,
                           sparse=_bind(cfg, folded, masks, streamed=True,
                                        implicit=False))
    np.testing.assert_array_equal(np.asarray(streamed), np.asarray(mat))
    # the wire costs only quantization error vs the f32-residual path
    plain = cnn.apply_folded(folded, x, cfg,
                             sparse=_bind(cfg, folded, masks))
    assert float(jnp.abs(streamed - plain).max()) < 0.1


def test_streamed_hbm_contract():
    """1-byte operands AND 1-byte output writes: the implicit streamed
    figure is exactly 1/4 of the f32 implicit one (every byte term
    scales), and the exec's own hbm_bytes follows its streamed policy."""
    cfg, folded, masks = _pruned_model()
    exec_ = _bind(cfg, folded, masks, streamed=True)
    assert exec_.streamed
    rep = exec_.report(cfg, batch=1)
    assert rep["streamed"] is True
    assert rep["hbm_bytes_streamed_int8"] * 4 == rep["hbm_bytes_implicit"]
    assert rep["hbm_bytes_streamed_int8"] < rep["hbm_bytes_implicit_int8"]
    # own-policy bytes = the streamed contract (implicit bind, auto bm)
    assert rep["hbm_bytes"] == rep["hbm_bytes_streamed_int8"]
    per = exec_.report(cfg, batch=1, per_layer=True)["per_layer"]
    for name, row in per.items():
        assert row["hbm_streamed_int8"] * 4 == row["hbm_implicit"], name


def test_apply_folded_wire_guards():
    cfg, folded, masks = _pruned_model()
    x = jnp.zeros((1, 16, 16, 3))
    with pytest.raises(ValueError, match="cannot be disabled"):
        cnn.apply_folded(folded, x, cfg,
                         sparse=_bind(cfg, folded, masks, streamed=True),
                         wire_quantize=False)
    f32_exec = cnn.bind_execution(
        folded, cfg, spec=cnn.ExecSpec(n_cu=4, folded=True,
                                       dense_fallback=2.0),
        group_masks=masks)
    with pytest.raises(ValueError, match="wire_quantize"):
        cnn.apply_folded(folded, x, cfg, sparse=f32_exec,
                         wire_quantize=True)


def test_wire_quantize_dense_reference_runs():
    """sparse=None + wire_quantize=True: the all-dense wire reference
    (every layer host-requantized) — the fallback-layer dataflow."""
    cfg, folded, masks = _pruned_model()
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 16, 16, 3))
    dense_wire = cnn.apply_folded(folded, x, cfg, wire_quantize=True)
    plain = cnn.apply_folded(folded, x, cfg)
    assert dense_wire.shape == plain.shape
    assert float(jnp.abs(dense_wire - plain).max()) < 0.5


def test_streamed_serving_bit_exact():
    """CnnServer with a streamed spec serves the streamed wire — bit
    identical to a direct streamed apply_folded."""
    from repro.launch.serve_cnn import CnnServer
    cfg, folded, masks = _pruned_model()
    params, state = cnn.init(jax.random.PRNGKey(0), cfg)
    spec = cnn.ExecSpec(n_cu=4, quantized=True, folded=True, streamed=True)
    server = CnnServer(params, state, cfg, spec=spec, buckets=(1, 2))
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(5), (2, 16, 16, 3)))
    served = np.asarray(server.infer(x))
    tree = cnn.fold_batchnorm(params, state, cfg)
    exec_ = cnn.bind_execution(tree, cfg, spec=spec,
                               group_masks=server.group_masks)
    direct = np.asarray(cnn.apply_folded(tree, jnp.asarray(x), cfg,
                                         sparse=exec_))
    np.testing.assert_array_equal(served, direct)
