"""Block-sparse matmul Pallas kernel — the TPU-native Dynamic Sparsity Bypass.

Grid: ``(M/bm, nNb, max_nnz)``. A scalar-prefetched ``(nNb, max_nnz)``
index table (from :mod:`repro.sparse.block_mask`) gathers only the live
K-tiles of each output column: the BlockSpec index maps read ``idx[j, s]``,
so pruned tiles cost neither MXU cycles nor HBM→VMEM DMA. ``pl.when``
guards the ragged tail (columns with fewer live tiles than ``max_nnz``).

Operands are f32/bf16 (f32 accumulation) **or int8 codes** — the paper's
Q3.4 × Q2.5 fixed point on the MXU's int8 path. int8 operands accumulate
in **int32** (exact integer arithmetic, bit-identical to the reference)
and require a ``scale`` row; the output is the dequantized f32.

Optional fused epilogue at the flush step, in dequant → bias → ReLU →
requantize order: a per-column ``scale`` multiply (f32 ``(N,)`` row — the
int8 dequant, ``out = acc * scale``, per-cout weight scales supported), a
per-column ``bias`` add (f32, broadcast over rows), ``relu``, and an
optional per-column ``out_scale`` row that requantizes the flushed value
back to int8 Q-format codes (``round_sat(out * out_scale, 127)``,
round-half-even — the same rule :meth:`QuantSpec.act_codes` applies on
the host) so the output write is 1 byte/value and the next layer's
gather consumes codes directly, no f32 round-trip through HBM.
Folded-BN inference (conv → +b → ReLU) runs entirely inside the kernel,
no extra HBM round trip for the activation. Fully-pruned columns still
flush ``bias`` (then ReLU), matching the dense ``conv(x, 0) + b``
semantics.

VMEM working set = ``bm·bk + bk·bn + bm·bn(acc)`` — (128,128,128)
defaults keep it ≈ 192 KiB f32 (int8 operands halve the operand tiles),
far under the ~16 MiB/core budget, and every matmul dim is a multiple of
the 128-lane MXU width.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.quant import round_sat

# int8 symmetric code bound: requantizing epilogues clamp to ±127 (both
# Q2.5 and Q3.4 share it — the sign bit plus 7 magnitude bits of an int8)
INT8_MAX_CODE = 127.0


# --- shared epilogue contract (also consumed by kernels.implicit_conv) ----
# Both block-sparse kernels carry the identical optional
# [scale?, bias?, out_scale?] trailing operands and the identical
# dequant -> bias -> ReLU -> requantize flush; keep the plumbing in ONE
# place so the kernels cannot drift apart (the bench asserts their
# bit-parity).

def quantized_contract(x, w, scale, out_scale=None):
    """-> (acc_dtype, out_dtype) for the operand dtypes, validating the
    int8-code contract: int8 × int8 accumulates exactly in int32 and
    needs a dequant ``scale`` row to emit float output; an ``out_scale``
    row requantizes the flush so the kernel emits int8 codes instead."""
    if x.dtype == jnp.int8:
        assert w.dtype == jnp.int8, "int8 x needs int8 w (codes × codes)"
        assert scale is not None, (
            "int8 operands accumulate integer codes — pass the dequant "
            "scale row so the flush epilogue can emit float output")
        return jnp.int32, (jnp.int8 if out_scale is not None else jnp.float32)
    assert out_scale is None, (
        "the requantizing epilogue (out_scale) is part of the int8-code "
        "contract — f32 operands flush f32")
    return jnp.float32, x.dtype


def mxu_precision(dtype):
    """Dot precision of one operand dtype: f32 operands contract at full
    f32 precision on the MXU (the f32 execution contract, held to the f32
    reference); bf16 and int8 operands are exact at Mosaic's default."""
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None


def unpack_epilogue_refs(refs, has_scale, has_bias, has_out=False):
    """Kernel-side view of the trailing operands: ``refs`` is
    ``[scale?, bias?, out_scale?, o_ref, acc_ref]``
    -> (scale_ref, b_ref, out_ref, o_ref, acc_ref)."""
    extra = refs[:-2]
    pos = 0
    scale_ref = b_ref = out_ref = None
    if has_scale:
        scale_ref, pos = extra[pos], pos + 1
    if has_bias:
        b_ref, pos = extra[pos], pos + 1
    if has_out:
        out_ref = extra[pos]
    return scale_ref, b_ref, out_ref, refs[-2], refs[-1]


def flush_epilogue(acc, scale_ref, b_ref, relu, out_ref=None):
    """dequant → bias → ReLU on the flushed accumulator, f32; with
    ``out_ref`` the result is requantized to int8 codes
    (``round_sat(out * out_scale, 127)``, round-half-even)."""
    out = acc
    if scale_ref is not None:           # int8 path: dequant the int32 acc
        out = out.astype(jnp.float32) * scale_ref[...]
    if b_ref is not None:
        out = out.astype(jnp.float32) + b_ref[...].astype(jnp.float32)
    if relu:
        out = jnp.maximum(out, 0.0)
    if out_ref is not None:             # requantize: emit Q-format codes
        out = round_sat(out * out_ref[...], INT8_MAX_CODE)
    return out


def append_epilogue_inputs(in_specs, inputs, scale, bias, bn, out_scale=None):
    """Host-side twin of :func:`unpack_epilogue_refs`: append the
    ``(1, bn)``-blocked scale/bias/out_scale rows (both kernels share
    the ``(i, j, s, idx, cnt)`` index-map arity)."""
    for row, cast in ((scale, jnp.float32), (bias, None),
                      (out_scale, jnp.float32)):
        if row is not None:
            in_specs.append(
                pl.BlockSpec((1, bn), lambda i, j, s, idx, cnt: (0, j)))
            r2 = row.reshape(1, -1)
            inputs.append(r2.astype(cast) if cast is not None else r2)


def _kernel(idx_ref, cnt_ref, x_ref, w_ref, *refs, acc_dtype, has_scale,
            has_bias, has_out, relu):
    scale_ref, b_ref, out_ref, o_ref, acc_ref = unpack_epilogue_refs(
        refs, has_scale, has_bias, has_out)
    j, s = pl.program_id(1), pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(s < cnt_ref[j])
    def _compute():
        acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                                preferred_element_type=acc_dtype,
                                precision=mxu_precision(x_ref.dtype))

    @pl.when(s == pl.num_programs(2) - 1)
    def _flush():
        out = flush_epilogue(acc_ref[...], scale_ref, b_ref, relu, out_ref)
        o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block", "bm", "relu", "interpret"))
def block_sparse_matmul(
    x: jnp.ndarray,            # (M, K) f32/bf16, or int8 codes
    w: jnp.ndarray,            # (K, N) same family as x
    idx: jnp.ndarray,          # (nNb, max_nnz) int32
    cnt: jnp.ndarray,          # (nNb,) int32
    bias: Optional[jnp.ndarray] = None,   # (N,) fused epilogue bias (f32 units)
    scale: Optional[jnp.ndarray] = None,  # (N,) fused dequant row (f32)
    out_scale: Optional[jnp.ndarray] = None,  # (N,) requantize row -> int8
    *,
    block: Tuple[int, int] = (128, 128),
    bm: int = 128,
    relu: bool = False,
    interpret: bool = False,
) -> jnp.ndarray:
    M, K = x.shape
    Kw, N = w.shape
    bk, bn = block
    assert Kw == K and K % bk == 0 and N % bn == 0 and M % bm == 0, (
        f"shapes must be tile-aligned: {x.shape} @ {w.shape}, block={block}, bm={bm}")
    acc_dtype, out_dtype = quantized_contract(x, w, scale, out_scale)
    nNb = N // bn
    max_nnz = idx.shape[1]
    has_scale = scale is not None
    has_bias = bias is not None
    has_out = out_scale is not None
    for name, row in (("scale", scale), ("bias", bias),
                      ("out_scale", out_scale)):
        assert row is None or row.shape == (N,), \
            f"{name} must be ({N},), got {row.shape}"

    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, s, idx, cnt: (i, idx[j, s])),
        pl.BlockSpec((bk, bn), lambda i, j, s, idx, cnt: (idx[j, s], j)),
    ]
    inputs = [idx, cnt, x, w]
    append_epilogue_inputs(in_specs, inputs, scale, bias, bn, out_scale)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(M // bm, nNb, max_nnz),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, s, idx, cnt: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), acc_dtype)],
    )
    return pl.pallas_call(
        functools.partial(_kernel, acc_dtype=acc_dtype, has_scale=has_scale,
                          has_bias=has_bias, has_out=has_out, relu=relu),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(*inputs)


def _grad_w_kernel(kk_ref, nn_ref, x_ref, g_ref, o_ref, acc_ref):
    m = pl.program_id(1)

    @pl.when(m == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], g_ref[...], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=mxu_precision(x_ref.dtype))

    @pl.when(m == pl.num_programs(1) - 1)
    def _flush():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "bm", "interpret"))
def block_sparse_grad_weight(
    x: jnp.ndarray,            # (M, K) f32/bf16 packed patches
    g: jnp.ndarray,            # (M, N) f32/bf16 packed output gradient
    kk: jnp.ndarray,           # (L,) int32 live-tile K coordinates
    nn: jnp.ndarray,           # (L,) int32 live-tile N coordinates
    *,
    block: Tuple[int, int] = (128, 128),
    bm: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """``dW = x^T @ g`` restricted to the live weight tiles — the backward
    twin of :func:`block_sparse_matmul`.

    Grid ``(L, M/bm)``: program ``(l, m)`` contracts the ``m``-th row block
    of ``x[:, kk[l]-tile]`` against ``g[:, nn[l]-tile]`` into a VMEM
    accumulator, flushed on the last row block. ``(kk, nn)`` are the
    scalar-prefetched live-tile coordinates (any order), so dead tiles cost
    neither MXU cycles nor HBM→VMEM DMA — same dispatch economics as the
    forward. Returns the **compact** ``(L, bk, bn)`` f32 stack of live dW
    tiles; the caller scatters it onto the full ``(K, N)`` grid, leaving
    pruned tiles exactly zero (HAPM's no-resurrection invariant holds by
    construction, not by masking a dense product).
    """
    M, K = x.shape
    Mg, N = g.shape
    bk, bn = block
    L = int(kk.shape[0])
    assert Mg == M and M % bm == 0 and K % bk == 0 and N % bn == 0, (
        f"shapes must be tile-aligned: {x.shape}, {g.shape}, "
        f"block={block}, bm={bm}")
    assert L > 0, "no live tiles — the caller short-circuits to zeros"

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(L, M // bm),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda l, m, kk, nn: (m, kk[l])),
            pl.BlockSpec((bm, bn), lambda l, m, kk, nn: (m, nn[l])),
        ],
        out_specs=pl.BlockSpec((1, bk, bn), lambda l, m, kk, nn: (l, 0, 0)),
        scratch_shapes=[pltpu.VMEM((bk, bn), jnp.float32)],
    )
    return pl.pallas_call(
        _grad_w_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((L, bk, bn), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(kk, nn, x, g)
