"""Implicit-im2col block-sparse conv — the DSB kernel gathers its own patches.

The materializing path (:mod:`repro.kernels.conv_lowering` +
``sparse.conv_plan``) lowers a conv to ``patches @ W`` by writing a
``(B·Ho·Wo, kx·ky·cin)`` patch matrix to HBM — a kx·ky× blowup of the
activation — and then repacking it onto the padded tile grid, per call,
per layer. The paper's accelerator (and HPIPE-style FPGA designs) never
do that: kernel windows stream straight out of the input feature map
while the DSB skips pruned groups. This kernel executes the same
contract on the Pallas grid:

- Grid is ``(B·bpi, nNb, max_nnz)`` — M-blocks × output tile columns ×
  live K-tiles, exactly like :mod:`block_sparse_matmul`.
- The x operand is the **padded NHWC activation itself**, left in HBM
  (``memory_space=ANY``), its channels padded to whole 128-lane groups
  and its columns to whole 8-row tiles (the tiled HBM layout pads them
  anyway; a DMA may slice those axes only on tile boundaries). Per live
  K-tile the kernel DMAs only the *window* its M-block reads —
  ``(rows, cols)`` pixels covering ``block_oh × block_ow`` output pixels
  at the conv's stride, of the 128-lane channel group that holds the
  K-tile's ``cpk`` channels — into a **double-buffered** VMEM slab with
  :func:`pltpu.make_async_copy`: the copy for live tile ``t+1`` (keyed
  on the scalar-prefetched next table entry) is started before tile
  ``t``'s gather+dot runs, so slab traffic hides behind compute. Pruned
  groups cost neither DMA nor MXU cycles: dead tiles are never in the
  table, so their slabs are never fetched.
- The gather builds the same ``(bm, bk)`` patch tile the materializing
  path reads from HBM, in the forms the TPU's Mosaic compiler lowers:
  int8 / bf16 slabs are widened to 32 bits (Mosaic's strided loads are
  32-bit only); each tap ``(dy, dx)`` is one strided 3-D load,
  reshaped to ``(bm, 128)`` and transposed so the K-tile's channels sit
  on sublanes; a sublane-strided store drops channel ``c`` at row
  ``c·slot + tap`` of a transposed ``(bk, bm)`` patch tile; one
  transpose back, a cast to the operand dtype, and one MXU dot per live
  step. The tile is element-for-element the materializing tile, so the
  two paths feed the MXU identical operands.
- M-blocking is **adaptive**: an M-block is ``block_oh`` whole output
  rows (``bm = ceil8(block_oh·Wo) ≤ cap`` — a batch-1 4×4 tail runs at
  ``bm=16`` instead of padding to 128), and when even one output row
  exceeds the cap the row is split into ``spi`` **column segments** of
  ``block_ow`` pixels, so wide-resolution inputs keep the implicit path
  instead of falling back to the materializing oracle.
  :func:`choose_m_block` returns the :class:`MBlock` geometry; blocks
  never straddle images.
- The fused bias+ReLU flush epilogue carries over unchanged.

Per live grid step the kernel moves one ``(rows, cols)`` window of one
128-lane channel group — the pixels its M-block actually reads —
instead of ``bm·bk`` patch-matrix elements, and the patch matrix is
never written to HBM at all; the price is the lane-padded copy of the
activation the windows are DMA'd from. :func:`implicit_hbm_bytes`
counts both as the chip moves them, and :func:`window_vmem_bytes` the
window working set as the chip allocates it (lane- and sublane-padded);
:data:`SLAB_VMEM_BUDGET` bounds it, and layers above it take the
materializing path (``sparse.conv_plan.implicit_m_block``).

Operands may be **int8 Q-format codes** (the paper's Q3.4 activations ×
Q2.5 coefficients): the in-VMEM gather moves codes exactly, accumulation
switches to exact int32, and the flush epilogue dequantizes through a
per-cout ``scale`` row before bias/ReLU — one byte per operand element
moved instead of four, on exactly the same grid and index table.

**Activation-side DSB** (``activation_dsb=True``, int8 codes only):
post-ReLU zeros are *exact* integer codes on the streamed wire, so the
kernel reduces each DMA'd window to an any-nonzero flag and branches
around the gather **and** the MXU dot (:func:`pl.when`) when the block
is all-zero. The accumulator is untouched on a skip, so results stay
bit-exact vs the non-skip kernel at every density — dual-sided
weight × activation sparsity (Zhu et al., arXiv 2001.01955) with no
tolerance question. ``count_skips=True`` adds a second output — a
``(B·bpi, nNb)`` int32 skip counter written from SMEM — so callers can
report the measured skip fraction (``skipped / (B·bpi·Σcnt)``) next to
the simulator's ``data_col_nonzero_frac`` prediction.

Differentiation: :func:`implicit_block_sparse_conv` itself has no JVP
(Pallas calls are opaque to AD) — the ``custom_vjp`` lives one level up,
in ``sparse.conv_plan.make_sparse_conv(trainable=True)``, whose primal
dispatches this kernel and whose backward runs the **transposed-plan**
``block_sparse_matmul`` for dX and the live-tile
``block_sparse_grad_weight`` for dW on the materialized patch layout
(the implicit gather is a forward data-movement optimization; the
backward's operands — packed dY and packed patches — have no windowed
structure to exploit).
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .block_sparse_matmul import (append_epilogue_inputs, flush_epilogue,
                                  mxu_precision, quantized_contract,
                                  unpack_epilogue_refs)
from .conv_lowering import same_pads
from .ops import named_jit

# Largest padded window working set (bytes, window_vmem_bytes) the
# implicit kernel will hold in VMEM. Above this the layer uses the
# materializing path (still correct, just HBM-hungrier).
SLAB_VMEM_BUDGET = 2 * 1024 * 1024
LANES = 128


def _ceil_to(n: int, m: int) -> int:
    return -(-n // m) * m


class MBlock(NamedTuple):
    """Adaptive M-block geometry: ``block_oh × block_ow`` output pixels
    per grid block, ``spi`` column segments per row band, ``bpi =
    ceil(ho/block_oh)·spi`` M-blocks per image."""
    block_oh: int
    block_ow: int
    spi: int
    bm: int
    bpi: int


def choose_m_block(ho: int, wo: int, cap: int = 128) -> Optional[MBlock]:
    """Adaptive M-blocking: whole output rows per grid block, column
    segments when a row is too wide.

    Picks the largest ``block_oh`` whole output rows with ``bm =
    ceil8(block_oh·wo) ≤ cap``, so small layers stop padding up to a
    fixed 128: a 4×4 output runs at ``bm=16``, an 8×8 at ``bm=64``.
    When even one output row exceeds ``cap`` the row splits into
    ``spi = ceil(wo/block_ow)`` column segments of ``block_ow =
    8·⌊cap/8⌋`` pixels — wide-resolution inputs keep the implicit path.
    ``None`` only when the cap can't fit one 8-pixel segment. Blocks
    never straddle images.
    """
    if ho < 1 or wo < 1:
        return None
    if _ceil_to(wo, 8) <= cap:
        block_oh = max(b for b in range(1, ho + 1)
                       if _ceil_to(b * wo, 8) <= cap)
        return MBlock(block_oh, wo, 1, _ceil_to(block_oh * wo, 8),
                      -(-ho // block_oh))
    block_ow = (cap // 8) * 8
    if block_ow < 8:
        return None
    spi = -(-wo // block_ow)
    return MBlock(1, block_ow, spi, block_ow, ho * spi)


def window_shape(mb: MBlock, kx: int, ky: int, stride: int) -> Tuple[int, int]:
    """(rows, cols) of padded input one M-block's window slab covers —
    the per-live-step DMA granule."""
    return ((mb.block_oh - 1) * stride + kx,
            (mb.block_ow - 1) * stride + ky)


def _input_pads(h: int, w: int, kx: int, ky: int, stride: int, padding: str,
                mb: MBlock):
    """((top, bottom), (left, right)) zero pads of :func:`pad_input`."""
    if padding == "SAME":
        (pt, pb), (pw0, pw1) = (same_pads(h, kx, stride),
                                same_pads(w, ky, stride))
    else:
        pt = pb = pw0 = pw1 = 0
    rb = mb.bpi // mb.spi
    rows_need = (rb - 1) * mb.block_oh * stride \
        + (mb.block_oh - 1) * stride + kx
    cols_need = (mb.spi - 1) * mb.block_ow * stride \
        + (mb.block_ow - 1) * stride + ky
    extra_r = max(rows_need - (h + pt + pb), 0)
    extra_c = max(cols_need - (w + pw0 + pw1), 0)
    return (pt, pb + extra_r), (pw0, pw1 + extra_c)


def pad_input(x: jnp.ndarray, kx: int, ky: int, stride: int, padding: str,
              mb: MBlock, c_packed: int) -> jnp.ndarray:
    """Zero-pad an NHWC input for the implicit kernel: the conv's own
    SAME/VALID pads, extra trailing rows/columns so the *last* M-block's
    window slab stays in bounds (its tail output pixels are cropped
    after the kernel), and channel padding to the packed K grid. Pure
    ``jnp.pad`` — no kx·ky patch blowup, no transpose."""
    B, H, W, C = x.shape
    rows, cols = _input_pads(H, W, kx, ky, stride, padding, mb)
    return jnp.pad(x, ((0, 0), rows, cols, (0, c_packed - C)))


def crop_output(out2d: jnp.ndarray, mb: MBlock, batch: int, ho: int,
                wo: int) -> jnp.ndarray:
    """Undo the M-block tiling: ``(B·bpi·bm, n_packed)`` kernel output →
    ``(B, ho, wo, n_packed)`` with the bm row padding and block
    overhang dropped."""
    rb = mb.bpi // mb.spi
    o = out2d.reshape(batch, rb, mb.spi, mb.bm, -1)
    o = o[:, :, :, :mb.block_oh * mb.block_ow]
    o = o.reshape(batch, rb, mb.spi, mb.block_oh, mb.block_ow, -1)
    o = o.transpose(0, 1, 3, 2, 4, 5)
    o = o.reshape(batch, rb * mb.block_oh, mb.spi * mb.block_ow, -1)
    return o[:, :ho, :wo]


def _sublane_tile(itemsize: int) -> int:
    """Rows of one ``(sublanes, 128 lanes)`` VMEM tile for this element
    width: 8 for 32-bit, 16 for bf16, 32 for int8."""
    return 8 * (4 // itemsize)


def _tiled_bytes(shape: Tuple[int, ...], itemsize: int) -> int:
    """Bytes the chip allocates for a VMEM buffer of ``shape``: the minor
    dim padded to 128 lanes, the second-minor to the sublane tile."""
    *lead, sub, lane = shape
    return (math.prod(lead) * _ceil_to(sub, _sublane_tile(itemsize))
            * _ceil_to(lane, LANES) * itemsize)


def dma_cols(mb: MBlock, kx: int, ky: int, stride: int) -> int:
    """Columns of one window DMA: the window's ``cols`` rounded up to the
    8-row tiling of the HBM activation's second-minor axis (a DMA may
    only slice that axis on tile boundaries)."""
    return _ceil_to(window_shape(mb, kx, ky, stride)[1], 8)


def hbm_view_shape(xp_shape: Tuple[int, int, int, int], mb: MBlock, kx: int,
                   ky: int, stride: int) -> Tuple[int, int, int, int]:
    """Shape of the activation copy the kernel DMAs its windows from:
    :func:`pad_input`'s ``(B, Hp, Wp, Cp)`` with the columns widened to
    whole 8-row tiles that cover the last window DMA and the channels to
    whole 128-lane groups (a DMA slices those axes only on tile
    boundaries)."""
    b, hp, wp, cp = xp_shape
    wd = _ceil_to(max(wp, (mb.spi - 1) * mb.block_ow * stride
                      + dma_cols(mb, kx, ky, stride)), 8)
    return b, hp, wd, _ceil_to(cp, LANES)


def implicit_hbm_bytes(batch: int, h: int, w: int, cin: int, c_packed: int,
                       kx: int, ky: int, stride: int, padding: str,
                       mb: MBlock, itemsize: int) -> Tuple[int, int]:
    """HBM bytes of the implicit kernel's activation traffic, as the chip
    moves it: ``(ingest, per_step)``. ``ingest`` reads the ``(batch, h,
    w, cin)`` input once and writes its padded copy
    (:func:`hbm_view_shape`); ``per_step`` is one live grid step's window
    DMA, ``rows × dma_cols`` pixels of a whole 128-lane channel group."""
    pads = _input_pads(h, w, kx, ky, stride, padding, mb)
    view = hbm_view_shape((batch, h + sum(pads[0]), w + sum(pads[1]),
                           c_packed), mb, kx, ky, stride)
    rows = window_shape(mb, kx, ky, stride)[0]
    return ((batch * h * w * cin + math.prod(view)) * itemsize,
            rows * dma_cols(mb, kx, ky, stride) * LANES * itemsize)


def window_vmem_bytes(mb: MBlock, kx: int, ky: int, stride: int, bk: int,
                      itemsize: int) -> int:
    """Padded VMEM bytes of the implicit kernel's window working set: both
    DMA slab slots (a window carries its whole 128-lane channel group),
    the 32-bit working copy of the live slab (int8 / bf16 operands), and
    the two transposed ``(128, bm)`` / ``(bk, bm)`` tap and patch tiles."""
    rows = window_shape(mb, kx, ky, stride)[0]
    cols = dma_cols(mb, kx, ky, stride)
    slab = _tiled_bytes((2, rows, cols, LANES), itemsize)
    work = 0 if itemsize == 4 else _tiled_bytes((rows, cols, LANES), 4)
    return (slab + work + _tiled_bytes((LANES, mb.bm), 4)
            + _tiled_bytes((bk, mb.bm), 4))


def _kernel(idx_ref, cnt_ref, x_ref, w_ref, *refs,
            kx, ky, stride, block_oh, block_ow, spi, bpi, cpk, slot,
            acc_dtype, has_scale, has_bias, has_out, relu, activation_dsb,
            count_skips, widen):
    n_ep = int(has_scale) + int(has_bias) + int(has_out)
    skip_ref = refs[n_ep + 1] if count_skips else None
    n_scratch = 6 if widen else 5
    acc_ref, slab_ref, *work, tap_ref, patch_ref, sem_ref = refs[-n_scratch:]
    scale_ref, b_ref, out_ref, o_ref, _ = unpack_epilogue_refs(
        (*refs[:n_ep + 1], acc_ref), has_scale, has_bias, has_out)
    i, j, s = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    live = cnt_ref[j]
    rows = (block_oh - 1) * stride + kx
    cols = (block_ow - 1) * stride + ky
    cols_dma = _ceil_to(cols, 8)
    m = block_oh * block_ow
    b = i // bpi
    p = i % bpi
    r0 = (p // spi) * (block_oh * stride)
    q0 = 0 if spi == 1 else pl.multiple_of((p % spi) * (block_ow * stride), 8)
    buf = jax.lax.rem(s, 2)

    def slab_copy(e, sl):
        # window of the 128-lane channel group holding live K-tile
        # (= cin-block) idx[j, e], into slab slot sl
        lane0 = pl.multiple_of(idx_ref[j, e] * cpk // LANES * LANES, LANES)
        return pltpu.make_async_copy(
            x_ref.at[b, pl.ds(r0, rows), pl.ds(q0, cols_dma),
                     pl.ds(lane0, LANES)],
            slab_ref.at[sl], sem_ref.at[sl])

    @pl.when(s == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        # unused tap slots and rows past the M-block stay zero
        patch_ref[...] = jnp.zeros_like(patch_ref)
        if count_skips:
            skip_ref[0, 0, 0] = 0

        @pl.when(live > 0)
        def _warmup():
            slab_copy(0, 0).start()

    @pl.when(s < live)
    def _step():
        slab_copy(s, buf).wait()

        @pl.when(s + 1 < live)
        def _prefetch():                    # overlap tile s+1's DMA with
            slab_copy(s + 1, 1 - buf).start()   # tile s's gather+dot

        # Mosaic loads strided windows of 32-bit data only: int8 / bf16
        # slabs are widened once per step into the working copy
        if widen:
            win_ref = work[0]
            win_ref[...] = slab_ref[buf].astype(win_ref.dtype)
        else:
            win_ref = slab_ref.at[buf]
        coff = idx_ref[j, s] * cpk % LANES     # K-tile's first lane
        if cpk % 8 == 0:
            coff = pl.multiple_of(coff, 8)

        def _gather_mac():
            # the im2col gather, in VMEM: tap (dy, dx) of output pixel
            # (oh, ow) is win[oh*stride + dy, ow*stride + dx]. Each tap is
            # one strided load, transposed so the K-tile's cpk channels
            # land on sublanes c*slot + tap of the (bk, bm) patch tile;
            # one transpose back gives the (bm, bk) tile the materializing
            # path would have read from HBM, element for element
            for dy in range(kx):
                for dx in range(ky):
                    tap = win_ref[pl.ds(dy, block_oh, stride=stride),
                                  pl.ds(dx, block_ow, stride=stride), :]
                    tap_ref[:, pl.ds(0, m)] = tap.reshape(m, LANES).T
                    patch_ref[pl.ds(dy * ky + dx, cpk, stride=slot),
                              pl.ds(0, m)] = tap_ref[pl.ds(coff, cpk),
                                                     pl.ds(0, m)]
            pt = patch_ref[...].T.astype(w_ref.dtype)
            acc_ref[...] += jnp.dot(pt, w_ref[...],
                                    preferred_element_type=acc_dtype,
                                    precision=mxu_precision(w_ref.dtype))

        if activation_dsb:
            # post-ReLU zeros are exact int8 codes: an all-zero window
            # contributes exactly nothing, so skip the gather AND the
            # MXU dot — the untouched accumulator keeps bit-exactness
            win = jnp.abs(win_ref[:, pl.ds(0, cols), :])
            lane = jax.lax.broadcasted_iota(jnp.int32, win.shape, 2)
            own = (lane >= coff) & (lane < coff + cpk)
            hit = jnp.max(jnp.where(own, win, 0)) > 0
            pl.when(hit)(_gather_mac)
            if count_skips:
                @pl.when(jnp.logical_not(hit))
                def _count():
                    skip_ref[0, 0, 0] += 1
        else:
            _gather_mac()

    @pl.when(s == pl.num_programs(2) - 1)
    def _flush():
        out = flush_epilogue(acc_ref[...], scale_ref, b_ref, relu, out_ref)
        o_ref[...] = out.astype(o_ref.dtype)


STATIC_ARGS = ("kx", "ky", "stride", "mb", "block", "cpk", "slot", "relu",
               "activation_dsb", "count_skips", "interpret")


# inline: called inside another jitted function, the kernel takes that
# function's name in the compiled program (see :func:`named`)
@functools.partial(jax.jit, static_argnames=STATIC_ARGS, inline=True)
def implicit_block_sparse_conv(
    xp: jnp.ndarray,           # (B, Hp, Wp, nKb*cpk) pad_input() output
    w: jnp.ndarray,            # (nKb*bk, nNb*bn) packed weight (f32/bf16/int8)
    idx: jnp.ndarray,          # (nNb, max_nnz) int32 live K-tile (= cin-block) ids
    cnt: jnp.ndarray,          # (nNb,) int32
    bias: Optional[jnp.ndarray] = None,    # (nNb*bn,) fused epilogue bias
    scale: Optional[jnp.ndarray] = None,   # (nNb*bn,) fused dequant row (int8)
    out_scale: Optional[jnp.ndarray] = None,  # (nNb*bn,) requantize row -> int8
    *,
    kx: int, ky: int, stride: int,
    mb: MBlock,
    block: Tuple[int, int], cpk: int, slot: int,
    relu: bool = False,
    activation_dsb: bool = False,
    count_skips: bool = False,
    interpret: bool = False,
):
    """-> (B*bpi*bm, nNb*bn). M-block ``(b, p)`` starts at row
    ``(b*bpi + p)*bm``; its first ``block_oh*block_ow`` rows are the
    block's output pixels row-major (row band ``p // spi``, column
    segment ``p % spi``), the rest padding — undo with
    :func:`crop_output`.

    int8 operands (``xp``/``w`` are Q-format codes): the gather works on
    codes, accumulation is exact **int32**, and the flush epilogue
    dequantizes through the per-cout ``scale`` row (then bias, then ReLU)
    — output is f32, or int8 Q-format codes when the requantizing
    ``out_scale`` row is passed (streamed layer-to-layer activations).
    Same contract as :mod:`block_sparse_matmul`.

    ``activation_dsb`` (int8 codes only) skips all-zero window slabs —
    bit-exact, see the module docstring. With ``count_skips`` the return
    is ``(out, skips)`` where ``skips`` is the ``(B*bpi, nNb)`` int32
    per-M-block/per-column skip counter (skipped live steps; total live
    steps are ``B*bpi*cnt.sum()``)."""
    B, Hp, Wp, Cp = xp.shape
    bk, bn = block
    assert Cp % cpk == 0 and w.shape[0] % bk == 0 and w.shape[1] % bn == 0, (
        f"packed shapes off-grid: x {xp.shape} (cpk={cpk}), w {w.shape}, "
        f"block={block}")
    if activation_dsb:
        assert xp.dtype == jnp.int8, (
            "activation_dsb keys the skip on exact int8 zero codes — "
            "quantize the activation (quant=...) to use it")
    rows, cols = window_shape(mb, kx, ky, stride)
    rb = mb.bpi // mb.spi
    assert ((rb - 1) * mb.block_oh * stride + rows <= Hp
            and (mb.spi - 1) * mb.block_ow * stride + cols <= Wp), (
        f"window slab out of bounds: pad_input() with this MBlock first "
        f"(xp {xp.shape}, mb {mb}, k ({kx},{ky}), stride {stride})")
    acc_dtype, out_dtype = quantized_contract(xp, w, scale, out_scale)
    nNb = w.shape[1] // bn
    max_nnz = idx.shape[1]
    has_scale = scale is not None
    has_bias = bias is not None
    has_out = out_scale is not None
    # HBM view with the channels padded to whole 128-lane groups (the
    # tiled layout pads them anyway) and the columns to whole 8-row
    # tiles: a window DMA then slices the lane axis and the column axis
    # only on tile boundaries
    cols_dma = dma_cols(mb, kx, ky, stride)
    _, _, wd, cd = hbm_view_shape(xp.shape, mb, kx, ky, stride)
    xk = jnp.pad(xp, ((0, 0), (0, 0), (0, wd - Wp), (0, cd - Cp)))

    in_specs = [
        pl.BlockSpec(memory_space=pl.ANY),      # activation stays in HBM;
        # the kernel DMAs per-M-block windows of the prefetched K-tile
        pl.BlockSpec((bk, bn), lambda i, j, s, idx, cnt: (idx[j, s], j)),
    ]
    inputs = [idx, cnt, xk, w]
    append_epilogue_inputs(in_specs, inputs, scale, bias, bn, out_scale)

    out_specs = pl.BlockSpec((mb.bm, bn), lambda i, j, s, idx, cnt: (i, j))
    out_shape = jax.ShapeDtypeStruct((B * mb.bpi * mb.bm, w.shape[1]),
                                     out_dtype)
    if count_skips:
        # one SMEM word per (M-block, column); the two minor dims are
        # whole-array so the (1, 1, 1) block is legal
        out_specs = [out_specs, pl.BlockSpec(
            (1, 1, 1), lambda i, j, s, idx, cnt: (i * nNb + j, 0, 0),
            memory_space=pltpu.SMEM)]
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct((B * mb.bpi * nNb, 1, 1), jnp.int32)]

    widen = xp.dtype.itemsize != 4
    wide_dtype = jnp.int32 if xp.dtype == jnp.int8 else jnp.float32
    scratch = [pltpu.VMEM((mb.bm, bn), acc_dtype),
               pltpu.VMEM((2, rows, cols_dma, LANES), xp.dtype)]
    if widen:
        scratch.append(pltpu.VMEM((rows, cols_dma, LANES), wide_dtype))
    scratch += [pltpu.VMEM((LANES, mb.bm), wide_dtype),
                pltpu.VMEM((bk, mb.bm), wide_dtype),
                pltpu.SemaphoreType.DMA((2,))]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B * mb.bpi, nNb, max_nnz),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    res = pl.pallas_call(
        functools.partial(_kernel, kx=kx, ky=ky, stride=stride,
                          block_oh=mb.block_oh, block_ow=mb.block_ow,
                          spi=mb.spi, bpi=mb.bpi, cpk=cpk, slot=slot,
                          acc_dtype=acc_dtype, has_scale=has_scale,
                          has_bias=has_bias, has_out=has_out, relu=relu,
                          activation_dsb=activation_dsb,
                          count_skips=count_skips, widen=widen),
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(*inputs)
    if count_skips:
        out, skips = res
        return out, skips.reshape(B * mb.bpi, nNb)
    return res


@functools.lru_cache(maxsize=None)
def named(name: str):
    """:func:`implicit_block_sparse_conv`, same kernel body, jitted under
    the name ``name`` (:func:`repro.kernels.ops.named_jit`): the compiled
    program and a device trace then call this kernel ``name.<n>``."""
    return named_jit(implicit_block_sparse_conv, name,
                     static_argnames=STATIC_ARGS)
