"""HAPM group masks -> BlockSparsePlan over the im2col weight matrix.

This is where the paper's schedule groups meet the Pallas grid: a conv is
lowered to ``patches @ W`` (:mod:`repro.kernels.conv_lowering`) and the
weight matrix is packed onto a tile grid aligned with the pruning groups,
so every pruned group is a *dead tile* the kernel's dispatch plan never
visits — compute and HBM→VMEM DMA both skipped, exactly the FPGA DSB's
skipped (f_block, g) schedule steps hoisted to dispatch time.

One layout, :class:`ConvGemmLayout` (from ``FpgaConvGroupSpec``): K is
channel-major and each K-tile packs ``bk // slot`` input channels, one
row *slot* per channel (:func:`channel_slot`: 8-aligned, one row for a
1x1, whose tile then holds a whole 128-lane channel group); each N-tile
packs ``bn // n_cu`` f_blocks, so the tile shape matches the 128-deep MXU
datapath instead of one group. A tile is live iff *any* covered (g,
f_block) group is live; pruned groups inside a live tile are zero slabs
in the packed (masked) weight, so the GEMM stays exact.
Paper-granularity accounting survives through
:meth:`ConvGemmLayout.tile_occupancy`: every tile records how many live /
total schedule groups it covers, so callers can report *both* packed grid
steps (what the hardware dispatches) and schedule-group steps (what the
cycle model prices) plus the padded-MAC utilization of the dispatched
tiles.

The layout packs zeros into the padding, so packed GEMM == conv for any
operand values; dead-tile skipping is additionally exact because pruned
groups are zero slabs in the masked weight.

:func:`make_sparse_conv` binds a layout to the Pallas kernel. Weight
packing is hoisted to *bind time* — pass ``weight=`` (and optionally a
folded-BN ``bias=`` / ``relu=`` epilogue, fused into the kernel's flush
step) and the returned closure only packs im2col patches per call.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.groups import FpgaConvGroupSpec, apply_group_mask
from .block_mask import BlockSparsePlan, plan_from_tile_mask, transpose_plan


def _ceil_to(n: int, m: int) -> int:
    return -(-n // m) * m


def channel_slot(kx: int, ky: int) -> int:
    """Rows of a packed K-tile one input channel's ``kx·ky`` taps take:
    ``kx·ky`` rounded up to the 8-row sublane tile, except a 1x1's single
    tap, which takes one row, so its tile holds 128 channels, one
    128-lane group of the activation, and the implicit kernel's patch
    tile is that group's window itself."""
    kxky = kx * ky
    return 1 if kxky == 1 else _ceil_to(kxky, 8)


def mask_fingerprint(group_masks) -> str:
    """Stable hex digest of a per-layer group-mask collection — the
    sparsity-pattern component of the serving exec-cache key
    (:mod:`repro.launch.exec_cache`). Two mask sets fingerprint equal iff
    every layer has the same live/pruned pattern; any HAPM epoch that
    prunes (or revives) a group changes the digest, which is what
    invalidates cached binds.

    Accepts either a ``{path-tuple: mask}`` dict (e.g.
    ``SparseConvExec.group_masks_np``) or an arbitrary pytree of masks
    (e.g. ``HAPMState.group_masks``); entries are digested in sorted path
    order so dict insertion order is irrelevant. Masks are binarized
    (``> 0``) before hashing — only the live/pruned pattern matters, not
    score values.
    """
    import hashlib

    import jax

    if isinstance(group_masks, dict) and all(
            isinstance(k, tuple) for k in group_masks):
        items = sorted(("/".join(map(str, k)), v)
                       for k, v in group_masks.items())
    else:
        leaves = jax.tree_util.tree_flatten_with_path(group_masks)[0]
        items = sorted((jax.tree_util.keystr(path), leaf)
                       for path, leaf in leaves)
    h = hashlib.sha1()
    for name, mask in items:
        m = np.asarray(mask)
        h.update(name.encode())
        h.update(str(m.size).encode())
        h.update(np.packbits(m > 0).tobytes())
    return h.hexdigest()


@dataclasses.dataclass(frozen=True)
class ConvGemmLayout:
    """Packing of one conv weight onto the block-sparse kernel's tile grid:
    ``cpk = bk // channel_slot(kx, ky)`` input channels per K-tile
    (channel ``g`` -> tile ``g // cpk``, row slot ``g % cpk``) and ``fpn =
    bn // n_cu`` f_blocks per N-tile (f_block ``f`` -> tile ``f // fpn``,
    lane slot ``f % fpn``). A tile is live iff any covered group is —
    pruned groups inside live tiles are zeros in the packed masked weight,
    so the GEMM stays exact while the grid holds up to ``cpk·fpn`` groups
    a tile."""

    spec: FpgaConvGroupSpec
    block: Tuple[int, int]          # (bk, bn) kernel tile
    tiles: Tuple[int, int]          # (nKb, nNb)

    @property
    def k_packed(self) -> int:
        return self.tiles[0] * self.block[0]

    @property
    def n_packed(self) -> int:
        return self.tiles[1] * self.block[1]

    def _packing(self):
        kx, ky, cin, cout = self.spec.shape
        n_cu, n_fb = self.spec.n_cu, self.spec.n_fblocks
        bk, bn = self.block
        slot = channel_slot(kx, ky)
        return kx * ky, cin, cout, n_cu, n_fb, slot, bk // slot, bn // n_cu

    def implicit_geometry(self) -> dict:
        """Window geometry of the K axis for the implicit-im2col kernel
        (the in-kernel gather contract: K-tile ``t`` covers input channels
        ``[t*cpk, (t+1)*cpk)``, channel slot ``c`` owns rows ``[c*slot,
        c*slot + kx*ky)`` = the (dy, dx) taps in row-major tap order).
        Keys: ``kx, ky, cpk, slot``."""
        kxky, cin, cout, n_cu, n_fb, slot, cpk, fpn = self._packing()
        kx, ky = self.spec.shape[:2]
        return {"kx": kx, "ky": ky, "cpk": cpk, "slot": slot}

    def _group_grid(self, group_mask) -> np.ndarray:
        """(num_groups,) -> (nKb, cpk, nNb, fpn) bool, padded with False."""
        kxky, cin, cout, n_cu, n_fb, slot, cpk, fpn = self._packing()
        nKb, nNb = self.tiles
        g = np.asarray(group_mask).reshape(cin, n_fb) > 0
        g = np.pad(g, ((0, nKb * cpk - cin), (0, nNb * fpn - n_fb)))
        return g.reshape(nKb, cpk, nNb, fpn)

    def tile_mask(self, group_mask) -> np.ndarray:
        """(num_groups,) {0,1} -> (nKb, nNb) bool, host-side."""
        return self._group_grid(group_mask).any(axis=(1, 3))

    def implicit_index_table(self, group_mask):
        """Offset-augmented dispatch table for the implicit kernel.

        Returns ``(entries, cnt, taps)``: ``entries[j, s] = (k_tile,
        cin_start, cin_count)`` for live step ``s`` of output tile column
        ``j`` (the kernel's BlockSpec consumes column 0; the cin slice is
        what that K-tile id *means* against the NHWC activation), and
        ``taps[t] = (row_slot, dy, dx)`` maps in-tile row ``c*slot +
        row_slot`` to input pixel ``(ho*stride + dy, wo*stride + dx)`` of
        channel ``cin_start + c`` — the gather contract, and the bridge
        back to the materialized im2col rows (property-tested in
        ``tests/test_implicit_conv.py``)."""
        geo = self.implicit_geometry()
        plan = self.plan(group_mask)
        cin = self.spec.shape[2]
        cpk = geo["cpk"]
        nNb, max_nnz = plan.idx.shape
        entries = np.zeros((nNb, max_nnz, 3), np.int32)
        for j in range(nNb):
            for s in range(int(plan.cnt[j])):
                t = int(plan.idx[j, s])
                c0 = t * cpk
                entries[j, s] = (t, c0, max(0, min(cpk, cin - c0)))
        taps = np.asarray([[dy * geo["ky"] + dx, dy, dx]
                           for dy in range(geo["kx"])
                           for dx in range(geo["ky"])], np.int32)
        return entries, plan.cnt.copy(), taps

    def k_row_fill(self, group_mask) -> float:
        """Share of the dispatched K-tiles' rows that hold a weight of the
        conv (one tap of one input channel), the rest being slot and tile
        padding — read from the packing, not timed. A full packed tile
        holds 128 of 128 (1x1), 72 (3x3) or 98 (7x7)."""
        bk = self.block[0]
        t = np.arange(self.tiles[0])
        geo = self.implicit_geometry()
        cin, cpk = self.spec.shape[2], geo["cpk"]
        real = np.clip(cin - t * cpk, 0, cpk) * geo["kx"] * geo["ky"]
        live = self.tile_mask(group_mask).sum(axis=1)
        return float((real * live).sum() / max(live.sum() * bk, 1))

    def tile_occupancy(self, group_mask) -> Tuple[np.ndarray, np.ndarray]:
        """(live, total) schedule groups covered per tile, (nKb, nNb) ints.

        ``live.sum()`` is the paper-granularity live-step count (== the
        cycle model's DSB steps) regardless of how many groups share a
        tile."""
        live = self._group_grid(group_mask).sum(axis=(1, 3))
        total = self._group_grid(np.ones(self.spec.num_groups)).sum(axis=(1, 3))
        return live.astype(np.int64), total.astype(np.int64)

    def mac_accounting(self, group_mask) -> Tuple[int, int]:
        """(live weight elements, dispatched-tile MAC area) for this layer —
        the single source for padded-MAC utilization (``SparseConvExec`` and
        ``accel.simulator`` aggregate these over the network)."""
        live_tiles = int(self.tile_mask(group_mask).sum())
        gm = np.asarray(group_mask).reshape(-1) > 0
        live_elems = int((gm * self.spec.group_elem_counts()).sum())
        return live_elems, live_tiles * self.block[0] * self.block[1]

    def mac_utilization(self, group_mask) -> float:
        """Live weight elements / MAC area of the *dispatched* tiles — how
        much of the padded tile grid the kernel visits is real work."""
        live_elems, area = self.mac_accounting(group_mask)
        return live_elems / area if area else 0.0

    def plan(self, group_mask) -> BlockSparsePlan:
        return plan_from_tile_mask(self.tile_mask(group_mask), self.block)

    def pack_weight(self, w: jnp.ndarray) -> jnp.ndarray:
        """(kx, ky, cin, cout) -> (k_packed, n_packed)."""
        kxky, cin, cout, n_cu, n_fb, slot, cpk, fpn = self._packing()
        nKb, nNb = self.tiles
        bk, bn = self.block
        w2 = jnp.transpose(w.reshape(kxky, cin, cout), (1, 0, 2))
        w2 = jnp.pad(w2, ((0, nKb * cpk - cin), (0, slot - kxky),
                          (0, n_fb * n_cu - cout)))
        w2 = w2.reshape(nKb, cpk * slot, n_fb, n_cu)
        w2 = jnp.pad(w2, ((0, 0), (0, bk - cpk * slot),
                          (0, nNb * fpn - n_fb), (0, 0)))
        w2 = w2.reshape(nKb, bk, nNb, fpn * n_cu)
        w2 = jnp.pad(w2, ((0, 0), (0, 0), (0, 0), (0, bn - fpn * n_cu)))
        return w2.reshape(nKb * bk, nNb * bn)

    def pack_bias(self, b: jnp.ndarray) -> jnp.ndarray:
        """(cout,) -> (n_packed,), lanes aligned with ``pack_weight``."""
        kxky, cin, cout, n_cu, n_fb, slot, cpk, fpn = self._packing()
        nNb = self.tiles[1]
        bn = self.block[1]
        b2 = jnp.pad(b, (0, nNb * fpn * n_cu - cout)).reshape(nNb, fpn * n_cu)
        return jnp.pad(b2, ((0, 0), (0, bn - fpn * n_cu))).reshape(nNb * bn)

    def pack_patches(self, patches: jnp.ndarray) -> jnp.ndarray:
        """(..., kx, ky, cin) im2col patches -> (M, k_packed)."""
        kxky, cin, cout, n_cu, n_fb, slot, cpk, fpn = self._packing()
        nKb = self.tiles[0]
        bk = self.block[0]
        p = patches.reshape(-1, kxky, cin)
        p = jnp.transpose(p, (0, 2, 1))                   # channel-major K
        p = jnp.pad(p, ((0, 0), (0, nKb * cpk - cin), (0, slot - kxky)))
        p = p.reshape(-1, nKb, cpk * slot)
        p = jnp.pad(p, ((0, 0), (0, 0), (0, bk - cpk * slot)))
        return p.reshape(-1, nKb * bk)

    def unpack_output(self, out2d: jnp.ndarray, lead_shape) -> jnp.ndarray:
        """(M, n_packed) -> (*lead_shape, cout)."""
        kxky, cin, cout, n_cu, n_fb, slot, cpk, fpn = self._packing()
        nNb = self.tiles[1]
        bn = self.block[1]
        o = out2d.reshape(-1, nNb, bn)[:, :, :fpn * n_cu]
        o = o.reshape(-1, nNb * fpn, n_cu)[:, :n_fb, :]
        return o.reshape(-1, n_fb * n_cu)[:, :cout].reshape(*lead_shape, cout)


def conv_gemm_layout(spec: FpgaConvGroupSpec, *, bn: int = 128,
                     bk: int = 128) -> ConvGemmLayout:
    """Layout for a conv's im2col GEMM, tile grid aligned with ``spec``:
    MXU-shaped ``(bk, bn)`` tiles covering many (g, f_block) groups, with
    schedule-group accounting via :meth:`ConvGemmLayout.tile_occupancy`."""
    if not isinstance(spec, FpgaConvGroupSpec):
        raise TypeError(f"no conv GEMM layout for {type(spec).__name__}")
    kx, ky, cin, cout = spec.shape
    if spec.n_cu > bn:
        raise ValueError(f"n_cu={spec.n_cu} exceeds the {bn}-lane tile")
    slot = channel_slot(kx, ky)
    bk_eff = max(bk, slot)          # giant kernels: one channel/tile
    cpk, fpn = bk_eff // slot, bn // spec.n_cu
    return ConvGemmLayout(spec=spec, block=(bk_eff, bn),
                          tiles=(-(-cin // cpk), -(-spec.n_fblocks // fpn)))


def adaptive_bm(m_rows: int, cap: int = 128) -> int:
    """Materializing-path adaptive M-block: the whole (padded-to-8) row
    count when it fits under ``cap``, else ``cap`` — batch-1 tails stop
    padding a 16-row output up to a fixed 128."""
    return min(cap, _ceil_to(max(int(m_rows), 1), 8))


def conv_m_blocks(ho: int, wo: int, batch: int, *,
                  bm="auto") -> Tuple[int, int]:
    """(number of M-blocks, effective bm) of the materializing path's
    grid for one conv layer: flat ``B·Ho·Wo`` rows. ``bm`` is an int
    (fixed, the PR-3 contract) or ``"auto"`` (adaptive). The implicit
    kernel's grid is :func:`implicit_m_block`'s; ``SparseConvExec``,
    ``accel.simulator`` and the benches count steps by the two."""
    cap = 128 if bm == "auto" else int(bm)
    bm_eff = adaptive_bm(batch * ho * wo, cap) if bm == "auto" else cap
    return -(-batch * ho * wo // bm_eff), bm_eff


def implicit_m_block(layout: ConvGemmLayout, h: int, w: int, stride: int,
                     padding: str, itemsize: int, bm="auto", batch: int = 1,
                     activation_dsb: bool = False):
    """The implicit kernel's M-block for one conv geometry and a
    ``batch``-image call, or ``None`` when the layer takes the
    materializing path: no whole-row or column-segment M-block fits the
    ``bm`` cap, or the kernel's padded VMEM working set
    (:func:`repro.kernels.implicit_conv.window_vmem_bytes`, at operand
    width ``itemsize``) exceeds :data:`~repro.kernels.implicit_conv.
    SLAB_VMEM_BUDGET` even for one image. A fold of whole images
    (``choose_m_block``) whose windows exceed the budget is reduced to
    the largest that fits; an ``activation_dsb`` bind never folds (its
    skip tests one image's window). The one route and grid rule:
    :func:`make_sparse_conv` applies it once per input geometry (at bind
    time where the layer's geometry is given) and once per batch, and
    :func:`conv_hbm_bytes` and ``SparseConvExec``'s step accounting
    count by it."""
    from ..kernels import implicit_conv as IC
    from ..kernels.conv_lowering import conv_out_size

    kx, ky = layout.spec.shape[:2]
    ho = conv_out_size(h, kx, stride, padding)
    wo = conv_out_size(w, ky, stride, padding)
    cap = 128 if bm == "auto" else int(bm)
    mb = IC.choose_m_block(ho, wo, cap=cap,
                           batch=1 if activation_dsb else batch)
    while mb is not None and IC.window_vmem_bytes(
            mb, kx, ky, stride, layout.block[0],
            itemsize) > IC.SLAB_VMEM_BUDGET:
        # a cap just under the fold keeps the image's own block and folds
        # the next smaller divisor of the batch
        mb = (IC.choose_m_block(ho, wo, cap=mb.m_rows - 1, batch=batch)
              if mb.ipb > 1 else None)
    return mb


def conv_hbm_bytes(layout: ConvGemmLayout, group_mask, batch: int, h: int,
                   w: int, stride: int = 1, padding: str = "SAME", *,
                   implicit: bool, bm="auto", dtype_bytes: int = 4,
                   operand_bytes: Optional[int] = None,
                   out_bytes: Optional[int] = None,
                   activation_dsb: bool = False) -> int:
    """Analytic HBM bytes one forward of this conv layer moves — the
    data-movement contract the implicit kernel changes.

    Materializing: read the activation once (im2col), write the packed
    ``(M̂, k_packed)`` patch matrix, then stream one ``(bm, bk)`` patch
    tile + one ``(bk, bn)`` weight tile per live grid step and write the
    ``(M̂, n_packed)`` output. (A lower bound — XLA's im2col/pack
    intermediates add more unless fully fused.)

    Implicit (the layers :func:`implicit_m_block` routes there, others
    are priced as materializing): read the activation once and write
    its padded copy, channels padded to whole 128-lane groups, then
    stream one window DMA — ``rows × dma_cols`` pixels of a whole
    128-lane channel group for each image of the M-block — + one weight
    tile per live grid step and write the output; the patch matrix never
    exists. The activation terms are
    :func:`repro.kernels.implicit_conv.implicit_hbm_bytes`, the kernel's
    own window and padding formulas, at the M-block
    :func:`implicit_m_block` gives (``activation_dsb``: one image per
    M-block).

    ``operand_bytes`` prices the *operand* traffic (activations /
    patches / weights) separately from the f32 output write
    (``dtype_bytes``): pass ``1`` for the int8 Q2.5×Q3.4 execution —
    every per-step slab, patch tile and weight tile shrinks 4×, which is
    where quantized execution banks its bandwidth win. Default ``None``
    = same as ``dtype_bytes`` (the f32 contract).

    ``out_bytes`` prices the *output* write separately: pass ``1`` for
    the streamed contract (the requantizing epilogue emits int8 codes,
    so the flush writes 1 byte/value and the next layer's ingest — the
    operand side of *its* accounting — reads codes back). Default
    ``None`` = ``dtype_bytes`` (the f32 output write the PR-5 quantized
    contract still paid for).
    """
    from ..kernels.conv_lowering import conv_out_size
    from ..kernels.implicit_conv import implicit_hbm_bytes

    ob = dtype_bytes if operand_bytes is None else operand_bytes
    ob_out = dtype_bytes if out_bytes is None else out_bytes
    kx, ky, cin, cout = layout.spec.shape
    ho, wo = conv_out_size(h, kx, stride, padding), conv_out_size(w, ky, stride, padding)
    plan = layout.plan(group_mask)
    live = int(plan.cnt.sum())
    bk, bn = layout.block
    mbk = (implicit_m_block(layout, h, w, stride, padding, ob, bm,
                            batch=batch, activation_dsb=activation_dsb)
           if implicit else None)
    mb, bm_eff = ((batch // mbk.ipb * mbk.bpi, mbk.m_rows) if mbk is not None
                  else conv_m_blocks(ho, wo, batch, bm=bm))
    steps = mb * live
    w_bytes = steps * bk * bn * ob
    out_write = mb * bm_eff * layout.n_packed * ob_out
    if mbk is not None:
        c_packed = layout.tiles[0] * layout.implicit_geometry()["cpk"]
        ingest, window = implicit_hbm_bytes(batch, h, w, cin, c_packed, kx,
                                            ky, stride, padding, mbk, ob)
        return ingest + steps * window + w_bytes + out_write
    x_bytes = batch * h * w * cin * ob
    patches = mb * bm_eff * layout.k_packed * ob               # write once
    patch_reads = steps * bm_eff * bk * ob                     # kernel DMA
    return x_bytes + patches + patch_reads + w_bytes + out_write


def make_sparse_conv(layout: ConvGemmLayout, group_mask, *, bm="auto",
                     weight: Optional[jnp.ndarray] = None,
                     bias: Optional[jnp.ndarray] = None,
                     relu: bool = False,
                     implicit: Optional[bool] = None,
                     quant=None,
                     out_quant=None,
                     activation_dsb: bool = False,
                     trainable: bool = False,
                     geometry: Optional[Tuple[int, int, int, str]] = None,
                     name: str = "block_sparse_conv"):
    """Bind a Pallas block-sparse kernel to one conv layer's plan.

    Returns ``conv(x, w=None, stride=1, padding="SAME") -> (B, Ho, Wo, cout)``
    computing ``conv(x, w ⊙ expand(group_mask))`` — pruned groups are dead
    tiles the grid never dispatches (or zero slabs inside live tiles).
    The plan is static: rebind after HAPM prunes more groups (an
    epoch-boundary event).

    ``implicit`` selects the kernel (default ``None`` = auto):
      - ``True`` / auto: the **implicit-im2col** kernel
        (:mod:`repro.kernels.implicit_conv`) gathers kernel windows from
        the padded NHWC activation inside the grid — the ``(B·Ho·Wo,
        kx·ky·cin)`` patch matrix is never materialized in HBM. A geometry :func:`implicit_m_block` rejects
        (no M-block fits, or the padded window working set exceeds
        :data:`implicit_conv.SLAB_VMEM_BUDGET`) runs the materializing
        path instead.
        Forward-only (the materializing non-epilogue path keeps its VJP).
      - ``False``: the materializing im2col + ``block_sparse_matmul``
        path — the parity oracle.

    ``geometry``: ``(h, w, stride, padding)`` of the layer's input —
    the route is then chosen here, once: the implicit kernel's M-block
    (or the materializing path), ``conv.implicit`` says which, and a
    call at any other geometry raises ``ValueError``. Without it (a conv
    called at several input sizes) the same rule picks the route on the
    first call at each geometry and keeps it.

    ``bm``: M-blocking. ``"auto"`` (default) adapts to the layer —
    whole-output-row blocks for the implicit kernel, whole images folded
    into one block where one image's output fills fewer rows than the cap,
    ``ceil8(B·Ho·Wo)`` capped at 128 for the materializing path — so
    batch-1 tails stop padding 10×; an int pins the cap (the PR-3
    contract).

    ``weight``: bind-time prepacking. The masked weight is packed **once**
    here and the closure only pads the activation (implicit) or packs
    im2col patches (materializing) per call. Without it the closure masks
    + packs ``w`` on every call (test / legacy path).
    ``bias`` / ``relu``: fused kernel epilogue (per-cout bias add and ReLU
    at the accumulator flush — folded-BN inference entirely in-kernel).
    The epilogue path is forward-only.

    ``quant`` (a :class:`repro.core.quant.QuantSpec`): quantization as a
    property of the execution plan. The masked weight is emitted as
    **int8 codes** at pack time (pruned groups stay exactly zero codes),
    the per-cout dequant scale row is packed onto the same N lanes as the
    bias, the closure quantizes each call's activation to int8 codes
    (static Q3.4 or the spec's calibrated scale), and *both* kernels run
    int8-operand / int32-accumulate passes with the dequant → bias → ReLU
    epilogue fused at the flush. Output is f32. Forward-only (QAT trains
    through the fake-quant dense path and rebinds). An activation that is
    *already* int8 codes skips the per-call quantize — the streamed
    layer-to-layer ingest.

    ``out_quant`` (a second :class:`QuantSpec`, requires ``quant``):
    requantize **in-epilogue** — the flush multiplies by the output
    activation scale and rounds-saturates to int8 Q-format codes inside
    the kernel, so the layer *emits* 1-byte codes the next layer's gather
    consumes directly (no f32 round-trip through HBM). The closure then
    returns int8 codes; dequantize at the chain boundary with
    ``code / out_quant.act_scale``.

    ``activation_dsb`` (requires ``quant``): dual-sided sparsity — the
    implicit kernel reduces each DMA'd activation window to an
    any-nonzero flag and skips the gather+MXU pass when the int8 code
    block is all-zero (post-ReLU zeros are exact codes, so the skip is
    bit-exact at every density). The skip exists only in the implicit
    kernel, so a geometry routed to the materializing path raises
    ``ValueError`` (at bind time with ``geometry``, else at the call).
    ``conv.skip_counts(x, ...)`` runs the same bound kernel with the
    skip counter enabled and returns ``(y, stats)`` where ``stats`` is
    ``{"skipped_steps", "live_steps"}`` (``None`` on the materializing
    path) — the measured ``dsb_skip_frac`` source.

    ``trainable=True`` makes the closure differentiable in **both**
    arguments via a ``jax.custom_vjp``: ``conv(x, w, ...)`` re-packs the
    (possibly traced) ``w`` per call — so grads reach the caller's params —
    while the forward still dispatches the bound plan (implicit kernel
    included). The backward reuses the plan machinery end to end: dX runs
    the **transposed-plan** block-sparse GEMM on the packed output
    gradient, then the ``im2col → pack`` pipeline's own VJP scatters patch
    gradients back onto the activation; dW visits only the live tiles
    (:func:`repro.kernels.ops.make_block_sparse_grad_weight`) and flows
    through the mask-and-pack transpose, so pruned groups receive *exactly*
    zero gradient — HAPM's no-resurrection invariant holds by
    construction. Incompatible with the forward-only ``bias``/``relu``
    epilogue and ``quant`` paths (QAT trains through the f32 fake-quant
    view; this path runs the f32 kernels on whatever view the caller
    passes).

    ``name``: the kernel's name in the compiled program and in a device
    trace (``name.<n>``; ``bind_execution`` passes the layer path, e.g.
    ``conv_s0b1_conv2``). It renames the jitted function that holds the
    kernel, never the kernel body.

    ``conv.plan`` / ``conv.layout`` / ``conv.group_mask`` /
    ``conv.implicit`` / ``conv.quant`` / ``conv.trainable`` expose the
    dispatch accounting; ``conv.m_block(h, w, stride, padding, batch=1)``
    the route at an input geometry (``None`` = materializing), its
    M-block folded for a ``batch``-image call.
    """
    from ..kernels import ops
    from ..kernels import implicit_conv as IC
    from ..kernels.block_sparse_matmul import block_sparse_matmul
    from ..kernels.conv_lowering import conv_out_size, im2col_patches

    if trainable and (quant is not None or bias is not None or relu):
        raise ValueError(
            "trainable sparse convs run the plain f32 kernels — the fused "
            "bias/ReLU epilogue and int8-code paths are inference-only "
            "(fold/quantize at inference bind time instead)")
    if out_quant is not None and quant is None:
        raise ValueError(
            "out_quant requantizes the int8 epilogue — it requires quant "
            "(int8-code operands) as well")
    if activation_dsb and quant is None:
        raise ValueError(
            "activation_dsb skips on exact int8 zero codes — it requires "
            "quant (int8-code operands); f32 zeros are a tolerance "
            "question the kernel refuses to answer")
    gm = np.asarray(group_mask)
    tm = layout.tile_mask(gm)
    plan = plan_from_tile_mask(tm, layout.block)
    geo = layout.implicit_geometry()
    use_implicit = implicit is not False
    if activation_dsb and not use_implicit:
        raise ValueError(
            "activation_dsb lives in the implicit kernel's window gather "
            "— bind with implicit=True")
    adaptive = bm == "auto"
    bm_cap = 128 if adaptive else int(bm)
    packed_bias = (None if bias is None
                   else layout.pack_bias(jnp.asarray(bias, jnp.float32)))
    # the dequant row is a bind-time constant: it depends on the quant
    # spec's (static or calibrated) scales, never on a per-call weight
    packed_scale = (None if quant is None else layout.pack_bias(
        jnp.asarray(quant.dequant_row(layout.spec.shape[-1]), jnp.float32)))
    # requantize row: one uniform output activation scale per cout lane
    # (padding lanes get scale 0 -> code 0, discarded by unpack_output)
    packed_out_scale = (None if out_quant is None else layout.pack_bias(
        jnp.full((layout.spec.shape[-1],), out_quant.act_scale, jnp.float32)))
    idx_dev, cnt_dev = jnp.asarray(plan.idx), jnp.asarray(plan.cnt)
    implicit_kernel = IC.named(name)
    mms: dict = {}        # materializing kernels, keyed by effective bm

    def _materializing(bm_eff):
        if bm_eff not in mms:
            mms[bm_eff] = ops.named_jit(ops.make_block_sparse_matmul(
                plan, tm, bm=bm_eff, bias=packed_bias, relu=relu,
                scale=packed_scale, out_scale=packed_out_scale), name)
        return mms[bm_eff]

    gm_dev = jnp.asarray(gm, jnp.float32)

    def _pack_w(w):
        wm = apply_group_mask(layout.spec, w, gm_dev.astype(w.dtype))
        if quant is None:
            return layout.pack_weight(wm)
        # int8 codes packed onto the tile grid: zero-masked groups emit
        # zero codes, padding stays zero codes — the GEMM is exact
        return layout.pack_weight(quant.weight_codes(wm))

    if weight is not None:
        w_packed = _pack_w(weight)
        bound_hw = weight.shape[:2]
    else:
        w_packed, bound_hw = None, None

    # operand width the route's VMEM budget is checked at: int8 codes
    # under quant, else f32 (the widest operand the kernels take)
    itemsize = 1 if quant is not None else 4
    routes: dict = {}     # (h, w, stride, padding) -> MBlock | None

    def _choose(h, w, stride, padding):
        mbk = (implicit_m_block(layout, h, w, stride, padding, itemsize, bm)
               if use_implicit else None)
        if activation_dsb and mbk is None:
            raise ValueError(
                f"activation_dsb lives in the implicit kernel, but input "
                f"geometry {(h, w, stride, padding)} does not fit its "
                "M-block cap and VMEM window budget")
        return mbk

    if geometry is not None:
        geometry = (int(geometry[0]), int(geometry[1]), int(geometry[2]),
                    geometry[3])
        routes[geometry] = _choose(*geometry)

    def _route(h, w, stride=1, padding="SAME", batch=1):
        key = (int(h), int(w), int(stride), padding)
        if key not in routes:
            if geometry is not None:
                raise ValueError(
                    f"conv bound for input geometry (h, w, stride, padding) "
                    f"= {geometry}, called at {key} — rebind for this "
                    "geometry")
            routes[key] = _choose(*key)
        # the route is the geometry's; a call's batch only folds whole
        # images into its blocks
        if routes[key] is None:
            return None
        return implicit_m_block(layout, *key, itemsize, bm, batch=int(batch),
                                activation_dsb=activation_dsb)

    def _run(x, wp, kx, ky, stride, padding, count_skips=False):
        """Forward with an already-packed weight ``wp`` (concrete or
        traced) on the route of ``x``'s geometry: the implicit kernel at
        its M-block, or the materializing path. With ``count_skips``
        returns ``(y, stats)`` — the kernel-side skip counter summed into
        ``{"skipped_steps", "live_steps"}``, ``None`` off the implicit
        path."""
        B, H, W, C = x.shape
        ho = conv_out_size(H, kx, stride, padding)
        wo = conv_out_size(W, ky, stride, padding)
        mbk = _route(H, W, stride, padding, B)
        if mbk is not None:
            cpk, slot = geo["cpk"], geo["slot"]
            xp = IC.pad_input(x, kx, ky, stride, padding, mbk,
                              layout.tiles[0] * cpk)
            res = implicit_kernel(
                xp, wp, idx_dev, cnt_dev, packed_bias, packed_scale,
                packed_out_scale,
                kx=kx, ky=ky, stride=stride, mb=mbk,
                block=layout.block, cpk=cpk, slot=slot, relu=relu,
                activation_dsb=activation_dsb,
                count_skips=count_skips,
                interpret=ops._interpret())
            out2d, skips = res if count_skips else (res, None)
            o = IC.crop_output(out2d, mbk, B, ho, wo)
            y = layout.unpack_output(o.reshape(B * ho * wo, -1), (B, ho, wo))
            if count_skips:
                live = B // mbk.ipb * mbk.bpi * int(plan.cnt.sum())
                return y, {"skipped_steps": int(skips.sum()),
                           "live_steps": live}
            return y
        patches = im2col_patches(x, kx, ky, stride, padding)
        bm_eff = adaptive_bm(B * ho * wo, bm_cap) if adaptive else bm_cap
        out2d = _materializing(bm_eff)(layout.pack_patches(patches), wp)
        y = layout.unpack_output(out2d, (B, ho, wo))
        return (y, None) if count_skips else y

    # -- trainable path: a custom_vjp per conv geometry --------------------
    # The primal dispatches the same bound plan as inference (implicit
    # kernel included) but re-packs the traced weight per call. Backward:
    #   dX: packed dY  --transposed-plan GEMM-->  packed dPatches
    #       --vjp of (im2col -> pack_patches)-->  dX      (pure jnp pipeline)
    #   dW: live tiles only (block_sparse_grad_weight), then the vjp of
    #       (mask -> pack_weight) — the group-mask multiply inside _pack_w
    #       zeroes pruned groups exactly, dead tiles were never computed.
    if trainable:
        t_plan = transpose_plan(plan, tm)
        t_idx, t_cnt = jnp.asarray(t_plan.idx), jnp.asarray(t_plan.cnt)
    train_fns: dict = {}
    dw_fns: dict = {}

    def _train_fn(kx, ky, stride, padding):
        key = (kx, ky, stride, padding)
        if key in train_fns:
            return train_fns[key]

        @jax.custom_vjp
        def fn(x, w):
            return _run(x, _pack_w(w), kx, ky, stride, padding)

        def fwd(x, w):
            return fn(x, w), (x, w)

        def bwd(res, g):
            x, w = res
            B, ho, wo = g.shape[:3]
            # pack the output gradient onto the kernel's padded N lanes —
            # unpack_output is a pure slice/reshape, so its VJP *is* the
            # transpose packing (zeros into the padded lanes)
            m_rows = B * ho * wo
            _, unpack_vjp = jax.vjp(
                lambda o2: layout.unpack_output(o2, (B, ho, wo)),
                jnp.zeros((m_rows, layout.n_packed), g.dtype))
            g2d, = unpack_vjp(g)
            # packed patches, with the activation-scatter VJP alongside
            p2d, patch_vjp = jax.vjp(
                lambda xx: layout.pack_patches(
                    im2col_patches(xx, kx, ky, stride, padding)), x)
            bm_eff = adaptive_bm(m_rows, bm_cap) if adaptive else bm_cap
            # dX: transposed-plan block-sparse GEMM (dP = dY @ Wp^T)
            wp = _pack_w(w)
            gp, _ = ops._pad_rows(g2d, bm_eff)
            dp = block_sparse_matmul(
                gp, jnp.swapaxes(wp, 0, 1), t_idx, t_cnt,
                block=t_plan.block, bm=bm_eff,
                interpret=ops._interpret())[:m_rows]
            dx, = patch_vjp(dp)
            # dW: live tiles only, then the mask-and-pack transpose
            if bm_eff not in dw_fns:
                dw_fns[bm_eff] = ops.make_block_sparse_grad_weight(
                    tm, layout.block, bm=bm_eff)
            dwp = dw_fns[bm_eff](p2d, g2d)
            _, packw_vjp = jax.vjp(_pack_w, w)
            dw, = packw_vjp(dwp)
            return dx.astype(x.dtype), dw.astype(w.dtype)

        fn.defvjp(fwd, bwd)
        train_fns[key] = fn
        return fn

    def conv(x, w=None, stride: int = 1, padding: str = "SAME"):
        if w is None:
            if w_packed is None:
                raise ValueError("no weight bound at build time — pass w or "
                                 "rebuild with make_sparse_conv(..., weight=w)")
            if quant is not None and x.dtype != jnp.int8:
                x = quant.act_codes(x)      # int8 Q3.4 (or calibrated) codes
            return _run(x, w_packed, *bound_hw, stride, padding)
        if trainable:
            return _train_fn(int(w.shape[0]), int(w.shape[1]), stride,
                             padding)(x, w)
        if quant is not None and x.dtype != jnp.int8:
            x = quant.act_codes(x)
        return _run(x, _pack_w(w), int(w.shape[0]), int(w.shape[1]), stride,
                    padding)

    def skip_counts(x, stride: int = 1, padding: str = "SAME"):
        """Run the bound conv with the kernel-side skip counter on:
        ``(y, {"skipped_steps", "live_steps"})`` — ``y`` identical to
        ``conv(x, ...)`` (the counter is a second output, not a
        different kernel), stats ``None`` when the geometry's route is
        the materializing path. Counts actual skips, so a bind without
        ``activation_dsb`` reports 0."""
        if w_packed is None:
            raise ValueError("no weight bound at build time — "
                             "skip_counts needs a prebound conv")
        if quant is not None and x.dtype != jnp.int8:
            x = quant.act_codes(x)
        return _run(x, w_packed, *bound_hw, stride, padding,
                    count_skips=True)

    conv.plan = plan
    conv.layout = layout
    conv.group_mask = gm
    conv.prebound = weight is not None
    # the route taken where the geometry is bound, else the request
    conv.implicit = (use_implicit if geometry is None
                     else routes[geometry] is not None)
    conv.m_block = _route
    conv.bm = bm
    conv.quant = quant
    conv.out_quant = out_quant
    conv.activation_dsb = activation_dsb
    conv.trainable = trainable
    conv.skip_counts = skip_counts
    return conv
