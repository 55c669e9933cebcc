"""Jit'd public wrappers around the Pallas kernels: shape normalization
(leading batch dims, M-padding), interpret-mode auto-detection (CPU runs the
kernel bodies in interpret mode; TPU compiles them), and custom VJPs so the
kernels compose with autodiff.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import quant as Q
from ..sparse.block_mask import BlockSparsePlan, plan_from_tile_mask, transpose_plan
from .block_sparse_matmul import block_sparse_grad_weight, block_sparse_matmul
from .int8_matmul import int8_matmul


def _interpret() -> bool:
    """Pallas interpret mode on the CPU (tests, rehearsals); compiled
    Mosaic kernels on a TPU. Any other backend has no way to run the
    kernels and is refused rather than silently interpreted."""
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(
        f"the Pallas kernels run compiled on a TPU or interpreted on the "
        f"CPU; JAX's default backend is {backend!r}")


def _pad_rows(x2d: jnp.ndarray, bm: int):
    M = x2d.shape[0]
    Mp = -(-M // bm) * bm
    if Mp != M:
        x2d = jnp.pad(x2d, ((0, Mp - M), (0, 0)))
    return x2d, M


def make_block_sparse_grad_weight(tile_mask: np.ndarray,
                                  block: Tuple[int, int], *, bm: int = 128):
    """Build ``dw_fn(x2d, g2d) -> x2d^T @ g2d`` on the live tiles of
    ``tile_mask`` only (``kernels.block_sparse_grad_weight``), scattered
    back onto the full packed ``(K, N)`` grid with pruned tiles *exactly*
    zero — the dW half of every block-sparse backward. Rows of ``x2d`` /
    ``g2d`` are zero-padded to the ``bm`` multiple (zero rows contribute
    nothing to the product)."""
    tm = np.asarray(tile_mask)
    live = np.argwhere(tm)
    nKb, nNb = tm.shape
    bk, bn = block
    kk = jnp.asarray(live[:, 0], jnp.int32)
    nn = jnp.asarray(live[:, 1], jnp.int32)

    def dw_fn(x2d, g2d):
        if live.shape[0] == 0:
            return jnp.zeros((nKb * bk, nNb * bn), jnp.float32)
        xp, _ = _pad_rows(x2d.astype(jnp.float32), bm)
        gp, _ = _pad_rows(g2d.astype(jnp.float32), bm)
        compact = block_sparse_grad_weight(xp, gp, kk, nn, block=(bk, bn),
                                           bm=bm, interpret=_interpret())
        dw = jnp.zeros((nKb, nNb, bk, bn), compact.dtype)
        dw = dw.at[live[:, 0], live[:, 1]].set(compact)
        return dw.transpose(0, 2, 1, 3).reshape(nKb * bk, nNb * bn)

    return dw_fn


def make_block_sparse_matmul(plan: BlockSparsePlan, tile_mask: np.ndarray, *,
                             bm: int = 128, bias=None, relu: bool = False,
                             scale=None, out_scale=None):
    """Build ``f(x, w) -> x @ (w ⊙ mask)`` for a *fixed* pruning plan.

    The plan is static (recompiled when HAPM prunes more groups — an
    epoch-boundary event). Backward:
      dx = dy @ (w ⊙ m)^T   — block-sparse with the transposed plan
      dw = x^T dy           — live tiles only (``block_sparse_grad_weight``),
                              pruned tiles exactly zero by construction

    ``bias`` (a length-N vector in the *packed* column layout) and/or
    ``relu`` fuse the inference epilogue into the kernel's flush step;
    that variant is forward-only (no custom VJP) — it exists for the
    folded-BN inference path, not training. ``scale`` (same packed column
    layout) is the int8 dequant row: pass it together with int8 code
    operands and the kernel accumulates in int32, flushing
    ``acc * scale (+ bias) (relu)`` as f32 — also forward-only.
    ``out_scale`` (same packed column layout) additionally requantizes
    the flush to int8 Q-format codes (streamed activations).
    """
    idx, cnt = jnp.asarray(plan.idx), jnp.asarray(plan.cnt)
    block = plan.block

    if bias is not None or relu or scale is not None:
        b = None if bias is None else jnp.asarray(bias, jnp.float32)
        sc = None if scale is None else jnp.asarray(scale, jnp.float32)
        osc = None if out_scale is None else jnp.asarray(out_scale,
                                                         jnp.float32)

        def f_epilogue(x, w):
            lead = x.shape[:-1]
            xp, M = _pad_rows(x.reshape(-1, x.shape[-1]), bm)
            out = block_sparse_matmul(xp, w, idx, cnt, b, sc, osc,
                                      block=block, bm=bm, relu=relu,
                                      interpret=_interpret())[:M]
            return out.reshape(*lead, w.shape[1])

        return f_epilogue

    assert out_scale is None, (
        "out_scale requires the epilogue path (scale/bias/relu)")

    t_plan = transpose_plan(plan, tile_mask)
    t_idx, t_cnt = jnp.asarray(t_plan.idx), jnp.asarray(t_plan.cnt)
    dw_fn = make_block_sparse_grad_weight(tile_mask, block, bm=bm)

    def _fwd2d(x2d, w):
        xp, M = _pad_rows(x2d, bm)
        out = block_sparse_matmul(xp, w, idx, cnt, block=block, bm=bm,
                                  interpret=_interpret())
        return out[:M]

    @jax.custom_vjp
    def f(x, w):
        lead = x.shape[:-1]
        out = _fwd2d(x.reshape(-1, x.shape[-1]), w)
        return out.reshape(*lead, w.shape[1])

    def f_fwd(x, w):
        return f(x, w), (x, w)

    def f_bwd(res, g):
        x, w = res
        lead = x.shape[:-1]
        g2d = g.reshape(-1, w.shape[1])
        gp, M = _pad_rows(g2d, bm)
        dx = block_sparse_matmul(gp, jnp.swapaxes(w, 0, 1), t_idx, t_cnt,
                                 block=t_plan.block, bm=bm, interpret=_interpret())[:M]
        x2d = x.reshape(-1, x.shape[-1])
        dw = dw_fn(x2d, g2d).astype(w.dtype)
        return dx.reshape(x.shape).astype(x.dtype), dw

    f.defvjp(f_fwd, f_bwd)
    return f


def fixed_point_matmul(
    x: jnp.ndarray,                 # (..., K) float
    w: jnp.ndarray,                 # (K, N) float
    x_fmt: Q.QFormat = Q.Q3_4,
    w_fmt: Q.QFormat = Q.Q2_5,
    *,
    bm: int = 128,
) -> jnp.ndarray:
    """Paper-faithful fixed-point GEMM: quantize to integer codes, int8 MXU
    matmul, scalar dequant. Straight-through gradient."""
    lead = x.shape[:-1]
    K, N = w.shape

    @jax.custom_vjp
    def f(x, w):
        xc = Q.to_int8(x, x_fmt).reshape(-1, K)
        wc = Q.to_int8(w, w_fmt)
        xp, M = _pad_rows(xc, bm)
        scale = jnp.asarray([1.0 / (x_fmt.scale * w_fmt.scale)], jnp.float32)
        out = int8_matmul(xp, wc, scale, bm=bm, interpret=_interpret())[:M]
        return out.reshape(*lead, N).astype(x.dtype)

    def f_fwd(x, w):
        return f(x, w), (x, w)

    def f_bwd(res, g):
        x, w = res
        dx = (g @ w.T).astype(x.dtype)
        x2d = x.reshape(-1, K)
        g2d = g.reshape(-1, N)
        dw = (x2d.T @ g2d).astype(w.dtype)
        return dx, dw

    f.defvjp(f_fwd, f_bwd)
    return f(x, w)


def block_sparse_from_hapm(w: np.ndarray, element_mask: np.ndarray,
                           block: Tuple[int, int] = (128, 128), *, bm: int = 128):
    """Convenience: HAPM element mask -> plan -> bound kernel + masked weight."""
    from ..sparse.block_mask import tile_mask_from_weight
    tm = tile_mask_from_weight(np.asarray(element_mask), block)
    plan = plan_from_tile_mask(tm, block)
    f = make_block_sparse_matmul(plan, tm, bm=bm)
    return f, plan
