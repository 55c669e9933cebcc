"""The benchmark's own copy of HAPM's group selection, frozen.

One epoch of HAPM (paper Algorithm 3) on a fresh network: every conv
weight (HWIO, ``(kx, ky, cin, cout)``) is cut into FPGA schedule groups,
one group per input channel and block of ``n_cu`` consecutive output
filters (all ``kx * ky`` taps of them); the groups of the whole network
are pooled, ranked by the sum of their absolute weights (stable order,
params in tree order), and the lowest ``round(sparsity * total)`` are
zeroed. Same selection as the program's ``core/hapm`` +
``core/groups.fpga_conv_groups`` with ``HAPMConfig(sparsity, epochs=1)``,
but computed in one jitted call on the device, so a later change to the
program's pruning cannot change the model the cells serve.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _conv_leaves(params):
    """``[(path, weight)]`` of every 4-D leaf, in tree-flatten order."""
    return [(path, leaf) for path, leaf
            in jax.tree_util.tree_flatten_with_path(params)[0]
            if getattr(leaf, "ndim", 0) == 4]


def _slab_scores(w, n_cu: int):
    """Sum of |w| per (cin, f_block) group, group ids cin-major."""
    kx, ky, cin, cout = w.shape
    n_fb = -(-cout // n_cu)
    if n_fb * n_cu != cout:
        w = jnp.pad(w, ((0, 0), (0, 0), (0, 0), (0, n_fb * n_cu - cout)))
    slabs = jnp.transpose(w.reshape(kx * ky, cin, n_fb, n_cu),
                          (1, 2, 0, 3)).reshape(cin, n_fb, kx * ky * n_cu)
    return jnp.sum(jnp.abs(slabs), axis=-1).reshape(-1)


def _expand(keep, shape, n_cu: int):
    kx, ky, cin, cout = shape
    n_fb = -(-cout // n_cu)
    m = jnp.broadcast_to(keep.reshape(cin, n_fb)[None, None, :, :, None],
                         (kx, ky, cin, n_fb, n_cu))
    return m.reshape(kx, ky, cin, n_fb * n_cu)[..., :cout]


def n_pruned(total: int, sparsity: float) -> int:
    """Groups pruned by one HAPM epoch of ``HAPMConfig(sparsity, 1)``:
    ``min(ceil(s * total), round(s * total))`` (Python's round)."""
    return min(int(math.ceil(sparsity * total)), int(round(sparsity * total)))


def group_keep_masks(params, n_cu: int, sparsity: float):
    """``{path: (cin * n_fb,) float32 0/1}`` group masks (jit-able)."""
    leaves = _conv_leaves(params)
    scores = [_slab_scores(w, n_cu) for _, w in leaves]
    pooled = jnp.concatenate(scores)
    g = n_pruned(int(pooled.shape[0]), sparsity)
    order = jnp.argsort(pooled, stable=True)
    keep = jnp.ones(pooled.shape, jnp.float32).at[order[:g]].set(0.0)
    out, off = {}, 0
    for (path, _), sc in zip(leaves, scores):
        out[jax.tree_util.keystr(path)] = keep[off:off + sc.shape[0]]
        off += sc.shape[0]
    return out


def prune(params, n_cu: int, sparsity: float):
    """``params`` with HAPM's lowest groups zeroed (jit-able)."""
    keeps = group_keep_masks(params, n_cu, sparsity)

    def f(path, leaf):
        if getattr(leaf, "ndim", 0) != 4:
            return leaf
        return leaf * _expand(keeps[jax.tree_util.keystr(path)], leaf.shape,
                              n_cu).astype(leaf.dtype)
    return jax.tree_util.tree_map_with_path(f, params)
