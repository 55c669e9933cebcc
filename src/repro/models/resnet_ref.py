"""Plain references of the served ResNets, written from the papers and
sharing no code with :mod:`repro.models.cnn` or the kernels.

The network (He et al. 2016, arXiv 1512.03385): a stem conv (CIFAR: 3x3
stride 1; ImageNet: 7x7 stride 2, then a 3x3 stride-2 max-pool), then
stages of residual blocks, ``relu(main(h) + shortcut(h))``. A stage's
first block strides by 2 from stage 1 on. A basic block's main branch
is 3x3 -> 3x3 at the stage width, its stride on the first conv; a
bottleneck's is 1x1 -> 3x3 -> 1x1 with the last at 4x the stage width,
its stride on the 3x3 ("v1.5"). The shortcut is a 1x1 projection at the
block's stride where the stride or the width changes. Global average
pool, a linear classifier. Every conv and BatchNorm has the param path
the program uses (``s1b0/conv2``, ``bn2``; ``proj``/``bnp``;
``conv0``/``bn0``), and every conv and the max-pool pad "SAME"; the
max-pool pads with zeros, which equals the max's identity after a ReLU.

Two forwards, both ``jax.numpy`` with no kernels, caches or batching:

- :func:`forward_f32`: float32, BatchNorm from its running statistics,
  every matmul and conv at ``jax.default_matmul_precision("highest")``;
- :func:`forward_int8`: the integer twin of the streamed int8 contract.
  BatchNorm folds into each conv; each conv's weights become int8 codes
  at a per-output-channel scale (absmax -> 127, or 7 at ``w_bits=4``);
  the frame becomes Q3.4 codes (x * 16, round half to even, saturate at
  +-127); each conv accumulates int8 x int8 in int32, dequantizes by
  ``1 / (scale * 16)``, adds its bias, applies its ReLU and requantizes
  to Q3.4 codes; the max-pool takes the max of codes; residual adds and
  their ReLU run on codes, clamped to [0, 127]; the head dequantizes
  once, averages and applies the classifier in f32 at full precision.

``cfg`` is any object with the fields of ``cnn.ResNetConfig`` (stages,
widths, in_channels, image_size, bn_eps, block, stem).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

ACT_SCALE = 16.0          # Q3.4
ACT_MAX = 127


def layers(cfg):
    """``(stem, blocks)``: the stem as ``(path, bn, k, stride, relu)`` and
    each block as ``(name, main convs, projection or None)`` in the same
    form, in execution order."""
    imagenet = cfg.stem == "imagenet"
    stem = (("conv0",), "bn0", 7 if imagenet else 3, 2 if imagenet else 1,
            True)
    blocks, cin = [], cfg.widths[0]
    for si, (n_blocks, width) in enumerate(zip(cfg.stages, cfg.widths)):
        cout = width * (4 if cfg.block == "bottleneck" else 1)
        for bi in range(n_blocks):
            name = f"s{si}b{bi}"
            s = 2 if si > 0 and bi == 0 else 1
            ks = ((1, 1, True), (3, s, True), (1, 1, False)) \
                if cfg.block == "bottleneck" else ((3, s, True), (3, 1, False))
            main = [((name, f"conv{i}"), f"bn{i}", k, st, relu)
                    for i, (k, st, relu) in enumerate(ks, 1)]
            proj = (((name, "proj"), "bnp", 1, s, False)
                    if s != 1 or cin != cout else None)
            blocks.append((name, main, proj))
            cin = cout
    return stem, blocks


def _node(tree, path):
    return tree if len(path) == 1 else tree[path[0]]


def _conv(x, w, stride, **kw):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), **kw)


def _max_pool(h):
    """3x3 stride-2 max over the SAME-padded input, padded with zeros."""
    pads = []
    for size in h.shape[1:3]:
        total = max((-(-size // 2) - 1) * 2 + 3 - size, 0)
        pads.append((total // 2, total - total // 2))
    h = jnp.pad(h, ((0, 0), *pads, (0, 0)))
    low = (-jnp.inf if jnp.issubdtype(h.dtype, jnp.floating)
           else jnp.iinfo(h.dtype).min)
    return jax.lax.reduce_window(h, jnp.array(low, h.dtype), jax.lax.max,
                                 (1, 3, 3, 1), (1, 2, 2, 1), "VALID")


def _walk(cfg, x, conv, pool, add):
    """The network's dataflow on ``x`` with ``conv(site, h)`` per conv,
    ``pool`` after the ImageNet stem and ``add(main, shortcut)`` per
    block; returns the last block's output."""
    stem, blocks = layers(cfg)
    h = conv(stem, x)
    if cfg.stem == "imagenet":
        h = pool(h)
    for _, main, proj in blocks:
        y = h
        for site in main:
            y = conv(site, y)
        h = add(y, h if proj is None else conv(proj, h))
    return h


def forward_f32(params, state, cfg, x):
    """float32 logits of frames ``x`` (B, H, W, C), BatchNorm in
    inference mode, at the highest matmul precision."""
    def conv(site, h):
        path, bn, _, stride, relu = site
        p, s = _node(params, path), _node(state, path)
        y = _conv(h, p[path[-1]]["w"], stride)
        y = ((y - s[bn]["mean"]) / jnp.sqrt(s[bn]["var"] + cfg.bn_eps)
             * p[bn]["scale"] + p[bn]["bias"])
        return jnp.maximum(y, 0.0) if relu else y

    with jax.default_matmul_precision("highest"):
        h = _walk(cfg, x, conv, _max_pool,
                  lambda y, sc: jnp.maximum(y + sc, 0.0))
        return jnp.mean(h, axis=(1, 2)) @ params["fc"]["w"] + params["fc"]["b"]


def _round_sat(x, max_code):
    return jnp.clip(jnp.round(x), -max_code, max_code)


def int8_layers(params, state, cfg, w_bits: int = 8):
    """``{path: (int8 weight codes, dequant row, bias)}``: BatchNorm folded
    into each conv, weights on a per-output-channel code scale (absmax ->
    ``2**(w_bits-1) - 1``; an all-zero channel takes scale 32)."""
    w_max = 2 ** (w_bits - 1) - 1
    out = {}
    stem, blocks = layers(cfg)
    for path, bn, *_ in [stem] + [site for _, main, proj in blocks
                                  for site in main + ([proj] if proj else [])]:
        p, s = _node(params, path), _node(state, path)
        g = p[bn]["scale"] * jax.lax.rsqrt(s[bn]["var"] + cfg.bn_eps)
        w = p[path[-1]]["w"] * g[None, None, None, :]
        b = p[bn]["bias"] - s[bn]["mean"] * g
        absmax = np.asarray(jnp.max(jnp.abs(w.reshape(-1, w.shape[-1])),
                                    axis=0), np.float64)
        ws = jnp.asarray(np.where(absmax > 0, w_max / np.maximum(absmax, 1e-30),
                                  32.0).astype(np.float32))
        out[path] = (_round_sat(w * ws, w_max).astype(jnp.int8),
                     1.0 / (ws * ACT_SCALE), b)
    return out


def forward_int8(params, state, cfg, x, w_bits: int = 8):
    """The integer twin's logits of frames ``x`` (B, H, W, C) in [0, 1]."""
    table = int8_layers(params, state, cfg, w_bits)

    def conv(site, h):
        path, _, _, stride, relu = site
        codes, row, b = table[path]
        acc = _conv(h, codes, stride, preferred_element_type=jnp.int32)
        y = acc.astype(jnp.float32) * row + b
        if relu:
            y = jnp.maximum(y, 0.0)
        return _round_sat(y * ACT_SCALE, ACT_MAX).astype(jnp.int8)

    def add(y, sc):
        return jnp.clip(y.astype(jnp.int32) + sc.astype(jnp.int32), 0,
                        ACT_MAX).astype(jnp.int8)

    frame = _round_sat(x * ACT_SCALE, ACT_MAX).astype(jnp.int8)
    h = _walk(cfg, frame, conv, _max_pool, add)
    pooled = jnp.mean(h.astype(jnp.float32) / ACT_SCALE, axis=(1, 2))
    return (jnp.dot(pooled, params["fc"]["w"],
                    precision=jax.lax.Precision.HIGHEST) + params["fc"]["b"])
