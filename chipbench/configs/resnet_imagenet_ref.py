"""Plain reference of the served ImageNet ResNets: a ``jax.numpy`` integer
twin of the streamed int8 contract. Imports nothing of the program.

The network (He et al. 2016, arXiv 1512.03385, Table 1): a 7x7 stride-2
stem conv, a 3x3 stride-2 max-pool, then stages of bottleneck blocks
``relu(main(h) + shortcut(h))``: 1x1 -> 3x3 -> 1x1, the last at
``expansion`` x the stage width, the stride-2 of a stage's first block
(from stage 1 on) on the 3x3 ("v1.5"); a 1x1 projection shortcut at the
block's stride where the stride or the width changes. Every conv and the
max-pool pad "SAME"; the max-pool pads with zero codes (its input
follows a ReLU).

The contract is :mod:`resnet_cifar_ref`'s: BatchNorm folded into each
conv, per-output-channel int8 weight codes (absmax -> 127; -> 7 at
``w_bits=4``, the control), the frame as Q3.4 codes, int8 x int8 convs
accumulated in int32, dequantize, bias, ReLU where the network has one,
requantize to Q3.4 codes; the max-pool on codes; residual adds and their
ReLU on codes, clamped to [0, 127]; the head dequantizes once, averages
and applies the classifier in f32 at full precision.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.configs.resnet_cifar_ref import (  # noqa: F401
    ACT_MAX, ACT_SCALE, HIGHEST, logits_in_blocks, round_sat, weight_scales)


def blocks(cfg: dict):
    """``(stem, [(main convs, projection or None)])``, each conv as
    ``(path, kernel, stride, in_size, cin, cout, relu)``, where ``path``
    is the param path of the conv's node (``("s1b0", "conv2")``)."""
    size, cin = cfg["image_size"], cfg["widths"][0]
    stem = (("conv0",), 7, 2, size, cfg["in_channels"], cin, True)
    feat = -(-(-(-size // 2)) // 2)              # the stem, the max-pool
    out = []
    for si, n_blocks in enumerate(cfg["stages"]):
        width = cfg["widths"][si]
        cout = width * cfg["expansion"]
        for bi in range(n_blocks):
            name = f"s{si}b{bi}"
            s = 2 if (si > 0 and bi == 0) else 1
            nxt = -(-feat // s)
            main = [((name, "conv1"), 1, 1, feat, cin, width, True),
                    ((name, "conv2"), 3, s, feat, width, width, True),
                    ((name, "conv3"), 1, 1, nxt, width, cout, False)]
            proj = (((name, "proj"), 1, s, feat, cin, cout, False)
                    if s != 1 or cin != cout else None)
            out.append((main, proj))
            feat, cin = nxt, cout
    return stem, out


def conv_layers(cfg: dict):
    """Every conv in execution order (a block's main branch, then its
    projection), as :func:`blocks` gives them."""
    stem, blks = blocks(cfg)
    return [stem] + [c for main, proj in blks
                     for c in main + ([proj] if proj else [])]


def bn_key(path) -> str:
    return {"conv0": "bn0", "proj": "bnp"}.get(path[-1], "bn" + path[-1][4:])


def fold(params, state, cfg: dict):
    """BatchNorm folded into each conv: ``{path: (w, b)}``."""
    out = {}
    for path, *_ in conv_layers(cfg):
        p = params if len(path) == 1 else params[path[0]]
        s = state if len(path) == 1 else state[path[0]]
        bn = bn_key(path)
        g = p[bn]["scale"] * jax.lax.rsqrt(s[bn]["var"] + cfg["bn_eps"])
        out[path] = (p[path[-1]]["w"] * g[None, None, None, :],
                     p[bn]["bias"] - s[bn]["mean"] * g)
    return out


def max_pool(h):
    """3x3 stride-2 max of int8 codes over the SAME-padded input, padded
    with zero codes."""
    pads = []
    for size in h.shape[1:3]:
        total = max((-(-size // 2) - 1) * 2 + 3 - size, 0)
        pads.append((total // 2, total - total // 2))
    h = jnp.pad(h, ((0, 0), *pads, (0, 0)))
    return jax.lax.reduce_window(h, jnp.array(-128, h.dtype), jax.lax.max,
                                 (1, 3, 3, 1), (1, 2, 2, 1), "VALID")


def make_forward(params, state, cfg: dict, w_bits: int = 8,
                 last_codes: bool = False):
    """Jitted ``frames (B, H, W, C) f32 -> logits (B, classes) f32``; with
    ``last_codes`` also the last block's output codes."""
    w_max = 2 ** (w_bits - 1) - 1
    layers = {}
    for path, (w, b) in fold(params, state, cfg).items():
        ws = jnp.asarray(weight_scales(w, w_max))
        layers["/".join(path)] = (round_sat(w * ws, w_max).astype(jnp.int8),
                                  1.0 / (ws * ACT_SCALE), b)
    fc_w, fc_b = params["fc"]["w"], params["fc"]["b"]
    stem, blks = blocks(cfg)

    @jax.jit
    def forward(layers, fc_w, fc_b, x):
        def conv(site, h):
            path, _, stride, _, _, _, relu = site
            codes, row, b = layers["/".join(path)]
            acc = jax.lax.conv_general_dilated(
                h, codes, (stride, stride), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                preferred_element_type=jnp.int32)
            y = acc.astype(jnp.float32) * row + b
            if relu:
                y = jnp.maximum(y, 0.0)
            return round_sat(y * ACT_SCALE, ACT_MAX).astype(jnp.int8)

        h = max_pool(conv(stem, round_sat(x * ACT_SCALE, ACT_MAX)
                          .astype(jnp.int8)))
        for main, proj in blks:
            y = h
            for site in main:
                y = conv(site, y)
            sc = h if proj is None else conv(proj, h)
            h = jnp.clip(y.astype(jnp.int32) + sc.astype(jnp.int32),
                         0, ACT_MAX).astype(jnp.int8)
        pooled = jnp.mean(h.astype(jnp.float32) / ACT_SCALE, axis=(1, 2))
        logits = jnp.dot(pooled, fc_w, precision=HIGHEST) + fc_b
        return (logits, h) if last_codes else logits

    return lambda x: forward(layers, fc_w, fc_b, x)
