"""Plain reference of the served CIFAR ResNets: a ``jax.numpy`` integer
twin of the streamed int8 contract. Imports nothing of the program.

From the float params it folds BatchNorm into each conv, calibrates a
per-output-channel int8 weight scale (absmax -> 127), and runs the
network on integer codes: the frame as Q3.4 codes (x * 16, round half
to even, saturate at +-127); each conv an int8 x int8 convolution
accumulated in int32 (exact at any depth); dequantize by the row
``1 / (w_scale * 16)``, add the bias, ReLU where the network has one,
requantize to Q3.4 codes; residual adds and their ReLU on codes,
clamped to [0, 127]; the head dequantizes once, averages, and applies
the classifier at full f32 precision.

``w_bits=4`` is the control: the same network with int4 weight codes
(absmax -> 7), the step below the configuration's int8.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

ACT_SCALE = 16.0          # Q3.4: 4 fractional bits
ACT_MAX = 127
HIGHEST = jax.lax.Precision.HIGHEST


def conv_layers(cfg: dict):
    """Execution order: ``(path, stride, in_size, relu)`` per conv, where
    ``path`` is the param path of the conv's node (``("s1b0", "proj")``)."""
    out = [(("conv0",), 1, cfg["image_size"], True)]
    feat, cin = cfg["image_size"], cfg["widths"][0]
    for si, n_blocks in enumerate(cfg["stages"]):
        for bi in range(n_blocks):
            name = f"s{si}b{bi}"
            stride = 2 if (si > 0 and bi == 0) else 1
            width = cfg["widths"][si]
            nxt = -(-feat // stride)
            out.append(((name, "conv1"), stride, feat, True))
            out.append(((name, "conv2"), 1, nxt, False))
            if stride != 1 or cin != width:
                out.append(((name, "proj"), stride, feat, False))
            feat, cin = nxt, width
    return out


def fold(params, state, eps: float):
    """BatchNorm folded into each conv: ``{path: (w, b)}``."""
    def one(w, bnp, bns):
        g = bnp["scale"] * jax.lax.rsqrt(bns["var"] + eps)
        return w * g[None, None, None, :], bnp["bias"] - bns["mean"] * g

    out = {("conv0",): one(params["conv0"]["w"], params["bn0"], state["bn0"])}
    for name in sorted(k for k in params if k.startswith("s")):
        for conv, bn in (("conv1", "bn1"), ("conv2", "bn2"), ("proj", "bnp")):
            if conv in params[name]:
                out[(name, conv)] = one(params[name][conv]["w"],
                                        params[name][bn], state[name][bn])
    return out


def weight_scales(w, max_code: int) -> np.ndarray:
    """Per-cout codes-per-unit: absmax -> ``max_code``; all-zero channels
    get the static Q2.5 scale 32 (their codes are zero either way)."""
    cout = w.shape[-1]
    absmax = np.asarray(jnp.max(jnp.abs(w.reshape(-1, cout)), axis=0),
                        np.float64)
    return np.where(absmax > 0, max_code / np.maximum(absmax, 1e-30),
                    32.0).astype(np.float32)


def round_sat(x, max_code):
    return jnp.clip(jnp.round(x), -max_code, max_code)


def make_forward(params, state, cfg: dict, w_bits: int = 8):
    """Jitted ``frames (B, H, W, C) f32 -> logits (B, classes) f32``."""
    w_max = 2 ** (w_bits - 1) - 1
    folded = fold(params, state, cfg["bn_eps"])
    layers = {}
    for path, (w, b) in folded.items():
        ws = jnp.asarray(weight_scales(w, w_max))
        codes = round_sat(w * ws, w_max).astype(jnp.int8)
        row = 1.0 / (ws * ACT_SCALE)
        layers["/".join(path)] = (codes, row, b)
    fc_w, fc_b = params["fc"]["w"], params["fc"]["b"]
    order = conv_layers(cfg)

    @jax.jit
    def forward(layers, fc_w, fc_b, x):
        def conv(path, h, stride, relu):
            codes, row, b = layers["/".join(path)]
            acc = jax.lax.conv_general_dilated(
                h, codes, (stride, stride), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                preferred_element_type=jnp.int32)
            y = acc.astype(jnp.float32) * row + b
            if relu:
                y = jnp.maximum(y, 0.0)
            return round_sat(y * ACT_SCALE, ACT_MAX).astype(jnp.int8)

        strides = {path: (stride, relu) for path, stride, _, relu in order}
        h = conv(("conv0",), round_sat(x * ACT_SCALE, ACT_MAX).astype(jnp.int8),
                 1, True)
        for si, n_blocks in enumerate(cfg["stages"]):
            for bi in range(n_blocks):
                name = f"s{si}b{bi}"
                s1 = strides[(name, "conv1")][0]
                y = conv((name, "conv1"), h, s1, True)
                y = conv((name, "conv2"), y, 1, False)
                sc = (conv((name, "proj"), h, s1, False)
                      if (name, "proj") in strides else h)
                h = jnp.clip(y.astype(jnp.int32) + sc.astype(jnp.int32),
                             0, ACT_MAX).astype(jnp.int8)
        pooled = jnp.mean(h.astype(jnp.float32) / ACT_SCALE, axis=(1, 2))
        return jnp.dot(pooled, fc_w, precision=HIGHEST) + fc_b

    return lambda x: forward(layers, fc_w, fc_b, x)


def logits_in_blocks(forward, frames: np.ndarray, block: int) -> np.ndarray:
    """``forward`` over ``frames`` in blocks of ``block`` rows (the last
    block zero-padded, so one program serves every block)."""
    out = []
    for lo in range(0, len(frames), block):
        x = frames[lo:lo + block]
        n = len(x)
        if n < block:
            x = np.concatenate([x, np.zeros((block - n,) + x.shape[1:],
                                            x.dtype)])
        out.append(np.asarray(forward(jnp.asarray(x)))[:n])
    return np.concatenate(out)
