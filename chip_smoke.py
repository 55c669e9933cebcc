"""Smoke run of the HAPM ResNet-21 server on one TPU chip.

Builds the paper's network (``configs/resnet21_cifar.CONFIG``: 21 convs,
widths 16/32/64, 32x32 inputs, 10 classes) from ``--seed``, prunes it
with HAPM at 50 % group sparsity, and serves it through ``CnnServer``
in two phases, every conv bound to a Pallas kernel:

  (a) f32, implicit kernel on auto;
  (b) streamed int8 with the activation skip (``activation_dsb``).

Each phase warms buckets 1, 8, 32 and 128, answers a few requests of
each size and checks them: (a) against the plain f32 ``jax.numpy``
forward at highest matmul precision, (b) against a plain ``jax.numpy``
integer twin of the streamed contract (:func:`wire_reference`: the
kernels' own operands, Q3.4 frame codes and per-cout int8 weight codes,
so only the head's f32 rounding may differ) and against the all-dense
wire reference (host-requantized int8 activations, f32 weights, no
kernel), whose gap is the quantization of the frame and the weights.
A control, the integer twin with one live output channel of one conv
zeroed, must fail the integer bound. The run also checks that the server
stays on ladder rung 0 with every resilience counter at 0, that
``report()`` puts every conv on the implicit kernel, and that the
compiled program holds the Mosaic kernels (``tpu_custom_call``).

Run from the checkout root:  python chip_smoke.py
The last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``;
any failed check exits non-zero without it, and so does a run on a host
where JAX finds no TPU. Timings printed here are smoke timings of one
run, not benchmark results.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.resnet21_cifar import CONFIG  # noqa: E402
from repro.core import (HAPMConfig, apply_masks, hapm_element_masks,  # noqa: E402
                        hapm_epoch_update, hapm_init)
from repro.core import quant as Q  # noqa: E402
from repro.launch.serve_cnn import CnnServer, use_compile_cache  # noqa: E402
from repro.models import cnn  # noqa: E402

SPARSITY = 0.5
N_CU = 12
BUCKETS = (1, 8, 32, 128)
REQUEST_SIZES = (1, 5, 8, 32, 128)      # 5 pads up to bucket 8
REQUESTS_PER_SIZE = 2
HIGHEST = jax.lax.Precision.HIGHEST
# f32 phase: the tolerance the repo's parity tests hold the f32 kernels
# to, elementwise |y - ref| <= F32_TOL + F32_TOL * |ref|
F32_TOL = 1e-4
# streamed phase vs the integer twin: every activation code is exact on
# both sides, so only the head's f32 mean and matmul may round apart;
# one code off by one in the last layer moves a logit ~1e-3 of its range
WIRE_EXACT_REL = 1e-5
# streamed phase vs the dense wire reference, which convolves the f32
# frame with f32 weights: the quantization of both (2.6 % of max |logit|
# over 16 seeded images on the CPU), held under this share
WIRE_REL_BOUND = 0.1


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def pruned_model(cfg: cnn.ResNetConfig, seed: int):
    """ResNet from ``seed``, HAPM group masks at ``SPARSITY`` applied."""
    params, state = cnn.init(jax.random.PRNGKey(seed), cfg)
    specs = cnn.conv_group_specs(params, N_CU)
    hcfg = HAPMConfig(SPARSITY, 1)
    st = hapm_epoch_update(hapm_init(specs, hcfg), specs, params, hcfg)
    return apply_masks(params, hapm_element_masks(specs, st)), state


def conv_node(folded, path):
    """The ``{"w", "b"}`` node of the conv at param ``path``."""
    return folded[path[0]] if len(path) == 2 else folded[path[0]][path[1]]


def wire_reference(folded, cfg, drop=None):
    """Plain ``jax.numpy`` twin of the streamed int8 contract, built from
    what the kernels consume and not from them: the frame as Q3.4 codes;
    each conv an integer convolution of codes by the layer's calibrated
    per-cout int8 weight codes (in f32 this is exact: every partial sum
    is an integer below 2^24), then dequant, bias, ReLU and requantization
    to Q3.4 codes in the kernel epilogue's order; residual adds on codes;
    the head dequantizes once. ``drop`` names a conv (param path) whose
    first live output channel is zeroed, the control a sound bound must
    catch."""
    wire = Q.QuantSpec()
    max_code = wire.a_fmt.max_code
    layers = {}
    for path, _, _ in cnn.conv_layer_order(cfg):
        node = conv_node(folded, path)
        q = Q.QuantSpec.calibrate(node["w"])
        codes = np.asarray(q.weight_codes(node["w"]), np.float32)
        if path == drop:
            live = np.flatnonzero(np.abs(codes).sum(axis=(0, 1, 2)))
            codes[..., live[0]] = 0.0
        layers[path[:-1]] = (jnp.asarray(codes),
                             q.dequant_row(codes.shape[-1]), node["b"])

    def conv(path, h, stride, relu):
        codes, row, b = layers[path]
        acc = jax.lax.conv_general_dilated(
            h, codes, (stride, stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST)
        y = acc * row + b
        if relu:
            y = jnp.maximum(y, 0.0)
        return Q.round_sat(y * wire.act_scale, max_code)

    @jax.jit
    def forward(x):
        h = conv(("conv0",), wire.act_codes(x).astype(jnp.float32), 1, True)
        for si, n_blocks in enumerate(cfg.stages):
            for bi in range(n_blocks):
                name = f"s{si}b{bi}"
                stride = 2 if (si > 0 and bi == 0) else 1
                y = conv((name, "conv1"), h, stride, True)
                y = conv((name, "conv2"), y, 1, False)
                sc = (conv((name, "proj"), h, stride, False)
                      if "proj" in folded[name] else h)
                h = jnp.clip(y + sc, 0, max_code)
        pooled = jnp.mean(h / wire.act_scale, axis=(1, 2))
        return (jnp.dot(pooled, folded["fc"]["w"], precision=HIGHEST)
                + folded["fc"]["b"])

    return forward


def serve_phase(name: str, cfg, params, state, spec: cnn.ExecSpec,
                checks, seed: int) -> CnnServer:
    """Warm, serve and check one bind. ``checks`` holds ``(label,
    reference, within)``: ``reference(x)`` gives logits to compare with,
    ``within(y, ref)`` says whether ``y`` is close enough."""
    server = CnnServer(params, state, cfg, spec=spec, buckets=BUCKETS)
    t0 = time.perf_counter()
    server.warmup()
    say(f"{name}: warm-up (bind + compile of buckets {list(BUCKETS)}) "
        f"{time.perf_counter() - t0:.3f} s [smoke timing]")

    n_convs = len(cnn.conv_layer_order(cfg))
    rep = server.report(batch=1)
    routes = {k: rep[f"layers_{k}"]
              for k in ("implicit", "materializing", "dense")}
    say(f"{name}: conv layers per path {routes} of {n_convs}")
    check(routes["implicit"] == n_convs,
          f"{name}: not every conv is on the implicit kernel: {routes}")

    h, c = cfg.image_size, cfg.in_channels
    rng = np.random.RandomState(seed)
    x1 = jnp.zeros((1, h, h, c), jnp.float32)
    fn = server.cache.get(server.bind_key + (BUCKETS[0],)).fn
    if jax.default_backend() == "tpu":
        n_kernels = fn.lower(x1).compile().as_text().count("tpu_custom_call")
        say(f"{name}: tpu_custom_call ops in the bucket-{BUCKETS[0]} "
            f"program: {n_kernels}")
        check(n_kernels > 0, f"{name}: no Mosaic kernel in the program")

    worst = {label: (0.0, 0.0) for label, _, _ in checks}
    for size in REQUEST_SIZES:
        lat = []
        for _ in range(REQUESTS_PER_SIZE):
            x = rng.rand(size, h, h, c).astype(np.float32)
            t0 = time.perf_counter()
            y = np.asarray(server.infer(x))
            lat.append(time.perf_counter() - t0)
            check(server.last_request_level == 0,
                  f"{name}: a request left rung 0")
            for label, reference, within in checks:
                ref = np.asarray(reference(jnp.asarray(x)))
                check(y.shape == ref.shape and bool(np.isfinite(y).all()),
                      f"{name}: bad logits {y.shape} vs {ref.shape}")
                err = float(np.abs(y - ref).max())
                check(within(y, ref), f"{name}: logits off the {label} by "
                                      f"up to {err} at batch {size}")
                worst[label] = (max(worst[label][0], err),
                                max(worst[label][1], float(np.abs(ref).max())))
        say(f"{name}: batch {size}: latency per request "
            f"{', '.join(f'{t * 1e3:.3f}' for t in lat)} ms [smoke timing]")

    for label, (err, scale) in worst.items():
        say(f"{name}: max abs error vs {label} {err!r}; max |logit| "
            f"{scale!r}; ratio {err / scale!r}")
    stats = server.stats()
    say(f"{name}: rung {stats['rung']!r} (level {stats['level']}); "
        f"resilience {stats['resilience']}")
    check(stats["level"] == 0, f"{name}: server left rung 0: {stats}")
    check(not any(stats["resilience"].values()),
          f"{name}: resilience counters moved: {stats['resilience']}")
    return server


def within_rel(bound: float):
    """``max |y - ref| <= bound * max |ref|``."""
    return lambda y, ref: bool(np.abs(y - ref).max()
                               <= bound * np.abs(ref).max())


def run(seed: int) -> None:
    """Both serving phases at ``CONFIG``; raises :class:`SmokeFailure`."""
    cfg = CONFIG
    params, state = pruned_model(cfg, seed)

    dense = jax.jit(lambda x: cnn.apply(params, state, x, cfg)[0])

    def f32_ref(x):
        with jax.default_matmul_precision("highest"):
            return dense(x)

    def within_f32(y, ref):
        return bool(np.all(np.abs(y - ref) <= F32_TOL * (1 + np.abs(ref))))

    serve_phase("phase a (f32, implicit auto)", cfg, params, state,
                cnn.ExecSpec(n_cu=N_CU, dense_fallback=2.0),
                [("f32 reference", f32_ref, within_f32)], seed + 1)

    folded = cnn.fold_batchnorm(params, state, cfg)
    wire = jax.jit(lambda x: cnn.apply_folded(folded, x, cfg,
                                              wire_quantize=True))

    def wire_ref(x):
        with jax.default_matmul_precision("highest"):
            return wire(x)

    server = serve_phase(
        "phase b (streamed int8, activation skip)", cfg, params, state,
        cnn.ExecSpec(n_cu=N_CU, quantized=True, folded=True, streamed=True,
                     activation_dsb=True, dense_fallback=2.0),
        [("integer twin", wire_reference(folded, cfg),
          within_rel(WIRE_EXACT_REL)),
         ("dense wire reference", wire_ref, within_rel(WIRE_REL_BOUND))],
        seed + 2)

    last = next(path for path, _, _ in reversed(cnn.conv_layer_order(cfg))
                if np.any(np.asarray(conv_node(folded, path)["w"])))
    x = np.random.RandomState(seed + 3).rand(
        BUCKETS[1], cfg.image_size, cfg.image_size,
        cfg.in_channels).astype(np.float32)
    y = np.asarray(server.infer(x))
    ctrl = np.asarray(wire_reference(folded, cfg, drop=last)(jnp.asarray(x)))
    err = float(np.abs(y - ctrl).max())
    say(f"control (integer twin, first live output channel of "
        f"{'/'.join(last)} zeroed): max abs error {err!r}, ratio "
        f"{err / float(np.abs(ctrl).max())!r}")
    check(not within_rel(WIRE_EXACT_REL)(y, ctrl),
          "the integer-twin bound does not catch a zeroed output channel")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX's device 0 is on platform "
              f"{dev.platform!r}", file=sys.stderr)
        return 2
    say(f"device kind {dev.device_kind!r}, {len(devices)} device(s); "
        f"compile cache {use_compile_cache()}")
    try:
        run(args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
