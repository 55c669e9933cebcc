"""Implicit kernel vs materializing path, ResNet-21 at full size.

Binds the paper's network (``configs/resnet21_cifar.CONFIG``), pruned
with HAPM at 50 % group sparsity, five ways and runs each at one batch:

  f32_implicit, f32_materializing        ``apply`` on a plain f32 bind
  streamed_implicit, streamed_skip,      ``apply_folded`` on a streamed
  streamed_materializing                 int8 bind (skip = activation_dsb)

It reports whether the two conv paths give the same logits bit for bit
(f32 and streamed), whether the activation skip changes any bit, each
conv's f32 kernel error against ``lax.conv`` at highest precision, and
forward wall times on the host clock (one warm-up call, then
``--calls`` calls each). The wall times are smoke timings of one run
with no trace, not benchmark results.

    PYTHONPATH=src python -m benchmarks.chip_parity [--batch 8] [--seed 0]

The last line of standard output is one JSON object with every reading.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.resnet21_cifar import CONFIG
from repro.core import (HAPMConfig, apply_masks, hapm_element_masks,
                        hapm_epoch_update, hapm_init)
from repro.models import cnn

SPARSITY = 0.5
N_CU = 12


def pruned_model(cfg, seed: int):
    """ResNet from ``seed``, HAPM group masks at ``SPARSITY`` applied."""
    params, state = cnn.init(jax.random.PRNGKey(seed), cfg)
    specs = cnn.conv_group_specs(params, N_CU)
    hcfg = HAPMConfig(SPARSITY, 1)
    st = hapm_epoch_update(hapm_init(specs, hcfg), specs, params, hcfg)
    return apply_masks(params, hapm_element_masks(specs, st)), state


def timed(fn, x, calls: int):
    """(first-call seconds, [per-call ms]) of ``fn(x)``, device-synced."""
    t0 = time.perf_counter()
    jax.block_until_ready(fn(x))
    first = time.perf_counter() - t0
    walls = []
    for _ in range(calls):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(x))
        walls.append((time.perf_counter() - t0) * 1e3)
    return first, walls


def run(batch: int, seed: int, calls: int) -> dict:
    cfg = CONFIG
    params, state = pruned_model(cfg, seed)
    folded = cnn.fold_batchnorm(params, state, cfg)
    x = jnp.asarray(np.random.RandomState(seed + 1).rand(
        batch, cfg.image_size, cfg.image_size, cfg.in_channels), jnp.float32)

    def bind(tree, **kw):
        return cnn.bind_execution(
            tree, cfg, spec=cnn.ExecSpec(n_cu=N_CU, dense_fallback=2.0, **kw))

    streamed = dict(quantized=True, folded=True, streamed=True)
    execs = {
        "f32_implicit": bind(params),
        "f32_materializing": bind(params, implicit=False),
        "streamed_implicit": bind(folded, **streamed),
        "streamed_skip": bind(folded, activation_dsb=True, **streamed),
        "streamed_materializing": bind(folded, implicit=False, **streamed),
    }
    out, walls = {}, {}
    for name, ex in execs.items():
        if name.startswith("f32"):
            fn = jax.jit(lambda v, ex=ex: cnn.apply(params, state, v, cfg,
                                                    sparse=ex)[0])
        else:
            fn = jax.jit(lambda v, ex=ex: cnn.apply_folded(folded, v, cfg,
                                                           sparse=ex))
        first, walls[name] = timed(fn, x, calls)
        out[name] = np.asarray(fn(x))
        print(f"{name}: routes {execs[name].report(cfg)['layers_implicit']} "
              f"implicit; first call {first:.3f} s; batch-{batch} forward ms "
              f"[smoke timing] {walls[name]}", flush=True)

    def same(a, b):
        return bool(np.array_equal(out[a], out[b]))

    parity = {
        "f32_implicit_eq_materializing": same("f32_implicit",
                                              "f32_materializing"),
        "streamed_implicit_eq_materializing": same("streamed_implicit",
                                                   "streamed_materializing"),
        "streamed_skip_eq_noskip": same("streamed_skip", "streamed_implicit"),
    }
    for k, v in parity.items():
        print(f"{k}: {v}", flush=True)

    # each conv's f32 kernel alone against lax.conv at highest precision,
    # on a post-ReLU-like input of the layer's own shape
    ex = execs["f32_implicit"]
    rng = np.random.RandomState(seed + 2)
    layers = {}
    for path, stride, feat in cnn.conv_layer_order(cfg):
        w = params[path[0]][path[1]] if len(path) == 2 else \
            params[path[0]][path[1]][path[2]]
        xl = jnp.asarray(rng.rand(batch, feat, feat, w.shape[2]), jnp.float32)
        y = np.asarray(jax.jit(lambda v: ex.table[path](v, stride=stride))(xl))
        ref = np.asarray(jax.lax.conv_general_dilated(
            xl, w, (stride, stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=jax.lax.Precision.HIGHEST))
        layers["/".join(path)] = {"max_abs_err": float(np.abs(y - ref).max()),
                                  "max_abs_ref": float(np.abs(ref).max())}
        print(f"layer {'/'.join(path)}: {layers['/'.join(path)]}", flush=True)
    return {"backend": jax.default_backend(),
            "device_kind": jax.devices()[0].device_kind,
            "batch": batch, "parity": parity,
            "forward_ms_smoke_timing": walls, "layer_f32_error": layers}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--calls", type=int, default=5)
    args = ap.parse_args(argv)
    print(json.dumps(run(args.batch, args.seed, args.calls)))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
