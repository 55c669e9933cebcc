"""CIFAR ResNets served through the program's ``CnnServer``.

A configuration file names this family (``"family": "resnet_cifar"``)
and gives the network's sizes, the HAPM pruning, the execution contract
and the serving buckets. This module makes the model from the seed (in
one jitted call on the device: He-normal convs, BatchNorm at its initial
state, HAPM group pruning by :mod:`hapm_select`), hands it to the
program's server, and builds the plain reference beside it
(:mod:`resnet_cifar_ref`).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import hapm_select, work
from chipbench.configs import resnet_cifar_ref as ref


def seed_key(seed: int):
    """A PRNG key that keeps every bit of a seed up to 2**64."""
    return jax.random.fold_in(jax.random.PRNGKey(np.uint32(seed % 2 ** 32)),
                              np.uint32(seed >> 32))


def init(key, cfg: dict):
    """``(params, state)`` in the program's tree layout."""
    keys = iter(jax.random.split(key, len(ref.conv_layers(cfg)) + 1))

    def conv(kx, cin, cout):
        return {"w": jax.random.normal(next(keys), (kx, kx, cin, cout))
                * np.sqrt(2.0 / (kx * kx * cin))}

    def bn(c):
        return {"scale": jnp.ones((c,)), "bias": jnp.zeros((c,))}

    def bn_state(c):
        return {"mean": jnp.zeros((c,)), "var": jnp.ones((c,))}

    cin = cfg["widths"][0]
    params = {"conv0": conv(3, cfg["in_channels"], cin), "bn0": bn(cin)}
    state = {"bn0": bn_state(cin)}
    for si, n_blocks in enumerate(cfg["stages"]):
        width = cfg["widths"][si]
        for bi in range(n_blocks):
            stride = 2 if (si > 0 and bi == 0) else 1
            blk = {"conv1": conv(3, cin, width), "bn1": bn(width),
                   "conv2": conv(3, width, width), "bn2": bn(width)}
            st = {"bn1": bn_state(width), "bn2": bn_state(width)}
            if stride != 1 or cin != width:
                blk["proj"], blk["bnp"] = conv(1, cin, width), bn(width)
                st["bnp"] = bn_state(width)
            params[f"s{si}b{bi}"], state[f"s{si}b{bi}"] = blk, st
            cin = width
    params["fc"] = {
        "w": jax.random.normal(next(keys), (cin, cfg["num_classes"]))
        * np.sqrt(1.0 / cin),
        "b": jnp.zeros((cfg["num_classes"],))}
    return params, state


def make_model(cfg: dict, seed: int):
    """Pruned ``(params, state)`` from ``seed``: one jitted call."""
    h = cfg["hapm"]

    @jax.jit
    def build(key):
        params, state = init(key, cfg)
        return hapm_select.prune(params, h["n_cu"], h["sparsity"]), state

    return build(seed_key(seed))


def conv_work(cfg: dict, params) -> work.ForwardWork:
    """Needed work of ``params`` at the sizes of ``cfg``."""
    layers = [(path, stride, in_hw)
              for path, stride, in_hw, _ in ref.conv_layers(cfg)]
    weights = {path: (params[path[0]] if len(path) == 1
                      else params[path[0]][path[1]])["w"]
               for path, _, _ in layers}
    return work.forward_work(layers, weights)


class _BindClock:
    """Host seconds spent inside ``cnn.bind_execution`` while installed."""

    def __init__(self, cnn):
        self.cnn, self.seconds = cnn, 0.0
        self.inner = cnn.bind_execution

    def __enter__(self):
        def timed(*a, **kw):
            t0 = time.perf_counter()
            try:
                return self.inner(*a, **kw)
            finally:
                self.seconds += time.perf_counter() - t0
        self.cnn.bind_execution = timed
        return self

    def __exit__(self, *exc):
        self.cnn.bind_execution = self.inner


class Model:
    """One configuration served by the program; ``infer`` is the timed
    path."""

    def __init__(self, cfg: dict, seed: int):
        from repro.launch.serve_cnn import CnnServer
        from repro.models import cnn

        self.cfg = cfg
        self.params, self.state = make_model(cfg, seed)
        jax.block_until_ready((self.params, self.state))
        self.work = conv_work(cfg, self.params)
        rcfg = cnn.ResNetConfig(
            stages=tuple(cfg["stages"]), widths=tuple(cfg["widths"]),
            num_classes=cfg["num_classes"], in_channels=cfg["in_channels"],
            image_size=cfg["image_size"], bn_eps=cfg["bn_eps"])
        spec = cnn.ExecSpec(**cfg["exec"])
        t0 = time.perf_counter()
        self.server = CnnServer(self.params, self.state, rcfg, spec=spec,
                                buckets=tuple(cfg["buckets"]))
        self.install_s = time.perf_counter() - t0
        self._clock = _BindClock(cnn)
        self.frame_shape = (cfg["image_size"], cfg["image_size"],
                            cfg["in_channels"])

    @property
    def bind_s(self) -> float:
        return self.install_s + self._clock.seconds

    def warmup(self, buckets, sizes) -> None:
        """Bind, compile each bucket, then run every request size the
        traffic can release (the server's padding and slicing compile
        per size)."""
        with self._clock:
            self.server.warmup(buckets)
            for n in sizes:
                self.infer(np.zeros((n,) + self.frame_shape, np.float32))

    def infer(self, frames: np.ndarray):
        return self.server.infer(frames)

    @property
    def last_level(self) -> int:
        return self.server.last_request_level

    def release(self) -> None:
        """Drop the server, its binds and its compiled programs."""
        self.server = None



def reference_logits(model: Model, frames, block: int, w_bits: int = 8):
    """The plain reference's logits of ``frames``, ``block`` rows a call."""
    fwd = ref.make_forward(model.params, model.state, model.cfg, w_bits)
    return ref.logits_in_blocks(fwd, frames, block)
