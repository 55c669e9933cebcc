"""ImageNet ResNets (7x7/2 stem and max-pool, bottleneck blocks) served
through the program's ``CnnServer``.

A configuration file names this family (``"family": "resnet_imagenet"``)
and gives the network's sizes (``stages``, ``widths``, ``expansion``,
``block``, ``stem``), the HAPM pruning, the execution contract and the
serving buckets. As in :mod:`resnet_cifar`, the model is made from the
seed in one jitted call (He-normal convs, BatchNorm at its initial
state, HAPM group pruning by :mod:`hapm_select`) and the plain reference
is built beside it (:mod:`resnet_imagenet_ref`). The server is given the
program's ``ResNetConfig`` with its block and stem fields, so a program
without them refuses the configuration at once (``TypeError``).

    python chipbench/configs/resnet_imagenet.py --workload <cell> --seeds 1,2

prints, one JSON line a seed, the int4-weight control's ``logit_gap``
over the cell's frame pool beside the limit (the number
``chipbench/control.py`` prints for the CIFAR family), and the share of
the reference's last-block activation codes that sit at the Q3.4
ceiling (127), so that a reader can see the comparison is not flattened
by saturation. The benchmark's runs never run this.
"""
from __future__ import annotations

import dataclasses
import pathlib
import sys
import time

if __name__ == "__main__":
    _root = pathlib.Path(__file__).resolve().parents[2]
    sys.path[:0] = [str(_root), str(_root / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from chipbench import hapm_select, work  # noqa: E402
from chipbench.configs import resnet_cifar  # noqa: E402
from chipbench.configs import resnet_imagenet_ref as ref  # noqa: E402


@dataclasses.dataclass(frozen=True)
class ConvWork(work.ConvWork):
    """:class:`work.ConvWork` with the conv's kernel size (``kernel`` x
    ``kernel``), so that a metric can pick the layers of one shape."""
    kernel: int = 0


def init(key, cfg: dict):
    """``(params, state)`` in the program's tree layout."""
    layers = ref.conv_layers(cfg)
    keys = iter(jax.random.split(key, len(layers) + 1))
    params, state = {}, {}
    for path, k, _, _, cin, cout, _ in layers:
        p = params if len(path) == 1 else params.setdefault(path[0], {})
        s = state if len(path) == 1 else state.setdefault(path[0], {})
        p[path[-1]] = {"w": jax.random.normal(next(keys), (k, k, cin, cout))
                       * np.sqrt(2.0 / (k * k * cin))}
        bn = ref.bn_key(path)
        p[bn] = {"scale": jnp.ones((cout,)), "bias": jnp.zeros((cout,))}
        s[bn] = {"mean": jnp.zeros((cout,)), "var": jnp.ones((cout,))}
    cin = layers[-1][5]
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

    return build(resnet_cifar.seed_key(seed))


def conv_work(cfg: dict, params) -> work.ForwardWork:
    """Needed work of ``params`` at the sizes of ``cfg``, each conv with
    its kernel size."""
    layers = ref.conv_layers(cfg)
    weights = {path: (params if len(path) == 1 else params[path[0]])
               [path[-1]]["w"] for path, *_ in layers}
    base = work.forward_work([(path, s, size) for path, _, s, size, *_
                              in layers], weights)
    return work.ForwardWork([
        ConvWork(**dataclasses.asdict(c), kernel=k)
        for c, (_, k, *_) in zip(base.convs, layers)])


class Model(resnet_cifar.Model):
    """One configuration served by the program; ``infer`` is the timed
    path (warm-up, bind clock and release as :class:`resnet_cifar.Model`)."""

    def __init__(self, cfg: dict, seed: int):
        from repro.launch.serve_cnn import CnnServer
        from repro.models import cnn

        # first, so that a program without these fields fails at once
        rcfg = cnn.ResNetConfig(
            stages=tuple(cfg["stages"]), widths=tuple(cfg["widths"]),
            num_classes=cfg["num_classes"], in_channels=cfg["in_channels"],
            image_size=cfg["image_size"], bn_eps=cfg["bn_eps"],
            block=cfg["block"], stem=cfg["stem"])
        self.cfg = cfg
        self.params, self.state = make_model(cfg, seed)
        jax.block_until_ready((self.params, self.state))
        self.work = conv_work(cfg, self.params)
        spec = cnn.ExecSpec(**cfg["exec"])
        t0 = time.perf_counter()
        self.server = CnnServer(self.params, self.state, rcfg, spec=spec,
                                buckets=tuple(cfg["buckets"]))
        self.install_s = time.perf_counter() - t0
        self._clock = resnet_cifar._BindClock(cnn)
        self.frame_shape = (cfg["image_size"], cfg["image_size"],
                            cfg["in_channels"])


def layer_kernel_s(r, kernel: int):
    """``(device seconds, work)`` of the Mosaic kernels of the convs of
    kernel size ``kernel`` in a traced run ``r`` (a ``RunRecord``), with
    those convs' needed work; ``None`` where the run has no trace or no
    such conv. A conv's kernel is the op the program names by its layer
    path (``conv_s2b3_conv3.<n>``)."""
    convs = [c for c in r.work.convs if getattr(c, "kernel", None) == kernel]
    if r.trace is None or not convs:
        return None
    names = {"conv_" + "_".join(c.path) for c in convs}
    ns = sum(o.end - o.start for o in r.trace.ops
             if o.kernel and o.name.rsplit(".", 1)[0] in names)
    return ns * 1e-9, work.ForwardWork(convs)


def reference_logits(model: Model, frames, block: int, w_bits: int = 8):
    """The plain reference's logits of ``frames``, ``block`` rows a call."""
    fwd = ref.make_forward(model.params, model.state, model.cfg, w_bits)
    return ref.logits_in_blocks(fwd, frames, block)


def control(cfg: dict, mix: dict, seed: int) -> dict:
    """The int4 control's ``logit_gap`` over the cell's pool, and the
    share of saturated last-block codes of the int8 reference."""
    from chipbench import control as C
    from chipbench import run as R

    params, state = make_model(cfg, seed)
    pool = np.random.default_rng([seed, 1]).random(
        (mix["pool_frames"], cfg["image_size"], cfg["image_size"],
         cfg["in_channels"]), dtype=np.float32)
    fwd = ref.make_forward(params, state, cfg, last_codes=True)
    want, sat = [], []
    for lo in range(0, len(pool), R.REF_BLOCK):
        logits, codes = fwd(jnp.asarray(pool[lo:lo + R.REF_BLOCK]))
        want.append(np.asarray(logits))
        sat.append(float(jnp.mean(codes == ref.ACT_MAX)))
    want = np.concatenate(want)
    int4 = ref.logits_in_blocks(ref.make_forward(params, state, cfg, 4),
                                pool, R.REF_BLOCK)
    return {"seed": seed, "limit": cfg["limits"]["logit_gap"],
            "int4": C.gap_over_pool(int4, want, cfg["limits"]),
            "last_block_saturated_share": float(np.mean(sat)),
            "max_logit": float(np.abs(want).max())}


def main(argv=None) -> int:
    import argparse
    import json

    from chipbench import run as R

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    bench = R.load_json(R.ROOT / "BENCHMARK.json")
    cell, cfg, mix = R.find_cell(bench, args.workload)
    R.use_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(dict(workload=cell["name"],
                              **control(cfg, mix, seed))), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
