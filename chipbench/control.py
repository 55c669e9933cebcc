"""The correctness check's controls, read at a cell's own size.

    python chipbench/control.py --workload <cell> --seeds 301,302,303

For each seed this makes the cell's model and frame pool as a run does,
then puts each control in the served program's place over every frame of
the pool and prints, one JSON line a seed, the number the run would
compare (``logit_gap``: widest |control - reference| logit over the
reference's largest |logit|) beside the cell's limit:

- ``int4``: the plain reference at int4 weight codes, the precision step
  below the configuration's int8;
- ``zeroed_channel``: the reference with the first live output channel
  of the last conv that has one zeroed.

The benchmark's runs never run this; it sets the upper reading of the
limit (``PERF.md``).
"""
from __future__ import annotations

import argparse
import importlib
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from chipbench import run as R  # noqa: E402
from chipbench import serve_loop  # noqa: E402
from chipbench.configs import resnet_cifar_ref as ref  # noqa: E402


def zero_last_channel(params, cfg):
    """``params`` with the first live output channel of the last conv
    that has one zeroed."""
    params = jax.tree_util.tree_map(lambda a: a, params)
    for path, *_ in reversed(ref.conv_layers(cfg)):
        node = params[path[0]] if len(path) == 1 else params[path[0]][path[1]]
        w = np.array(node["w"])
        live = np.flatnonzero(np.abs(w).sum(axis=(0, 1, 2)))
        if len(live):
            w[..., live[0]] = 0.0
            node["w"] = w
            return params
    raise ValueError("no conv has a live output channel")


def gap_over_pool(control: np.ndarray, want: np.ndarray, limits: dict):
    """The run's ``logit_gap`` had the control served every pool frame
    once, one request a frame."""
    reqs = [serve_loop.Served(0.0, np.array([i]), logits=control[i:i + 1],
                              level=0) for i in range(len(want))]
    window = serve_loop.Window(reqs, 0.0, [], len(reqs), {})
    return R.compare(window, want, limits)["logit_gap"][0]


def readings(cfg: dict, mix: dict, seed: int) -> dict:
    fam = importlib.import_module(f"chipbench.configs.{cfg['family']}")
    params, state = fam.make_model(cfg, seed)
    shape = (cfg["image_size"], cfg["image_size"], cfg["in_channels"])
    pool = np.random.default_rng([seed, 1]).random(
        (mix["pool_frames"],) + shape, dtype=np.float32)
    t0 = time.perf_counter()
    want = ref.logits_in_blocks(ref.make_forward(params, state, cfg), pool,
                                R.REF_BLOCK)
    ref_s = time.perf_counter() - t0
    int4 = ref.logits_in_blocks(
        ref.make_forward(params, state, cfg, w_bits=4), pool, R.REF_BLOCK)
    zeroed = ref.logits_in_blocks(
        ref.make_forward(zero_last_channel(params, cfg), state, cfg), pool,
        R.REF_BLOCK)
    lim = cfg["limits"]
    return {"seed": seed, "limit": lim["logit_gap"],
            "int4": gap_over_pool(int4, want, lim),
            "zeroed_channel": gap_over_pool(zeroed, want, lim),
            "max_logit": float(np.abs(want).max()), "reference_s": ref_s}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    bench = R.load_json(ROOT / "BENCHMARK.json")
    cell, cfg, mix = R.find_cell(bench, args.workload)
    R.use_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(dict(workload=cell["name"],
                              **readings(cfg, mix, seed))), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
