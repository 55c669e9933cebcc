"""Serve one benchmark cell with the program's span recorder on, and say
which host step the device waits on.

    python chipbench/span_window.py --workload <cell> --seed <n> --seconds <s> \\
        [--windows 3] [--out chiprun_out/spans.json]

Run from the checkout root, on a TPU. The cell is built and warmed as
``run.py`` builds it, but its ``CnnServer`` is built again with a
:class:`repro.spans.Spans`, so set-up records ``install``, ``bind``,
``bind.layer.<kernel>`` and ``warmup.bucket`` spans. Then ``--windows``
pairs of windows are served: one traced (profiler with its host tracer off, the harness's and
the server's spans in one record, JAX's compiles as ``compile`` spans),
one untraced (the server and the loop handed the no-op recorder), for
the cost of recording. Each traced window is reduced by
``trace_reduce.reduce``, and its device-idle time put down to the
innermost span in progress (:func:`idle_by_innermost`). Every ``infer``
call longer than :data:`STALL_MS` is listed with its steps and the
compiles inside it. The last stdout line is the summary as JSON;
``--out`` keeps it whole. Needs a program with ``repro.spans``.
"""
from __future__ import annotations

import argparse
import gc
import heapq
import importlib
import json
import pathlib
import re
import shutil
import sys
import tempfile
import time
from typing import Dict, List, Optional

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent), str(HERE.parent / "src")]

from chipbench import run as R  # noqa: E402
from chipbench import serve_loop  # noqa: E402
from chipbench import trace_reduce as tr  # noqa: E402

STEPS = ("infer.prepare", "infer.dispatch", "infer.collect")
STALL_MS = 50.0      # an ``infer`` call longer than this is a stall


def idle_by_innermost(summary: tr.TraceSummary,
                      names: Optional[List[str]] = None) -> Dict[str, float]:
    """Device-idle seconds of the window by the innermost span in
    progress: the one that started last of those not yet ended. Spans
    are ``names`` (default every span but ``window``), cut to the window;
    idle time inside none of them is ``other``. The values sum to the
    window's idle time."""
    names = [n for n in (names or summary.spans) if n != "window"]
    w0, w1 = summary.window
    ivs = sorted((max(s, w0), min(e, w1), i)
                 for i, n in enumerate(names)
                 for s, e in summary.spans.get(n, []) if min(e, w1) > max(s, w0))
    cuts = np.unique(np.array([w0, w1] + [t for s, e, _ in ivs
                                          for t in (s, e)], np.int64))
    owner = np.full(len(cuts) - 1, len(names))          # ``other``
    live: list = []                                     # (-start, end, i)
    j = 0
    for k, t in enumerate(cuts[:-1]):
        while j < len(ivs) and ivs[j][0] <= t:
            s, e, i = ivs[j]
            heapq.heappush(live, (-s, e, i))
            j += 1
        while live and live[0][1] <= t:
            heapq.heappop(live)
        if live:
            owner[k] = live[0][2]
    busy = np.array(summary.busy_intervals(), np.int64).reshape(-1, 2)
    lo, hi = cuts[:-1], cuts[1:]
    idle = (hi - lo) - (tr.busy_before(busy, hi) - tr.busy_before(busy, lo))
    tot = np.bincount(owner, weights=idle, minlength=len(names) + 1) * 1e-9
    out = {n: float(tot[i]) for i, n in enumerate(names)}
    out["other"] = (summary.window_s - summary.busy_s) - float(tot[:-1].sum())
    return out


def layer(op: str) -> str:
    """An op's name without XLA's ``.<n>`` suffix: one name for a layer's
    kernel across the bucket programs."""
    return re.sub(r"\.\d+$", "", op)


def ops_by_layer(summary: tr.TraceSummary, k: int = 30) -> List[list]:
    tot: Dict[str, int] = {}
    for o in summary.ops:
        tot[layer(o.name)] = tot.get(layer(o.name), 0) + o.end - o.start
    return [[n, t * 1e-9] for n, t in
            sorted(tot.items(), key=lambda kv: -kv[1])[:k]]


def inside(spans: Dict[str, list], name: str, s: int, e: int) -> list:
    return [(a, b) for a, b in spans.get(name, []) if s <= a and b <= e]


def stalls(summary: tr.TraceSummary, ms: float) -> List[dict]:
    """Every ``infer`` span longer than ``ms``, with the ms of each step
    and of each compile inside it, the ms the device idled in it, and its
    longest device op."""
    spans, out = summary.spans, []
    busy = np.array(summary.busy_intervals(), np.int64).reshape(-1, 2)
    for s, e in spans.get("infer", []):
        if (e - s) * 1e-6 <= ms:
            continue
        on = tr.busy_before(busy, np.array([s, e]))
        ops = [o for o in summary.ops if o.end > s and o.start < e]
        top = max(ops, key=lambda o: o.end - o.start, default=None)
        out.append({"at_s": (s - summary.window[0]) * 1e-9,
                    "ms": (e - s) * 1e-6,
                    "device_idle_ms": ((e - s) - (on[1] - on[0])) * 1e-6,
                    "longest_op": None if top is None else
                    [top.name, (top.end - top.start) * 1e-6],
                    **{n: [(b - a) * 1e-6 for a, b in inside(spans, n, s, e)]
                       for n in STEPS + ("compile",)}})
    return out


def set_up_record(spans: Dict[str, list]) -> dict:
    """Install, bind and warm-up seconds from the set-up spans: each
    install, each bind and each layer's bind, and each bucket's warm-up
    less the binds inside it."""
    layers = {n[len("bind.layer."):]: sum(e - s for s, e in v) * 1e-9
              for n, v in spans.items() if n.startswith("bind.layer.")}
    binds = spans.get("bind", [])
    warm = []
    for s, e in spans.get("warmup.bucket", []):
        bound = sum(min(b, e) - max(a, s) for a, b in binds
                    if min(b, e) > max(a, s))
        warm.append(((e - s) - bound) * 1e-9)
    return {"install_s": [(e - s) * 1e-9
                          for s, e in spans.get("install", [])],
            "bind_s": [(e - s) * 1e-9 for s, e in binds],
            "bind_layers_s": sum(layers.values()),
            "bind_layer_s": dict(sorted(layers.items(),
                                        key=lambda kv: -kv[1])),
            "warmup_bucket_self_s": warm,
            "warmup_buckets_s": sum(warm)}


def window_record(window: serve_loop.Window) -> dict:
    lat = np.array([r.done - r.due for r in window.requests]) * 1e3
    return {"requests": len(window.requests),
            "infer_calls": window.infer_calls,
            "p50_ms": float(np.percentile(lat, 50)),
            "p99_ms": float(np.percentile(lat, 99)),
            "failed": sum(1 for r in window.requests
                          if r.error or r.level != 0)}


def traced_record(summary: tr.TraceSummary, stall_ms: float) -> dict:
    n = len(summary.spans.get("infer", []))
    per_call = {n_: float(summary.uncovered_s(n_).sum()) / n * 1e3
                for n_ in ("infer",) + STEPS}
    return {"busy_s": summary.busy_s, "window_s": summary.window_s,
            "idle_s": summary.window_s - summary.busy_s,
            "host_ms_per_infer_call": per_call,
            "idle_by_innermost_s": dict(sorted(
                idle_by_innermost(summary).items(), key=lambda kv: -kv[1])),
            "device_ops_by_layer": ops_by_layer(summary),
            "kernel_s": summary.kernel_s(), "glue_s": summary.glue_s(),
            "stalls": stalls(summary, stall_ms)}


def serve_windows(cell: dict, cfg: dict, mix: dict, seed: int,
                  seconds: float, windows: int,
                  stall_ms: float = STALL_MS) -> dict:
    """Build and warm ``cell`` with a recorder on its server, then serve
    ``windows`` pairs of windows, traced and untraced; the summary."""
    import jax
    from repro.launch.exec_cache import BucketBatcher
    from repro.launch.serve_cnn import CnnServer
    from repro.spans import NO_SPANS, Spans

    family = importlib.import_module(f"chipbench.configs.{cfg['family']}")
    model = family.Model(cfg, seed)
    rec = Spans()
    # the family's server, built again with the recorder (binds are lazy,
    # so only its install is done twice)
    old = model.server
    model.server = CnnServer(model.params, model.state, old.cfg,
                             spec=old.spec, buckets=old.buckets, spans=rec)
    del old
    pool = np.random.default_rng([seed, 1]).random(
        (mix["pool_frames"],) + model.frame_shape, dtype=np.float32)
    sizes = R.release_sizes(mix, cfg["buckets"])
    t0 = time.perf_counter()
    model.warmup(R.buckets_used(sizes, cfg["buckets"]), sizes)
    out = {"workload": cell["name"], "seed": seed,
           "device": jax.devices()[0].device_kind,
           "warmup_s": time.perf_counter() - t0,
           "set_up": set_up_record(rec.drain()), "windows": []}
    for i in range(2 * windows):
        traced = i % 2 == 0
        batcher = BucketBatcher(cfg["buckets"], max_wait_s=cfg["max_wait_s"])
        model.server.spans = rec if traced else NO_SPANS
        trace_dir = tempfile.mkdtemp(prefix="spans-trace-")
        if traced:
            jax.profiler.start_trace(trace_dir,
                                     profiler_options=R.profile_options())
        gc.collect()
        gc.freeze()
        with rec.watch_compiles():
            window = serve_loop.run(model, batcher, mix, seed + i // 2,
                                    seconds, pool,
                                    spans=rec if traced else NO_SPANS)
        gc.unfreeze()
        one = {"traced": traced, "seed": seed + i // 2,
               **window_record(window),
               "compiles": rec.compiles, "cache_loads": rec.cache_loads}
        rec.compiles = rec.cache_loads = 0
        spans = rec.drain()
        if traced:
            jax.profiler.stop_trace()
            summary = tr.reduce(tr.find_xplane(trace_dir), spans)
            one.update(traced_record(summary, stall_ms))
        shutil.rmtree(trace_dir, ignore_errors=True)
        out["windows"].append(one)
        R.say(json.dumps({k: v for k, v in one.items()
                          if k != "device_ops_by_layer"}))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--windows", type=int, default=3)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax
    bench = R.load_json(R.ROOT / "BENCHMARK.json")
    cell, cfg, mix = R.find_cell(bench, args.workload)
    if jax.devices()[0].platform != "tpu":
        R.say(f"needs a TPU, but JAX's device 0 is on "
              f"{jax.devices()[0].platform!r}")
        return 2
    R.say(f"compile cache {R.use_compile_cache()}")
    out = serve_windows(cell, cfg, mix, args.seed, args.seconds,
                        args.windows)
    if args.out:
        path = pathlib.Path(args.out)
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(out, indent=1))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
