"""Benchmark of served HAPM-pruned ResNets on one TPU chip.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the checkout root. ``BENCHMARK.json`` names each cell's
configuration (``chipbench/configs/<config>.json``, whose ``family``
module builds the model, serves it through the program's ``CnnServer``
and holds the plain reference) and traffic mix
(``chipbench/traffic/<mix>.json``, read by ``traffic.py``); each metric
is read by ``chipbench/metrics/<metric>.py``. A run makes the model and
the frames from ``--seed``, warms every shape the mix can use, serves
the mix for ``--seconds`` (``serve_loop.py``), then checks every answer
of the window against the plain reference.

With ``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window. The last stdout line is the result as JSON; the numbers compared
and their limits close standard error. A host whose device 0 is not a
TPU, or whose device kind has no published peaks in ``work.PEAKS``,
gets exit code 2 and no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from chipbench import serve_loop, work  # noqa: E402
from chipbench import trace_reduce as tr  # noqa: E402
from chipbench.record import RunRecord  # noqa: E402

REF_BLOCK = 256


def say(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def load_json(path: pathlib.Path):
    with open(path) as f:
        return json.load(f)


def find_cell(bench: dict, name: str):
    """``(cell, configuration file, mix)`` of workload ``name``."""
    cells = {c["name"]: c for c in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = load_json(ROOT / conf["file"])
    mix = load_json(HERE / "traffic" / f"{cell['traffic']}.json")
    return cell, cfg, mix


def cell_metrics(bench: dict, cell: str, traced: bool):
    """The metric entries this cell reports in this kind of run."""
    group = bench["per_layer"] if traced else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def reader(name: str):
    spec = importlib.util.spec_from_file_location(
        f"metric_{name}", HERE / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def release_sizes(mix: dict, buckets):
    """Images per ``infer`` call the batcher can release under ``mix``:
    the request sizes themselves where one client sends one request at a
    time; the buckets themselves where every request is one image (the
    batcher takes whole buckets of them); else every size up to the
    largest bucket."""
    req = sorted({n for c in mix["sizes"] for n in range(c["lo"], c["hi"] + 1)})
    if mix["loop"] == "closed" and mix["clients"] == 1:
        return req
    if req == [1]:
        return sorted(buckets)
    return sorted(set(range(1, max(buckets) + 1)) | set(req))


def buckets_used(sizes, buckets):
    return sorted({min([b for b in buckets if b >= n] or [max(buckets)])
                   for n in sizes})


class CompileCounter:
    """Counts compiles and persistent-cache loads while ``on``."""

    def __init__(self):
        import jax
        self.on, self.compiles, self.cache_hits = False, 0, 0

        def duration(event, *a, **kw):
            if self.on and event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1

        def count(event, *a, **kw):
            if self.on and event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(duration)
        jax.monitoring.register_event_listener(count)


def profile_options():
    """The device's ops only: no Python calls and no host events, whose
    recording slows the host path under test (the harness records its
    own spans)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 0
    return opts


def compare(window, ref_logits: np.ndarray, limits: dict):
    """The numbers compared, each ``(value, limit)``: the widest gap of a
    served logit from the reference's, over the reference's largest
    |logit|, and the requests left without a whole answer."""
    scale = float(np.abs(ref_logits).max())
    worst, unanswered = 0.0, 0
    for r in window.requests:
        want = ref_logits[r.frames]
        if r.logits is None or r.logits.shape != want.shape or \
                not np.isfinite(r.logits).all():
            unanswered += 1
            continue
        worst = max(worst, float(np.abs(r.logits - want).max()))
    return {"logit_gap": (worst / scale if scale > 0 else float("inf"),
                          limits["logit_gap"]),
            "unanswered": (unanswered, 0)}


def run_cell(bench: dict, cell: dict, cfg: dict, mix: dict, seed: int,
             seconds: float, traced: bool, peaks: dict) -> dict:
    """One run of ``cell`` (its configuration ``cfg``, its mix ``mix``);
    returns the result line's object."""
    import jax
    from repro.launch.exec_cache import BucketBatcher

    name = cell["name"]
    family = importlib.import_module(f"chipbench.configs.{cfg['family']}")
    model = family.Model(cfg, seed)
    pool = np.random.default_rng([seed, 1]).random(
        (mix["pool_frames"],) + model.frame_shape, dtype=np.float32)
    sizes = release_sizes(mix, cfg["buckets"])
    used = buckets_used(sizes, cfg["buckets"])
    t0 = time.perf_counter()
    bind_before = model.bind_s
    model.warmup(used, sizes)
    compile_s = time.perf_counter() - t0 - (model.bind_s - bind_before)
    batcher = BucketBatcher(cfg["buckets"], max_wait_s=cfg["max_wait_s"])
    counter = CompileCounter()
    setup_s = time.perf_counter() - T_START
    say(f"set-up {setup_s!r} s: bind {model.bind_s!r} s, compile and "
        f"warm-up {compile_s!r} s of buckets {used} and {len(sizes)} "
        f"request sizes")

    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if traced else None
    spans = serve_loop.HostSpans() if traced else serve_loop.no_spans
    if traced:
        jax.profiler.start_trace(trace_dir, profiler_options=profile_options())
    gc.collect()
    gc.freeze()      # the harness's records stay out of the window's GC
    counter.on = True
    window = serve_loop.run(model, batcher, mix, seed, seconds, pool,
                            spans=spans)
    counter.on = False
    gc.unfreeze()
    summary = None
    if traced:
        jax.profiler.stop_trace()
        summary = tr.reduce(tr.find_xplane(trace_dir), spans.spans)
        shutil.rmtree(trace_dir, ignore_errors=True)
    lag = np.array(window.lag_s) * 1e3
    say(f"window {seconds} s: {len(window.requests)} requests, "
        f"{window.infer_calls} infer calls; compiles in the window "
        f"{counter.compiles}, persistent-cache loads {counter.cache_hits}")
    if len(lag):
        say(f"generator lateness per wake, ms: p50 "
            f"{float(np.percentile(lag, 50))!r} p99 "
            f"{float(np.percentile(lag, 99))!r} max {float(lag.max())!r} "
            f"over {len(lag)} wakes")
    say("images per infer call: " + json.dumps(
        dict(sorted(window.release_sizes.items()))))

    devices = jax.devices()[:cell["chips"]]
    mem = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
           for d in devices]
    model.release()
    gc.collect()

    ref = family.reference_logits(model, pool, REF_BLOCK)
    checks = compare(window, ref, cfg["limits"])
    correct = all(v <= lim for v, lim in checks.values())
    failed = sum(1 for r in window.requests if r.error or r.level != 0)

    rec = RunRecord(window=window, work=model.work, peaks=peaks,
                    max_bucket=max(cfg["buckets"]), setup_s=setup_s,
                    bind_s=model.bind_s, compile_s=compile_s, trace=summary)
    metrics = {}
    for m in cell_metrics(bench, name, traced):
        v = reader(m["name"])(rec)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    if summary is not None:
        least, by = rec.conv_least_s()
        say(f"conv work: least {least!r} s ({by}) against kernel time "
            f"{summary.kernel_s()!r} s, glue {summary.glue_s()!r} s, busy "
            f"{summary.busy_s!r} s of {summary.window_s!r} s")

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": max(mem)}
    result = {"correct": bool(correct), "attempted": len(window.requests),
              "failed": failed, "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = {
            "device_ops": summary.top_ops(10),
            "idle_gaps": summary.idle_by_span(serve_loop.SPAN_NAMES, 10)}
    for k, (v, lim) in checks.items():
        say(f"check {k} {v!r} limit {lim!r}")
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    return result


def use_compile_cache() -> str:
    """JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``
    where set, else ``<checkout>/.jax_cache``; every program is kept."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = load_json(ROOT / "BENCHMARK.json")
    cell, cfg, mix = find_cell(bench, args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        say(f"needs a TPU, but JAX's device 0 is on {devices[0].platform!r}")
        return 2
    if len(devices) < cell["chips"]:
        say(f"cell {cell['name']} needs {cell['chips']} chips, found "
            f"{len(devices)}")
        return 2
    try:
        peaks = work.peaks_for(devices[0].device_kind)
    except KeyError as e:
        say(str(e))
        return 2
    say(f"device {devices[0].device_kind!r} x{len(devices)}; compile cache "
        f"{use_compile_cache()}")
    result = run_cell(bench, cell, cfg, mix, args.seed, args.seconds,
                      bool(args.trace), peaks)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
