"""CI gate for the executed-sparsity benchmark: fail if the 50 %-group-
sparsity dispatch ratios in ``BENCH_sparse_cnn.json`` regress above the
committed baseline (``benchmarks/sparse_cnn_baseline.json``).

Most gated ratios are deterministic given the bench config (M-row blocks
scale linearly with batch, so they cancel), which makes them hard gates
rather than noisy perf bounds. The implicit-vs-materializing kernel
wall-clock speedup is the one timing-based gate — it is a *ratio of two
walls on the same machine* (so machine speed cancels) and gets
``WALL_SLACK`` headroom instead of the exact tolerance; absolute
wall-clock columns stay ungated (CI machines vary). Refresh the baseline
on purposeful layout/kernel changes:

    PYTHONPATH=src python -m benchmarks.check_sparse_regression --update

With ``--require-serving`` the serving artifact
(``BENCH_serving_cnn.json``) is additionally gated — baseline-free hard
floors, because both quantities have absolute contracts: steady-state
cache hit-rate must be exactly 1.0 (any miss after warmup means the
cache key or invalidation is broken, not that the machine is slow) and
the bind-amortization ratio must clear the acceptance floor of 5x (a
machine-speed-cancelling ratio of two walls on the same process).

With ``--require-streaming`` the bench's int8-streaming columns are
additionally gated — baseline-free hard floors, because both quantities
have absolute contracts: ``streamed_hbm_ratio_vs_f32`` must clear the
acceptance ceiling of 0.28 (the 1-byte-operand + 1-byte-output contract
prices every byte term at 1/4 of f32 — deterministic given the config)
and ``streamed_max_err_vs_quantized`` must be *exactly* zero (the
in-epilogue requantize either reproduces the per-layer-quantized wire
codes bitwise or it is wrong — not a tolerance question). The ratio also
joins the baseline ``GATES`` so drift below 0.28 still can't regress.

With ``--require-resilience`` the serving artifact's ``chaos`` row is
additionally gated — every gate an absolute contract, because the
resilience properties are binary: **zero wrong answers** under fault
injection (each served output bit-exact against a clean reference
server pinned to the ladder rung the request ran under), every injected
bind failure resolved by a retry or a recorded ladder downgrade, every
submitted request served or counted as shed (never hung), at least
three distinct fault kinds actually injected, a bounded shed rate
(<= 0.5), and a fingerprint-verified snapshot warm restart.

With ``--require-dsb`` the bench's dual-sided-sparsity columns are
additionally gated — absolute contracts on the 50 % row:
``dsb_max_err_vs_noskip`` must be *exactly* zero (skipping an all-zero
activation window elides an MXU pass whose contribution is exactly zero,
so skip-on either reproduces the non-skip kernel bitwise or it is
wrong), ``dsb_skip_frac`` must clear 0.3 on the bench's ReLU-sparse
input (the kernel-side skip counter — if it reads zero the skip is dead
code), the skip-vs-non-skip kernel wall ratio must clear 1.2× (machine
speed cancels), and the dense-activation ratio must clear 0.95 (a dense
input pays at most the any-nonzero reduction, never a real slowdown).
The skip fraction and speedup also join the baseline ``GATES`` so drift
above the floors still can't regress silently.

With ``--require-training`` the bench's training columns (the 50 % row's
``train_step_*`` / ``grad_parity_max_err`` / ``pruned_group_grad_max``)
are additionally gated: gradient parity vs the dense path is an absolute
contract (≤ 1e-4; the custom VJP either reproduces the masked-loss
gradients or it is wrong), pruned-group gradients must be *exactly* zero
(the HAPM no-resurrection invariant holds bitwise by construction), and
the sparse-vs-dense train-step wall ratio is gated against the baseline
with ``WALL_SLACK`` headroom like every timing ratio.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_JSON = os.path.join(ROOT, "BENCH_sparse_cnn.json")
SERVING_JSON = os.path.join(ROOT, "BENCH_serving_cnn.json")
# serving gates: absolute floors, no baseline file needed
SERVING_HIT_RATE_MIN = 1.0          # steady state must be all hits
SERVING_AMORTIZATION_MIN = 5.0      # acceptance floor (bench observes ~100x)
BASELINE_JSON = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "sparse_cnn_baseline.json")
TARGET = 0.5
TOL = 1e-6

# key -> direction: "max" = current must not exceed baseline (ratios where
# smaller is better), "min" = current must not fall below (speedup factors)
GATES = {
    "grid_step_ratio": "max",                 # dispatched grid-step ratio
    "schedule_step_ratio": "max",             # paper-granularity live steps
    "hbm_bytes_ratio": "max",                 # implicit must keep moving less
    "adaptive_vs_fixed_b1_util": "min",       # batch-1 adaptive-bm recovery
    "implicit_vs_materializing_wallclock_speedup": "min",   # timing-based
    # native int8 execution: operand-byte cut vs the f32 implicit contract
    # (deterministic; the bench additionally hard-asserts <= 0.5) and the
    # quantization-error bound vs the unquantized f32 reference
    # (deterministic given the seeded bench config; exact-on-codes parity
    # vs QAT is hard-asserted == 0 inside the bench itself)
    "quantized_hbm_ratio_vs_f32": "max",
    "quantized_max_err_vs_f32": "max",
    # end-to-end int8 streaming: 1-byte operands AND 1-byte output writes
    # (deterministic; --require-streaming additionally hard-floors it at
    # 0.28 and the wire parity at exactly zero)
    "streamed_hbm_ratio_vs_f32": "max",
    # dual-sided sparsity: the kernel-side skip counter on the bench's
    # seeded ReLU-sparse input (deterministic given the config) and the
    # skip-vs-non-skip kernel wall ratio (--require-dsb additionally
    # hard-floors both, plus exactness == 0 and the dense-act ratio)
    "dsb_skip_frac": "min",
    "dsb_kernel_speedup": "min",
}
# timing-based gates may drop to this fraction of baseline before failing
# (interpret-mode kernel ratios wobble ~10-20 % across runs/machines);
# the bench itself asserts the hard >=1.3x floor when it regenerates
WALL_KEYS = {"implicit_vs_materializing_wallclock_speedup",
             "dsb_kernel_speedup"}
WALL_SLACK = 0.7
# float-error gates get multiplicative headroom: the int8 side is exact
# integer arithmetic, but the f32 reference it is compared against can
# drift at ulp level across BLAS/XLA builds
ERR_KEYS = {"quantized_max_err_vs_f32"}
ERR_SLACK = 1.5
# streaming gates: absolute contracts, no baseline file needed
STREAMED_HBM_RATIO_MAX = 0.28       # acceptance ceiling (contract prices 0.25)
STREAMED_WIRE_ERR_MAX = 0.0         # in-epilogue requantize: bitwise or wrong
# dual-sided sparsity gates: absolute contracts on the 50 % row
DSB_SKIP_FRAC_MIN = 0.3             # ReLU-sparse input: skip >= 30 % of passes
DSB_SPEEDUP_MIN = 1.2               # skip vs non-skip kernel wall (same machine)
DSB_DENSE_ACT_RATIO_MIN = 0.95      # dense activations must not pay for the skip
DSB_EXACT_ERR_MAX = 0.0             # skip-on == skip-off: bitwise or wrong
# resilience gates: absolute contracts over the chaos row, baseline-free
CHAOS_MIN_FAULT_KINDS = 3           # the scenario must actually inject chaos
CHAOS_SHED_RATE_MAX = 0.5           # bounded shedding, never wholesale refusal
# training gates: absolute contracts (baseline-free) + one timing ratio
TRAIN_GRAD_PARITY_MAX = 1e-4        # dense-vs-sparse gradient max |err|
TRAIN_PRUNED_GRAD_MAX = 0.0         # no-resurrection: exactly zero
TRAIN_RATIO_KEY = "train_step_sparse_vs_dense_ratio"


def _row_at(report: dict, target: float) -> dict:
    for row in report["rows"]:
        if row["target_group_sparsity"] == target:
            return row
    raise SystemExit(f"no row at target_group_sparsity={target} in report")


def check_serving() -> list:
    """Gate the serving artifact's absolute contracts; returns failures."""
    if not os.path.exists(SERVING_JSON):
        return [f"missing {SERVING_JSON} (run benchmarks.bench_serving_cnn)"]
    with open(SERVING_JSON) as f:
        rep = json.load(f)
    failures = []
    for key, floor in (("steady_hit_rate", SERVING_HIT_RATE_MIN),
                       ("bind_amortization_ratio", SERVING_AMORTIZATION_MIN)):
        cur = rep.get(key)
        bad = cur is None or cur < floor - TOL
        print(f"  {key:>44}: {cur if cur is not None else 'MISSING'} "
              f"(floor {floor}) {'REGRESSED' if bad else 'ok'}")
        if bad:
            failures.append(key)
    return failures


def check_streaming(row: dict) -> list:
    """Gate the 50 %-row int8-streaming columns; returns failures."""
    failures = []
    for key, ceil in (("streamed_hbm_ratio_vs_f32", STREAMED_HBM_RATIO_MAX),
                      ("streamed_max_err_vs_quantized",
                       STREAMED_WIRE_ERR_MAX)):
        cur = row.get(key)
        bad = cur is None or cur > ceil + TOL
        print(f"  {key:>44}: {cur if cur is not None else 'MISSING'} "
              f"(ceiling {ceil}) {'REGRESSED' if bad else 'ok'}")
        if bad:
            failures.append(key)
    return failures


def check_resilience() -> list:
    """Gate the chaos row's absolute contracts; returns failures.

    The chaos scenario's value is binary properties, so every gate is a
    hard contract, not a tolerance: zero wrong answers (each served
    output bit-exact vs a clean reference at the rung it ran under),
    every injected bind failure absorbed by a retry or a recorded ladder
    downgrade, every submitted request either served or counted as shed
    (never hung), at least CHAOS_MIN_FAULT_KINDS distinct fault kinds
    actually injected, and a bounded shed rate."""
    if not os.path.exists(SERVING_JSON):
        return [f"missing {SERVING_JSON} (run benchmarks.bench_serving_cnn)"]
    with open(SERVING_JSON) as f:
        rep = json.load(f)
    chaos = rep.get("chaos")
    if not chaos:
        print("  chaos row: MISSING (run benchmarks.bench_serving_cnn "
              "--chaos) REGRESSED")
        return ["chaos_row_missing"]
    failures = []
    res = chaos.get("resilience", {})
    trace = chaos.get("trace", {})
    injected = chaos.get("faults_injected", {})
    checks = [
        ("chaos_wrong_answers", chaos.get("wrong_answers"), 0,
         "== (bit-exact per rung or it is a wrong answer)"),
        ("chaos_fault_kinds", len(chaos.get("fault_kinds", [])),
         CHAOS_MIN_FAULT_KINDS, ">="),
        ("chaos_bind_faults_resolved",
         injected.get("bind_fail", 0)
         - res.get("bind_retries", 0) - res.get("bind_failures", 0), 0,
         "== (each injected bind failure retried or downgraded)"),
        ("chaos_requests_accounted",
         trace.get("submitted", -1)
         - trace.get("requests", 0) - trace.get("shed", 0), 0,
         "== (served + shed == submitted: nothing hangs)"),
        ("chaos_shed_rate", chaos.get("shed_rate"), CHAOS_SHED_RATE_MAX,
         "<="),
        ("chaos_snapshot_warm_restart",
         chaos.get("snapshot_warm_restart"), True, "=="),
    ]
    for key, cur, bound, op in checks:
        if cur is None:
            bad = True
        elif op.startswith("=="):
            bad = cur != bound
        elif op == ">=":
            bad = cur < bound
        else:
            bad = cur > bound + TOL
        print(f"  {key:>44}: {cur if cur is not None else 'MISSING'} "
              f"({op} {bound}) {'REGRESSED' if bad else 'ok'}")
        if bad:
            failures.append(key)
    return failures


def check_dsb(row: dict) -> list:
    """Gate the 50 %-row dual-sided-sparsity columns; returns failures.

    A missing column fails too (bench freshness: an artifact produced by
    a pre-DSB bench has nothing to gate and must be regenerated)."""
    failures = []
    checks = (
        ("dsb_max_err_vs_noskip", DSB_EXACT_ERR_MAX, "<="),
        ("dsb_skip_frac", DSB_SKIP_FRAC_MIN, ">="),
        ("dsb_kernel_speedup", DSB_SPEEDUP_MIN, ">="),
        ("dsb_dense_act_ratio", DSB_DENSE_ACT_RATIO_MIN, ">="),
    )
    for key, bound, op in checks:
        cur = row.get(key)
        if cur is None:
            bad = True
        elif op == ">=":
            bad = cur < bound - TOL
        else:
            bad = cur > bound + TOL
        print(f"  {key:>44}: {cur if cur is not None else 'MISSING'} "
              f"({op} {bound}) {'REGRESSED' if bad else 'ok'}")
        if bad:
            failures.append(key)
    return failures


def check_training(row: dict, baseline: dict) -> list:
    """Gate the 50 %-row training columns; returns failures."""
    failures = []
    for key, ceil in (("grad_parity_max_err", TRAIN_GRAD_PARITY_MAX),
                      ("pruned_group_grad_max", TRAIN_PRUNED_GRAD_MAX)):
        cur = row.get(key)
        bad = cur is None or cur > ceil + TOL
        print(f"  {key:>44}: {cur if cur is not None else 'MISSING'} "
              f"(ceiling {ceil}) {'REGRESSED' if bad else 'ok'}")
        if bad:
            failures.append(key)
    cur = row.get(TRAIN_RATIO_KEY)
    base = baseline.get("gates", {}).get(TRAIN_RATIO_KEY)
    if cur is None:
        print(f"  {TRAIN_RATIO_KEY:>44}: MISSING (rerun the bench) REGRESSED")
        failures.append(TRAIN_RATIO_KEY)
    elif base is not None:
        # smaller is better; allow the same timing headroom as WALL_KEYS
        bad = cur > base / WALL_SLACK + TOL
        print(f"  {TRAIN_RATIO_KEY:>44}: {cur:.6f} (baseline {base:.6f}, "
              f"max, slack 1/{WALL_SLACK}) {'REGRESSED' if bad else 'ok'}")
        if bad:
            failures.append(TRAIN_RATIO_KEY)
    else:
        print(f"  {TRAIN_RATIO_KEY:>44}: {cur:.6f} (no baseline — refresh "
              f"with --update) ok")
    return failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--update", action="store_true",
                    help="rewrite the baseline from the current bench output")
    ap.add_argument("--require-serving", action="store_true",
                    help="also gate BENCH_serving_cnn.json (hit-rate, "
                         "bind amortization)")
    ap.add_argument("--require-streaming", action="store_true",
                    help="also hard-floor the bench's int8-streaming "
                         "columns (HBM ratio <= 0.28, wire parity == 0)")
    ap.add_argument("--require-dsb", action="store_true",
                    help="also hard-floor the bench's dual-sided-sparsity "
                         "columns (skip frac >= 0.3, kernel speedup >= 1.2x, "
                         "dense-act ratio >= 0.95, exactness == 0)")
    ap.add_argument("--require-training", action="store_true",
                    help="also gate the bench's training columns (grad "
                         "parity, pruned-group grads, train-step ratio)")
    ap.add_argument("--require-resilience", action="store_true",
                    help="also gate the serving chaos row (zero wrong "
                         "answers, bind faults resolved, bounded shed rate)")
    args = ap.parse_args(argv)

    with open(BENCH_JSON) as f:
        report = json.load(f)
    row = _row_at(report, TARGET)

    if args.update:
        gates = {k: row[k] for k in GATES}
        if TRAIN_RATIO_KEY in row:
            gates[TRAIN_RATIO_KEY] = row[TRAIN_RATIO_KEY]
        baseline = {"config": report["config"], "target_group_sparsity": TARGET,
                    "gates": gates}
        with open(BASELINE_JSON, "w") as f:
            json.dump(baseline, f, indent=2)
        print(f"wrote {BASELINE_JSON}: {baseline['gates']}")
        return 0

    with open(BASELINE_JSON) as f:
        baseline = json.load(f)
    # batch / fast don't move the gated ratios (M-row blocks cancel)
    relevant = lambda c: {k: v for k, v in c.items() if k not in ("batch", "fast")}
    if relevant(baseline["config"]) != relevant(report["config"]):
        print(f"bench config changed ({report['config']} vs baseline "
              f"{baseline['config']}) — refresh the baseline with --update",
              file=sys.stderr)
        return 1

    failures = []
    for key, direction in GATES.items():
        cur, base = row[key], baseline["gates"][key]
        if key in WALL_KEYS:
            assert direction == "min", "wall gates are speedup floors"
            bad = cur < base * WALL_SLACK - TOL
            note = f"baseline {base:.6f}, {direction}, slack {WALL_SLACK}"
        elif key in ERR_KEYS:
            assert direction == "max", "error gates are upper bounds"
            bad = cur > base * ERR_SLACK + TOL
            note = f"baseline {base:.6f}, {direction}, slack {ERR_SLACK}"
        else:
            bad = (cur > base + TOL) if direction == "max" else (cur < base - TOL)
            note = f"baseline {base:.6f}, {direction}"
        mark = "REGRESSED" if bad else "ok"
        print(f"  {key:>44}: {cur:.6f} ({note}) {mark}")
        if bad:
            failures.append(key)
    if args.require_serving:
        failures += check_serving()
    if args.require_streaming:
        failures += check_streaming(row)
    if args.require_dsb:
        failures += check_dsb(row)
    if args.require_training:
        failures += check_training(row, baseline)
    if args.require_resilience:
        failures += check_resilience()
    if failures:
        print(f"\nexecuted-sparsity regression at {TARGET:.0%} group "
              f"sparsity: {failures}", file=sys.stderr)
        return 1
    print("\nno executed-sparsity regression vs committed baseline")
    return 0


if __name__ == "__main__":
    sys.exit(main())
