"""Executed group sparsity: HAPM masks through the Pallas DSB kernels, on
both data-movement contracts. Sweeps group sparsity
0/25/50/75 % on the paper's CNN (reduced, 3 stages so the 4×4 tail layers
exercise adaptive M-blocking) and for each level reports dense-vs-sparse
*dispatched grid steps*, wall clock, parity vs the dense path, and the
cycle model's DSB prediction for the same masks — the paper's Table II
loop as an executed measurement, not just a priced one.

Execution columns:

- ``wall_sparse_ms`` — the production path: packed MXU-shaped layout,
  **implicit-im2col** kernel (windows gathered from the padded NHWC
  activation inside the grid, no ``(M, kx·ky·cin)`` patch matrix in HBM)
  with adaptive ``bm`` M-blocking.
- ``wall_materializing_ms`` — the PR-3 contract: same layout and plans,
  patch matrix materialized + repacked in HBM, fixed ``bm=128``. The
  parity oracle the implicit kernel must match bit-for-bit in schedule
  accounting.
- ``wall_implicit_kernel_ms`` / ``wall_materializing_kernel_ms`` — the
  same pair with the dense-lax fallback *disabled*, so every layer runs
  its kernel: the isolated data-movement comparison
  (``implicit_vs_materializing_wallclock_speedup`` gates ≥ 1.3× at the
  paper's 50 % operating point).
- ``hbm_bytes_moved_*`` — analytic HBM traffic of each contract
  (``sparse.conv_plan.conv_hbm_bytes``); ``bm_effective`` — the adaptive
  M-block per layer.
- ``padded_mac_utilization*`` — M-padding-aware MAC utilization of the
  dispatched tiles; the ``_b1`` columns show the batch-1 tail, where
  adaptive bm must recover ≥ 2× over fixed ``bm=128``.
- ``wall_quantized_ms`` / ``quantized_*`` — **native Q2.5×Q3.4 int8
  execution** (``ExecSpec(quantized=True)``): int8 operand
  codes, int32 accumulation, per-cout dequant fused at the flush, on the
  same plans and schedule as the f32 implicit path (asserted identical).
  Parity vs the dense QAT forward is *bit-exact* (integer arithmetic;
  asserted == 0), ``quantized_max_err_vs_f32`` records the quantization
  error vs the unquantized f32 reference, and
  ``quantized_hbm_ratio_vs_f32`` the int8-operand byte cut (gated
  ≤ 0.5× at the 50 % operating point).
- ``wall_streamed_ms`` / ``streamed_*`` — **end-to-end int8 activation
  streaming** (``ExecSpec(streamed=True)``, BN-folded tree): every
  layer's fused flush requantizes in-epilogue and emits int8 Q3.4
  codes which the next layer's gather ingests directly — the wire
  between layers carries 1 byte/element, no f32 round-trip through
  HBM. Parity vs the PR-5 per-layer-quantized path with host-side
  ``round_sat`` at the identical program points
  (``apply_folded(wire_quantize=True)``) is *bit-exact on codes*
  (asserted == 0), and ``streamed_hbm_ratio_vs_f32`` prices the
  1-byte-operand + 1-byte-output contract (gated ≤ 0.28× at 50 %).
- ``dsb_*`` / ``wall_dsb*_ms`` — **dual-sided sparsity**
  (``ExecSpec(activation_dsb=True)``): the implicit kernel skips the
  gather + MXU pass of every all-zero activation window (exact int8
  codes on the streamed wire). Measured per row on a designated workload
  layer fed a structured ReLU-sparse activation (every other K-tile's
  channel block dead — the pattern a structurally-pruned upstream layer
  emits — plus elementwise post-ReLU zeros): ``dsb_skip_frac`` (the
  kernel-side skip counter, gated ≥ 0.3 at 50 %), wall clock vs the
  non-skip twin (``dsb_kernel_speedup``, gated ≥ 1.2× at 50 %),
  bit-exactness (``dsb_max_err_vs_noskip``, asserted == 0 every row),
  and the dense-activation non-regression (``dsb_dense_act_ratio``,
  gated ≥ 0.95: a dense input pays only the any-nonzero reduction).
  ``dsb_skip_frac_e2e`` is the served end-to-end skip on a half-dead
  frame through ``measure_dsb_skip``.

``schedule_steps_live`` is the layout-independent paper granularity,
asserted equal to the cycle model's DSB step count AND identical across
the implicit / materializing / per-group executions. At density 1.0
every layer must hit the dense ``lax.conv`` fallback in every exec (all
paths are then the *same* jitted graph, so their wall clock is timed
once and the speedup columns are exactly 1.0 — the PR-3 bench timed the
identical graphs separately and recorded timing noise as a 0.80×
"regression").

Emits ``BENCH_sparse_cnn.json`` at the repo root (uploaded as a CI
artifact: the perf trajectory; ``benchmarks.check_sparse_regression``
gates the 50 %-sparsity ratios against the committed baseline).
"""
from __future__ import annotations

import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.accel import BOARDS, simulate
from repro.core import (HAPMConfig, apply_masks, hapm_element_masks,
                        hapm_epoch_update, hapm_init)
from repro.models import cnn

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT_JSON = os.path.join(ROOT, "BENCH_sparse_cnn.json")

SWEEP = (0.0, 0.25, 0.5, 0.75)


def _timed(fn, *a, reps=5):
    # min over blocking reps, not a pipelined mean: a single scheduler
    # spike inflates a mean and flips the near-threshold speedup asserts,
    # while the min estimates the uncontended cost
    fn(*a)[0].block_until_ready()            # warmup / compile
    best = float("inf")
    for _ in range(reps):
        t0 = time.time()
        out = fn(*a)
        out[0].block_until_ready()
        best = min(best, time.time() - t0)
    return out, best


def run(args=None) -> dict:
    fast = bool(getattr(args, "fast", False))
    print("=" * 72)
    print("group-sparse CNN inference through the Pallas DSB kernels")
    print("=" * 72)
    n_cu = 12                               # the paper's CU count
    batch = 2 if fast else 4
    cfg = cnn.ResNetConfig(stages=(1, 1, 2), widths=(16, 32, 64),
                           image_size=16)
    n_layers = len(cnn.conv_layer_order(cfg))
    params, state = cnn.init(jax.random.PRNGKey(0), cfg)
    # equal per-layer weight scale so the *global* HAPM sort spreads groups
    # across layers (isolates the kernel measurement from init-scale skew)
    params = jax.tree_util.tree_map_with_path(
        lambda p, l: l / jnp.std(l) * 0.1 if cnn.is_conv_weight(p, l) else l,
        params)
    specs = cnn.conv_group_specs(params, n_cu)
    x = jax.random.uniform(jax.random.PRNGKey(1), (batch, 16, 16, 3))
    accel = dataclasses.replace(BOARDS["zedboard_100mhz_72dsp"], n_cu=n_cu)

    dense_apply = jax.jit(lambda p, s, xx: cnn.apply(p, s, xx, cfg))
    qcfg = dataclasses.replace(cfg, quantized=True)
    dense_qat_apply = jax.jit(lambda p, s, xx: cnn.apply(p, s, xx, qcfg))
    rows = []
    st50 = None
    print(f"\n{'target':>7} {'impl exec/dense':>16} {'dsb':>6} "
          f"{'dense ms':>9} {'impl ms':>8} {'mat ms':>7} {'kern x':>7} "
          f"{'hbm x':>6} {'q ms':>7} {'q hbm x':>8} {'s ms':>7} "
          f"{'s hbm x':>8} {'util b1':>8} {'max err':>9}")
    for target in SWEEP:
        hcfg = HAPMConfig(target, 1)
        st = hapm_init(specs, hcfg)
        if target > 0:
            st = hapm_epoch_update(st, specs, params, hcfg)
        pruned = apply_masks(params, hapm_element_masks(specs, st))
        if target == 0.5:
            st50, pruned50 = st, pruned

        # one bind per execution contract per sparsity level, reused for
        # step accounting AND timing (weights prepacked at bind time) —
        # all through the unified entry point, one ExecSpec per contract
        bind = lambda **kw: cnn.bind_execution(
            pruned, cfg, spec=cnn.ExecSpec(n_cu=n_cu, **kw),
            specs=specs, group_masks=st.group_masks)
        execs = {
            # production: implicit kernel, adaptive bm
            "implicit": bind(implicit=True),
            # PR-3 contract: HBM patch matrix, fixed bm
            "materializing": bind(implicit=False, bm=128),
        }
        # kernel-only twins (no dense fallback): the isolated
        # implicit-vs-materializing data-movement comparison
        kernel_only = {
            kind: bind(implicit=(kind == "implicit"),
                       bm="auto" if kind == "implicit" else 128,
                       dense_fallback=2.0)
            for kind in ("implicit", "materializing")
        }
        # native Q2.5×Q3.4 int8 execution: same layouts/plans/schedule,
        # int8 operand codes + int32 accumulation + fused per-cout dequant.
        # dense_fallback=2.0 so every layer runs its int8 kernel — the bench
        # claim is about the executed fixed-point path, not the lax fallback
        q_execs = {
            kind: bind(implicit=(kind == "implicit"),
                       bm="auto" if kind == "implicit" else 128,
                       quantized=True, dense_fallback=2.0)
            for kind in ("implicit", "materializing")
        }

        # exactness of the bridge, all contracts: schedule-group accounting
        # (per-tile occupancy) is kernel-independent and equals the cycle
        # model's DSB step count
        live_groups = int(sum(np.asarray(cnn._get_path(st.group_masks, k)).sum()
                              for k in execs["implicit"].plans))
        total_groups = sum(np.asarray(cnn._get_path(st.group_masks, k)).size
                           for k in execs["implicit"].plans)
        for kind, e in {**execs, **{"ko_" + k: v for k, v in kernel_only.items()},
                        **{"q_" + k: v for k, v in q_execs.items()}}.items():
            assert e.schedule_step_counts() == (live_groups, total_groups), kind
        # acceptance: the int8 execution dispatches the identical schedule
        # (and grid) as the f32 path — quantization changes operand bytes,
        # never the DSB plan
        for kind in ("implicit", "materializing"):
            assert (q_execs[kind].step_counts(cfg, batch=1)
                    == kernel_only[kind].step_counts(cfg, batch=1)), kind

        # dispatch accounting at batch=1 (per image, like the simulator):
        # the 4x4 tail layers make M-blocks round with ceil, so per-batch
        # counts are NOT linear in batch — per-image numbers are the
        # config-only deterministic quantity the CI baseline can gate
        steps = {k: e.step_counts(cfg, batch=1) for k, e in execs.items()}
        fallbacks = {k: sum(v is None for v in e.table.values())
                     for k, e in execs.items()}
        # density 1.0 must fall back to dense lax.conv for EVERY layer in
        # EVERY exec — the packed any-group-live tiles make the plan fully
        # dense, and dispatching a full padded grid would only add work
        if target == 0.0:
            assert all(n == n_layers for n in fallbacks.values()), fallbacks

        (ref, _), t_dense = _timed(dense_apply, pruned, state, x)
        walls, errs = {}, {}
        timed_graphs = {}
        for kind, e in {**execs,
                        **{"ko_" + k: v for k, v in kernel_only.items()}}.items():
            # identical fallback graphs are timed once (all-fallback execs
            # dispatch the exact same dense lax.conv computation — timing
            # them separately only measures noise)
            graph_key = ("all-dense" if all(v is None for v in e.table.values())
                         else kind)
            if graph_key in timed_graphs:
                (out, _), walls[kind] = timed_graphs[graph_key]
            else:
                sparse_apply = jax.jit(
                    lambda p, s, xx, ee=e: cnn.apply(p, s, xx, cfg, sparse=ee))
                (out, _), walls[kind] = timed_graphs.setdefault(
                    graph_key, _timed(sparse_apply, pruned, state, x))
            errs[kind] = float(jnp.max(jnp.abs(out - ref)))

        # the fixed-point execution: parity vs the dense QAT forward must
        # be BIT-EXACT (int32 accumulation == the f32 reference's exact
        # sub-2^24 code sums), both kernels agreeing with each other too.
        # That claim has a precondition — the f32 reference itself must be
        # exact — so guard it loudly before asserting hard equality:
        from repro.core.quant import f32_parity_is_exact
        max_k = max(3 * 3 * cin for cin in (3,) + cfg.widths)
        assert f32_parity_is_exact(max_k), (
            f"bench config grew past the f32-exactness bound (K={max_k}): "
            "the f32 QAT reference would round while the int32 kernels stay "
            "exact — switch the parity asserts below to a tolerance")
        (qat_ref, _), _ = _timed(dense_qat_apply, pruned, state, x)
        q_outs = {}
        for kind, e in q_execs.items():
            sparse_apply = jax.jit(
                lambda p, s, xx, ee=e: cnn.apply(p, s, xx, qcfg, sparse=ee))
            (q_outs[kind], _), walls["q_" + kind] = _timed(
                sparse_apply, pruned, state, x)
        err_q_qat = max(float(jnp.max(jnp.abs(o - qat_ref)))
                        for o in q_outs.values())
        assert err_q_qat == 0.0, \
            f"int8 execution diverged from QAT codes at {target}: {err_q_qat}"
        assert bool(jnp.all(q_outs["implicit"] == q_outs["materializing"]))
        err_q_f32 = float(jnp.max(jnp.abs(q_outs["implicit"] - ref)))

        # end-to-end int8 activation streaming: BN-folded tree, every
        # layer's flush requantizes in-epilogue, the next layer ingests the
        # emitted Q3.4 codes — the inter-layer wire is 1 byte/element. The
        # parity reference is the SAME per-layer-quantized kernels (the
        # PR-5 contract: f32 flush) with host-side round_sat at the
        # identical program points (apply_folded(wire_quantize=True)), so
        # code equality isolates *where* the requantize runs, nothing else
        folded_t = cnn.fold_batchnorm(pruned, state, cfg)
        fbind = lambda **kw: cnn.bind_execution(
            folded_t, cfg,
            spec=cnn.ExecSpec(n_cu=n_cu, quantized=True, folded=True,
                              dense_fallback=2.0, **kw),
            specs=specs, group_masks=st.group_masks)
        s_execs = {kind: fbind(streamed=True, implicit=(kind == "implicit"),
                               bm="auto" if kind == "implicit" else 128)
                   for kind in ("implicit", "materializing")}
        s_outs = {}
        for kind, e in s_execs.items():
            fn = jax.jit(lambda xx, ee=e: (cnn.apply_folded(
                folded_t, xx, cfg, sparse=ee),))
            out_s, walls["s_" + kind] = _timed(fn, x)
            s_outs[kind] = out_s[0]
        wire_exec = fbind(implicit=True)
        wire_ref = jax.jit(lambda xx: cnn.apply_folded(
            folded_t, xx, cfg, sparse=wire_exec, wire_quantize=True))(x)
        err_s_wire = max(float(jnp.max(jnp.abs(o - wire_ref)))
                         for o in s_outs.values())
        assert err_s_wire == 0.0, \
            f"streamed wire diverged from the requantized reference at " \
            f"{target}: {err_s_wire}"
        assert bool(jnp.all(s_outs["implicit"] == s_outs["materializing"]))
        err_s_f32 = float(jnp.max(jnp.abs(s_outs["implicit"] - ref)))

        # ---- dual-sided sparsity: activation-DSB on the streamed wire ----
        # The skip twin of the streamed implicit exec: identical bind plus
        # @pl.when branches around the gather+MXU pass of every all-zero
        # activation window (exact int8 codes — post-ReLU zeros are exact
        # on the wire, so skipping is bit-free). Measured on a designated
        # workload layer fed a *structured* ReLU-sparse activation: every
        # other K-tile's channel block killed (the pattern a structurally
        # pruned upstream layer emits — dead couts are exact zero codes)
        # plus ~30 % elementwise post-ReLU zeros, at a batch sized so the
        # kernel (not dispatch overhead) dominates the wall clock.
        d_exec = fbind(streamed=True, implicit=True, activation_dsb=True)
        DSB_LAYER = ("s2b0", "conv1", "w")     # 32 -> 64, stride 2, 8x8 in
        d_conv = d_exec.table[DSB_LAYER]
        s_conv = s_execs["implicit"].table[DSB_LAYER]
        dsb_stride, dsb_batch, dsb_cin = 2, 16, cfg.widths[1]
        cpk = d_conv.layout.implicit_geometry()["cpk"]
        drng = np.random.RandomState(7)
        xa = np.abs(drng.randn(dsb_batch, 8, 8, dsb_cin).astype(np.float32))
        xa[drng.rand(*xa.shape) < 0.3] = 0.0        # elementwise ReLU zeros
        for c0 in range(0, dsb_cin, 2 * cpk):
            xa[..., c0:c0 + cpk] = 0.0              # every other K-tile dead
        xa = jnp.asarray(xa)
        xa_dense = jnp.asarray(np.abs(
            np.random.RandomState(8).randn(*xa.shape)).astype(np.float32) + 0.1)
        y_dsb, dsb_stats = d_conv.skip_counts(xa, stride=dsb_stride)
        dsb_skip_frac = (dsb_stats["skipped_steps"]
                         / max(dsb_stats["live_steps"], 1))
        err_dsb = float(jnp.max(jnp.abs(
            y_dsb.astype(jnp.int32)
            - s_conv(xa, stride=dsb_stride).astype(jnp.int32)))) \
            if dsb_stats["live_steps"] else 0.0
        assert err_dsb == 0.0, \
            f"activation-DSB diverged from the non-skip kernel at " \
            f"{target}: {err_dsb}"
        _dl = lambda fn: (lambda xx: (fn(xx, stride=dsb_stride),))
        _, t_dsb = _timed(_dl(d_conv), xa)
        _, t_noskip = _timed(_dl(s_conv), xa)
        _, t_dsb_d = _timed(_dl(d_conv), xa_dense)
        _, t_noskip_d = _timed(_dl(s_conv), xa_dense)
        # end-to-end served skip on a ReLU-sparse frame (dead bottom half)
        x_relu = np.array(x)
        x_relu[:, cfg.image_size // 2:] = 0.0
        dsb_e2e = d_exec.measure_dsb_skip(folded_t, jnp.asarray(x_relu), cfg)

        rep = simulate(pruned, state, cfg, accel)
        assert (rep.schedule_steps_live, rep.schedule_steps_total) == \
            (live_groups, total_groups), "cycle-model step accounting drifted"
        # every accounting field from the one report() artifact (the same
        # dict the simulator and the serving driver consume); the implicit
        # exec's canonical hbm_bytes_* contracts cover all four pricing
        # corners, so the quantized/materializing execs need no re-query
        imp_rep = execs["implicit"].report(cfg, batch=1)   # per image
        imp_rep_b = execs["implicit"].report(cfg, batch=batch)
        mat_rep = execs["materializing"].report(cfg, batch=1)
        util_b1 = imp_rep["padded_mac_utilization"]
        util_b1_fixed = mat_rep["padded_mac_utilization"]
        hbm_imp = imp_rep["hbm_bytes_implicit"]
        hbm_mat = imp_rep["hbm_bytes_materialized"]
        # int8 operand pricing: same plans, 1-byte slabs/patches/weights
        q_hbm = imp_rep["hbm_bytes_implicit_int8"]
        q_hbm_mat = imp_rep["hbm_bytes_materialized_int8"]
        assert q_hbm == q_execs["implicit"].hbm_bytes(cfg, batch=1)
        # streamed pricing: 1-byte operands AND 1-byte output writes; a
        # streamed exec's own-policy hbm_bytes IS the streamed contract
        s_hbm = imp_rep["hbm_bytes_streamed_int8"]
        assert s_hbm == s_execs["implicit"].hbm_bytes(cfg, batch=1)
        assert s_execs["implicit"].report(cfg, batch=1)["streamed"]
        row = {
            "target_group_sparsity": target,
            # grid steps at the PR-3 fixed blocking (deterministic,
            # baseline-comparable) and at the implicit adaptive blocking
            "executed_grid_steps": steps["materializing"][0],
            "dense_grid_steps": steps["materializing"][1],
            "grid_step_ratio": steps["materializing"][0] / steps["materializing"][1],
            "implicit_executed_grid_steps": steps["implicit"][0],
            "implicit_dense_grid_steps": steps["implicit"][1],
            # wall clock: production paths (dense fallback active)
            "wall_sparse_ms": walls["implicit"] * 1e3,
            "wall_materializing_ms": walls["materializing"] * 1e3,
            # wall clock: kernels isolated (fallback disabled)
            "wall_implicit_kernel_ms": walls["ko_implicit"] * 1e3,
            "wall_materializing_kernel_ms": walls["ko_materializing"] * 1e3,
            "implicit_vs_materializing_wallclock_speedup":
                walls["ko_materializing"] / walls["ko_implicit"],
            # the data-movement contract, analytically
            "hbm_bytes_moved_implicit": hbm_imp,
            "hbm_bytes_moved_materialized": hbm_mat,
            "hbm_bytes_ratio": hbm_imp / hbm_mat,
            "bm_effective": imp_rep["bm_effective"],
            # native int8 execution: wall clock, byte cut, parity
            "wall_quantized_ms": walls["q_implicit"] * 1e3,
            "wall_quantized_materializing_ms": walls["q_materializing"] * 1e3,
            "quantized_max_err_vs_qat": err_q_qat,
            "quantized_max_err_vs_f32": err_q_f32,
            "hbm_bytes_moved_quantized": q_hbm,
            "hbm_bytes_moved_quantized_materialized": q_hbm_mat,
            "quantized_hbm_ratio_vs_f32": q_hbm / hbm_imp,
            # int8 activation streaming: wall clock, wire parity, byte cut
            "wall_streamed_ms": walls["s_implicit"] * 1e3,
            "wall_streamed_materializing_ms": walls["s_materializing"] * 1e3,
            "streamed_max_err_vs_quantized": err_s_wire,
            "streamed_max_err_vs_f32": err_s_f32,
            "hbm_bytes_moved_streamed": s_hbm,
            "streamed_hbm_ratio_vs_f32": s_hbm / hbm_imp,
            # dual-sided sparsity: activation-DSB skip on the streamed
            # wire, measured on the designated workload layer (ReLU-sparse
            # input) and end-to-end on a half-dead frame
            "dsb_skip_frac": dsb_skip_frac,
            "dsb_skipped_steps": dsb_stats["skipped_steps"],
            "dsb_live_steps": dsb_stats["live_steps"],
            "wall_dsb_ms": t_dsb * 1e3,
            "wall_noskip_ms": t_noskip * 1e3,
            "dsb_kernel_speedup": t_noskip / t_dsb,
            "wall_dsb_dense_act_ms": t_dsb_d * 1e3,
            "wall_noskip_dense_act_ms": t_noskip_d * 1e3,
            "dsb_dense_act_ratio": t_noskip_d / t_dsb_d,
            "dsb_max_err_vs_noskip": err_dsb,
            "dsb_skip_frac_e2e": dsb_e2e["dsb_skip_frac"],
            # M-padding-aware MAC utilization of the dispatched tiles
            "padded_mac_utilization": imp_rep_b["padded_mac_utilization"],
            "padded_mac_utilization_b1": util_b1,
            "padded_mac_utilization_b1_fixed_bm": util_b1_fixed,
            "adaptive_vs_fixed_b1_util": util_b1 / util_b1_fixed,
            # layout-independent accounting + model prediction + parity
            "schedule_steps_live": live_groups,
            "schedule_steps_total": total_groups,
            "schedule_step_ratio": live_groups / total_groups,
            "dsb_cycle_ratio": rep.dsb_cycle_ratio,
            "wall_dense_ms": t_dense * 1e3,
            "max_err_vs_dense": max(errs.values()),
            "dense_fallback_layers": fallbacks["implicit"],
        }
        rows.append(row)
        print(f"{target:>7.2f} {steps['implicit'][0]:>6}/{steps['implicit'][1]:<9} "
              f"{row['dsb_cycle_ratio']:>6.3f} {t_dense*1e3:>9.2f} "
              f"{walls['implicit']*1e3:>8.2f} {walls['materializing']*1e3:>7.2f} "
              f"{row['implicit_vs_materializing_wallclock_speedup']:>7.2f} "
              f"{row['hbm_bytes_ratio']:>6.2f} {walls['q_implicit']*1e3:>7.2f} "
              f"{row['quantized_hbm_ratio_vs_f32']:>8.2f} "
              f"{walls['s_implicit']*1e3:>7.2f} "
              f"{row['streamed_hbm_ratio_vs_f32']:>8.2f} {util_b1:>8.3f} "
              f"{row['max_err_vs_dense']:>9.2e}")
        print(f"{'':>7} dual-sided: skip {dsb_skip_frac:.2f} "
              f"({dsb_stats['skipped_steps']}/{dsb_stats['live_steps']}), "
              f"kernel {t_noskip * 1e3:.2f} -> {t_dsb * 1e3:.2f} ms "
              f"({row['dsb_kernel_speedup']:.2f}x), dense-act ratio "
              f"{row['dsb_dense_act_ratio']:.2f}, e2e skip "
              f"{row['dsb_skip_frac_e2e']:.3f}, err {err_dsb:.1f}")
        assert row["max_err_vs_dense"] < 1e-4, \
            f"sparse path diverged from dense at {target}"
        if target == 0.0:
            # the production execs are all identical all-fallback graphs:
            # exactly no speedup recorded (the kernel-only twins still run
            # their kernels — that comparison stays live at full density)
            assert row["wall_sparse_ms"] == row["wall_materializing_ms"]

    # both the executed grid (any contract) and the priced FPGA schedule
    # shrink monotonically with group sparsity (HAPM masks are nested
    # across targets); network totals weight layers differently — per-step
    # FPGA cycles vs M-row blocks — so only the per-layer step counts,
    # asserted above, are exactly equal
    for a, b in zip(rows, rows[1:]):
        assert b["grid_step_ratio"] <= a["grid_step_ratio"] + 1e-9
        assert b["dsb_cycle_ratio"] <= a["dsb_cycle_ratio"] + 1e-9
    at50 = next(r for r in rows if r["target_group_sparsity"] == 0.5)
    # the implicit kernel's whole point: same plans and schedule, less data
    # moved (deterministic) and measurably faster with the patch matrix gone
    assert at50["hbm_bytes_ratio"] <= 0.8, at50
    assert at50["implicit_vs_materializing_wallclock_speedup"] >= 1.3, at50
    # adaptive M-blocking's whole point: batch-1 tails stop padding to 128
    assert at50["adaptive_vs_fixed_b1_util"] >= 2.0, at50
    # the quantized execution's whole point: int8 operand codes move no
    # more than half the f32-operand bytes at the paper's operating point
    # (2-4x on the operand terms; the output write stays f32)
    assert at50["quantized_hbm_ratio_vs_f32"] <= 0.5, at50
    # and parity vs QAT is exact on codes at every sparsity (asserted per
    # row == 0.0); vs the f32 reference only quantization noise remains
    assert all(r["quantized_max_err_vs_qat"] == 0.0 for r in rows)
    assert at50["quantized_max_err_vs_f32"] <= 1.0, at50
    # the streamed execution's whole point: 1-byte operands AND 1-byte
    # output writes — the end-to-end wire moves ~1/4 the f32 bytes — with
    # logits code-exact vs the per-layer-quantized path at every sparsity
    assert at50["streamed_hbm_ratio_vs_f32"] <= 0.28, at50
    assert all(r["streamed_max_err_vs_quantized"] == 0.0 for r in rows)
    assert at50["streamed_max_err_vs_f32"] <= 1.0, at50
    # dual-sided sparsity's whole point: on a ReLU-sparse activation the
    # kernel elides >= 30 % of its MXU passes and is measurably faster,
    # bit-exactly (asserted == 0 per row), while a dense activation pays
    # at most the per-window any-nonzero reduction (ratio >= 0.95)
    assert all(r["dsb_max_err_vs_noskip"] == 0.0 for r in rows)
    assert at50["dsb_skip_frac"] >= 0.3, at50
    assert at50["dsb_kernel_speedup"] >= 1.2, at50
    assert at50["dsb_dense_act_ratio"] >= 0.95, at50

    # ---- training through the kernels at the 50 % operating point -------
    # one SGD-style fwd+bwd step, dense lax.conv vs the trainable sparse
    # bind (custom VJP through the block-sparse kernels). Grad parity is
    # the acceptance claim; the wall-clock ratio is recorded for the
    # baseline gate (on CPU the sparse step runs the kernels in interpret
    # mode, so the ratio is hardware-meaningful only on TPU — same caveat
    # as every wall column above).
    masks50 = hapm_element_masks(specs, st50)
    texec = cnn.bind_execution(pruned50, cfg,
                               spec=cnn.ExecSpec(n_cu=n_cu, trainable=True),
                               specs=specs, group_masks=st50.group_masks)
    y = jax.random.randint(jax.random.PRNGKey(2), (batch,), 0, cfg.num_classes)

    def _step(sparse):
        def loss(p):
            logits, _ = cnn.apply(apply_masks(p, masks50), state, x, cfg,
                                  train=True, sparse=sparse)
            lp = jax.nn.log_softmax(logits)
            return -jnp.mean(jnp.take_along_axis(lp, y[:, None], axis=-1))
        return jax.jit(lambda p: jax.value_and_grad(loss)(p))

    (ld, gd), t_train_dense = _timed(_step(None), pruned50, reps=3)
    (ls, gs), t_train_sparse = _timed(_step(texec), pruned50, reps=3)
    grad_err = max(float(jnp.max(jnp.abs(a - b))) for a, b in
                   zip(jax.tree.leaves(gd), jax.tree.leaves(gs)))
    pruned_grad = max(
        float(jnp.max(jnp.abs(g * (1 - m)))) if m is not None else 0.0
        for g, m in zip(jax.tree.leaves(gs),
                        jax.tree.leaves(masks50, is_leaf=lambda v: v is None)))
    at50.update({
        "train_step_dense_ms": t_train_dense * 1e3,
        "train_step_sparse_ms": t_train_sparse * 1e3,
        "train_step_sparse_vs_dense_ratio": t_train_sparse / t_train_dense,
        "grad_parity_max_err": grad_err,
        "pruned_group_grad_max": pruned_grad,
    })
    print(f"\ntrain step @50%: dense {t_train_dense*1e3:.2f} ms, sparse "
          f"{t_train_sparse*1e3:.2f} ms "
          f"({at50['train_step_sparse_vs_dense_ratio']:.2f}x), "
          f"grad parity {grad_err:.2e}, pruned-group grad {pruned_grad:.2e}")
    assert grad_err <= 1e-4, f"gradient parity broke: {grad_err}"
    assert pruned_grad == 0.0, "pruned groups must get exactly-zero gradients"
    assert abs(float(ld) - float(ls)) <= 1e-5

    out = {"config": {"n_cu": n_cu, "batch": batch, "fast": fast,
                      "stages": cfg.stages, "widths": cfg.widths,
                      "image_size": cfg.image_size},
           "rows": rows}
    with open(OUT_JSON, "w") as f:
        json.dump(out, f, indent=2)
    print(f"\nwrote {OUT_JSON}")
    print("implicit kernel: identical plans and schedule accounting as the "
          "materializing path (asserted), a fraction of the HBM bytes (no "
          "patch matrix), adaptive bm for the batch-1 tails. Quantized "
          "execution: int8 codes / int32 accumulation on the same schedule "
          "(asserted), bit-exact vs the QAT forward, <= 0.5x the f32 "
          "operand bytes. Streamed execution: layers exchange int8 Q3.4 "
          "codes (in-epilogue requantize), code-exact vs the per-layer-"
          "quantized wire reference (asserted), <= 0.28x the f32 bytes "
          "end-to-end. Wall clock on CPU runs the kernels in interpret "
          "mode — step counts, HBM bytes and MAC utilization are the "
          "hardware-meaningful columns there.")
    return out


if __name__ == "__main__":
    run()
