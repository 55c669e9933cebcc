"""Functional accelerator simulator: fixed-point inference + cycle counting.

Runs the (BN-folded, Q2.5/Q3.4-quantized) CNN exactly as the accelerator
computes it, and prices every conv layer with the Eq.-3 cycle model plus
DSB skips derived from the *actual* weight groups — reproducing the paper's
Table II / Fig. 6 measurement loop without silicon.

Activation-side DSB (zero data columns) is measured from real activations
but disabled by default in the headline figure: the paper observes only a
0.79 % win for unpruned models, i.e. the coefficient-group bypass is the
operative mechanism. Whenever sample images are given the simulator still
prices the *dual-sided* (weight + activation) cycle count next to the
weight-only one (``cycles_dual`` / ``dual_dsb_cycle_ratio``), and with
``measure_dsb=True`` additionally runs a real
``ExecSpec(activation_dsb=True)`` bind through the implicit kernel's
skip counter so the predicted skip (``1 - data_col_nonzero_frac``) sits
next to the fraction of MXU passes the kernel actually elided.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core import quant as Q
from ..models import cnn
from ..sparse.conv_plan import conv_m_blocks
from .config import AcceleratorConfig
from .cycle_model import NetworkCycles, network_cycles

PyTree = Any


@dataclasses.dataclass
class SimulationReport:
    cycles: NetworkCycles
    accel: AcceleratorConfig
    accuracy: Optional[float]
    mean_time_per_image_s: float
    gops: float                      # ops = 2*MACs (standard); paper counts ~1 OP/MAC
    gops_paper_convention: float
    group_sparsity_per_layer: dict
    data_col_nonzero_frac: dict
    # Executed TPU dispatch accounting for the same group masks the cycle
    # model prices, via one accounting-only bind (bind_execution with
    # bind_kernels=False) reported through SparseConvExec.report at the
    # production contract — implicit kernel, adaptive bm — i.e. what the
    # serving path actually dispatches (packed_*: tiles cover many groups).
    # executed/dense_grid_steps count (g, f_block) schedule groups, live
    # and total, from per-tile occupancy times each layer's M-blocks at
    # fixed bm=128: one step per group per M-block, the cycle model's
    # granularity. schedule_steps_* is the same count for one M-block and
    # equals the cycle model's DSB step count.
    grid_steps_per_layer: dict = dataclasses.field(default_factory=dict)
    executed_grid_steps: int = 0
    dense_grid_steps: int = 0
    packed_executed_grid_steps: int = 0
    packed_dense_grid_steps: int = 0
    schedule_steps_live: int = 0
    schedule_steps_total: int = 0
    padded_mac_utilization: float = 0.0      # dispatched tiles
    # HBM data-movement contract per image on the dispatched layout (the
    # canonical hbm_bytes_* fields of SparseConvExec.report):
    # materializing (im2col patch matrix in HBM, fixed bm=128 — the PR-3
    # execution) vs implicit (in-kernel window gather from the NHWC
    # activation, adaptive bm), each priced with f32 operands AND with
    # int8 Q2.5×Q3.4 operand codes (the quantized execution: 1-byte
    # slabs/patches/weight tiles, f32 output writes) — and streamed
    # (1-byte operands AND 1-byte output writes: the requantizing
    # epilogue emits Q3.4 codes the next layer ingests). Per-layer
    # numbers sit in grid_steps_per_layer ("hbm_materialized"/
    # "hbm_implicit"/"hbm_implicit_int8"/"hbm_streamed_int8") next to the
    # grid steps; bm_effective_per_layer is the adaptive M-block.
    hbm_bytes_materialized: int = 0
    hbm_bytes_implicit: int = 0
    hbm_bytes_materialized_int8: int = 0
    hbm_bytes_implicit_int8: int = 0
    hbm_bytes_streamed_int8: int = 0
    bm_effective_per_layer: dict = dataclasses.field(default_factory=dict)
    # Dual-sided DSB: the cycle model re-priced with the *measured*
    # per-layer data-column fractions (None without sample images), plus
    # prediction-vs-measurement of the kernel's activation skip. The
    # prediction is 1 - data_col_nonzero_frac (CU_h-column granularity);
    # the measurement is the implicit kernel's own skip counter under an
    # activation_dsb bind — coarser (rows x cols x cpk window) by
    # construction, so measured <= predicted is the expected shape.
    cycles_dual: Optional[NetworkCycles] = None
    dsb_skip_frac_predicted: Optional[float] = None
    dsb_skip_frac_measured: Optional[float] = None
    dsb_skip_per_layer: dict = dataclasses.field(default_factory=dict)

    @property
    def hbm_bytes_ratio(self) -> float:
        return self.hbm_bytes_implicit / max(self.hbm_bytes_materialized, 1)

    @property
    def hbm_bytes_int8_ratio(self) -> float:
        """Quantized-over-f32 operand traffic on the implicit contract —
        what halving (×4) the operand bytes buys on top of pruning."""
        return self.hbm_bytes_implicit_int8 / max(self.hbm_bytes_implicit, 1)

    @property
    def hbm_bytes_streamed_ratio(self) -> float:
        """End-to-end int8 streaming over the f32 implicit contract —
        what pricing the output write at 1 byte buys on top of int8
        operands (the ROADMAP's ≈0.25 floor, reached exactly: every byte
        term scales by 1/4)."""
        return self.hbm_bytes_streamed_int8 / max(self.hbm_bytes_implicit, 1)

    @property
    def grid_step_ratio(self) -> float:
        return self.executed_grid_steps / max(self.dense_grid_steps, 1)

    @property
    def packed_grid_step_ratio(self) -> float:
        return self.packed_executed_grid_steps / max(self.packed_dense_grid_steps, 1)

    @property
    def dsb_cycle_ratio(self) -> float:
        return self.cycles.total_dsb / max(self.cycles.total_min, 1)

    @property
    def dual_dsb_cycle_ratio(self) -> Optional[float]:
        """Dual-sided (weight + measured activation) DSB cycles over the
        dense floor — sits next to the weight-only ``dsb_cycle_ratio``.
        None when no sample images were given."""
        if self.cycles_dual is None:
            return None
        return self.cycles_dual.total_dsb / max(self.cycles.total_min, 1)

    def row(self) -> dict:
        return {
            "dsb": self.accel.dsb,
            "fifo_depth": self.accel.fifo_depth,
            "freq_mhz": self.accel.freq_mhz,
            "dsps": self.accel.dsps,
            "accuracy": self.accuracy,
            "mean_time_per_image_ms": self.mean_time_per_image_s * 1e3,
            "gops": self.gops,
            "gops_paper_convention": self.gops_paper_convention,
            "executed_grid_steps": self.executed_grid_steps,
            "dense_grid_steps": self.dense_grid_steps,
            "grid_step_ratio": self.grid_step_ratio,
            "packed_executed_grid_steps": self.packed_executed_grid_steps,
            "packed_dense_grid_steps": self.packed_dense_grid_steps,
            "packed_grid_step_ratio": self.packed_grid_step_ratio,
            "schedule_steps_live": self.schedule_steps_live,
            "schedule_steps_total": self.schedule_steps_total,
            "padded_mac_utilization": self.padded_mac_utilization,
            "dsb_cycle_ratio": self.dsb_cycle_ratio,
            "hbm_bytes_materialized": self.hbm_bytes_materialized,
            "hbm_bytes_implicit": self.hbm_bytes_implicit,
            "hbm_bytes_ratio": self.hbm_bytes_ratio,
            "hbm_bytes_materialized_int8": self.hbm_bytes_materialized_int8,
            "hbm_bytes_implicit_int8": self.hbm_bytes_implicit_int8,
            "hbm_bytes_int8_ratio": self.hbm_bytes_int8_ratio,
            "hbm_bytes_streamed_int8": self.hbm_bytes_streamed_int8,
            "hbm_bytes_streamed_ratio": self.hbm_bytes_streamed_ratio,
            "dual_dsb_cycle_ratio": self.dual_dsb_cycle_ratio,
            "dsb_skip_frac_predicted": self.dsb_skip_frac_predicted,
            "dsb_skip_frac_measured": self.dsb_skip_frac_measured,
        }


def _data_col_nonzero_frac(act: jnp.ndarray, cu_h: int) -> float:
    """Fraction of CU_h-tall data columns containing any non-zero value.
    ``act``: (B, H, W, C) post-quantization activations entering a conv."""
    nz = (jnp.abs(act) > 0).astype(jnp.float32)
    # sliding max over H with window cu_h (stride 1, the stream order)
    win = jax.lax.reduce_window(
        nz, 0.0, jax.lax.max, (1, cu_h, 1, 1), (1, cu_h, 1, 1), "VALID")
    return float(jnp.mean(win))


def simulate(
    params: PyTree,
    state: PyTree,
    cfg: cnn.ResNetConfig,
    accel: AcceleratorConfig,
    images: Optional[jnp.ndarray] = None,
    labels: Optional[jnp.ndarray] = None,
    data_bypass: bool = False,
    measure_dsb: bool = False,
    dsb_sample: int = 4,
) -> SimulationReport:
    """Price one image's inference (per-image cycles are input-independent
    unless ``data_bypass``) and optionally measure accuracy on (images, labels).

    With images given, the report additionally carries ``cycles_dual`` —
    the cycle model re-run with the measured per-layer data-column
    fractions, i.e. the dual-sided DSB price next to the weight-only one.
    ``measure_dsb=True`` (needs images) further runs a real folded +
    quantized + streamed ``activation_dsb`` bind over ``images[:dsb_sample]``
    and reports the kernel skip counter's ``dsb_skip_frac_measured`` next
    to the column-granularity prediction ``dsb_skip_frac_predicted``."""
    qcfg = dataclasses.replace(cfg, quantized=True)
    dims = cnn.layer_dims(cfg, params)

    # --- dispatch + HBM accounting via an accounting-only bind -------------
    # No kernels (bind_kernels=False — plans/layouts/masks only), reported
    # through SparseConvExec.report at the production contract (implicit
    # kernel, adaptive bm), so the simulator prices exactly what the
    # executed path dispatches. quantized=True reproduces this simulator's
    # skippability rule: masks from the Q2.5-quantized weights' zero groups.
    pk = cnn.bind_execution(
        params, cfg, bind_kernels=False,
        spec=cnn.ExecSpec(quantized=True, implicit=True, bm="auto",
                          n_cu=accel.n_cu))
    pk_rep = pk.report(cfg, batch=1, per_layer=True)

    group_masks, layer_sparsity, grid_steps, bm_eff_per_layer = [], {}, {}, {}
    for path, layer in dims:
        name = "/".join(path)
        gm = np.asarray(pk.group_masks_np[path])
        group_masks.append(gm)
        layer_sparsity[name] = float(1.0 - gm.mean())
        pk_l = pk_rep["per_layer"][name]
        # schedule groups per M-block at fixed bm=128, paper granularity
        mb = conv_m_blocks(layer.out_x, layer.out_y, 1, bm=128)[0]
        live, total = pk.layouts[path].tile_occupancy(gm)
        # per-layer HBM contracts priced on the dispatched layout
        grid_steps[name] = {"executed": mb * int(live.sum()),
                            "dense": mb * int(total.sum()),
                            "packed_executed": pk_l["executed"],
                            "packed_dense": pk_l["dense"],
                            "hbm_materialized": pk_l["hbm_materialized"],
                            "hbm_implicit": pk_l["hbm_implicit"],
                            "hbm_materialized_int8": pk_l["hbm_materialized_int8"],
                            "hbm_implicit_int8": pk_l["hbm_implicit_int8"],
                            "hbm_streamed_int8": pk_l["hbm_streamed_int8"]}
        bm_eff_per_layer[name] = pk_l["bm_effective"]

    # --- optional activation-side bypass measurement -----------------------
    data_fracs = [1.0] * len(dims)
    col_fracs = {}
    if images is not None:
        acts = _capture_conv_inputs(params, state, qcfg, images[:64])
        for li, (path, layer) in enumerate(dims):
            f = _data_col_nonzero_frac(acts[li], accel.cu_h)
            col_fracs["/".join(path)] = f
            if data_bypass:
                data_fracs[li] = f

    cyc = network_cycles([d for _, d in dims], accel, group_masks, data_fracs)

    # --- dual-sided pricing + kernel-measured skip -------------------------
    cyc_dual = None
    dsb_pred = dsb_meas = None
    dsb_per_layer = {}
    if col_fracs:
        dual_fracs = [col_fracs["/".join(path)] for path, _ in dims]
        cyc_dual = network_cycles([d for _, d in dims], accel, group_masks,
                                  dual_fracs)
        dsb_pred = 1.0 - float(np.mean(dual_fracs))
        dsb_per_layer = {n: {"predicted_skip": 1.0 - f}
                         for n, f in col_fracs.items()}
    if measure_dsb:
        if images is None:
            raise ValueError("measure_dsb=True needs sample images")
        folded = cnn.fold_batchnorm(params, state, cfg)
        dsb_exec = cnn.bind_execution(
            folded, cfg,
            spec=cnn.ExecSpec(folded=True, quantized=True, streamed=True,
                              implicit=True, activation_dsb=True,
                              n_cu=accel.n_cu))
        m = dsb_exec.measure_dsb_skip(folded, images[:dsb_sample], cfg)
        dsb_meas = m["dsb_skip_frac"]
        for name, st_l in m["dsb_per_layer"].items():
            d = dsb_per_layer.setdefault(name, {})
            d["measured_skip"] = (st_l["skipped_steps"] /
                                  max(st_l["live_steps"], 1))
            d["live_steps"] = st_l["live_steps"]

    acc = None
    if images is not None and labels is not None:
        logits, _ = cnn.apply(params, state, images, qcfg, train=False)
        acc = float(jnp.mean(jnp.argmax(logits, -1) == labels))

    t = cyc.seconds(accel, with_dsb=True)
    ops = cyc.total_ops
    return SimulationReport(
        cycles=cyc,
        accel=accel,
        accuracy=acc,
        mean_time_per_image_s=t,
        gops=ops / t / 1e9,
        gops_paper_convention=(ops / 2) / t / 1e9,
        group_sparsity_per_layer=layer_sparsity,
        data_col_nonzero_frac=col_fracs,
        grid_steps_per_layer=grid_steps,
        executed_grid_steps=sum(v["executed"] for v in grid_steps.values()),
        dense_grid_steps=sum(v["dense"] for v in grid_steps.values()),
        packed_executed_grid_steps=pk_rep["executed_grid_steps"],
        packed_dense_grid_steps=pk_rep["dense_grid_steps"],
        schedule_steps_live=pk_rep["schedule_steps_live"],
        schedule_steps_total=pk_rep["schedule_steps_total"],
        padded_mac_utilization=pk_rep["padded_mac_utilization"],
        hbm_bytes_materialized=pk_rep["hbm_bytes_materialized"],
        hbm_bytes_implicit=pk_rep["hbm_bytes_implicit"],
        hbm_bytes_materialized_int8=pk_rep["hbm_bytes_materialized_int8"],
        hbm_bytes_implicit_int8=pk_rep["hbm_bytes_implicit_int8"],
        hbm_bytes_streamed_int8=pk_rep["hbm_bytes_streamed_int8"],
        bm_effective_per_layer=bm_eff_per_layer,
        cycles_dual=cyc_dual,
        dsb_skip_frac_predicted=dsb_pred,
        dsb_skip_frac_measured=dsb_meas,
        dsb_skip_per_layer=dsb_per_layer,
    )


def _capture_conv_inputs(params, state, cfg, x):
    """Forward pass capturing each conv layer's (quantized) input, exec order."""
    acts = []
    qw = lambda w: Q.quantize(w, Q.Q2_5)
    qa = lambda a: Q.quantize(a, Q.Q3_4)
    h = qa(x)       # the accelerator ingests Q3.4 codes, input frame included
    acts.append(h)  # conv0 input
    conv = cnn._conv
    bn = lambda y, p, s: cnn._bn(y, p, s, False, cfg)[0]
    h1 = bn(conv(h, qw(params["conv0"]["w"]), 1), params["bn0"], state["bn0"])
    h = qa(jax.nn.relu(h1))
    for si, n_blocks in enumerate(cfg.stages):
        for bi in range(n_blocks):
            name = f"s{si}b{bi}"
            blk, st = params[name], state[name]
            stride = 2 if (si > 0 and bi == 0) else 1
            acts.append(h)  # conv1 input
            y = bn(conv(h, qw(blk["conv1"]["w"]), stride), blk["bn1"], st["bn1"])
            y = qa(jax.nn.relu(y))
            acts.append(y)  # conv2 input
            y = bn(conv(y, qw(blk["conv2"]["w"]), 1), blk["bn2"], st["bn2"])
            if "proj" in blk:
                acts.append(h)  # proj input
                sc = bn(conv(h, qw(blk["proj"]["w"]), stride), blk["bnp"], st["bnp"])
            else:
                sc = h
            h = qa(jax.nn.relu(y + sc))
    return acts
