"""Needed work of the served convs, and the chip's peaks.

A conv's needed work is counted from its shapes and its nonzero weights
alone, so it is the same whatever kernel, layout or padding runs it:

- operations: 2 x output pixels x the layer's nonzero weights, per image;
- bytes, per call of batch B: the int8 input activation once per image,
  the int8 output once per image, and once per call the live int8
  weights and the per-output-channel f32 rows the int8 contract needs
  (dequantization scale, bias, and the requantization scale of the
  streamed wire).

Residual adds, the pooling and the classifier are not conv work.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

# Published peaks per chip, keyed by ``jax.Device.device_kind``. Source:
# Google Cloud documentation, "TPU v5e" (system architecture): 197 TFLOP/s
# bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9},
}

ROWS_PER_COUT = 3          # dequant scale, bias, requant scale (f32 each)


def peaks_for(device_kind: str) -> Dict[str, float]:
    """The peaks of ``device_kind``; a device not in the table is an error."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


@dataclasses.dataclass(frozen=True)
class ConvWork:
    path: Tuple[str, ...]
    in_hw: int
    out_hw: int
    cin: int
    cout: int
    nnz: int                       # nonzero weights

    @property
    def ops_per_image(self) -> int:
        return 2 * self.out_hw * self.out_hw * self.nnz

    def bytes_per_call(self, batch: int) -> int:
        act = self.in_hw * self.in_hw * self.cin + \
            self.out_hw * self.out_hw * self.cout
        return batch * act + self.nnz + 4 * ROWS_PER_COUT * self.cout


@dataclasses.dataclass(frozen=True)
class ForwardWork:
    convs: List[ConvWork]

    @property
    def ops_per_image(self) -> int:
        return sum(c.ops_per_image for c in self.convs)

    def bytes_per_call(self, batch: int) -> int:
        return sum(c.bytes_per_call(batch) for c in self.convs)

    def least_seconds(self, batch: int, peaks: Dict[str, float]):
        """``(seconds, bound)``: the least time the chip needs for one call
        of ``batch`` images at int8, and which bound ("compute" or
        "memory") sets it."""
        t_ops = batch * self.ops_per_image / peaks["int8_ops"]
        t_mem = self.bytes_per_call(batch) / peaks["hbm_bytes_per_s"]
        return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")


def forward_work(layers, weights) -> ForwardWork:
    """Needed work of the convs ``layers`` (``[(path, stride, in_hw)]`` in
    execution order) with HWIO ``weights`` (``{path: array}``), whose
    zeros are what HAPM pruned."""
    ws = [weights[path] for path, _, _ in layers]
    nnz = jax.jit(lambda ws: [jnp.count_nonzero(w) for w in ws])(ws)
    return ForwardWork([
        ConvWork(path=path, in_hw=in_hw, out_hw=-(-in_hw // stride),
                 cin=w.shape[2], cout=w.shape[3], nnz=int(n))
        for (path, stride, in_hw), w, n in zip(layers, ws, nnz)])
