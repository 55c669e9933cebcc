"""Reduction of a profiler trace (``.xplane.pb``) to device busy and idle
time, kernel and glue time, and idle gaps by host span.

Device operations are the events of the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane (the ``Async XLA Ops`` line, copies in flight
beside them, is not counted). A Mosaic kernel (a Pallas call) is an op
whose HLO text is a custom call; every other op is glue.
Host spans are recorded by the harness on the host's real-time clock
(``serve_loop.HostSpans``) and placed on the trace's time line by the
profile's start time; the ``window`` span bounds what is counted.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, List, Optional, Tuple

import numpy as np

Interval = Tuple[int, int]             # [start_ns, end_ns)


@dataclasses.dataclass
class DeviceOp:
    name: str
    start: int
    end: int
    kernel: bool


@dataclasses.dataclass
class TraceSummary:
    window: Interval
    n_devices: int
    ops: List[DeviceOp]                      # of device 0, inside the window
    busy_ns: List[int]                       # per device
    spans: Dict[str, List[Interval]]         # host spans by name
    _busy: Optional[List[Interval]] = None

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        """Seconds with an op running, averaged over the devices."""
        return float(np.mean(self.busy_ns)) * 1e-9

    def kernel_s(self) -> float:
        return sum(o.end - o.start for o in self.ops if o.kernel) * 1e-9

    def glue_s(self) -> float:
        return sum(o.end - o.start for o in self.ops if not o.kernel) * 1e-9

    def busy_intervals(self) -> List[Interval]:
        if self._busy is None:
            self._busy = union([(o.start, o.end) for o in self.ops])
        return self._busy

    def uncovered_s(self, name: str) -> np.ndarray:
        """Per span ``name``: its seconds with no device op running."""
        iv = np.array(self.spans.get(name, []), np.int64).reshape(-1, 2)
        busy = np.array(self.busy_intervals(), np.int64).reshape(-1, 2)
        covered = busy_before(busy, iv[:, 1]) - busy_before(busy, iv[:, 0])
        return ((iv[:, 1] - iv[:, 0]) - covered) * 1e-9

    def top_ops(self, k: int = 10) -> List[list]:
        """The ``k`` op names with the most device time, with seconds."""
        tot: Dict[str, int] = {}
        for o in self.ops:
            tot[o.name] = tot.get(o.name, 0) + o.end - o.start
        return [[n, t * 1e-9] for n, t in
                sorted(tot.items(), key=lambda kv: -kv[1])[:k]]

    def idle_by_span(self, names, k: int = 10) -> List[list]:
        """Device-idle seconds of the window split by the host span in
        progress (the harness's spans run one at a time on one thread);
        idle time outside them is ``other``."""
        tot = {n: float(self.uncovered_s(n).sum()) for n in names}
        tot["other"] = (self.window_s - self.busy_s) - sum(tot.values())
        return [[n, t] for n, t in
                sorted(tot.items(), key=lambda kv: -kv[1])[:k]]


def busy_before(busy: np.ndarray, t: np.ndarray) -> np.ndarray:
    """ns of the sorted, disjoint intervals ``busy`` that lie before each
    time in ``t``."""
    if len(busy) == 0:
        return np.zeros(len(t), np.int64)
    cum = np.cumsum(busy[:, 1] - busy[:, 0])
    i = np.searchsorted(busy[:, 0], t, side="right")
    last = np.maximum(i - 1, 0)
    out = cum[last] - np.maximum(busy[last, 1] - t, 0)
    return np.where(i > 0, out, 0)


def union(iv: List[Interval]) -> List[Interval]:
    out: List[List[int]] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def is_kernel(hlo: str) -> bool:
    """A Mosaic kernel: XLA runs a Pallas call as a ``tpu_custom_call``
    custom call, and the trace names each op by its HLO text."""
    return "custom-call(" in hlo or "custom_call_target" in hlo


def op_name(hlo: str) -> str:
    """``%implicit_block_sparse_conv.26 = s8[...] custom-call(...)`` ->
    ``implicit_block_sparse_conv.26``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def profile_start_ns(data) -> int:
    """The real-time clock's reading (``time.time_ns``) at the trace's
    time 0."""
    for plane in data.planes:
        if plane.name == "Task Environment":
            return int(dict(plane.stats)["profile_start_time"])
    raise ValueError("the trace has no 'Task Environment' plane")


def reduce(path: str, spans: Dict[str, List[Interval]]) -> TraceSummary:
    """Read ``path`` and keep what lies inside the ``window`` span.
    ``spans`` are the harness's host spans by name, in ``time.time_ns``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    t0 = profile_start_ns(data)
    spans = {k: [(s - t0, e - t0) for s, e in v] for k, v in spans.items()}
    if not spans.get("window"):
        raise ValueError("no 'window' span")
    window = (spans["window"][0][0], spans["window"][-1][1])
    devices = sorted(
        (p for p in data.planes if p.name.startswith("/device:TPU:")
         and p.name[len("/device:TPU:"):].isdigit()),
        key=lambda p: int(p.name[len("/device:TPU:"):]))
    ops, busy = [], []
    for d, plane in enumerate(devices):
        evs = []
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                s, e = int(ev.start_ns), int(ev.end_ns)
                s, e = max(s, window[0]), min(e, window[1])
                if e <= s:
                    continue
                evs.append(DeviceOp(op_name(ev.name), s, e,
                                    is_kernel(ev.name)))
        busy.append(sum(b - a for a, b in union([(o.start, o.end)
                                                 for o in evs])))
        if d == 0:
            ops = evs
    return TraceSummary(window=window, n_devices=len(devices), ops=ops,
                        busy_ns=busy, spans=spans)
