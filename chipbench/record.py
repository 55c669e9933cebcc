"""What one run measured, as the metric readers under ``metrics/`` see it.

A reader is ``metrics/<metric name>.py`` with ``read(r: RunRecord)``,
which returns the metric's value, or ``None`` where the run holds
nothing to read (the metric is then left out of the result line).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import numpy as np

from chipbench.serve_loop import Window
from chipbench.trace_reduce import TraceSummary
from chipbench.work import ForwardWork


@dataclasses.dataclass
class RunRecord:
    window: Window
    work: ForwardWork
    peaks: Dict[str, float]
    max_bucket: int
    setup_s: float
    bind_s: float
    compile_s: float
    trace: Optional[TraceSummary] = None

    def latencies_s(self) -> np.ndarray:
        """Due time to logits on the host, every request of the window."""
        return np.array([r.done - r.due for r in self.window.requests])

    def queue_waits_s(self) -> np.ndarray:
        """Due time to the handoff to ``infer``."""
        return np.array([r.handoff - r.due for r in self.window.requests])

    def images_per_s(self) -> float:
        """Images answered, over the time from the window's start to the
        last answer: the requests sent inside the window run to their
        end, so no request is cut in two by the window's close."""
        done = [r for r in self.window.requests if not r.error]
        if not done:
            return 0.0
        return sum(len(r.frames) for r in done) / max(r.done for r in done)

    def images_served(self) -> int:
        """Images of every request answered in the window."""
        return sum(len(r.frames) for r in self.window.requests
                   if not r.error)

    def infer_spans(self) -> int:
        return len(self.trace.spans.get("infer", [])) if self.trace else 0

    def idle_share_pct(self) -> Optional[float]:
        if self.trace is None:
            return None
        return 100.0 * (1.0 - self.trace.busy_s / self.trace.window_s)

    def forwards(self):
        """Images per forward the server ran: each call is cut into
        chunks of at most the largest bucket."""
        for n, calls in self.window.release_sizes.items():
            for _ in range(calls):
                full, rest = divmod(n, self.max_bucket)
                yield from [self.max_bucket] * full
                if rest:
                    yield rest

    def conv_least_s(self):
        """``(seconds, {bound: seconds})``: the least time the chip needs
        for the window's conv work, and how much of it each bound sets."""
        tot, by = 0.0, {}
        for n in self.forwards():
            t, bound = self.work.least_seconds(n, self.peaks)
            tot += t
            by[bound] = by.get(bound, 0.0) + t
        return tot, by
