"""The measured window: traffic through ``BucketBatcher`` into the
served model, on one thread and the host's wall clock.

The loop admits the requests that are due, polls the batcher, and hands
each released batch to ``model.infer`` as one frame array, then waits
for its logits on the host. A request's latency runs from its due time
to that moment. Host spans (``generator_wait``, ``batcher_poll``,
``frame_assembly``, ``infer``, ``block_result``, and ``window`` around
it all) are recorded on the host's real-time clock when ``spans`` is a
:class:`HostSpans`, for the trace reduction to place beside the
device's ops.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from collections import OrderedDict
from typing import Callable, Dict, List, Tuple

import numpy as np

from chipbench import traffic as T

SPIN_S = 0.002        # the last stretch of a wait is spun, not slept

SPAN_NAMES = ("generator_wait", "batcher_poll", "frame_assembly", "infer",
              "block_result")


def no_spans(name: str):
    return contextlib.nullcontext()


class HostSpans:
    """Each span's start and end in ``time.time_ns``, the clock the
    profiler's trace is anchored to, by span name."""

    def __init__(self):
        self.spans: Dict[str, List[Tuple[int, int]]] = {}

    @contextlib.contextmanager
    def __call__(self, name: str):
        start = time.time_ns()
        try:
            yield
        finally:
            self.spans.setdefault(name, []).append((start, time.time_ns()))


@dataclasses.dataclass
class Served:
    """One request's record."""
    due: float
    frames: np.ndarray
    handoff: float = float("nan")      # handed to infer
    done: float = float("nan")         # logits on the host
    level: int = -1                    # ladder rung it ran at
    error: str = ""
    logits: np.ndarray = None


@dataclasses.dataclass
class Window:
    requests: List[Served]
    seconds: float                     # the window's length
    lag_s: List[float]                 # how late the loop woke, per wake
    infer_calls: int
    release_sizes: Dict[int, int]      # images per infer call -> count


def run(model, batcher, mix: dict, seed: int, seconds: float,
        pool: np.ndarray, spans: Callable = no_spans,
        clock: Callable[[], float] = time.perf_counter) -> Window:
    """Serve ``mix`` for ``seconds``; requests still queued when the
    window closes are served to the end."""
    closed = mix["loop"] == "closed"
    if closed:
        source, clients = T.closed_sizes(mix, seed), mix["clients"]
        reqs: List[Served] = []
    else:
        reqs = [Served(r.due, r.frames)
                for r in T.open_schedule(mix, seed, seconds)]
    max_wait = batcher.max_wait_s
    pending: "OrderedDict[int, int]" = OrderedDict()   # batcher id -> req
    lag, sizes = [], {}
    calls = 0
    nxt = 0                                  # next open-loop arrival
    in_flight = 0

    def serve(ids):
        nonlocal calls, in_flight
        idx = [pending.pop(i) for i in ids]
        with spans("frame_assembly"):
            x = np.concatenate([pool[reqs[j].frames] for j in idx])
        t = clock() - t0
        for j in idx:
            reqs[j].handoff = t
        calls += 1
        sizes[len(x)] = sizes.get(len(x), 0) + 1
        try:
            with spans("infer"):
                y = model.infer(x)
                level = model.last_level
            with spans("block_result"):
                y = np.asarray(y)
        except Exception as e:             # counted as failed, run goes on
            for j in idx:
                reqs[j].error = f"{type(e).__name__}: {e}"[:200]
                reqs[j].done = clock() - t0
            in_flight -= len(idx)
            return
        t = clock() - t0
        off = 0
        for j in idx:
            k = len(reqs[j].frames)
            reqs[j].logits, reqs[j].done, reqs[j].level = y[off:off + k], t, level
            off += k
        in_flight -= len(idx)

    with spans("window"):
        t0 = clock()
        while True:
            now = clock() - t0
            with spans("batcher_poll"):
                if closed:
                    while in_flight < clients and now < seconds:
                        reqs.append(Served(now, next(source)))
                        pending[batcher.submit(len(reqs[-1].frames), now)] = \
                            len(reqs) - 1
                        in_flight += 1
                else:
                    while nxt < len(reqs) and reqs[nxt].due <= now:
                        r = reqs[nxt]
                        pending[batcher.submit(len(r.frames), r.due)] = nxt
                        nxt += 1
                        in_flight += 1
                released = batcher.poll(now)
            for _, ids in released:
                serve(ids)
            if released:
                continue
            if not pending and (now >= seconds if closed
                                else nxt == len(reqs)):
                break
            wake = float("inf")
            if not closed and nxt < len(reqs):
                wake = reqs[nxt].due
            if pending:
                wake = min(wake, reqs[next(iter(pending.values()))].due
                           + max_wait)
            with spans("generator_wait"):
                dt = wake - (clock() - t0) - SPIN_S
                if dt > 0:
                    time.sleep(dt)
                while clock() - t0 < wake:   # the sleep overshoots ~1 ms
                    pass
                lag.append(clock() - t0 - wake)
    return Window(requests=reqs, seconds=seconds, lag_s=lag,
                  infer_calls=calls, release_sizes=sizes)
