"""One general generator for every traffic mix.

A mix is a JSON file under ``traffic/``. Its keys:

- ``loop``: ``"open"`` (requests arrive on a schedule, whatever the
  server does) or ``"closed"`` (``clients`` callers, each sending its
  next request when the last one returned);
- ``sizes``: images per request, as classes ``{"p", "lo", "hi"}``; a
  class's requests spread evenly over ``lo..hi``;
- ``pool_frames``: the frames requests draw from (made from the seed);
- open loop: ``rate_rps``, the mean request rate, and ``phases``, a
  cycle of ``{"name", "mean_s", "relative_rate"}`` whose lengths are
  exponential with mean ``mean_s`` and whose rates stand in the ratio
  ``relative_rate`` (a Markov-modulated Poisson process);
- closed loop: ``clients`` (1: one request in flight).

Every seed gets the same work in another order: the phase lengths, the
gaps between arrivals (in the process's own time) and the request sizes
are fixed multisets, made from quantiles and shuffled by the seed, so
the window always holds the same number of arrivals at the same sizes
and the same time in each phase. Only which frames a request carries is
drawn freely.
"""
from __future__ import annotations

import dataclasses
from typing import Iterator, List

import numpy as np


@dataclasses.dataclass
class Request:
    due: float                 # seconds after the window opens
    frames: np.ndarray         # indices into the frame pool


def _exp_quantiles(n: int) -> np.ndarray:
    """``n`` exponential quantiles at mid-ranks, scaled to mean 1."""
    q = -np.log1p(-(np.arange(n) + 0.5) / n)
    return q / q.mean()


def _sizes(mix: dict, n: int, rng) -> np.ndarray:
    """``n`` request sizes in the mix's proportions, shuffled."""
    classes = mix["sizes"]
    counts = [int(round(c["p"] * n)) for c in classes]
    counts[int(np.argmax([c["p"] for c in classes]))] += n - sum(counts)
    out = []
    for c, k in zip(classes, counts):
        span = c["hi"] - c["lo"] + 1
        out.append(c["lo"] + (np.arange(k) * span) // max(k, 1))
    return rng.permutation(np.concatenate(out).astype(np.int64))


def open_schedule(mix: dict, seed: int, seconds: float) -> List[Request]:
    """Arrivals due in ``[0, seconds)``."""
    rng = np.random.default_rng(seed)
    phases = mix["phases"]
    cycle = sum(p["mean_s"] for p in phases)
    n_cycles = max(1, int(round(seconds / cycle)))
    # each phase's lengths: a fixed multiset scaled to its share of the
    # window, in a seeded order
    segs = np.zeros((n_cycles, len(phases), 2))
    for j, p in enumerate(phases):
        segs[:, j, 0] = (rng.permutation(_exp_quantiles(n_cycles))
                         * seconds * p["mean_s"] / cycle / n_cycles)
        segs[:, j, 1] = p["relative_rate"]
    segs = segs.reshape(-1, 2)
    base = mix["rate_rps"] * cycle / sum(p["mean_s"] * p["relative_rate"]
                                         for p in phases)
    rates = base * segs[:, 1]
    t_edges = np.concatenate([[0.0], np.cumsum(segs[:, 0])])
    lam_edges = np.concatenate([[0.0], np.cumsum(segs[:, 0] * rates)])
    n = int(round(lam_edges[-1]))
    # unit-rate arrivals in the process's own time, mapped through the
    # inverse of the cumulative intensity
    gaps = rng.permutation(_exp_quantiles(n)) * lam_edges[-1] / (n + 1)
    lam = np.cumsum(gaps)
    seg = np.clip(np.searchsorted(lam_edges, lam, side="right") - 1,
                  0, len(rates) - 1)
    due = t_edges[seg] + (lam - lam_edges[seg]) / rates[seg]
    sizes = _sizes(mix, n, rng)
    pool = mix["pool_frames"]
    return [Request(float(t), rng.integers(0, pool, int(k)))
            for t, k in zip(due, sizes)]


def closed_sizes(mix: dict, seed: int) -> Iterator[np.ndarray]:
    """Endless closed-loop requests: each run of ``len(sizes) * 12``
    requests holds the mix's sizes in fixed proportions, in a seeded
    order. Yields frame indices."""
    rng = np.random.default_rng(seed)
    block = 12 * len(mix["sizes"])
    while True:
        for k in _sizes(mix, block, rng):
            yield rng.integers(0, mix["pool_frames"], int(k))
