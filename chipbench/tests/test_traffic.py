"""The traffic generator gives every seed the same work in another
order, and the serving loop times each request from its due time."""
import json

import numpy as np
import pytest

from chipbench import serve_loop, traffic
from conftest import ROOT


def _mix(name):
    return json.loads((ROOT / f"chipbench/traffic/{name}.json").read_text())


@pytest.mark.parametrize("seed", [0, 2 ** 40 + 11])
def test_open_loop_same_work_in_another_order(seed):
    mix = _mix("interactive")
    a = traffic.open_schedule(mix, seed, 10.0)
    b = traffic.open_schedule(mix, seed + 1, 10.0)
    assert len(a) == len(b)
    assert abs(len(a) - mix["rate_rps"] * 10.0) <= 1
    assert sorted(len(r.frames) for r in a) == sorted(len(r.frames) for r in b)
    assert [r.due for r in a] != [r.due for r in b]
    due = np.array([r.due for r in a])
    assert np.all(np.diff(due) >= 0) and 0 <= due[0] and due[-1] < 10.0
    sizes = np.array([len(r.frames) for r in a])
    want = sum(c["p"] * (c["lo"] + c["hi"]) / 2 for c in mix["sizes"])
    assert abs(sizes.mean() - want) < 0.05
    assert sizes.max() <= max(c["hi"] for c in mix["sizes"])
    again = traffic.open_schedule(mix, seed, 10.0)
    assert [r.due for r in again] == [r.due for r in a]
    assert all(np.array_equal(x.frames, y.frames) for x, y in zip(a, again))


def test_open_loop_phases_keep_the_mean_rate():
    """A Markov-modulated mix (calm and burst phases) keeps its mean rate
    and gives every seed the same number of arrivals."""
    mix = {"loop": "open", "rate_rps": 500.0, "pool_frames": 16,
           "phases": [{"name": "calm", "mean_s": 0.8, "relative_rate": 1.0},
                      {"name": "burst", "mean_s": 0.2, "relative_rate": 2.0}],
           "sizes": [{"p": 0.9, "lo": 1, "hi": 1}, {"p": 0.1, "lo": 2, "hi": 8}]}
    counts = {len(traffic.open_schedule(mix, s, 20.0)) for s in (3, 2 ** 41)}
    assert len(counts) == 1 and abs(counts.pop() - 500 * 20) <= 1
    due = np.array([r.due for r in traffic.open_schedule(mix, 3, 20.0)])
    per_s = np.bincount(due.astype(int), minlength=20)
    assert per_s.max() > 1.3 * per_s.min()   # the bursts show


def test_closed_loop_sizes_cycle_in_fixed_proportions():
    mix = _mix("bulk")
    gen = traffic.closed_sizes(mix, 2 ** 33)
    sizes = [len(next(gen)) for _ in range(72)]
    for lo in (0, 36):
        block = sizes[lo:lo + 36]
        assert sorted(set(block)) == [128, 256, 512]
        assert all(block.count(n) == 12 for n in (128, 256, 512))


class _Echo:
    """A model that answers at once: logits are the frames' first pixel."""
    last_level = 0

    def infer(self, x):
        return x[:, 0, 0, :]


def test_loop_answers_every_request_with_its_own_rows():
    from repro.launch.exec_cache import BucketBatcher
    mix = dict(_mix("interactive"), rate_rps=200.0, pool_frames=64)
    pool = np.random.default_rng(0).random((64, 2, 2, 3), dtype=np.float32)
    w = serve_loop.run(_Echo(), BucketBatcher((1, 8, 32, 128)), mix, 5,
                       0.5, pool)
    assert len(w.requests) == 100
    for r in w.requests:
        np.testing.assert_array_equal(r.logits, pool[r.frames, 0, 0, :])
        assert r.due <= r.handoff <= r.done and r.level == 0
    assert sum(n * k for n, k in w.release_sizes.items()) == \
        sum(len(r.frames) for r in w.requests)


def test_single_image_requests_release_whole_buckets():
    """The sizes the harness warms are the sizes the batcher releases."""
    from repro.launch.exec_cache import BucketBatcher
    from chipbench import run as R
    buckets = (1, 8, 32, 128)
    mix = dict(_mix("interactive"), pool_frames=64)
    assert R.release_sizes(mix, buckets) == list(buckets)
    pool = np.zeros((64, 2, 2, 3), np.float32)
    for rate in (300.0, 3000.0):
        w = serve_loop.run(_Echo(), BucketBatcher(buckets), dict(
            mix, rate_rps=rate), 7, 0.3, pool)
        assert set(w.release_sizes) <= set(buckets)
