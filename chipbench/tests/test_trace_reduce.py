"""The trace reduction on a trace recorded on a TPU v5e: a 0.25 s traced
window of ``resnet21-int8s-interactive`` (``data/r21_interactive.xplane.pb``).
That trace holds the harness's spans as host events too; read on the
real-time clock, they are the spans the reduction takes."""
import numpy as np
import pytest

from chipbench import serve_loop
from chipbench import trace_reduce as tr
from conftest import ROOT

TRACE = ROOT / "chipbench/tests/data/r21_interactive.xplane.pb"


def host_spans(path, names):
    """The trace's host events named ``names``, in ``time.time_ns``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    t0 = tr.profile_start_ns(data)
    out = {}
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in names:
                        out.setdefault(ev.name, []).append(
                            (t0 + int(ev.start_ns), t0 + int(ev.end_ns)))
    return out


@pytest.fixture(scope="module")
def spans():
    return host_spans(TRACE, ("window",) + serve_loop.SPAN_NAMES)


@pytest.fixture(scope="module")
def summary(spans):
    return tr.reduce(str(TRACE), spans)


def test_spans_land_on_the_trace_time_line(spans, summary):
    from jax.profiler import ProfileData
    t0 = tr.profile_start_ns(ProfileData.from_file(str(TRACE)))
    assert t0 > 1.7e18                       # real-time clock, ns
    assert summary.window == (spans["window"][0][0] - t0,
                              spans["window"][0][1] - t0)
    moved = {k: [(s + 10 ** 9, e + 10 ** 9) for s, e in v]
             for k, v in spans.items()}      # a second later: no ops
    assert tr.reduce(str(TRACE), moved).busy_s == 0


def test_device_and_window(summary):
    assert summary.n_devices == 1
    assert 0.2 < summary.window_s < 0.4
    assert 0 < summary.busy_s < summary.window_s


def test_kernels_are_the_mosaic_convs(summary):
    kernels = {o.name.split(".")[0] for o in summary.ops if o.kernel}
    assert kernels == {"implicit_block_sparse_conv"}
    assert summary.kernel_s() > summary.glue_s() > 0
    # one TensorCore runs one op at a time: the ops' sum is their union
    assert abs(summary.kernel_s() + summary.glue_s() - summary.busy_s) \
        < 1e-3 * summary.busy_s


def test_idle_split_by_host_span(summary):
    idle = summary.window_s - summary.busy_s
    split = dict(summary.idle_by_span(serve_loop.SPAN_NAMES))
    assert abs(sum(split.values()) - idle) < 1e-9
    assert max(split, key=split.get) == "infer"
    n_infer = len(summary.spans["infer"])
    assert n_infer > 10
    host = summary.uncovered_s("infer")
    assert len(host) == n_infer and np.all(host >= 0)
    spans = np.array(summary.spans["infer"])
    assert np.all(host <= (spans[:, 1] - spans[:, 0]) * 1e-9 + 1e-12)


def test_busy_before_counts_only_the_past():
    busy = np.array([[10, 20], [30, 40]])
    got = tr.busy_before(busy, np.array([0, 10, 15, 20, 25, 35, 50]))
    assert got.tolist() == [0, 0, 5, 10, 10, 15, 20]
