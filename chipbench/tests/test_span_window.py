"""``span_window.py``: device-idle time by the innermost host span, op
names grouped by layer, and a whole run on the CPU with the program's
recorder on the server."""
import pytest

from chipbench import serve_loop
from chipbench import span_window as SW
from chipbench import trace_reduce as tr
from conftest import ROOT

TRACE = ROOT / "chipbench/tests/data/r21_interactive.xplane.pb"


def summary_of(ops, spans, window=(0, 100)):
    ops = [tr.DeviceOp("k", s, e, True) for s, e in ops]
    busy = sum(e - s for s, e in tr.union([(o.start, o.end) for o in ops]))
    return tr.TraceSummary(window=window, n_devices=1, ops=ops,
                           busy_ns=[busy], spans=spans)


def test_idle_goes_to_the_innermost_span():
    # infer [10, 60) holds prepare [10, 20), dispatch [20, 30) and collect
    # [30, 60), which holds a compile [35, 45); the device runs [25, 40)
    s = summary_of([(25, 40)], {
        "window": [(0, 100)], "infer": [(10, 60)],
        "infer.prepare": [(10, 20)], "infer.dispatch": [(20, 30)],
        "infer.collect": [(30, 60)], "compile": [(35, 45)],
        "generator_wait": [(70, 90)]})
    idle = SW.idle_by_innermost(s)
    ns = {k: round(v * 1e9) for k, v in idle.items()}
    assert ns == {"infer": 0, "infer.prepare": 10, "infer.dispatch": 5,
                  "infer.collect": 15, "compile": 5, "generator_wait": 20,
                  "other": 30}
    assert abs(sum(idle.values()) - (s.window_s - s.busy_s)) < 1e-15
    # the span that opens the parent and the first child at once
    s = summary_of([], {"window": [(0, 100)], "infer": [(10, 60)],
                        "infer.prepare": [(10, 20)]})
    ns = {k: round(v * 1e9) for k, v in SW.idle_by_innermost(s).items()}
    assert ns == {"infer": 40, "infer.prepare": 10, "other": 50}


def test_spans_are_cut_to_the_window():
    s = summary_of([], {"window": [(0, 100)], "bind": [(-50, -10)],
                        "infer": [(90, 130)]})
    ns = {k: round(v * 1e9) for k, v in SW.idle_by_innermost(s).items()}
    assert ns == {"bind": 0, "infer": 10, "other": 90}


@pytest.fixture(scope="module")
def recorded():
    from test_trace_reduce import host_spans
    spans = host_spans(TRACE, ("window",) + serve_loop.SPAN_NAMES)
    return tr.reduce(str(TRACE), spans)


def test_one_level_of_spans_splits_as_before(recorded):
    """The harness's own spans do not nest: each span's idle time is its
    time with no device op running, as ``TraceSummary.idle_by_span``
    gives it."""
    new = SW.idle_by_innermost(recorded, list(serve_loop.SPAN_NAMES))
    old = dict(recorded.idle_by_span(serve_loop.SPAN_NAMES, 10))
    assert set(new) == set(old)
    for k in old:
        assert abs(new[k] - old[k]) < 1e-9, k


def test_a_stall_says_how_long_the_device_idled_in_it():
    s = summary_of([(20, 40), (45, 50)], {
        "window": [(0, 200_000_000)], "infer": [(10, 150_000_000)],
        "infer.collect": [(30, 150_000_000)]})
    (st,) = SW.stalls(s, ms=50.0)
    assert st["ms"] == pytest.approx(149.99999)
    assert st["device_idle_ms"] == pytest.approx(149.99997)
    assert st["longest_op"] == ["k", pytest.approx(2e-5)]
    assert st["infer.collect"] == [pytest.approx(149.99997)]
    assert SW.stalls(s, ms=150.0) == []


def test_ops_grouped_by_layer():
    s = summary_of([], {"window": [(0, 100)]})
    s.ops = [tr.DeviceOp("conv_s0b1_conv2.12", 0, 5, True),
             tr.DeviceOp("conv_s0b1_conv2.40", 10, 13, True),
             tr.DeviceOp("conv_conv0.3", 20, 21, True),
             tr.DeviceOp("fusion.7", 30, 32, False)]
    got = [[n, round(t * 1e9)] for n, t in SW.ops_by_layer(s)]
    assert got == [["conv_s0b1_conv2", 8], ["fusion", 2], ["conv_conv0", 1]]


def test_cpu_windows_record_every_step(tiny_cfg, monkeypatch):
    """Set-up and windows of a tiny cell, on the CPU: the trace's device
    ops are replaced by none, so only the host's record is checked."""
    def reduce(path, spans):
        w = spans["window"][0]
        return tr.TraceSummary(window=w, n_devices=1, ops=[], busy_ns=[0],
                               spans=spans)

    monkeypatch.setattr(tr, "reduce", reduce)
    mix = {"loop": "closed", "clients": 1, "pool_frames": 16,
           "sizes": [{"p": 1.0, "lo": 12, "hi": 12}]}
    cell = {"name": "tiny-bulk"}
    out = SW.serve_windows(cell, tiny_cfg, mix, seed=2 ** 33 + 5,
                           seconds=0.5, windows=1, stall_ms=0.0)
    setup = out["set_up"]
    assert len(setup["install_s"]) == 1 and setup["install_s"][0] > 0
    assert len(setup["bind_layer_s"]) == 6 and setup["bind_layers_s"] > 0
    assert len(setup["warmup_bucket_self_s"]) == 1      # bucket 8
    traced, untraced = out["windows"]
    assert traced["traced"] and not untraced["traced"]
    assert traced["failed"] == untraced["failed"] == 0
    calls = traced["infer_calls"]
    assert len(traced["stalls"]) == calls > 0
    for st in traced["stalls"]:                       # 12 images: 8 + 4
        assert [len(st[n]) for n in SW.STEPS] == [2, 2, 2]
        assert st["device_idle_ms"] == pytest.approx(st["ms"])
        assert st["longest_op"] is None
    per_call = traced["host_ms_per_infer_call"]
    assert sum(per_call[n] for n in SW.STEPS) <= per_call["infer"] + 1e-9
    idle = traced["idle_by_innermost_s"]
    assert abs(sum(idle.values()) - traced["idle_s"]) < 1e-9
    assert idle["infer.collect"] > 0
