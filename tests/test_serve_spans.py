"""Host spans inside ``CnnServer`` (``repro.spans``).

A served request records ``infer.prepare`` / ``infer.dispatch`` /
``infer.collect`` once per chunk, nested in the caller's span, on
``time.time_ns``; a bind records one ``bind.layer.<kernel name>`` span
per conv; a compile while compiles are watched is a ``compile`` span
nested in the step that compiled. A server without a recorder returns
the same logits, bit for bit, and records nothing.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (HAPMConfig, apply_masks, hapm_element_masks,
                        hapm_epoch_update, hapm_init)
from repro.launch.serve_cnn import CnnServer
from repro.spans import NO_SPANS, Spans
from repro.models import cnn

N_CU = 4
SPEC = cnn.ExecSpec(n_cu=N_CU, quantized=True, folded=True, streamed=True,
                    dense_fallback=2.0)
STEPS = ("infer.prepare", "infer.dispatch", "infer.collect")


@pytest.fixture(scope="module")
def tiny():
    cfg = cnn.ResNetConfig(stages=(1, 1), widths=(8, 16), image_size=16)
    params, state = cnn.init(jax.random.PRNGKey(0), cfg)
    specs = cnn.conv_group_specs(params, N_CU)
    hcfg = HAPMConfig(0.5, 1)
    st = hapm_epoch_update(hapm_init(specs, hcfg), specs, params, hcfg)
    return cfg, apply_masks(params, hapm_element_masks(specs, st)), state


@pytest.fixture(scope="module")
def frames():
    return np.random.default_rng(0).random((3, 16, 16, 3), dtype=np.float32)


@pytest.fixture(scope="module")
def recorded(tiny, frames):
    """A server built with a recorder, warmed on both buckets, and one
    3-image request (two chunks at a largest bucket of 2) inside an
    enclosing span."""
    cfg, pruned, state = tiny
    rec = Spans()
    before = time.time_ns()
    server = CnnServer(pruned, state, cfg, spec=SPEC, buckets=(1, 2),
                       spans=rec)
    server.warmup()
    setup = {k: list(v) for k, v in rec.spans.items()}
    rec.drain()
    with rec("request"):
        y = np.asarray(server.infer(frames))
    return rec.drain(), setup, y, before


def within(inner, outer):
    return outer[0] <= inner[0] <= inner[1] <= outer[1]


def test_infer_records_each_step_once_per_chunk(recorded):
    spans, _, y, before = recorded
    assert y.shape == (3, 10)
    (request,) = spans["request"]
    assert request[0] >= before                  # time.time_ns, not a
    assert request[1] <= time.time_ns()          # monotonic clock
    for name in STEPS:
        assert len(spans[name]) == 2, name
        assert all(within(s, request) for s in spans[name])
    # in turn, chunk by chunk: prepare, dispatch, collect
    flat = sorted((s, name) for name in STEPS for s in spans[name])
    assert [name for _, name in flat] == list(STEPS) * 2
    for (a, _), (b, _) in zip(flat, flat[1:]):
        assert a[1] <= b[0]


def test_server_without_recorder_gives_the_same_logits(tiny, frames,
                                                       recorded):
    cfg, pruned, state = tiny
    plain = CnnServer(pruned, state, cfg, spec=SPEC, buckets=(1, 2))
    assert plain.spans is NO_SPANS
    y = np.asarray(plain.infer(frames))
    np.testing.assert_array_equal(y, recorded[2])
    assert dict(NO_SPANS.spans) == {}
    with NO_SPANS("anything"):
        pass
    assert NO_SPANS.mark("infer.prepare", NO_SPANS.now()) == 0
    assert dict(NO_SPANS.spans) == {}


def test_set_up_spans_nest_install_bind_and_warmup(recorded):
    _, setup, _, _ = recorded
    assert len(setup["install"]) == 1
    (bind,) = setup["bind"]                      # one bind, both buckets
    warm = setup["warmup.bucket"]
    assert len(warm) == 2
    assert within(bind, warm[0])                 # the first bucket binds
    layers = [k for k in setup if k.startswith("bind.layer.")]
    assert layers
    for k in layers:
        assert all(within(s, bind) for s in setup[k])


def test_bind_records_one_layer_span_per_conv(tiny):
    cfg, pruned, state = tiny
    folded = cnn.fold_batchnorm(pruned, state, cfg)
    rec = Spans()
    exec_ = cnn.bind_execution(folded, cfg, spec=SPEC, spans=rec)
    paths = [p for p, _, _ in cnn.conv_layer_order(cfg)]
    assert sorted(exec_.table) == sorted(paths)
    want = {"bind.layer." + cnn.conv_kernel_name(p) for p in paths}
    assert set(rec.spans) == want
    assert all(len(v) == 1 for v in rec.spans.values())
    assert "bind.layer.conv_s1b0_proj" in want


def test_a_compile_while_watched_is_a_compile_span(tiny, frames):
    """The first request at an unwarmed bucket compiles its program while
    dispatching it: the compile span lies inside ``infer.dispatch``."""
    cfg, pruned, state = tiny
    rec = Spans()
    server = CnnServer(pruned, state, cfg, spec=SPEC, buckets=(1, 2),
                       spans=rec)
    server.warmup([1])
    rec.drain()
    with rec.watch_compiles():
        np.asarray(server.infer(frames[:2]))
    after = rec.compiles
    np.asarray(jax.jit(lambda x: x * 3)(jnp.ones(7)))   # not watched
    assert rec.compiles == after >= 1
    spans = rec.drain()
    assert len(spans["compile"]) == after
    (dispatch,) = spans["infer.dispatch"]
    slack = 5_000_000        # the reported duration against two clocks
    assert any(dispatch[0] - slack <= s and e <= dispatch[1]
               for s, e in spans["compile"])


def test_drain_starts_a_new_record():
    rec = Spans()
    with rec("a"):
        pass
    t = rec.mark("b", rec.now())
    assert t >= rec.spans["b"][0][0]
    first = rec.drain()
    assert set(first) == {"a", "b"} and rec.spans == {}
