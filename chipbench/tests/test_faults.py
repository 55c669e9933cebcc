"""A whole run on the CPU (the look for a chip skipped) with the timed
path sound, broken underneath in each way a served cell can be, and
with each control (``control.py``) served in the program's place:
``correct`` has to come out true, then false."""
import json

import numpy as np
import pytest

from chipbench import control
from chipbench import run as R
from chipbench import work
from chipbench.configs import resnet_cifar as fam
from chipbench.configs import resnet_cifar_ref as ref

MIX = {"loop": "closed", "clients": 1, "pool_frames": 32,
       "sizes": [{"p": 0.5, "lo": 4, "hi": 4}, {"p": 0.5, "lo": 8, "hi": 8}]}


def half_batch(y):
    """Half of the batch left out."""
    return y[: len(y) // 2]


def rows_swapped(y):
    """An answer altered where it is produced: two rows trade places."""
    y = np.array(y)
    y[[0, 1]] = y[[1, 0]]
    return y


def logit_nudged(y):
    """An answer altered where it is produced: one logit moved by 1 % of
    the batch's largest."""
    y = np.array(y)
    y[-1, 3] += 0.01 * np.abs(y).max()
    return y


def int4_reference(model):
    """The control: the plain reference at int4 weight codes."""
    return ref.make_forward(model.params, model.state, model.cfg, w_bits=4)


def zeroed_channel(model):
    """The reference with one live output channel of the last live conv
    zeroed."""
    return ref.make_forward(
        control.zero_last_channel(model.params, model.cfg), model.state,
        model.cfg)


def _run(tiny_cfg, monkeypatch, fault):
    if fault is not None:
        inner = fam.Model.infer
        monkeypatch.setattr(fam.Model, "infer",
                            lambda self, x: fault(np.asarray(inner(self, x))))
    bench = R.load_json(R.ROOT / "BENCHMARK.json")
    cell = {"name": "tiny-bulk", "config": "tiny", "traffic": "tiny",
            "chips": 1, "why": "test"}
    return R.run_cell(bench, cell, tiny_cfg, MIX, seed=2 ** 36 + 1,
                      seconds=1.0, traced=False,
                      peaks=work.peaks_for("TPU v5 lite"))


def test_sound_run_is_correct(tiny_cfg, monkeypatch):
    res = _run(tiny_cfg, monkeypatch, None)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"
    json.dumps(res)


@pytest.mark.parametrize("fault", [half_batch, rows_swapped, logit_nudged])
def test_fault_is_not_correct(tiny_cfg, monkeypatch, fault):
    res = _run(tiny_cfg, monkeypatch, fault)
    assert res["correct"] is False


@pytest.mark.parametrize("make", [int4_reference, zeroed_channel])
def test_control_in_the_programs_place_is_not_correct(tiny_cfg, monkeypatch,
                                                      make):
    forwards = {}

    def served_by_control(self, x):
        if id(self) not in forwards:
            forwards[id(self)] = make(self)
        return forwards[id(self)](x)

    monkeypatch.setattr(fam.Model, "infer", served_by_control)
    res = _run(tiny_cfg, monkeypatch, None)
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["checks"]["logit_gap"]["value"] > \
        res["checks"]["logit_gap"]["limit"]
    assert res["correct"] is False
