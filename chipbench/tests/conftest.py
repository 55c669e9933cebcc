"""Puts the checkout root and the program's ``src`` on ``sys.path`` and
gives the tests a tiny served ResNet configuration (CPU, interpret mode)."""
import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)


def tiny_config():
    """ResNet-21's configuration file cut to two blocks of widths 8/16 at
    16x16 (CPU sizes), with its execution contract unchanged."""
    cfg = json.loads((ROOT / "chipbench/configs/"
                      "resnet21_cifar-hapm50-int8s.json").read_text())
    cfg.update(stages=[1, 1], widths=[8, 16], image_size=16, buckets=[1, 4, 8])
    cfg["hapm"]["n_cu"] = cfg["exec"]["n_cu"] = 4
    return cfg


@pytest.fixture
def tiny_cfg():
    return tiny_config()
