"""``chip_smoke.py`` on the CPU: both serving phases at a tiny config
(kernels interpreted, the platform check steered to a fake TPU), the
format of its last line, its refusal of a host without a TPU, and where
the compilation cache it sets up lives."""
import importlib.util
import json
import os
import pathlib
import subprocess
import sys
import types

import jax
import pytest

from repro.launch import serve_cnn
from repro.models import cnn

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def cache_dir_restored():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def _on_fake_tpu(smoke, monkeypatch):
    """Steer the platform check to a TPU, and keep this process's
    compilation cache as it is (``test_compile_cache_dir`` covers it)."""
    fake = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    monkeypatch.setattr(jax, "devices", lambda: [fake])
    monkeypatch.setattr(smoke, "use_compile_cache", lambda: "unchanged")


def test_phases_pass_and_last_line_names_the_device(smoke, monkeypatch,
                                                    capsys):
    _on_fake_tpu(smoke, monkeypatch)
    cfg = cnn.ResNetConfig(stages=(1, 1), widths=(8, 16), image_size=16)
    monkeypatch.setattr(smoke, "CONFIG", cfg)
    monkeypatch.setattr(smoke, "N_CU", 4)
    monkeypatch.setattr(smoke, "BUCKETS", (1, 2))
    monkeypatch.setattr(smoke, "REQUEST_SIZES", (1, 2))
    monkeypatch.setattr(smoke, "REQUESTS_PER_SIZE", 1)
    assert smoke.main([]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert json.loads(lines[-1]) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
    routes = [ln for ln in lines if "conv layers per path" in ln]
    n = len(cnn.conv_layer_order(cfg))
    assert len(routes) == 2          # one per phase, every conv implicit
    assert all(f"'implicit': {n}, 'materializing': 0, 'dense': 0" in ln
               for ln in routes)
    # f32 reference in phase a; integer twin and dense wire reference in
    # phase b; then the control the integer bound must catch
    assert sum("max abs error vs" in ln for ln in lines) == 3
    assert sum(ln.startswith("[chip_smoke] control") for ln in lines) == 1


def test_failed_check_exits_nonzero_without_a_result(smoke, monkeypatch,
                                                     capsys):
    _on_fake_tpu(smoke, monkeypatch)

    def failing_run(seed):
        smoke.check(False, "forced")

    monkeypatch.setattr(smoke, "run", failing_run)
    assert smoke.main([]) == 1
    captured = capsys.readouterr()
    assert '"ok"' not in captured.out
    assert "FAILED: forced" in captured.err


def test_plain_run_on_the_cpu_exits_nonzero():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert done.returncode != 0
    assert '"ok"' not in done.stdout
    assert "platform 'cpu'" in done.stderr


def test_compile_cache_dir(monkeypatch, cache_dir_restored):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = serve_cnn.use_compile_cache()
    assert path == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    # set in the environment: JAX reads it, nothing is set in code
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    assert serve_cnn.use_compile_cache() == "/elsewhere"
    assert jax.config.jax_compilation_cache_dir is None
