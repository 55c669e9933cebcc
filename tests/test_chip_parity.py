"""``benchmarks/chip_parity.py`` on the CPU at a tiny config: the two
conv paths agree bit for bit, the activation skip changes no bit, each
f32 kernel sits within the parity tests' tolerance of ``lax.conv``, and
the last line is the JSON record."""
import json

from benchmarks import chip_parity
from repro.models import cnn


def test_paths_agree_bit_for_bit_at_a_tiny_config(monkeypatch, capsys):
    cfg = cnn.ResNetConfig(stages=(1, 1), widths=(8, 16), image_size=16)
    monkeypatch.setattr(chip_parity, "CONFIG", cfg)
    monkeypatch.setattr(chip_parity, "N_CU", 4)
    assert chip_parity.main(["--batch", "2", "--calls", "1"]) == 0
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rec["parity"] == {"f32_implicit_eq_materializing": True,
                             "streamed_implicit_eq_materializing": True,
                             "streamed_skip_eq_noskip": True}
    assert set(rec["forward_ms_smoke_timing"]) == {
        "f32_implicit", "f32_materializing", "streamed_implicit",
        "streamed_skip", "streamed_materializing"}
    layers = rec["layer_f32_error"]
    assert len(layers) == len(cnn.conv_layer_order(cfg))
    assert all(v["max_abs_err"] <= 1e-4 * (1 + v["max_abs_ref"])
               for v in layers.values())
