"""The command refuses hosts it cannot measure, and BENCHMARK.json keeps
to the contract the harness reads it by."""
import json
import re
import subprocess
import sys


from chipbench import run as R
from conftest import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_no_tpu_exits_nonzero_without_a_result():
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "resnet21-int8s-interactive", "--seed", str(2 ** 40 + 1),
         "--seconds", "1", "--trace", "0"], cwd=ROOT, capture_output=True,
        text=True, env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"},
        timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_device_kind_outside_the_peaks_table(monkeypatch, capsys):
    import jax

    class Dev:
        platform, device_kind = "tpu", "TPU v99"
    monkeypatch.setattr(jax, "devices", lambda *a: [Dev()])
    assert R.main(["--workload", "resnet18-int8s-bulk", "--seed", "1",
                   "--seconds", "1", "--trace", "0"]) == 2
    assert capsys.readouterr().out == ""


def test_benchmark_json_is_complete():
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    configs = {c["name"]: c for c in b["configs"]}
    for c in b["configs"]:
        assert NAME.match(c["name"]) and (ROOT / c["file"]).is_file()
        assert c["file"].startswith("chipbench/")
    cells = {w["name"]: w for w in b["workloads"]}
    for w in b["workloads"]:
        assert NAME.match(w["name"]) and w["config"] in configs
        assert (ROOT / "chipbench/traffic" / f"{w['traffic']}.json").is_file()
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    e2e = {m["name"] for m in b["end_to_end"]}
    assert "setup_s" in e2e
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert (ROOT / "chipbench/metrics" / f"{m['name']}.py").is_file()
        assert set(m.get("workloads", cells)) <= set(cells)
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        moved = next(e for e in b["end_to_end"] if e["name"] == m["moves"])
        assert set(m.get("workloads", cells)) <= set(moved.get("workloads",
                                                               cells))
    for cell in cells:
        mine = [m for m in b["end_to_end"] if cell in m.get("workloads", [cell])]
        assert len(mine) >= 2
        assert R.cell_metrics(b, cell, True)
