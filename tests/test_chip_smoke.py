"""``chip_smoke.py``: refuses every platform but the TPU, and its path runs
end to end when a test lets it run on the CPU devices at a tiny scale."""

import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def _last_json(out):
    return json.loads(out.strip().splitlines()[-1])


def test_refuses_cpu_and_prints_no_result(capsys):
    assert chip_smoke.main(["--scale", "8"]) != 0
    assert "ok" not in capsys.readouterr().out


def test_refuses_without_the_repository(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          capture_output=True, text=True, env=env, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout == ""


@pytest.mark.parametrize("argv,chips", [
    (["--scale", "10"], None),
    (["--scale", "10", "--chips", "4"], 4),
], ids=["all-devices", "four-chip-path"])
def test_runs_end_to_end_on_cpu_devices(monkeypatch, capsys, argv, chips):
    monkeypatch.setattr(chip_smoke, "PLATFORM", "cpu")
    assert chip_smoke.main(argv) == 0
    out = capsys.readouterr().out
    doc = _last_json(out)
    assert doc == {"ok": True, "device": {
        "platform": "cpu", "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices())}}
    assert "served 48/48, failed 0" in out
    assert "'full', 'linger'" in out
    assert "0 mismatches" in out
    assert ("single-source" in out) == (chips is None)


def test_mismatch_fails_the_run(monkeypatch, capsys):
    monkeypatch.setattr(chip_smoke, "PLATFORM", "cpu")
    real = chip_smoke.reference_distances
    monkeypatch.setattr(chip_smoke, "reference_distances",
                        lambda g, roots: real(g, roots) + 1)
    assert chip_smoke.main(["--scale", "9"]) == 1
    doc = _last_json(capsys.readouterr().out)
    assert doc["ok"] is False
    assert doc["failed"] == ["check-served", "single-source"]
