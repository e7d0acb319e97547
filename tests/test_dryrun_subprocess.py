"""Dry-run machinery integration test (subprocess: needs 512 fake devices,
while the test process itself must keep the single real CPU device)."""
import json
import subprocess
import sys
from pathlib import Path

import pytest


REPO = Path(__file__).resolve().parents[1]


@pytest.mark.slow
def test_dryrun_cell_end_to_end(tmp_path):
    """Lower+compile one cheap cell on the 16x16 mesh; artifact is complete."""
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun",
         "--arch", "mamba2-370m", "--shape", "long_500k",
         "--mesh", "single", "--out", str(tmp_path)],
        env={"PYTHONPATH": str(REPO / "src"), "PATH": "/usr/bin:/bin"},
        capture_output=True, text=True, timeout=560, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    rec = json.loads((tmp_path / "mamba2-370m__long_500k__single.json").read_text())
    assert rec["status"] == "ok"
    assert rec["fits"] is True
    assert rec["cost"]["flops"] > 0
    assert rec["collectives"]["total_bytes"] >= 0
    assert rec["memory"]["peak_bytes"] < 16 * 2**30


@pytest.mark.slow
def test_dryrun_skip_policy(tmp_path):
    """long_500k on a pure full-attention arch records a documented skip."""
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun",
         "--arch", "qwen3-32b", "--shape", "long_500k",
         "--mesh", "single", "--out", str(tmp_path)],
        env={"PYTHONPATH": str(REPO / "src"), "PATH": "/usr/bin:/bin"},
        capture_output=True, text=True, timeout=300, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    rec = json.loads((tmp_path / "qwen3-32b__long_500k__single.json").read_text())
    assert rec["status"] == "skipped"
    assert "full-attention" in rec["reason"]
