"""chip_smoke.py rehearsed on the CPU: every phase but the device check, at
the reduced amr-paper-100m config with interpreted kernels, plus the
script's refusal to report a result without a TPU or without the repo."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from repro.configs.registry import get_reduced_config

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402

CFG = get_reduced_config(chip_smoke.ARCH)


def _run(args, cwd, **env):
    return subprocess.run([sys.executable, *args], cwd=cwd, capture_output=True,
                          text=True, timeout=600,
                          env={**os.environ, "JAX_PLATFORMS": "cpu", **env})


def test_kernels_phase():
    body = chip_smoke.phase_kernels(CFG, rows=(16, 4))
    assert body["lowrank"] == body["lut_bitexact"] == 8
    assert body["lut_grouped_bitexact"] == 4
    assert body["inject_replay"] == "refused"


def test_train_phase():
    out = chip_smoke.phase_train(CFG, steps=2, batch=2, seq=16)
    assert set(out) == {"exact", CFG.numerics.mode}
    assert all(len(run["losses"]) == 2 for run in out.values())


def test_serve_phase():
    out = chip_smoke.phase_serve(CFG, slots=2, requests=3, prompt_len=8, gen=4)
    assert set(out) == {"exact", "amr_kernel"}
    assert all(run["solo_match"] for run in out.values())


def test_sharded_train_phase_on_four_host_devices():
    """The --chips 4 path on four virtual CPU devices (own process: the
    device count is fixed when JAX starts)."""
    code = ("import chip_smoke; from repro.configs.registry import "
            "get_reduced_config as g; chip_smoke.phase_sharded_train("
            "g(chip_smoke.ARCH), steps=3, batch=4, seq=16)")
    proc = _run(["-c", code], REPO, PYTHONPATH=str(REPO / "src"),
                XLA_FLAGS="--xla_force_host_platform_device_count=4")
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [x for x in proc.stdout.splitlines() if x.startswith("[sharded_train]")]
    body = json.loads(line[-1].split(" ", 1)[1])
    assert len(body["bytes_per_device"]) == 4 and body["leaves_split"] > 0


@pytest.mark.parametrize("where", ["repo", "alone"])
def test_no_result_without_tpu_or_repo(where, tmp_path):
    """On the CPU, or copied away from the repo, the script exits non-zero
    and prints no result line."""
    if where == "alone":
        shutil.copy(REPO / "chip_smoke.py", tmp_path / "chip_smoke.py")
        proc = _run(["chip_smoke.py"], tmp_path, PYTHONPATH="")
    else:
        proc = _run(["chip_smoke.py"], REPO)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    if where == "repo":
        assert "platform 'cpu'" in proc.stderr
