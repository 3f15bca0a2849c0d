"""`chip_smoke.py` off the chip: it must refuse, and its phases must work.

The script's verdict needs a TPU; what a CPU can check is that it says no
without one (non-zero exit, no result line) and that the four-chip
comparison it would run there is sound on four virtual devices at a tiny
size — the rehearsal the on-chip-measurement guide asks for, kept as a
test.
"""

from __future__ import annotations

import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

import chip_smoke

REPO = Path(__file__).resolve().parent.parent


def _run(cwd, script):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def _assert_refused(proc):
    assert proc.returncode != 0, proc.stdout[-2000:]
    lines = proc.stdout.strip().splitlines()
    assert not lines or '"ok"' not in lines[-1], lines[-1]
    assert '"ok": true' not in proc.stdout


def test_on_the_cpu_it_exits_nonzero_and_prints_no_result():
    proc = _run(REPO, REPO / "chip_smoke.py")
    _assert_refused(proc)
    assert "platform=cpu" in proc.stdout  # it named the device it found


def test_alone_in_a_directory_it_exits_nonzero(tmp_path):
    shutil.copy(REPO / "chip_smoke.py", tmp_path / "chip_smoke.py")
    _assert_refused(_run(tmp_path, tmp_path / "chip_smoke.py"))


@pytest.fixture()
def scratch_run(tmp_path, monkeypatch):
    """The script's working directory moved under the test's, and
    `train.main` kept from placing the compile cache: that setting is the
    process's, and the tests run without a persistent cache."""
    import train

    monkeypatch.setattr(chip_smoke, "WORK", tmp_path)
    monkeypatch.setattr(train, "place_compile_cache", lambda: None)


@pytest.mark.parametrize("model,batch", [("net", 32), ("resnet18", 16)])
def test_three_way_comparison_on_four_virtual_devices(
        scratch_run, capsys, model, batch):
    """One device vs four (replicated) vs four (sharded), through
    `train.main` and a ``--resume=auto``: same per-step and eval losses,
    state and batch on all four devices, a quarter of the optimizer state
    each when sharded, and each mode's collectives in its compiled step."""
    # True f32 on the CPU: a few ulp of reduction order per step.
    chip_smoke.compare_data_parallel(model=model, batch=batch, steps=3,
                                     world=4, atol=1e-4)
    out = capsys.readouterr().out
    assert "sharded vs one device" in out
    assert "'reduce-scatter'" in out and "'all-gather'" in out


def test_three_way_comparison_catches_a_departure(scratch_run):
    """The loss tolerance is what fails when a run departs from one
    device: an allowance of zero rounding cannot be met."""
    with pytest.raises(AssertionError, match="departs from one device"):
        chip_smoke.compare_data_parallel(model="resnet18", batch=16,
                                         steps=3, world=4, atol=0.0)
