"""chip_smoke.py: refuses to run without a TPU, and its phases pass on the
CPU backend at a tiny size (the chip run is the same code at 512^3)."""

import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


def _run(args, cwd, extra_env=None, timeout=300):
    env = dict(os.environ)
    env.pop("PYTHONPATH", None)  # the script finds src/ next to itself
    env.update(extra_env or {})
    return subprocess.run(
        [sys.executable, *args], capture_output=True, text=True, env=env, cwd=cwd, timeout=timeout
    )


def _no_result(proc):
    return not any(line.lstrip().startswith("{") for line in proc.stdout.splitlines())


@pytest.mark.parametrize("chips", ["1", "4"])
def test_refuses_cpu(chips):
    proc = _run([SCRIPT, "--chips", chips], ROOT, {"JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert _no_result(proc)
    assert "no TPU" in proc.stderr


def test_fails_without_the_repo(tmp_path):
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    proc = _run([str(tmp_path / "chip_smoke.py")], tmp_path, {"JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert _no_result(proc)


@pytest.fixture(scope="module")
def chip_smoke():
    sys.path.insert(0, ROOT)
    try:
        import chip_smoke
    finally:
        sys.path.remove(ROOT)
    return chip_smoke


def test_service_phases_at_tiny_size(chip_smoke, capsys):
    chip_smoke.run_service(seed=0, edge=16, eeg=(3, 4, 64))
    out = capsys.readouterr().out
    for uid in ("field-xla", "field-pallas", "pencils-0", "pencils-2"):
        assert f"  {uid}: max|err|=" in out


_SHARDED = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, sys.argv[1])
import jax
import chip_smoke
if sys.argv[2] == "tpu-transforms":
    from repro.kernels.rfft import ops
    ops.c2r_is_accurate = lambda: False
chip_smoke.run_sharded(16, 0, jax.devices()[:4])
"""


@pytest.mark.parametrize("transforms", ["cpu-transforms", "tpu-transforms"])
def test_sharded_phase_on_four_fake_devices(transforms):
    """Both transform paths (the TPU one runs per-axis passes with the
    pack-trick C2R pass on one device and sharded) keep the payloads equal."""
    proc = _run(["-c", _SHARDED, ROOT, transforms], ROOT, {"JAX_PLATFORMS": "cpu"})
    if proc.returncode != 0:  # the child's whole stderr, not a tail
        pytest.fail(f"child exited {proc.returncode}:\n{proc.stderr}", pytrace=False)
    assert "parity class 'bitwise'" in proc.stdout
    assert "identical=True" in proc.stdout
    assert "sharded-4: max|err|=" in proc.stdout
