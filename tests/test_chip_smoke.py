"""chip_smoke.py: it refuses to report success without a TPU, and its
phases hold on the CPU at a tiny size (the chip runs them at Set1 size)."""

import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

from repro.api import ComputeSpec, ExecSpec, MethodSpec, PipelineSpec, SourceSpec

REPO = Path(__file__).resolve().parent.parent
SMOKE = REPO / "chip_smoke.py"

TINY = PipelineSpec(
    source=SourceSpec(num_slices=8, lines_per_slice=6, points_per_line=10,
                      observations=80),
    method=MethodSpec(name="grouping_ml", rep_bucket=64),
    compute=ComputeSpec(num_bins=20, window_lines=3),
    execution=ExecSpec(slices=(5,)),
)


def _cpu_env(devices: int = 1) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    return env


def _smoke_module():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_smoke_fails_without_tpu(tmp_path):
    """On the CPU, and in a directory that holds chip_smoke.py and nothing
    else of the repo, the script exits non-zero and prints no result."""
    p = subprocess.run([sys.executable, str(SMOKE)], env=_cpu_env(),
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert '"ok"' not in p.stdout
    assert "no TPU" in p.stderr
    alone = tmp_path / "chip_smoke.py"
    shutil.copy(SMOKE, alone)
    env = _cpu_env()
    env.pop("PYTHONPATH")
    p = subprocess.run([sys.executable, str(alone)], env=env, cwd=tmp_path,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert '"ok"' not in p.stdout


def test_main_phase_on_cpu(tmp_path, capsys):
    """Fused vs reference within tolerance, no quarantine, device Select
    bitwise equal to host Select — the main phase's checks at a tiny size."""
    smoke = _smoke_module()
    smoke.main_phase(TINY, tmp_path)
    out = capsys.readouterr().out
    assert "[compare fused_vs_reference slice=5] type_idx_mismatch=0" in out
    assert "[compare device_select] bitwise_equal=True" in out
    assert (tmp_path / "fused" / "slice5_watermark.json").exists()


def test_four_chip_phase_on_cpu_devices(tmp_path):
    """Shards pinned to four (CPU) devices: bitwise equal to one device,
    and every shard's windows staged on its own device."""
    code = (
        "import sys; from pathlib import Path\n"
        f"sys.path.insert(0, {str(REPO / 'tests')!r})\n"
        "import test_chip_smoke as t\n"
        "smoke = t._smoke_module()\n"
        "spec = smoke.with_execution(t.TINY, slices=(4, 5, 6, 7))\n"
        f"smoke.four_chip_phase(spec, Path({str(tmp_path)!r}))\n"
    )
    p = subprocess.run([sys.executable, "-c", code], env=_cpu_env(4),
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stdout + p.stderr
    assert "staged_devices={0: [0], 1: [1], 2: [2], 3: [3]}" in p.stdout
    assert "[compare four_chips_vs_one_chip] bitwise_equal=True" in p.stdout
