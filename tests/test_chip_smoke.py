"""chip_smoke.py on the CPU: it refuses to run without a TPU, and its
phase functions — plain functions of the widths — pass at tiny widths with
the kernels interpreted. The chip itself is exercised by running
``python chip_smoke.py`` through the chip tool (PERF.md), never by pytest.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def test_refuses_without_a_tpu():
    """No accelerator: non-zero exit before any phase, no phase result and
    no result line on stdout — as a program it has no option that passes
    without a TPU."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": ""}
    proc = subprocess.run([sys.executable,
                           os.path.join(REPO, "chip_smoke.py")],
                          capture_output=True, text=True, timeout=120,
                          env=env, cwd=REPO)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr
    # ... and extra arguments change nothing
    proc = subprocess.run([sys.executable,
                           os.path.join(REPO, "chip_smoke.py"), "--tiny"],
                          capture_output=True, text=True, timeout=120,
                          env=env, cwd=REPO)
    assert proc.returncode != 0 and proc.stdout.strip() == ""


@pytest.mark.slow
def test_phases_pass_at_tiny_widths(tmp_path, capsys):
    """Every phase the chip run takes, driven on the CPU test backend:
    same entry points, tiny widths, kernels interpreted. Eight virtual
    devices: the multichip phase runs too."""
    report = chip_smoke.Report()
    chip_smoke.run_phases(chip_smoke.TINY, report, str(tmp_path))
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    assert report.failed == [], lines
    names = [p["phase"] for p in report.phases]
    assert names == ["data", "train_vae", "train_dalle", "serve",
                     "serve_kernel", "kernels", "sync", "multichip"]
    by = {p["phase"]: p for p in report.phases}
    assert by["kernels"]["interpreted"] is True
    assert by["kernels"]["f32_engine_tokens_equal"] \
        == {"prefix": True, "int8kv": True, "visible": True}
    assert by["serve_kernel"]["first_difference_vs_gather"] \
        == [None] * len(by["serve_kernel"]["first_difference_vs_gather"])
    for p in report.phases:
        assert {"wall_s", "compile_s", "run_s",
                "peak_bytes_in_use"} <= set(p)
