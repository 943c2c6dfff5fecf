"""``chip_smoke.py`` measures the chip only: a run that finds no TPU
exits non-zero and prints no result line, instead of timing the CPU
under a device label."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_without_a_chip_exits_nonzero(tmp_path):
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert out.returncode != 0
    assert "found no TPU" in out.stderr
    assert not out.stdout.strip()
