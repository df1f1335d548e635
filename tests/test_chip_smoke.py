"""``chip_smoke.py`` refuses to run without a TPU, and without the repo.

The script itself runs only on the chip (``python chip_smoke.py``
through the chip tool); here it must fail fast and print no result
line, so a CPU run can never pass for a chip run.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent


def _run(script: Path, cwd: Path) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, str(script)], cwd=str(cwd),
                          env=env, capture_output=True, text=True,
                          timeout=300)


def _no_result_line(r: subprocess.CompletedProcess) -> None:
    for line in r.stdout.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        assert "ok" not in obj, f"result line printed: {line}"


def test_exits_nonzero_without_tpu():
    r = _run(REPO / "chip_smoke.py", REPO)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    _no_result_line(r)


def test_exits_nonzero_outside_the_repo(tmp_path):
    script = tmp_path / "chip_smoke.py"
    shutil.copy(REPO / "chip_smoke.py", script)
    r = _run(script, tmp_path)
    assert r.returncode != 0
    assert "no mpitest_tpu package" in r.stderr
    _no_result_line(r)
