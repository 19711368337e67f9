"""chip_smoke.py has no CPU fallback: without a TPU it exits non-zero and
prints no result line, from the checkout and as a lone copy."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

SMOKE = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.mark.parametrize("where", ["checkout", "lone_copy"])
def test_refuses_without_tpu(tmp_path, where):
    script = SMOKE
    if where == "lone_copy":
        script = Path(shutil.copy(SMOKE, tmp_path / SMOKE.name))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(script)], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no TPU" in r.stderr
