"""``chip_smoke.py`` fails loudly without a TPU: a non-zero exit and no
result line, whether it runs in the checkout or alone in a directory.
(Its phases run only on the chip.)"""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_chip_smoke_fails_without_tpu(tmp_path, where):
    script = ROOT / "chip_smoke.py"
    if where == "alone":
        script = Path(shutil.copy(script, tmp_path / "chip_smoke.py"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, str(script)], cwd=script.parent,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0, out.stdout
    assert '"ok"' not in out.stdout
    assert "no TPU" in out.stderr, out.stderr[-2000:]
