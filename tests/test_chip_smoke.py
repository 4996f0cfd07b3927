"""chip_smoke.py never carries on without a TPU: on the CPU it names the
platform it found, prints no result line and exits non-zero."""
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_refuses_the_cpu():
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=REPO,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert "platform 'cpu'" in r.stderr
    assert '"ok"' not in r.stdout
