"""benchmarks/run.py reports a failed suite in its exit code."""
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_failed_suite_exits_nonzero():
    env = {**os.environ, "PYTHONPATH": os.path.join(ROOT, "src"),
           "JAX_PLATFORMS": "cpu"}
    out = subprocess.run([sys.executable, "-m", "benchmarks.run",
                          "no-such-suite"], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 1, out.stdout + out.stderr
    assert "no-such-suite/SUITE-FAILED" in out.stdout
