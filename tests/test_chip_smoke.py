"""chip_smoke.py's contract off the chip: with no TPU it fails in seconds,
names the backend it found, and prints no result line. (What it proves ON the
chip is in the file's docstring; tests/test_tpu_lowering.py reuses its kernel
cases.)"""
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_fails_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")], cwd=REPO,
        env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    lines = proc.stdout.strip().splitlines()
    assert "FAILED in phase device" in lines[-1]
    assert "'cpu'" in lines[-1]          # the backend it found
    for line in lines:                   # and no result object anywhere
        try:
            assert "ok" not in json.loads(line)
        except ValueError:
            pass
