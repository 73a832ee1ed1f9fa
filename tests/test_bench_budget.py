"""bench.py has no fallback: with the TPU platform required on a host
that has no chip it prints ONE error line, exits non-zero at once, and
prints no metric — a number is only ever printed for a TPU."""

import os
import subprocess
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_bench_fails_at_once_without_a_chip():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "tpu"        # required: no chip → init raises

    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "bench.py")],
        capture_output=True, text=True, timeout=110, env=env, cwd=_REPO)
    elapsed = time.monotonic() - t0

    assert proc.returncode != 0
    assert elapsed < 60
    assert proc.stdout.strip() == "", f"printed a result: {proc.stdout!r}"
    errors = [ln for ln in proc.stderr.splitlines()
              if ln.startswith("bench.py:")]
    assert len(errors) == 1, proc.stderr
    assert "no TPU" in errors[0]
