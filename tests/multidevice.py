"""One bounded runner for the multi-device test cases.

Multi-device cases run in a child Python process so the pytest process keeps
a single CPU device: the child sees ``n`` fake host devices through
``XLA_FLAGS``, imports ``repro`` from ``src/``, and inherits the rest of the
environment (``JAX_PLATFORMS``, ``REPRO_TEST_USE_PALLAS``).

A child that overruns ``timeout`` is killed and fails its one test with the
output it had written so far, instead of holding the whole suite until the
suite's own limit cuts it.
"""
import os
import pathlib
import subprocess
import sys
import textwrap

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]

# About twice the slowest child measured under a six-worker tier-1 run on 8
# CPU cores (the 2-peer mesh sweep, 359 s).
TIMEOUT_S = 720


def _text(out) -> str:
    # on a timeout the partial output comes back as bytes even in text mode
    return out.decode(errors="replace") if isinstance(out, bytes) else out or ""


def run_with_devices(code_or_args, n: int = 8, timeout: int = TIMEOUT_S) -> str:
    """Run ``code_or_args`` on ``n`` fake devices; return its stdout.

    ``code_or_args`` is Python source (run with ``-c``) or a list of
    arguments to the interpreter (a script path and its arguments).  The
    test fails on a non-zero exit or on overrunning ``timeout`` seconds,
    showing the child's stdout and the tail of its stderr.
    """
    if isinstance(code_or_args, str):
        argv = ["-c", textwrap.dedent(code_or_args)]
    else:
        argv = [str(a) for a in code_or_args]
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = str(ROOT / "src")
    try:
        r = subprocess.run([sys.executable, *argv], capture_output=True, text=True,
                           timeout=timeout, env=env)
    except subprocess.TimeoutExpired as e:
        pytest.fail(f"timed out after {timeout} s on {n} devices\n"
                    f"stdout:\n{_text(e.stdout)}\nstderr:\n{_text(e.stderr)[-4000:]}",
                    pytrace=False)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-4000:]}"
    return r.stdout
