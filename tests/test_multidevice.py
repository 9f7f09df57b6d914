"""The multi-device runner fails one test, with the child's output, when the
child overruns its limit."""
import pytest
from multidevice import run_with_devices


def test_overrun_fails_with_partial_output():
    code = """
import sys, time
print("started", flush=True)
print("still going", file=sys.stderr, flush=True)
time.sleep(60)
"""
    with pytest.raises(pytest.fail.Exception) as e:
        run_with_devices(code, n=1, timeout=5)
    msg = str(e.value)
    assert "timed out after 5 s" in msg, msg
    assert "started" in msg and "still going" in msg, msg
