"""Shared pytest wiring.

``--interpret`` flips the codec tests onto the Pallas-kernel dispatch branch
(``CompressorConfig.use_pallas=True``; on CPU the kernels execute in
interpret mode).  Tier-1 runs without it and exercises the shard_map-safe
jnp fallbacks; the CI ``kernels-interpret`` job runs with it so both decode
dispatch branches are covered on every PR.  The option is exported through
``REPRO_TEST_USE_PALLAS`` so the subprocess-based distributed tests inherit
it.

When hypothesis is installed (the ``dev`` extra; it is not in the pinned
runtime deps) a derandomized profile is loaded for every run, so the property
tests in ``tests/test_properties.py`` draw the same examples each time: a run
cannot pass once and fail the next, and the drawn shapes do not compile anew
per run.  The example counts stay those of each test's ``@settings``.
"""
import contextlib
import os

import pytest

# hypothesis ships via the dev extra only; tier-1 must run without it
with contextlib.suppress(ImportError):
    from hypothesis import settings as _hyp_settings

    _hyp_settings.register_profile("derandomized", derandomize=True, deadline=None,
                                   print_blob=True)
    _hyp_settings.load_profile("derandomized")


def pytest_addoption(parser):
    parser.addoption(
        "--interpret", action="store_true", default=False,
        help="run codec paths with use_pallas=True (interpret-mode kernels off-TPU)")


def pytest_configure(config):
    if config.getoption("--interpret"):
        os.environ["REPRO_TEST_USE_PALLAS"] = "1"


@pytest.fixture
def use_pallas() -> bool:
    return os.environ.get("REPRO_TEST_USE_PALLAS", "0") not in ("", "0")
