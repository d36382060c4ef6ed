"""Shared pytest configuration.

``@pytest.mark.slow`` marks subprocess tests that re-launch python with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (the main pytest
process must keep seeing 1 device).  They take minutes, so the tier-1 loop
skips them; opt in with ``--runslow`` (CI runs them as a separate job).
"""

import pytest


@pytest.fixture
def compile_sentry():
    """Active :class:`repro.analysis.sentry.CompileSentry` for the test."""
    from repro.analysis.sentry import CompileSentry

    with CompileSentry() as sentry:
        yield sentry


def pytest_addoption(parser):
    parser.addoption(
        "--runslow",
        action="store_true",
        default=False,
        help="run @pytest.mark.slow multi-device subprocess tests",
    )


def pytest_collection_modifyitems(config, items):
    if config.getoption("--runslow"):
        return
    skip_slow = pytest.mark.skip(reason="slow multi-device test: use --runslow")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip_slow)
