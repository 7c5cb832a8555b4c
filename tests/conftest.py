"""Shared pytest config.  NOTE: device count is NOT forced here — smoke
tests see 1 device; multi-device tests skip unless the session provides
devices (scripts/run_tests.sh runs the sharding module with XLA_FLAGS).

Every test's call runs under a time limit of its own (``TIME_LIMIT_S``,
or ``@pytest.mark.time_limit(seconds)``), so one slow or hung test fails
by name and the run goes on to its end.  The limit is a SIGALRM timer:
its handler runs between bytecodes, so a native call that never returns
to Python is stopped only when it does.  Hosts without SIGALRM run
unlimited."""

import signal
import time

import pytest

TIME_LIMIT_S = 300.0


class TimeLimitExceeded(BaseException):
    """A test's call ran past its time limit.

    A ``BaseException``, so that neither the test's own ``except
    Exception`` nor hypothesis (which would replay and shrink the
    example, with no timer armed) catches it.
    """


def require_dev_extra(name: str):
    """Dev-extra gate: skip the calling module unless ``name`` imports.

    Property-test modules (hypothesis-driven) call this at import time so
    the deterministic suites stay runnable on minimal installs::

        hyp = require_dev_extra("hypothesis")
    """
    return pytest.importorskip(
        name,
        reason=f"{name} is a dev extra (pip install -e '.[dev]'); "
        "the deterministic equivalents run in the non-property suites",
    )


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        f"time_limit(seconds): fail the test if its call runs longer than "
        f"seconds (default {TIME_LIMIT_S:g})",
    )


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    if not hasattr(signal, "SIGALRM"):
        return (yield)
    marker = item.get_closest_marker("time_limit")
    limit = float(marker.args[0]) if marker else TIME_LIMIT_S

    def expired(signum, frame):
        raise TimeLimitExceeded(
            f"{item.nodeid} ran past its time limit of {limit:g} s"
        )

    start = time.monotonic()
    old_handler = signal.signal(signal.SIGALRM, expired)
    old_delay, old_interval = signal.setitimer(signal.ITIMER_REAL, limit)
    try:
        return (yield)
    finally:
        # nested, so that an expiry between the call's end and the
        # disarm still restores what was armed before
        try:
            signal.setitimer(signal.ITIMER_REAL, 0)
        finally:
            signal.signal(signal.SIGALRM, old_handler)
            if old_delay:
                # a timer armed before the call keeps its deadline; one
                # that came due during the call fires at once
                left = old_delay - (time.monotonic() - start)
                signal.setitimer(
                    signal.ITIMER_REAL, max(left, 1e-6), old_interval
                )
