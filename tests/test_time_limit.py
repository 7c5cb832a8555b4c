"""The per-test time limit of ``tests/conftest.py``.

Runs an inner pytest session in a subprocess, with the conftest copied
in, so that the inner timer and the failure it causes stay out of this
run: a test past its limit fails by name while the session goes on, a
test that ends in time leaves no timer armed, and a timer armed before
a test's call keeps its deadline.
"""

import pathlib
import shutil
import signal

import pytest

pytest_plugins = ["pytester"]

pytestmark = pytest.mark.skipif(
    not hasattr(signal, "SIGALRM"), reason="the time limit needs SIGALRM"
)

_INNER = '''
import signal
import time

import pytest


@pytest.fixture
def no_timer_after_call():
    yield
    assert signal.getitimer(signal.ITIMER_REAL) == (0.0, 0.0)


@pytest.fixture
def outer_timer():
    fired = []
    old = signal.signal(signal.SIGALRM, lambda *a: fired.append(1))
    signal.setitimer(signal.ITIMER_REAL, 60.0)
    yield fired
    left, _ = signal.getitimer(signal.ITIMER_REAL)
    signal.setitimer(signal.ITIMER_REAL, 0)
    signal.signal(signal.SIGALRM, old)
    assert 50.0 < left <= 60.0 and not fired


@pytest.mark.time_limit(1)
def test_sleeps_past_its_limit():
    time.sleep(3)


@pytest.mark.time_limit(1)
def test_ends_in_time(no_timer_after_call):
    time.sleep(0.01)


def test_keeps_the_timer_armed_before_it(outer_timer):
    time.sleep(0.01)
'''


def test_time_limit_fails_the_test_and_the_run_goes_on(pytester):
    shutil.copy(pathlib.Path(__file__).with_name("conftest.py"), pytester.path)
    pytester.makepyfile(test_inner=_INNER)
    result = pytester.runpytest_subprocess("-p", "no:cacheprovider")
    result.assert_outcomes(passed=2, failed=1)
    result.stdout.fnmatch_lines(
        [
            "*TimeLimitExceeded: test_inner.py::test_sleeps_past_its_limit "
            "ran past its time limit of 1 s*"
        ]
    )
