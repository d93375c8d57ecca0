"""Test bootstrap: the test helpers' import path + a ``timeout`` marker.

When ``pytest-timeout`` is installed it is used as-is; otherwise a
SIGALRM-based fallback honours the same marker.  The ``timeout`` marker is
what lets a deadlocked async serving step fail fast in the
serving-conformance CI job instead of hanging the runner.
"""
import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

try:
    import pytest_timeout  # noqa: F401
    _HAVE_PYTEST_TIMEOUT = True
except ImportError:
    _HAVE_PYTEST_TIMEOUT = False


def pytest_configure(config):
    config.addinivalue_line(
        'markers',
        'timeout(seconds): fail the test if it runs longer than this '
        '(pytest-timeout when installed, SIGALRM fallback otherwise)')


if not _HAVE_PYTEST_TIMEOUT:
    import signal

    @pytest.hookimpl(hookwrapper=True)
    def pytest_runtest_call(item):
        marker = item.get_closest_marker('timeout')
        if marker is None or not hasattr(signal, 'SIGALRM'):
            yield
            return
        seconds = int(marker.args[0] if marker.args
                      else marker.kwargs.get('seconds', 60))

        def _alarm(signum, frame):
            raise TimeoutError(
                f'{item.nodeid} exceeded its {seconds}s timeout marker')

        old = signal.signal(signal.SIGALRM, _alarm)
        signal.alarm(seconds)
        try:
            yield
        finally:
            signal.alarm(0)
            signal.signal(signal.SIGALRM, old)
