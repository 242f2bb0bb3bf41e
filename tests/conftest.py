"""Shared test config.

NOTE: tests must see the CPU devices the process was started with; no test
sets ``XLA_FLAGS`` in-process (CI forces 4 host devices from outside).

Property tests use ``hypothesis`` directly, under the fast profile below.

``pytest-timeout`` is optional: the ``timeout`` ini option in
pyproject.toml guards the suite against a hung serving drive loop.  When
the plugin is absent this conftest registers the option itself and enforces
it with a SIGALRM fallback (main-thread, POSIX only — a no-op elsewhere,
matching the plugin's own signal-method constraints).
"""
import os
import signal
import sys
import threading

import pytest
from hypothesis import HealthCheck, settings

# Make `import repro` work without an editable install.
_SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)

settings.register_profile(
    "fast",
    max_examples=25,
    deadline=None,
    suppress_health_check=[HealthCheck.too_slow, HealthCheck.data_too_large],
)
settings.load_profile("fast")

try:
    import pytest_timeout  # noqa: F401
    _HAVE_PYTEST_TIMEOUT = True
except ModuleNotFoundError:
    _HAVE_PYTEST_TIMEOUT = False


def pytest_addoption(parser):
    # pytest-timeout owns the `timeout` ini key when installed; claim it
    # only for the fallback so the two never double-register.
    if not _HAVE_PYTEST_TIMEOUT:
        parser.addini("timeout",
                      "per-test timeout in seconds (SIGALRM fallback)",
                      default="0")


if not _HAVE_PYTEST_TIMEOUT:

    @pytest.hookimpl(hookwrapper=True)
    def pytest_runtest_call(item):
        try:
            limit = float(item.config.getini("timeout") or 0)
        except (TypeError, ValueError):
            limit = 0.0
        if (limit <= 0 or not hasattr(signal, "SIGALRM")
                or threading.current_thread() is not threading.main_thread()):
            yield
            return

        def _on_alarm(signum, frame):
            raise TimeoutError(
                f"test exceeded {limit:.0f}s (conftest SIGALRM fallback; "
                "install pytest-timeout for richer reporting)")

        old = signal.signal(signal.SIGALRM, _on_alarm)
        signal.setitimer(signal.ITIMER_REAL, limit)
        try:
            yield
        finally:
            signal.setitimer(signal.ITIMER_REAL, 0)
            signal.signal(signal.SIGALRM, old)
