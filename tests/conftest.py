"""Test bootstrap: repo-root import path + virtual 8-device CPU JAX.

Mirrors the reference's conftest sys.path trick
(reference: tests/conftest.py:14-19) and forces JAX onto the host
platform with 8 virtual devices so multi-chip sharding tests run in
CPU-only CI (see SURVEY.md §4 "fake device layer").

Must run before anything imports jax — conftest import time is early
enough as long as test modules import jax at module scope or later.
"""

import os
import sys
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parent.parent
if str(REPO_ROOT) not in sys.path:
    sys.path.insert(0, str(REPO_ROOT))

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import faulthandler  # noqa: E402
import threading  # noqa: E402
import time  # noqa: E402

import pytest  # noqa: E402

# A hung collective / wedged transport thread turns into a silent CI
# timeout without this: dump every thread's stack on SIGABRT so the
# killed run still says WHERE it was stuck.
faulthandler.enable()


@pytest.fixture()
def tmp_session_dir(tmp_path):
    d = tmp_path / "session"
    d.mkdir()
    return d


@pytest.fixture(scope="session", autouse=True)
def _no_leaked_threads():
    """Every subsystem in this package promises clean teardown
    (close()/stop() joins its workers).  A non-daemon thread that
    outlives the whole test session broke that promise somewhere —
    fail loudly with the survivors' names instead of letting pytest
    hang at interpreter exit."""
    yield
    deadline = time.time() + 5.0
    while time.time() < deadline:
        leaked = [
            t
            for t in threading.enumerate()
            if t is not threading.main_thread()
            and t.is_alive()
            and not t.daemon
        ]
        if not leaked:
            return
        time.sleep(0.05)
    names = ", ".join(sorted(t.name for t in leaked))
    pytest.fail(
        f"non-daemon thread(s) survived session teardown: {names}",
        pytrace=False,
    )
