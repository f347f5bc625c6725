"""One traced rehearsal at a time. ``chipbench/run.py`` keeps a run's
trace under ``chipbench/out/trace/<cell>/`` and clears that directory
before and after: two test files that rehearse the same cell in two
xdist workers at once delete each other's trace (``no .xplane.pb under
...``). Every test that runs ``run.py`` end to end asks for the
``rehearsal_env`` fixture of its file; those take a lock in
``chipbench/out/`` for their duration."""

import fcntl
import os

import pytest

_OUT = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "chipbench", "out")


@pytest.fixture(autouse=True)
def _one_rehearsal_at_a_time(request):
    if "rehearsal_env" not in request.fixturenames:
        yield
        return
    os.makedirs(_OUT, exist_ok=True)
    with open(os.path.join(_OUT, ".rehearsal.lock"), "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        try:
            yield
        finally:
            fcntl.flock(lock, fcntl.LOCK_UN)
