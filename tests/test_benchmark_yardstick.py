"""Tier-1 collects the benchmark's own tests of what judges a run and
what ends one: ``benchmark/tests/test_yardstick.py`` (which reference and
checks a configuration is held to) and ``test_deadline.py`` (a served
call that does not return ends the run) - 13 cases.  The rest of
``benchmark/tests`` stays with ``python -m pytest benchmark/tests``.

``tests/conftest.py`` gives this process eight virtual CPU devices, and
a cell of one chip is refused on eight: the in-process runs here are
held to one device (``$ISOTOPE_MESH`` = 1x1, the served path's own
switch) and the harness is told of that one."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402
from benchmark.tests.test_deadline import *  # noqa: E402,F401,F403
from benchmark.tests.test_yardstick import *  # noqa: E402,F401,F403


@pytest.fixture(autouse=True)
def one_device(monkeypatch):
    real = run.device_doc
    monkeypatch.setenv("ISOTOPE_MESH", "1x1")
    monkeypatch.setattr(run, "device_doc", lambda: dict(real(), count=1))
