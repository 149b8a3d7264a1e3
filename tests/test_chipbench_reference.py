"""Tier-1 runs the benchmark's own cases of chipbench/tests/test_reference.py
(ISSUE 36, ROADMAP C14): they guard the code that decides a run's
``correct``, and until now only ``python -m pytest chipbench/tests`` ran
them.  The cases stay where they are (a benchmark file is a ``benchmark``
PR's to move); this file imports them, so each counts here under its name.
"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

pytest.register_assert_rewrite("chipbench.tests.test_reference")

from chipbench.tests.test_reference import *  # noqa: E402,F401,F403
