"""Tier-1 runs the benchmark's own rehearsals with the served path broken
underneath (``chipbench/tests/test_run_rehearsal.py``; ISSUE 36, ROADMAP
C14): an altered reply, one replica holding another value, a lost write, a
stale read must each read ``correct: false``.  The case stays where it is;
this file imports it.
"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

pytest.register_assert_rewrite("chipbench.tests.test_run_rehearsal")

from chipbench.tests.test_run_rehearsal import (  # noqa: E402,F401
    test_a_run_whose_served_path_is_broken_is_not_correct,
)
