"""Tier-1 runs the benchmark's own cases of
chipbench/tests/test_plane_runs.py: the readers that pair each run of the
tick program on the chip with the launch that enqueued it, and split the
chip's idle time by the program's host spans.  The cases stay where they
are (a benchmark file is a ``benchmark`` PR's to move); this file imports
them, so each counts here under its name.
"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

pytest.register_assert_rewrite("chipbench.tests.test_plane_runs")

from chipbench.tests.test_plane_runs import *  # noqa: E402,F401,F403
