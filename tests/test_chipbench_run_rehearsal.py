"""Tier-1 runs the benchmark's own end-to-end rehearsals of
``chipbench/tests/test_run_rehearsal.py`` (ISSUE 36, ROADMAP C14):
``run.py`` off the chip, whole and with several waves of preload.  The cases
stay where they are; this file imports them, so each counts here under its
name.  The five runs with the served path broken underneath are
``tests/test_chipbench_run_broken.py``'s, so that two workers share the
eleven subprocesses.
"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

pytest.register_assert_rewrite("chipbench.tests.test_run_rehearsal")

from chipbench.tests.test_run_rehearsal import (  # noqa: E402,F401
    test_a_cpu_without_the_switch_prints_no_result,
    test_a_preload_of_several_waves_rehearses_correct,
    test_rehearsal_prints_the_contracts_line,
    test_the_mix_of_reads_and_updates_rehearses_correct,
)
