"""Tier-1 guard of the traced window (PR 32 left it for a PR that may
touch `tests/`): the cases of `benchmarks/tests/test_window.py`, collected
here by import, so that the gate runs the check of the line that refused
PR 31 (`0 < device.busy_s <= device.window_s`, both read inside the marker).
The traced rehearsal of `run.py` stays under `benchmarks/tests` only: it
starts a process and takes 11 s of a quiet box, and asserts a width of the
window that a box running six workers does not keep.
"""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

pytest.register_assert_rewrite("benchmarks.tests.test_window")
from benchmarks.tests.test_window import *  # noqa: E402,F401,F403

del test_a_traced_rehearsal_ends_in_a_line_that_parses  # noqa: F821
