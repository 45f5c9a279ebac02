"""Tier-1 hook for the benchmark's own checks.

``chipbench/selftest/`` holds the yardstick's tests (the manifest, the
training loop at ``BERT_TINY`` width and at a small decoder's, the trace
reductions and the readers on the trimmed recordings, the FLOP functions): a benchmark PR may add no file outside its
directories, so they live there, and this file imports them so that each
counts in the driver's run on the CPU.  Nothing here measures anything.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench.selftest.test_chipbench import *  # noqa: E402,F401,F403
from chipbench.selftest.test_program_spans import *  # noqa: E402,F401,F403
from chipbench.selftest.test_kanana import *  # noqa: E402,F401,F403
from chipbench.selftest.test_mellum2 import *  # noqa: E402,F401,F403
