"""The hybrid decoder's cell among the benchmark's own tests
(``benchmarks/tests/test_hybrid_lm.py``: ``kimi-linear-48b-a3b-ep32``'s
file, metrics, the chunked scan's roofline on a made-up trace and the
rehearsal cell ``tiny-hybrid-lm-freq10``: three ``run.py`` subprocesses).
A file of its own beside ``tests/test_benchmark_suite.py``: under
``--dist loadfile`` a file runs on one worker, and that one already holds
seven such subprocesses."""

import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(_REPO, 'benchmarks', 'tests'),
           os.path.join(_REPO, 'benchmarks'), _REPO):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from test_hybrid_lm import *  # noqa: E402,F401,F403
