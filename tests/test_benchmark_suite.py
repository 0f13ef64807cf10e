"""The benchmark's own tests, collected under ``tests/`` so that tier-1
runs them: the configuration files ``BENCHMARK.json`` names, the
reduction from a trace to the ledger's metrics, and the readers of the
program's spans, the plain K-FAC reference and the tally of a run's
failures, and the two sparse decoders' cells
(``benchmarks/tests/test_configs.py``, ``test_reduce.py``,
``test_spans.py``, ``test_reference.py``, ``test_sparse_lm.py``,
``test_mixed_lm.py``, ``test_step_reads.py``, ``test_decomp_scopes.py``).
They stay where the benchmark keeps them; this
file only puts their directories on the path and imports their cases:

- ``test_configs.py``: one case a configuration of ``BENCHMARK.json``
  (four);
- ``test_reference.py``: the tally of a run's failures and ``kfac_plain``
  on a two-expert model; imported by name here and, for its own runs
  under ``benchmarks/tests``, by ``test_configs.py`` too (the names are
  the same objects, so the cases count once);
- ``test_reduce.py`` and ``test_spans.py``: the trace reduction and the
  readers of the program's spans (``test_spans`` imports ``test_reduce``);
- ``test_sparse_lm.py``: ``kanana-2-30b-a3b-ep16``'s file, metrics and
  rehearsal cell ``tiny-sparse-lm-freq10`` (four ``run.py`` subprocesses);
- ``test_mixed_lm.py``: ``trinity-mini-ep16``'s file, metrics and
  rehearsal cell ``tiny-mixed-lm-freq10`` (three ``run.py`` subprocesses:
  correct, and both controls fail);
- ``test_step_reads.py``: ``step_reads_in_trace`` through its file and
  ``span_count`` (PR 44; imports ``test_spans``' traces);
- ``test_decomp_scopes.py``: the ten metrics that read the inside of
  ``kfac.ComputeInverse`` through their files, the three reducers that
  came with them and ``tools/decomp_table.py``, on a hand-made trace, on
  the two older recorded traces and on one recorded with the scopes
  (PR 45).

``test_hybrid_lm.py`` (``kimi-linear-48b-a3b-ep32``'s file, metrics and
rehearsal cell, three more ``run.py`` subprocesses) is collected by a file
of its own, ``tests/test_benchmark_hybrid.py``, so that another worker can
take it. ``test_run_cpu.py`` (19 cases, each a ``run.py`` subprocess) is not
collected: alone on this CPU it takes 313 s, more than a tier-1 worker
has to spare (CHANGES.md, PR 35).
"""

import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# test_spans imports test_reduce; benchmarks/tests/conftest.py (not read
# from here) adds the other two
for _p in (os.path.join(_REPO, 'benchmarks', 'tests'),
           os.path.join(_REPO, 'benchmarks'), _REPO):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from test_configs import *  # noqa: E402,F401,F403
from test_reduce import *  # noqa: E402,F401,F403
from test_spans import *  # noqa: E402,F401,F403
# test_configs imports test_reference's cases too: by name here, so that
# they do not hang on that
from test_reference import *  # noqa: E402,F401,F403
from test_sparse_lm import *  # noqa: E402,F401,F403
from test_mixed_lm import *  # noqa: E402,F401,F403
from test_step_reads import *  # noqa: E402,F401,F403
from test_decomp_scopes import *  # noqa: E402,F401,F403

# test_step_reads and test_decomp_scopes pin their metrics' ``workloads`` to
# the five cells BENCHMARK.json had when they were written. A PR that adds a
# cell appends its name to those lists and may not edit a file the benchmark
# already has, so the cell is appended to the two modules' lists here, before
# their cases run (run from benchmarks/tests/ itself they still read five
# cells: a `benchmark` PR's to let them read BENCHMARK.json; PERF.md 7).
import test_decomp_scopes as _decomp  # noqa: E402
import test_step_reads as _reads  # noqa: E402

for _cells in (_decomp.CELLS, _reads.CELLS):
    if 'kimi-linear-ep32-freq10' not in _cells:
        _cells.append('kimi-linear-ep32-freq10')
