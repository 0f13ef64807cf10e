"""Tracing and phase attribution.

The reference's tracing story is manual wall-clock phase timers
(IO/FW+BW/COMM/KFAC/UPDATE, examples/pytorch_cifar10_resnet.py:289-339)
plus the --exclude-parts subtraction method (kfac_preconditioner_base.py:
96-99, consumed by scripts/parse_logs.py:44-73). Under jit the phases fuse
into one program, so the TPU equivalent is :func:`trace`: a jax.profiler
context writing an XLA trace (Perfetto / TensorBoard viewable), in which
the step's ``jax.named_scope``s (``kfac.ComputeFactor`` ...
``kfac.ComputeInverse`` with ``decomp.b<D>x<n>`` and ``decomp.<stage>``
inside it) name every device operation; ``benchmarks/reducers`` turn such
a trace into per-phase device times. (The subtraction method automated,
``exclude_parts_breakdown``, went with PR 45: it differenced the host-clock
times of DIFFERENT programs, which says nothing of one program's phases.)
"""

import contextlib
import time

import jax
import numpy as np


@contextlib.contextmanager
def trace(log_dir):
    """jax.profiler trace context — the on-chip replacement for the manual
    phase timers."""
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def host_fence(out):
    """Wait until every array of ``out`` is computed — THE execution
    fence of this framework's timing code, and it is
    ``jax.block_until_ready``: on the v5e a host fetch issued right
    after it returns at once, behind a multi-second program
    (``chip_smoke.py``'s *fence* line and the ``fence`` entry of every
    K-FAC leg; PERF.md, PR 21). A TPU core executes programs in
    submission order, so fencing the LAST dispatched program's output
    fences all of them; on a mesh it waits for every addressable shard.
    Returns ``out``."""
    return jax.block_until_ready(out)


def time_steps(step_fn, state, batch, iters=30, warmup=5, kw_fn=None,
               tracer=None, **kw):
    """Mean/std steady-state iteration time (the SPEED-mode measurement,
    reference :333-344). Fences each iteration via :func:`host_fence`.

    kw_fn: optional ``kw_fn(i) -> dict`` of per-iteration step kwargs
    (e.g. a stepped LR schedule); merged over ``**kw``.
    tracer: optional ``obs.trace.TraceRecorder`` — each timed iteration
    is recorded as a ``bench.iter`` span (the same number that enters
    the mean), so a SPEED run leaves a
    per-iteration trace next to its one-line summary.
    """
    def kwargs(i):
        return {**kw, **(kw_fn(i) if kw_fn else {})}

    for i in range(warmup):
        state, m = step_fn(state, batch, **kwargs(i))
    host_fence(m)
    times = []
    for i in range(iters):
        t0 = time.perf_counter()
        state, m = step_fn(state, batch, **kwargs(warmup + i))
        host_fence(m)
        t = time.perf_counter() - t0
        times.append(t)
        if tracer is not None:
            tracer.complete('bench.iter', t, cat='bench', i=i)
    return float(np.mean(times)), float(np.std(times)), state


def speed_report(log, step_fn, state, batch, units_per_iter,
                 unit='tokens/sec', iters=60, warmup=5, kw_fn=None,
                 tracer=None, **kw):
    """The SPEED-mode measurement + log line shared by the example
    trainers: steady-state iteration time via :func:`time_steps`, one
    canonical format (scripts/parse_logs.py parses it). Pass the REAL
    per-iteration work in ``units_per_iter`` (e.g. actual batch rows x
    sequence length — not the requested batch size, which a small
    dataset may silently truncate). Returns the advanced state."""
    mean, std, state = time_steps(step_fn, state, batch, iters=iters,
                                  warmup=warmup, kw_fn=kw_fn,
                                  tracer=tracer, **kw)
    log.info('SPEED: iter time %.4f +- %.4f s (%s %.1f)',
             mean, std, unit, units_per_iter / mean)
    return state
