"""The measured window: the one call and feed that set-up, the window and
the traced segment all drive.

``--seconds S`` is split by the traffic's ``fenced_share``: first chunks
of ``CHUNK`` steps with one fence at the end of each chunk (the host may
run ahead inside a chunk), then single fenced steps in whole cadence
periods (the tail the user sees: at cadence 10 the factor+decomposition
step). The rate is taken over all steps and all time of the window.
"""

import math
import time

import numpy as np

CHUNK = 10


class Stepper:
    """Feeds the batch pool to the program's step function and keeps the
    newest state; every dispatched step's metrics and phases are kept."""

    def __init__(self, prog, fence):
        self.prog, self.fence = prog, fence
        self.count = 0
        self.metrics, self.phases = [], []

    def step(self):
        prog = self.prog
        batch = prog.pool[self.count % len(prog.pool)]
        prog.state, mets = prog.step_fn(prog.state, batch)
        self.count += 1
        self.metrics.append(mets)
        self.phases.append(tuple(prog.step_fn.last_phases))
        return mets

    def run(self, n, fenced=False):
        """n steps; -> list of (seconds, steps) spans: one per step when
        ``fenced``, else one for all n (fence at the end only)."""
        spans = []
        t0 = time.perf_counter()
        for _ in range(n):
            mets = self.step()
            if fenced:
                self.fence(mets['loss'])
                t1 = time.perf_counter()
                spans.append((t1 - t0, 1))
                t0 = t1
        if not fenced:
            self.fence(mets['loss'])
            spans.append((time.perf_counter() - t0, n))
        return spans


def period(traffic):
    return math.lcm(traffic['fac_update_freq'], traffic['kfac_update_freq'])


def measure(stepper, traffic, seconds):
    """Run the window; -> dict of raw measurements."""
    per = period(traffic)
    chunk = math.lcm(CHUNK, per)
    first = stepper.count
    share = traffic['fenced_share']
    chunks, singles = [], []
    start = time.perf_counter()
    while True:
        chunks += stepper.run(chunk)
        if time.perf_counter() - start >= (1.0 - share) * seconds:
            break
    while share > 0 and time.perf_counter() - start < seconds:
        singles += stepper.run(per, fenced=True)
    elapsed = time.perf_counter() - start
    steps = stepper.count - first
    single_ms = np.array([s * 1e3 for s, _ in singles])
    phases = stepper.phases[first + sum(n for _, n in chunks):]
    out = {
        'window_s': elapsed, 'steps': steps, 'first_step': first,
        'samples_per_s': steps * stepper.prog.samples_per_step / elapsed,
        'chunk_step_ms': float(np.median([s / n for s, n in chunks]) * 1e3),
        'chunks': len(chunks), 'fenced_steps': len(singles),
        'single_ms': single_ms, 'single_phases': phases,
    }
    if len(single_ms):
        out['step_ms_p95'] = float(np.percentile(single_ms, 95))
    return out


def _bad_steps(mets):
    """Per step: its loss is not finite, or the health guard refused its
    batch."""
    return [not np.isfinite(m['loss'])
            or not bool(m.get('health/ok', True)) for m in mets]


def step_health(mets):
    """``mets``: every dispatched step's metrics, on the host. -> (losses,
    bad, first_bad): every loss, the number of steps whose loss is not
    finite or whose batch the health guard refused, and the index of the
    first such step (None: none)."""
    losses = [float(m['loss']) for m in mets]
    flags = _bad_steps(mets)
    return losses, sum(flags), flags.index(True) if True in flags else None


def tally(mets, first, steps, counters, compiles):
    """A run's failures, counted over one set of steps: the window's.

    ``mets``: every step's metrics from the run's first (set-up, then the
    window's ``steps`` from index ``first``); ``counters``: the health
    counters after the window; ``compiles``: compilations inside it.
    -> (attempted, failed, parts). ``attempted`` is the window's steps;
    ``failed`` those of them whose loss is not finite or whose batch the
    guard refused, and at least 1 for any other fault (a bad step in
    set-up, a raised counter, a compile in the window), so that it is 0
    only where nothing is wrong and never above ``attempted``. ``parts``
    names each fault; all are 0 in a sound run but ``first_bad_step``, the
    run's first bad step counted from its first step, which is -1."""
    flags = _bad_steps(mets[:first + steps])
    bad_setup, bad_window = sum(flags[:first]), sum(flags[first:])
    parts = {'bad_steps_window': bad_window, 'bad_steps_setup': bad_setup,
             **counters, 'compiles_in_window': compiles,
             'first_bad_step': flags.index(True) if True in flags else -1}
    other = bad_setup or compiles or any(counters.values())
    return steps, min(steps, max(bad_window, int(bool(other)))), parts
