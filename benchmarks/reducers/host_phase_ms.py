"""Median host-clock time of the window's single fenced steps whose
dispatched phase set has (``has``) or lacks (``lacks``) a K-FAC phase
(``step_fn.last_phases``: 'pred', 'stats', 'decomp', 'gather')."""

import numpy as np


def reduce(ctx, has=(), lacks=()):
    w = ctx['window']
    picked = [ms for ms, ph in zip(w['single_ms'], w['single_phases'])
              if all(p in ph for p in has) and not any(p in ph for p in lacks)]
    return float(np.median(picked)) if picked else None
