"""A reducer that exists only under ``tests/``: shows that a later PR adds
a per-layer metric by adding files (``tests/metrics/steps_in_window.json``
names it, ``tests/workloads/tiny-bert-freq1.json`` lists the metric)."""


def reduce(ctx):
    return float(ctx['window']['steps'])
