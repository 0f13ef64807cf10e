"""Find the benchmark's data files and plug-ins by name.

Everything that belongs to one configuration, one traffic mix, one metric
or one reducer is a file of its own; a later PR adds files and entries in
``BENCHMARK.json`` and edits nothing here. Each kind is looked up first
under ``benchmarks/<kind>/`` and then under ``benchmarks/tests/<kind>/``:
what is found only under ``tests/`` is a *rehearsal* piece (tiny sizes for
the CPU) and is the only thing ``run.py`` runs off a TPU.
"""

import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def find(kind, name, ext):
    """-> (path, rehearsal) of ``<kind>/<name><ext>``; KeyError if absent."""
    for base, rehearsal in ((BENCH, False),
                            (os.path.join(BENCH, 'tests'), True)):
        path = os.path.join(base, kind, name + ext)
        if os.path.isfile(path):
            return path, rehearsal
    raise KeyError(f'no benchmarks/{kind}/{name}{ext} '
                   f'(nor under benchmarks/tests/)')


def load_json(kind, name):
    path, rehearsal = find(kind, name, '.json')
    with open(path) as f:
        data = json.load(f)
    return data, rehearsal


def load_module(kind, name):
    """Import ``<kind>/<name>.py`` as a module of its own."""
    path, _ = find(kind, name, '.py')
    spec = importlib.util.spec_from_file_location(
        f'bench_{kind}_{name}', path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_kfac_reference(config):
    """The K-FAC algebra the configuration is checked against:
    ``reference/kfac_plain.py`` unless it names another."""
    return load_module('reference',
                       config.get('kfac_reference', 'kfac_plain'))


def benchmark_json():
    with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
        return json.load(f)


def resolve_workload(name):
    """The cell called ``name``: its entry in ``BENCHMARK.json``, or a
    rehearsal cell ``benchmarks/tests/workloads/<name>.json`` (same keys
    plus the metric names it reports). Returns ``(cell, rehearsal)``
    where ``cell`` has ``name, config, traffic, chips, end_to_end,
    per_layer`` (the last two: lists of metric entries that apply)."""
    bench = benchmark_json()
    for w in bench['workloads']:
        if w['name'] == name:
            return dict(
                w,
                end_to_end=[m for m in bench['end_to_end']
                            if name in m.get('workloads', [name])],
                per_layer=[m for m in bench['per_layer']
                           if name in m.get('workloads', [name])]), False
    path = os.path.join(BENCH, 'tests', 'workloads', name + '.json')
    if not os.path.isfile(path):
        raise KeyError(f'workload {name!r} is neither in BENCHMARK.json '
                       f'nor benchmarks/tests/workloads/')
    with open(path) as f:
        cell = json.load(f)
    cell['name'] = name
    for key in ('end_to_end', 'per_layer'):
        cell[key] = [{'name': m} if isinstance(m, str) else m
                     for m in cell.get(key, [])]
    return cell, True
