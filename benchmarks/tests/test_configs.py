"""The configuration files ``BENCHMARK.json`` names hold what ``run.py``
and ``harness/check.py`` read from them, and what they say was changed
from the source is a key they hold."""

import json
import os
import re

import numpy as np
import pytest

from harness import check, files
# the reference's own tests ride along: tests/test_benchmark_suite.py
# collects what this module holds
from test_reference import *  # noqa: E402,F401,F403

KEY_PATH = re.compile(r'[a-z_0-9]+(\.[a-z_0-9]+)+$')


def compared_numbers():
    """The names ``check.numbers`` gives, read off a one-leaf example."""
    one = {'losses': [1.0], 'first_update': {'w': 1.0},
           'param_change': {'w': 1.0},
           'factors': {'layer': [np.eye(2), np.eye(2)]}}
    return set(check.numbers(one, one))


def changed_key_paths(entry):
    """The key paths an entry of ``changed`` starts with: ``group.key[,
    group.key]: why``; none where it starts with plain words."""
    head = entry.split(': ')[0].split(', ')
    return head if all(KEY_PATH.match(h) for h in head) else []


@pytest.mark.parametrize('entry', files.benchmark_json()['configs'],
                         ids=lambda e: e['name'])
def test_configuration_file_holds_what_is_read(entry):
    path = os.path.join(files.ROOT, entry['file'])
    assert os.path.isfile(path)
    with open(path) as f:
        config = json.load(f)
    assert config['name'] == entry['name']
    assert config['reduced'] == entry['reduced']
    # a limit for every number compared, and no other
    assert set(config['check']['limits']) == compared_numbers()
    assert all(limit > 0 for limit in config['check']['limits'].values())
    # the pieces it names are files of the benchmark
    files.find('builders', config['builder'], '.py')
    files.find('reference', config['plain'], '.py')
    assert callable(files.load_kfac_reference(config).run)
    assert all(isinstance(name, str)
               for name in config['check'].get('counters', []))
    paths = [p for line in config['changed'] for p in changed_key_paths(line)]
    assert 'optimizer.lr' in paths      # both cells run a constant rate
    for key_path in paths:
        node = config
        for key in key_path.split('.'):
            assert key in node, f'{key_path}: no such key in {entry["file"]}'
            node = node[key]
