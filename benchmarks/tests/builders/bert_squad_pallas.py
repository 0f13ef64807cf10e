"""The BERT builder with factor statistics routed through the fused Pallas
capture kernels (``KFAC(capture_impl='pallas')``; the cells of
``BENCHMARK.json`` keep the XLA path). The chip trace recorded under
``tests/data/`` comes from this builder, so that it holds the named
kernels (``kfac_stat_rows``) beside the program's spans and scopes. A
source of traces, not a check: on the chip the kernels take f32 operands at
the default (bf16-pass) precision, so the float32-``highest`` limits of the
tiny configuration do not hold there (``factor_gap`` 4.6e-3 against 1e-4,
my chip run, PR 24); on the CPU, interpreted, they do."""

from harness import files


def build(config, traffic, kfac=True, axis_name=None):
    parts = files.load_module('builders', 'bert_squad').build(
        config, traffic, kfac=kfac, axis_name=axis_name)
    if parts['precond'] is not None:
        parts['precond'].capture_impl = 'pallas'
    return parts
