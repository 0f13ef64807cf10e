"""Device time per step of the program's Pallas kernels: the operations
whose name or ``tf_op`` holds one of ``kernels`` (the ``name=`` each
``pl.pallas_call`` of ``ops/pallas_capture.py`` and
``ops/pallas_attention.py`` gives; autodiff wraps it, ``jvp(kfac_flash_fwd)``).
Time of their own, averaged over the devices, divided by the steps. A
trace with device operations and none of these kernels reads 0: the step
ran none (``capture_impl=None`` keeps statistics on the XLA path). No
device plane: None.
"""

from harness import spans, tracefile


def reduce(ctx, kernels):
    trace = ctx.get('trace')
    if not trace:
        return None
    return spans.own_device_ms_per_step(
        trace, lambda events: tracefile.matching(events, kernels))
