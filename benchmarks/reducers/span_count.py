"""How many of the program's host spans called ``name`` (``name...`` when
it ends in ``/``) the traced segment holds, e.g. ``kfac.step.build/``
spans: step programs built inside it. A trace with ``kfac.step`` spans and
none called ``name`` reads 0; one without ``kfac.step`` spans: None."""

from harness import spans


def reduce(ctx, name):
    trace = ctx.get('trace')
    if not trace:
        return None
    host = spans.host_events(trace['data'])
    if not spans.named(host, spans.STEP):
        return None
    return float(len(spans.named(host, name)))
