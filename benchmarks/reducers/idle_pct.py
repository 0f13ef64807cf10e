"""Share of the traced segment in which no operation ran on the device:
1 - (union of device operation intervals) / segment, averaged over the
devices, in per cent."""


def reduce(ctx):
    trace = ctx.get('trace')
    if not trace or not trace.get('window_s'):
        return None
    return 100.0 * (1.0 - trace['busy_s'] / trace['window_s'])
