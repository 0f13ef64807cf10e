"""A number some part of the run put into the context: ``section`` is
``window`` (what the window measured itself), ``sgd`` (the first-order
leg) or ``plans`` (the compiler's memory plan)."""


def reduce(ctx, section, key):
    value = (ctx.get(section) or {}).get(key)
    return None if value is None else float(value)
