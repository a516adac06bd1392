"""Share of the rows selected by ``recommend.select`` that took the
tie-boundary repair loop (%): the spans' ``repaired`` over ``rows``."""

from bench.metrics.span_time import repair_share


def read(ctx):
    return repair_share(ctx)
