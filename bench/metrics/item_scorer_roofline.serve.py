"""Share of its roofline that the item index's score stage reached in
the traced window (%): ``bench/metrics/score_work.py`` counts the work."""

from bench.metrics.score_work import roofline_share


def read(ctx):
    return roofline_share(ctx)
