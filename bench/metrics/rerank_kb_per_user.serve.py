"""Neighbor-row bytes the exact rerank reads per query row (KB): the sum
of ``gather_bytes`` over the sum of ``rows`` of the window's
``recommend.rerank`` spans (``bench/metrics/span_time.py`` says what a
span record holds).  A row reads its ``k`` neighbors' ratings over the
whole item tiles at the gather code's width: one byte a rating on the
int8 code, four on the float32 matrix.  None where no span carries the
count."""

RERANK = "recommend.rerank"


def read(ctx):
    rows = read_bytes = 0
    for s in ctx.get("spans") or ():
        if s.name == RERANK and "gather_bytes" in s.attrs:
            rows += int(s.attrs["rows"])
            read_bytes += int(s.attrs["gather_bytes"])
    if rows <= 0:
        return None
    return read_bytes / rows / 1e3
