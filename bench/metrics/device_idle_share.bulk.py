"""Share of the traced window in which no operation ran on the device
(%): 1 − busy / window, busy being the union of the ``XLA Ops`` events."""


def read(ctx):
    trace = ctx.get("trace")
    if not trace or ctx.get("window_s", 0) <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / ctx["window_s"])
