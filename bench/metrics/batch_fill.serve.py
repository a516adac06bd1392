"""Mean fill of the server's batches over the window (%):
``serve.batch_fill`` sum / count (requests over ``max_batch``)."""


def read(ctx):
    h = (ctx.get("serve") or {}).get("serve.batch_fill")
    if not h or h["count"] <= 0:
        return None
    return 100.0 * h["sum"] / h["count"]
