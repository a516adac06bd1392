"""Mean wait of a request in the server's queue, from submit to its
batch's launch, over the window: ``serve.queue_seconds`` sum / count."""


def read(ctx):
    h = (ctx.get("serve") or {}).get("serve.queue_seconds")
    if not h or h["count"] <= 0:
        return None
    return 1e3 * h["sum"] / h["count"]
