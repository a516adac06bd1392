"""Mean duration of the server's ``serve.predict`` spans in the window:
one recommend call of the engine and item index per batch, fenced by the
host copy of its results."""


def read(ctx):
    d = [s.duration for s in ctx.get("spans") or ()
         if s.name == "serve.predict"]
    if not d:
        return None
    return 1e3 * sum(d) / len(d)
