"""Main-thread milliseconds per step blocked on the host pipeline: the
batch future and ``LookaheadPrefetcher.next()`` (benchmark span)."""


def read(ctx):
    if ctx["kind"] != "train" or not ctx["steps"]:
        return None
    return ctx["spans"].get("host_wait", 0.0) / ctx["steps"] * 1e3
