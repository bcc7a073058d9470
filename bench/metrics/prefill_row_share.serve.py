"""Share of the window's busy pool rows that replayed a prompt token
rather than decoding (counted from the scheduler's plans), in percent."""


def read(ctx):
    if ctx["kind"] != "serve" or not ctx["rows"]["busy"]:
        return None
    return 100.0 * ctx["rows"]["prefill"] / ctx["rows"]["busy"]
