"""Host milliseconds per engine iteration spent planning (the
scheduler's ``plan()``) and admitting (``ServeEngine._admit``)
(benchmark spans)."""


def read(ctx):
    if ctx["kind"] != "serve" or not ctx["span_counts"].get("plan"):
        return None
    s = ctx["spans"]
    return (s.get("plan", 0.0) + s.get("admit", 0.0)) \
        / ctx["span_counts"]["plan"] * 1e3
