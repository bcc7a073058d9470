"""Share of rounds whose weights the coding layer's memo spared a
decode, in percent: 100 x (1 - novel / rounds) over the program's
``coding.lookahead`` spans whose first round lies in the window. None
where the program records no spans."""


def read(ctx):
    if ctx.get("kind") != "train" or not ctx.get("steps"):
        return None
    try:
        from repro import spans
    except ImportError:
        return None
    recs = spans.records()
    # This run's records: those after its newest step-0 batch.
    start = max((r.id for r in recs
                 if r.name == "data.batch" and r.step == 0), default=None)
    if start is None:
        return None
    lo = ctx["traffic"]["check_steps"]
    hi = lo + ctx["steps"]
    chunks = [r for r in recs if r.id > start
              and r.name == "coding.lookahead" and lo <= r.step < hi]
    rounds = sum(r.attrs["rounds"] for r in chunks)
    if not rounds:
        return None
    return 100.0 * (1.0 - sum(r.attrs["novel"] for r in chunks) / rounds)
