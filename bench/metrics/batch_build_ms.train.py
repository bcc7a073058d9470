"""The data layer's busy time in milliseconds per window step: the
program's ``data.batch`` (``SyntheticLM.batch``) and ``data.blocks``
(``CodedBatcher.unique_blocks``) spans of the window's steps, built on
the worker thread one step ahead. None where the program records no
spans."""


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
    mine = [r for r in recs if r.id > start
            and r.step is not None and lo <= r.step < hi
            and r.name in ("data.batch", "data.blocks")]
    built = {r.step for r in mine if r.name == "data.batch"}
    if not built:
        return None
    return sum(r.ms for r in mine) / len(built)
