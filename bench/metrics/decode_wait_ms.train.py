"""The main thread's wait for the coding layer in milliseconds per
window step: the program's ``coding.wait`` spans
(``LookaheadPrefetcher.next()``, blocked on the chunk the worker thread
decodes) of the window's rounds. None where the program records no
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
    waits = [r for r in recs if r.id > start
             and r.name == "coding.wait" and lo <= r.step < hi]
    if not waits:
        return None
    return sum(r.ms for r in waits) / len(waits)
