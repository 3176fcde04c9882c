"""Median ``scheduler/queue_wait`` of the requests sent in the window, in
ms: submit to the start of the step that packs the request's first
image.  None when the program recorded no spans."""
import statistics


def read(run):
    rec = getattr(run, "spans", None)
    if rec is None:
        return None
    sent = {r.uid for r in run.requests[:run.in_window]}
    waits = [(r.end - r.start) * 1e-6 for r in rec.spans
             if r.name == "scheduler/queue_wait" and r.parent in sent]
    return statistics.median(waits) if waits else None
