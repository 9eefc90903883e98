"""Scheduler: 85th percentile, over the requests due in the window, of
due time to first admission (the engine tracer's ``admit`` event).  A
request never admitted counts as infinitely late."""

import math

from stats import percentile


def read(run):
    if not run.events:
        return None
    admitted = {}
    for e in run.events:
        if e.kind == "admit" and e.rid not in admitted:
            admitted[e.rid] = e.ts
    waits = [admitted[s.arrival.rid] - s.due if s.arrival.rid in admitted
             else math.inf for s in run.due_in_window]
    return percentile(waits, 85)
