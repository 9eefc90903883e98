"""Scheduler, radix prefix sharing: share (%) of the prompt tokens of
the admissions inside the window that were served from cached pages
(``suffix_start`` over ``plen`` of the tracer's ``admit`` events;
resumed requests left out)."""


def read(run):
    hit = total = 0
    for e in run.events:
        if e.kind == "admit" and not e.attrs["resume"] \
                and run.w0 < e.ts <= run.w1:
            hit += e.attrs["suffix_start"]
            total += e.attrs["plen"]
    return 100.0 * hit / total if total else None
