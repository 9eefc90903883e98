"""Engine and scheduler: mean, over the chunks drained inside the window,
of the slots that ran in the chunk over all slots (%), from the live
slot count the tracer's ``chunk`` event carries."""


def read(run):
    live = [e.attrs["live_slots"] for e in run.events
            if e.kind == "chunk" and run.w0 < e.ts <= run.w1]
    if not live:
        return None
    return 100.0 * sum(live) / len(live) / run.settings["engine"]["slots"]
