"""Engine host loop and fused chunk: mean wall time, in ms, of the
``Engine.step()`` calls inside the window that ran a chunk.  Each ends
in the drain's device-to-host copy, so it holds the chunk's device time
and the host's work at the boundary."""


def read(run):
    t = [b - a for a, b, ran in run.steps if ran and run.w0 < b <= run.w1]
    return 1e3 * sum(t) / len(t) if t else None
