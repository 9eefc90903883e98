"""Engine: host time per chunk boundary while no chunk is in flight, in
ms.  The engine's ``span`` events inside the window whose name starts
with ``engine.`` (reap, admit, dispatch, drain, submit), less
``engine.wait``, the drain's wait on the device: their summed ``dur``
over the ``chunk`` events in the window.  ``Engine.step()`` is
synchronous, so this host time is device idle time.  A program that
records no phase spans reports nothing."""


def read(run):
    host = chunks = 0
    spans = False
    for e in run.events:
        if not run.w0 < e.ts <= run.w1:
            continue
        if e.kind == "chunk":
            chunks += 1
        elif e.kind == "span" and e.attrs["name"].startswith("engine."):
            spans = True
            if e.attrs["name"] != "engine.wait":
                host += e.attrs["dur"]
    if not spans or not chunks:
        return None
    return 1e3 * host / chunks
