"""Model step: share (%) of the query rows the fused chunks computed
that carried a token, over the ``chunk`` events inside the window:
(``prefill_rows`` + ``decode_rows``) / ``rows_run``, all three counted
by the engine's drain.  Every micro-step runs ``chunk_rows`` rows per
slot, so a slot that only decodes fills one of them.  A program whose
``chunk`` events carry no row counters reports nothing."""


def read(run):
    used = ran = 0
    for e in run.events:
        if e.kind == "chunk" and "rows_run" in e.attrs \
                and run.w0 < e.ts <= run.w1:
            used += e.attrs["prefill_rows"] + e.attrs["decode_rows"]
            ran += e.attrs["rows_run"]
    return 100.0 * used / ran if ran else None
