"""Generated tokens drained inside the window over the window's seconds.

The window opens and closes at chunk boundaries, so it holds whole
chunks: all the work and all the time between them."""


def read(run):
    return run.tokens_in_window() / run.window_s
