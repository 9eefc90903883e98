"""85th percentile, over every request due in the window, of (last token
- first token) / (tokens - 1).  A request that did not finish counts as
infinitely slow.  85th: ten of the about 70 requests lie beyond it."""

from stats import percentile


def read(run):
    return percentile([s.tpot for s in run.due_in_window], 85)
