"""85th percentile, over every request due in the window, of first token
drained minus due time.  A request refused, failed or with no first
token when the run ends counts as infinitely late.  85th: the window
holds about 70 requests due at the cell's rate, and this is the highest
percentile with ten of them beyond it."""

from stats import percentile


def read(run):
    return percentile([s.ttft for s in run.due_in_window], 85)
