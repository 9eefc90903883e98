"""Small arithmetic shared by the harness and the metric readers."""

from __future__ import annotations

import math
from typing import List, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Nearest-rank ``q``-th percentile; ``inf`` entries count as the
    largest values.  None for an empty sample."""
    if not values:
        return None
    s = sorted(values)
    return s[max(math.ceil(q / 100.0 * len(s)) - 1, 0)]


def union_length(intervals: List[tuple]) -> float:
    """Total length covered by ``[(start, end), ...]``."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total
